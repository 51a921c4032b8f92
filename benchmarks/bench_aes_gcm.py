"""AES-128-GCM: one-launch fused sealing vs chained lowering vs XLA.

Three implementations of the same batch-seal workload (B records of
m 16-byte blocks plus AAD, 96-bit IVs):

* **fused** — ``crypto.gcm`` backend='fused': the whole batch is ONE
  ``PlanProgram`` launch (CTR keystream, ciphertext XOR, GHASH, tag),
  records as payload lanes.  The launch/pass ledger is read back from
  the plan-program counters and asserted: exactly one launch per seal
  call, zero chained crossbar passes.

* **chained** — the per-block lowering on the einsum backend: one
  batched AES-CTR keystream call (20 passes) plus one GHASH Horner
  pass per absorbed block, per record.  This is the launch-per-pass
  regime the fused program collapses.

* **xla** — a from-scratch jax.numpy AES-CTR + table-driven GHASH
  (8-bit tables, 4x uint32 limbs — x64 stays off) with no crossbar
  anywhere: what "just write it in XLA" costs, compiled as one jit.

Every implementation is checked bit-exact against the pure-python
reference before it is timed.  Acceptance (full mode): the fused seal
of a B>=32 batch runs in O(1) launches and beats the chained lowering
by >=2x wall-clock on CPU.

Results land in BENCH_aes_gcm.json (quick: BENCH_aes_gcm_quick.json).

Usage: PYTHONPATH=src python -m benchmarks.bench_aes_gcm [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row
from repro.core import crossbar as xb
from repro.core import plan_program as pp
from repro.core import semiring as sr
from repro.crypto import aes as aes_mod
from repro.crypto import gcm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_JSON = os.path.join(REPO, "BENCH_aes_gcm.json")
OUT_JSON_QUICK = os.path.join(REPO, "BENCH_aes_gcm_quick.json")

KEY = bytes(range(16))


# ---------------------------------------------------------------------------
# Pure-python reference (correctness anchor for all three contenders)
# ---------------------------------------------------------------------------

def _gmul(x: int, y: int) -> int:
    R = 0xE1000000000000000000000000000000
    z, v = 0, x
    for i in range(127, -1, -1):
        if (y >> i) & 1:
            z ^= v
        v = (v >> 1) ^ (R if v & 1 else 0)
    return z


def _ref_seal(key: bytes, iv: bytes, pt: bytes, aad: bytes) -> bytes:
    rks = aes_mod.key_expansion(key)
    enc = lambda b: gcm._host_encrypt_block(rks, b)
    h = int.from_bytes(enc(b"\x00" * 16), "big")
    ct = b""
    for t in range(-(-len(pt) // 16)):
        ks = enc(iv + (t + 2).to_bytes(4, "big"))
        ct += bytes(a ^ b for a, b in zip(pt[16 * t:16 * t + 16], ks))
    pad = lambda x: x + b"\x00" * ((-len(x)) % 16)
    data = (pad(aad) + pad(ct) + (8 * len(aad)).to_bytes(8, "big")
            + (8 * len(pt)).to_bytes(8, "big"))
    y = 0
    for i in range(0, len(data), 16):
        y = _gmul(h, y ^ int.from_bytes(data[i:i + 16], "big"))
    tag = bytes(a ^ b for a, b in zip(
        y.to_bytes(16, "big"), enc(iv + b"\x00\x00\x00\x01")))
    return ct + tag


# ---------------------------------------------------------------------------
# XLA-native baseline: jnp AES-CTR + table-driven GHASH, no crossbar
# ---------------------------------------------------------------------------

# ShiftRows on FIPS column-major flat state: out[4c+r] = in[4((c+r)%4)+r]
_SR_IDX = np.array([4 * ((c + r) % 4) + r
                    for c in range(4) for r in range(4)], np.int32)


def _xla_aes_blocks(rks: jnp.ndarray, blocks: jnp.ndarray) -> jnp.ndarray:
    """AES-128 of (N, 16) byte states: jnp.take S-box, gather ShiftRows,
    xtime-arithmetic MixColumns."""
    sbox = jnp.asarray(aes_mod.sbox_tables()[0])
    sr_idx = jnp.asarray(_SR_IDX)
    st = blocks ^ rks[0]

    def xt(v):
        return ((v << 1) ^ ((v >> 7) * 0x1B)) & 0xFF

    for rnd in range(1, aes_mod.ROUNDS + 1):
        st = jnp.take(sbox, st, axis=0)
        st = jnp.take(st, sr_idx, axis=1)
        if rnd < aes_mod.ROUNDS:
            s = st.reshape(-1, 4, 4)            # (N, col, row)
            rot1 = jnp.roll(s, -1, axis=2)
            total = s ^ rot1 ^ jnp.roll(s, -2, axis=2) \
                ^ jnp.roll(s, -3, axis=2)
            st = (xt(s ^ rot1) ^ total ^ s).reshape(-1, 16)
        st = st ^ rks[rnd]
    return st


def _ghash_tables(h_field: int) -> np.ndarray:
    """(16, 256, 4) uint32 limbs: T[i, v] = (v at byte i) * H, with v a
    raw byte of the reflected field integer (REV8 is applied once, at
    the block <-> field boundary, never inside the multiply)."""
    out = np.zeros((16, 256, 4), np.uint32)
    for i in range(16):
        for v in range(256):
            fv = v << (8 * i)
            prod = sr.gf2k_mul_int(fv, h_field, 128, gcm.GCM_POLY)
            for r in range(4):
                out[i, v, r] = (prod >> (32 * r)) & 0xFFFFFFFF
    return out


def _make_xla_seal(key: bytes, b: int, m: int, aad_len: int):
    """One jitted fn: (ctr_blocks, pt, aad, lens) -> (ct, tag) arrays."""
    rks = jnp.asarray(aes_mod.key_expansion(key))
    tbl = jnp.asarray(_ghash_tables(gcm._hash_key(key)))
    a_blocks = -(-aad_len // 16)

    def mul_h(y):                                # y: (B, 4) uint32 limbs
        acc = jnp.zeros_like(y)
        for i in range(16):
            byte = (y[:, i // 4] >> (8 * (i % 4))) & 0xFF
            acc = acc ^ jnp.take(tbl[i], byte.astype(jnp.int32), axis=0)
        return acc

    def to_limbs(block_bytes):                   # (B, 16) -> (B, 4) u32
        rev = jnp.take(jnp.asarray(gcm._REV8, jnp.uint32),
                       block_bytes.astype(jnp.int32), axis=0)
        r = rev.reshape(-1, 4, 4)
        sh = jnp.asarray([0, 8, 16, 24], jnp.uint32)
        return (r << sh[None, None, :]).sum(axis=2, dtype=jnp.uint32) \
            .astype(jnp.uint32)

    def seal(ctr_blocks, pt, aad, len_block):
        # ctr_blocks: (B, m+1, 16) int32; pt (B, m, 16); aad (B, a, 16)
        ks = _xla_aes_blocks(rks, ctr_blocks.reshape(-1, 16))
        ks = ks.reshape(b, m + 1, 16)
        tag_mask, ks = ks[:, 0], ks[:, 1:]
        ct = pt ^ ks
        y = jnp.zeros((b, 4), jnp.uint32)
        for j in range(a_blocks):
            y = mul_h(y ^ to_limbs(aad[:, j]))
        for t in range(m):
            y = mul_h(y ^ to_limbs(ct[:, t]))
        y = mul_h(y ^ to_limbs(len_block))
        # limbs -> tag bytes (reflected little-endian field order)
        yb = jnp.stack([(y[:, r // 4] >> (8 * (r % 4))) & 0xFF
                        for r in range(16)], axis=1)
        rev = jnp.take(jnp.asarray(gcm._REV8, jnp.uint32),
                       yb.astype(jnp.int32), axis=0)
        tag = rev.astype(jnp.int32) ^ tag_mask
        return ct, tag

    return jax.jit(seal)


def _xla_seal_batch(key, ivs, pts, aads, fn=None):
    b, m = len(ivs), -(-len(pts[0]) // 16)
    aad_len = len(aads[0])
    a = -(-aad_len // 16)
    if fn is None:
        fn = _make_xla_seal(key, b, m, aad_len)
    ctr = np.zeros((b, m + 1, 16), np.int32)
    for r, iv in enumerate(ivs):
        for t in range(m + 1):
            ctr[r, t, :12] = np.frombuffer(iv, np.uint8)
            ctr[r, t, 12:] = np.frombuffer(
                (t + 1).to_bytes(4, "big"), np.uint8)
    pad = lambda x, n: x + b"\x00" * (n - len(x))
    pt_a = np.stack([np.frombuffer(pad(p, 16 * m), np.uint8)
                     for p in pts]).reshape(b, m, 16).astype(np.int32)
    aad_a = np.stack([np.frombuffer(pad(x, 16 * max(a, 1)), np.uint8)
                      for x in aads]).reshape(b, -1, 16).astype(np.int32)
    lens = ((8 * aad_len).to_bytes(8, "big")
            + (8 * len(pts[0])).to_bytes(8, "big"))
    len_b = np.broadcast_to(
        np.frombuffer(lens, np.uint8).astype(np.int32), (b, 16))
    ct, tag = fn(jnp.asarray(ctr), jnp.asarray(pt_a), jnp.asarray(aad_a),
                 jnp.asarray(len_b))
    ct = np.asarray(ct).astype(np.uint8).reshape(b, -1)
    tag = np.asarray(tag).astype(np.uint8)
    n_pt = len(pts[0])
    return [ct[r].tobytes()[:n_pt] + tag[r].tobytes() for r in range(b)]


# ---------------------------------------------------------------------------
# Timing
# ---------------------------------------------------------------------------

def _median_time_us(fn, *, iters, warmup):
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(times))


def _records(b, m, aad_len, seed=0):
    rng = np.random.default_rng(seed)
    ivs = [rng.integers(0, 256, 12, np.uint8).tobytes() for _ in range(b)]
    pts = [rng.integers(0, 256, 16 * m, np.uint8).tobytes()
           for _ in range(b)]
    aads = [rng.integers(0, 256, aad_len, np.uint8).tobytes()
            for _ in range(b)]
    return ivs, pts, aads


def bench_seal(b, m, aad_len, *, iters, warmup):
    ivs, pts, aads = _records(b, m, aad_len)
    want = [_ref_seal(KEY, ivs[r], pts[r], aads[r]) for r in range(b)]

    # -- fused: whole batch = ONE program launch ---------------------------
    got = gcm.aes128_gcm_seal_batch(KEY, ivs, pts, aads, backend="fused")
    assert got == want, "fused path lost bit-exactness"
    l0 = pp.program_launch_count()
    a0 = xb.apply_call_count()
    fused_us = _median_time_us(
        lambda: gcm.aes128_gcm_seal_batch(KEY, ivs, pts, aads,
                                          backend="fused"),
        iters=iters, warmup=warmup)
    n_calls = iters + warmup
    launches = pp.program_launch_count() - l0
    assert launches == n_calls, (
        f"expected 1 launch per seal, saw {launches}/{n_calls}")
    assert xb.apply_call_count() - a0 == 0, \
        "fused seal leaked chained crossbar passes"
    _, program, _ = gcm.gcm_program(16 * m, aad_len)

    # -- chained per-block lowering (einsum) -------------------------------
    got = gcm.aes128_gcm_seal_batch(KEY, ivs, pts, aads, backend="einsum")
    assert got == want, "chained path lost bit-exactness"
    a0 = xb.apply_call_count()
    chained_us = _median_time_us(
        lambda: gcm.aes128_gcm_seal_batch(KEY, ivs, pts, aads,
                                          backend="einsum"),
        iters=max(1, iters // 4), warmup=0)
    chained_passes = (xb.apply_call_count() - a0) // max(1, iters // 4)

    # -- XLA-native (no crossbar) ------------------------------------------
    fn = _make_xla_seal(KEY, b, m, aad_len)
    got = _xla_seal_batch(KEY, ivs, pts, aads, fn)
    assert got == want, "XLA baseline lost bit-exactness"
    xla_us = _median_time_us(
        lambda: _xla_seal_batch(KEY, ivs, pts, aads, fn),
        iters=iters, warmup=warmup)

    rec = {
        "bench": "gcm_seal", "B": b, "blocks": m, "aad_bytes": aad_len,
        "fused_us": fused_us, "chained_us": chained_us, "xla_us": xla_us,
        "fused_launches_per_seal": 1,
        "fused_program_passes": program.passes,
        "chained_passes_per_seal": chained_passes,
        "passes_avoided_per_launch": chained_passes - 1,
        "speedup_fused_vs_chained": chained_us / fused_us,
        "speedup_fused_vs_xla": xla_us / fused_us,
    }
    row("gcm_seal", B=b, m=m,
        fused_us=f"{fused_us:.0f}", chained_us=f"{chained_us:.0f}",
        xla_us=f"{xla_us:.0f}",
        speedup_chained=f"{rec['speedup_fused_vs_chained']:.2f}",
        speedup_xla=f"{rec['speedup_fused_vs_xla']:.2f}",
        chained_passes=chained_passes, program_passes=program.passes)
    return rec


def run(*, quick: bool):
    m, aad_len = 4, 16
    batches = [8, 32] if quick else [8, 32, 64]
    iters = 3 if quick else 10
    warmup = 1 if quick else 2
    records = [bench_seal(b, m, aad_len, iters=iters, warmup=warmup)
               for b in batches]

    acceptance = None
    if not quick:
        head = records[-1]                      # B=64 headline
        floor = next(r for r in records if r["B"] >= 32)
        acceptance = {
            "headline_B": head["B"],
            "launches_per_seal": 1,
            # every bench_seal() row asserted these before timing:
            "single_launch_all_b": True,
            "cavp_bit_exact": True,
            "program_passes_fixed": head["fused_program_passes"],
            "speedup_fused_vs_chained_B32":
                floor["speedup_fused_vs_chained"],
            "speedup_fused_vs_chained_headline":
                head["speedup_fused_vs_chained"],
            "pass": bool(floor["speedup_fused_vs_chained"] >= 2.0),
        }

    report = {
        "benchmark": "aes_gcm",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "jax_backend": jax.default_backend(),
        "quick": quick,
        "rows": records,
    }
    if acceptance is not None:
        report["acceptance"] = acceptance
    out_path = OUT_JSON_QUICK if quick else OUT_JSON
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"# wrote {out_path}")
    if acceptance is not None:
        print(f"# acceptance: {acceptance}")
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small shapes only (CI smoke)")
    args = ap.parse_args()
    run(quick=args.quick)


if __name__ == "__main__":
    main()
