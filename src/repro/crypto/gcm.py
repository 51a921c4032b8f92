"""AES-128-GCM on the crossbar: authenticated encryption in O(1) launches.

GCM is the workload that fronts real serving traffic, and it stresses
both ends of the engine's width axis at once: AES-CTR is the byte-wide
(GF(2^8)) permutation pipeline already built in ``crypto.aes``, while
GHASH is a *128-bit-wide* field multiply — the "minimum supported
element width" axis of the paper's Table 1, pushed to its top end.
Two lowerings share the math:

* **Chained per-block lowering** (``backend='einsum'|'kernel'|'sparse'
  |'reference'``): counter blocks batch through the 20-pass AES plan
  pipeline, then GHASH absorbs one block per pass — ``ghash(...,
  mode='horner')`` multiplies the accumulator by H via ONE weighted
  PERMUTE pass per block over the ``gf2_128`` semiring (the matmul
  backends execute its GF(2) bit lift, built by ``lift_gf2_k`` from
  the 8-bit tile table).  ``mode='powers'`` goes further: with
  host-precomputed H-powers as per-element weights the entire
  Σ X_j·H^(M+1-j) is ONE k=M pass.  This path runs on all four
  crossbar backends and is the CAVP differential reference.

* **Fused program** (``backend='fused'``): one ``PlanProgram`` per
  record geometry, whatever the keys, executes the *whole* seal — CTR
  keystream for every block, ciphertext XOR, GHASH absorb, and the
  final tag — in a single megakernel launch for a whole batch of
  records, each lane under its own key.  The program state is a bit
  matrix: payload lanes are records, rows are

  ``[stream | E(J0) | IV | LEN | AAD | Y window | one-hot scratch]``

  and each lane's key material rides beside it as the launch's key
  operand (``KEY_ROWS`` rows: the 11 round keys and H = E_K(0), from
  ``key_material``), so the program's steps, select entries and dense
  tables are the same for every key.

  - AES runs on 128 bit rows per block with the S-box factored through
    *nibble* one-hots so the lookup never needs a 128-select parity: a
    weighted PERMUTE spreads each byte's bit rows to 32 candidate rows
    (16 low-nibble + 16 high-nibble values, weights 2^b), ``EQ_CONST``
    one-hots them, a k=16 GF(2) PERMUTE forms the low-nibble partial
    sums P[b,h] = XOR_l sbox_bit(b,16h+l)*lo[l], an ``AND`` against
    the replicated high-nibble one-hot picks the live column, and a
    k=16 fold reads S(v)'s bits back out — 37 gather columns per round
    where the byte-wide one-hot decode needed 136.  The per-round
    linear layer is ``lift_gf2_k(ShiftRows∘MixColumns)``,
    select-compacted (32 slots -> ~7).  AddRoundKey is ``XOR_KEY``
    with the lane's round key.
  - Counter blocks never ride as input: each trip re-routes the
    record's IV bits and XORs a *per-trip constant* row carrying the
    32-bit block counter — counter agility as control information.
  - GHASH multiplies by the lane's H with ``CLMUL``: the 128-row
    factor, in coefficient order at the head of the Y window, times key
    block ``H_BLOCK``, unreduced, fills the 256-row window.  Its fixed
    reduction mod ``GCM_POLY`` is select lists that the next gather
    reads, so ONE PERMUTE per trip shifts the plaintext stream, appends
    the new ciphertext block, keeps E(J0), and forms the next factor
    reduce(Y) ^ C_t, followed by one ``CLMUL``.
  - Partial final blocks mask their dead bit rows in the absorb plan's
    control (the keystream tail must not leak into the tag), so
    non-multiple-of-16 records are exact without any data-dependent
    branch.

  Trip 0 encrypts J0 itself (the tag mask); the AAD blocks are
  absorbed by the same factor-and-``CLMUL`` pair before it; the
  epilogue forms reduce(Y) ^ LEN, runs the final multiply, and lands
  ``[ciphertext bits | tag bits]`` in register 0.  Launches and avoided
  passes feed the telemetry ledger; ``fixed_latency=True`` asserts 1
  launch / 0 crossbar passes under the registry's program fingerprint.

Only 96-bit IVs are supported (J0 = IV || 0^31 || 1 — the NIST
SP 800-38D fast path and the CAVP coverage target); other IV lengths
would route through a GHASH-derived J0 and are left to the AES-256 /
GCM-SIV follow-up.
"""

from __future__ import annotations

import hmac
from collections import OrderedDict
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as _obs
from repro.core import crossbar as xb
from repro.core import plan_algebra as pa
from repro.core import plan_program as pp
from repro.core import semiring as sr
from repro.core import telemetry
from repro.crypto import aes as aes_mod
from repro.crypto.registry import REGISTRY

Array = jax.Array

BLOCK = 16
TAG_BYTES = 16
IV_BYTES = 12

# GHASH's field in the reflected-integer convention: block bit 8r+k
# (bit k of byte r, MSB first) is coefficient x^(8r+k), so mapping each
# byte through REV8 and reading the 16 bytes little-endian gives an
# integer whose bit e IS coefficient e — carry-less mul mod this poly
# is then ordinary GF(2^128) arithmetic on ints/limbs.
GCM_POLY = (1 << 128) | 0x87

_REV8 = np.array([int(f"{i:08b}"[::-1], 2) for i in range(256)], np.int32)

# Per-lane key material: key blocks 0..10 are the AES round keys in the
# state's bit order (row 8j + b = bit b of byte j), block H_BLOCK is
# H = E_K(0^128) in field-coefficient order (row e = coefficient x^e).
# Packed, one session is KEY_BYTES: the 176 round-key bytes, then H's
# 16 bytes as REV8 maps them.
H_BLOCK = aes_mod.ROUNDS + 1
KEY_ROWS = pp.KEY_BLOCK * (H_BLOCK + 1)
KEY_BYTES = KEY_ROWS // 8


class InvalidTagError(Exception):
    """Authentication failed for at least one record (``.indices`` says
    which); no plaintext is returned for any record in the batch."""

    def __init__(self, indices: Sequence[int]):
        self.indices = tuple(indices)
        super().__init__(f"GCM tag verification failed for record(s) "
                         f"{list(self.indices)}")


# ---------------------------------------------------------------------------
# Field plumbing (host-side control information)
# ---------------------------------------------------------------------------

def _block_to_field(b: bytes) -> int:
    """16-byte block -> reflected field integer (bit e = coeff x^e)."""
    return int.from_bytes(bytes(int(_REV8[x]) for x in b), "little")


def _field_to_block(v: int) -> bytes:
    return bytes(int(_REV8[x]) for x in v.to_bytes(BLOCK, "little"))


def _field_limbs(v: int) -> np.ndarray:
    """Field integer -> (16,) int32 byte limbs (little-endian limb order,
    the ``gf2_128`` semiring's carrier layout)."""
    return np.frombuffer(v.to_bytes(BLOCK, "little"), np.uint8).astype(
        np.int32)


def _hpowers(h: int, n: int) -> List[int]:
    """[H^1, ..., H^n] in the reflected-integer field."""
    out, v = [], 1
    for _ in range(n):
        v = sr.gf2k_mul_int(v, h, 128, GCM_POLY)
        out.append(v)
    return out


# ---------------------------------------------------------------------------
# Host AES (key material: the round keys and H = E_K(0), as a handshake
# installs them)
# ---------------------------------------------------------------------------

def _host_encrypt_block(rks: np.ndarray, block: bytes) -> bytes:
    """Pure-NumPy AES-128 block encryption of one block.

    H is a function of the key alone, so it is computed host-side like
    the key schedule itself: here for the chained lowering's GHASH
    weights, and in ``key_material`` for many keys at once.
    """
    sbox, _ = aes_mod.sbox_tables()
    st = np.frombuffer(block, np.uint8).astype(np.int32) ^ rks[0]
    for rnd in range(1, aes_mod.ROUNDS + 1):
        st = sbox[st]
        sq = st.reshape(4, 4)                     # sq[c, r] = st[4c + r]
        st = np.stack([sq[(np.arange(4) + r) % 4, r]
                       for r in range(4)], axis=1).reshape(16)
        if rnd < aes_mod.ROUNDS:
            ns = np.empty(16, np.int32)
            for c in range(4):
                col = st[4 * c:4 * c + 4]
                for r in range(4):
                    acc = 0
                    for j in range(4):
                        acc ^= int(sr.gf2_8_mul(
                            np.int32(aes_mod._MC_MAT[r, j]),
                            np.int32(col[j])))
                    ns[4 * c + r] = acc
            st = ns
        st = st ^ rks[rnd]
    return bytes(int(x) for x in st)


def _hash_key(key: bytes) -> int:
    """H = E_K(0^128) as a reflected field integer."""
    rks = aes_mod.key_expansion(key)
    return _block_to_field(_host_encrypt_block(rks, b"\x00" * BLOCK))


def _xtime(v: np.ndarray) -> np.ndarray:
    return ((v << 1) ^ np.where(v & 0x80, 0x1B, 0)).astype(np.uint8)


def key_material(keys: Sequence[bytes]) -> np.ndarray:
    """(S, KEY_BYTES) uint8: each AES-128 key expanded as a handshake
    installs it — the 11 round keys (FIPS 197 5.2) and H = E_K(0^128) —
    all keys at once, on the host."""
    k = np.frombuffer(b"".join(keys), np.uint8).reshape(-1, BLOCK)
    if k.shape[0] != len(keys) or any(len(x) != BLOCK for x in keys):
        raise ValueError("AES-128 keys must be 16 bytes each")
    sbox = aes_mod.sbox_tables()[0].astype(np.uint8)
    w = [k[:, 4 * i:4 * i + 4] for i in range(4)]
    rcon = np.uint8(1)
    for i in range(4, 4 * (aes_mod.ROUNDS + 1)):
        t = w[i - 1]
        if i % 4 == 0:
            t = sbox[np.roll(t, -1, axis=1)]
            t[:, 0] ^= rcon
            rcon = _xtime(np.array(rcon))
        w.append(w[i - 4] ^ t)
    rks = np.stack([np.concatenate(w[4 * r:4 * r + 4], axis=1)
                    for r in range(aes_mod.ROUNDS + 1)], axis=1)
    # H = E_K(0): columns c of the state are bytes 4c..4c+3.
    st = rks[:, 0].copy()
    c, r = np.meshgrid(np.arange(4), np.arange(4), indexing="ij")
    for rnd in range(1, aes_mod.ROUNDS + 1):
        sq = sbox[st].reshape(-1, 4, 4)[:, (c + r) % 4, r]
        if rnd < aes_mod.ROUNDS:
            t = sq[:, :, 0] ^ sq[:, :, 1] ^ sq[:, :, 2] ^ sq[:, :, 3]
            sq = sq ^ t[:, :, None] ^ _xtime(sq ^ np.roll(sq, -1, axis=2))
        st = sq.reshape(-1, BLOCK) ^ rks[:, rnd]
    return np.concatenate([rks.reshape(-1, BLOCK * (aes_mod.ROUNDS + 1)),
                           _REV8[st].astype(np.uint8)], axis=1)


def key_lanes(keys: Sequence[bytes]) -> np.ndarray:
    """(KEY_ROWS, S) int32 bits: the key operand of S lanes, lane i
    keyed by ``keys[i]``."""
    return np.unpackbits(key_material(keys), axis=1,
                         bitorder="little").T.astype(np.int32)


@jax.jit
def session_keys(table: Array, idx: Array) -> Array:
    """The key operand of ``len(idx)`` lanes gathered from a device
    session table (``key_material`` rows): lane i reads session
    ``idx[i]``, unpacked to (KEY_ROWS, len(idx)) int32 bits."""
    rows = table[idx]
    bits = (rows[:, :, None] >> jnp.arange(8, dtype=rows.dtype)) & 1
    return bits.reshape(idx.shape[0], KEY_ROWS).T.astype(jnp.int32)


# ---------------------------------------------------------------------------
# GHASH as crossbar passes over the gf2_128 semiring (chained lowering)
# ---------------------------------------------------------------------------

def _ghash_plan_key(key_or_h, mode: str, m: int) -> str:
    h = key_or_h if isinstance(key_or_h, int) else _hash_key(key_or_h)
    import hashlib
    hdig = hashlib.sha256(b"gcm-h:" + h.to_bytes(16, "little")).hexdigest()
    return f"gcm/ghash/{hdig[:12]}/{mode}{m}"


def ghash_plan(h: int, *, mode: str = "powers",
               m: int = 1) -> Tuple[xb.PermutePlan, str]:
    """The GHASH multiply as a registered ``gf2_128``-weighted plan.

    mode='horner': 1->1 multiply-by-H (one pass per absorbed block).
    mode='powers': M->1 gather weighted by [H^M, ..., H^1] — the whole
    Σ X_j·H^(M+1-j) as ONE pass.  Either way the matmul backends run
    the plan's tiled GF(2) bit lift (``lift_gf2_k``).
    """
    g = sr.gf2_k(128, GCM_POLY)
    key = _ghash_plan_key(h, mode, m)
    if mode == "horner":
        def build():
            w = jnp.asarray(_field_limbs(h)[None, None, :])
            return xb.gather_plan(jnp.zeros((1, 1), jnp.int32), 1,
                                  weights=w, semiring=g)
    elif mode == "powers":
        def build():
            pw = _hpowers(h, m)[::-1]            # H^M first: weight of X_1
            w = jnp.asarray(np.stack([_field_limbs(p)
                                      for p in pw])[None, :, :])
            return xb.gather_plan(jnp.arange(m, dtype=jnp.int32)[None, :],
                                  m, weights=w, semiring=g)
    else:
        raise ValueError(f"unknown ghash mode {mode!r}")
    return REGISTRY.get_or_register(key, build), key


def _blocks_to_limbs(data: bytes) -> np.ndarray:
    """Zero-padded blocks -> (M, 16) int32 field limbs (REV8 per byte)."""
    pad = (-len(data)) % BLOCK
    arr = np.frombuffer(data + b"\x00" * pad, np.uint8).reshape(-1, BLOCK)
    return _REV8[arr]


def ghash(h: int, data: bytes, *, mode: str = "powers",
          backend: str = "einsum",
          interpret: Optional[bool] = None) -> bytes:
    """GHASH_H(data) (length must be a multiple of 16) via crossbar
    passes: one (mode='powers') or one-per-block (mode='horner')."""
    if len(data) % BLOCK:
        raise ValueError(f"GHASH input must be whole blocks, got "
                         f"{len(data)} bytes")
    if not data:
        return b"\x00" * BLOCK
    limbs = _blocks_to_limbs(data)
    m = limbs.shape[0]
    if mode == "powers":
        plan, key = ghash_plan(h, mode="powers", m=m)
        out = REGISTRY.execute(key, jnp.asarray(limbs), backend=backend,
                               interpret=interpret)
        acc = np.asarray(out, np.int32)[0]
    else:
        plan, key = ghash_plan(h, mode="horner")
        acc = jnp.zeros((1, BLOCK), jnp.int32)
        for j in range(m):
            acc = REGISTRY.execute(key, acc ^ limbs[j][None, :],
                                   backend=backend, interpret=interpret)
        acc = np.asarray(acc, np.int32)[0]
    return bytes(int(_REV8[x & 0xFF]) for x in acc)


# ---------------------------------------------------------------------------
# The fused GCM plan program
# ---------------------------------------------------------------------------

# S-box scratch: per state byte, 32 nibble one-hot rows (16 low + 16
# high values) plus a 128-row product region (8 output bits x 16 high
# nibbles) where the low-nibble partial sums meet the high-nibble
# one-hot.  The byte-wide alternative (256 one-hot rows + a k=128
# parity decode) costs ~3.7x the gather columns per round.
ONEHOT_ROWS = 32 * BLOCK
PRODUCT_ROWS = 128 * BLOCK


def _geometry(pt_len: int, aad_len: int) -> Tuple[int, int, int]:
    """(m blocks, last-block bytes, a AAD blocks) for a record shape."""
    m = -(-pt_len // BLOCK)
    last = pt_len - BLOCK * (m - 1) if m else 0
    a = -(-aad_len // BLOCK)
    return m, last, a


def _layout(m: int, a: int) -> dict:
    """Row offsets of the state's regions; ``y`` is GHASH's 256-row
    window: the unreduced product of the last multiply by H, whose first
    128 rows are the next factor while it is formed."""
    lay = {"stream": 0, "ej0": 128 * m, "iv": 128 * m + 128,
           "len": 128 * m + 224, "aad": 128 * m + 352,
           "y": 128 * m + 352 + 128 * a, "onehot": 128}
    n = max(128 + ONEHOT_ROWS + PRODUCT_ROWS, lay["y"] + 256)
    lay["n"] = n + (-n) % 8
    return lay


def _ragged_gather(rows: List[List[int]], n: int, weights=None,
                   semiring=sr.GF2) -> xb.PermutePlan:
    """Row-indexed select lists -> a DROP-padded (n -> n) gather plan."""
    k = max([len(s) for s in rows if s] or [1])
    idx = np.full((n, k), pa.DROP, np.int32)
    w = None
    if weights is not None:
        w = np.zeros((n, k), np.int32)
    for i, sel in enumerate(rows):
        idx[i, :len(sel)] = sel
        if weights is not None:
            w[i, :len(sel)] = weights[i][:len(sel)]
    return xb.gather_plan(jnp.asarray(idx), n,
                          weights=None if w is None else jnp.asarray(w),
                          semiring=semiring)


def _bit_rows_of(plan: xb.PermutePlan) -> np.ndarray:
    """A 16-byte-level plan's idx, concrete, gather-normal."""
    return np.asarray(pa.to_gather(plan).idx, np.int32)


def _aes_bit_plans(n: int) -> dict:
    """The in-program AES round plans, embedded in the n-row state.

    nspread/u_row/psel/hirep/nfold implement the nibble-factored
    one-hot S-box (see module docstring); linear is the
    select-compacted GF(2) lift of the fused ShiftRows∘MixColumns plan;
    sr_bits is the final round's pure bit permutation.
    """
    sbox, _ = aes_mod.sbox_tables()
    onehot = 128                     # 32 rows per byte: lo | hi nibble
    prod = onehot + ONEHOT_ROWS      # 128 rows per byte: (bit b, hi h)

    rows: List[List[int]] = [[] for _ in range(n)]
    wts: List[List[int]] = [[] for _ in range(n)]
    for j in range(BLOCK):
        for u in range(32):
            base = 0 if u < 16 else 4        # low vs high nibble bits
            rows[onehot + 32 * j + u] = [8 * j + base + b
                                         for b in range(4)]
            wts[onehot + 32 * j + u] = [1 << b for b in range(4)]
    nspread = _ragged_gather(rows, n, weights=wts, semiring=sr.REAL)

    u_row = np.full(n, -1, np.int32)
    for j in range(BLOCK):
        u_row[onehot + 32 * j:onehot + 32 * (j + 1)] = \
            np.arange(32) % 16

    # P[b,h] = XOR_l sbox_bit(b, 16h+l) * onehot_lo[l]
    rows = [[] for _ in range(n)]
    for j in range(BLOCK):
        for b in range(8):
            for h in range(16):
                rows[prod + 128 * j + 16 * b + h] = [
                    onehot + 32 * j + l for l in range(16)
                    if (int(sbox[16 * h + l]) >> b) & 1]
    psel = _ragged_gather(rows, n)

    # high-nibble one-hot replicated across the 8 output-bit strips
    rows = [[] for _ in range(n)]
    for j in range(BLOCK):
        for b in range(8):
            for h in range(16):
                rows[prod + 128 * j + 16 * b + h] = [
                    onehot + 32 * j + 16 + h]
    hirep = _ragged_gather(rows, n)

    # S(v) bit b = XOR_h (hi[h] AND P[b,h])
    rows = [[] for _ in range(n)]
    for j in range(BLOCK):
        for b in range(8):
            rows[8 * j + b] = [prod + 128 * j + 16 * b + h
                               for h in range(16)]
    nfold = _ragged_gather(rows, n)

    lin16 = pa.compact_selects(xb.lift_gf2_k(aes_mod.round_linear_plan()))
    lin_idx = np.asarray(lin16.idx, np.int32)
    rows = [[] for _ in range(n)]
    for i in range(128):
        rows[i] = [int(s) for s in lin_idx[i] if s >= 0]
    linear = _ragged_gather(rows, n)

    aes_mod._ensure_plans(False, True)
    sr_idx = _bit_rows_of(REGISTRY["aes/shift_rows"])
    rows = [[] for _ in range(n)]
    for i in range(BLOCK):
        for b in range(8):
            rows[8 * i + b] = [8 * int(sr_idx[i, 0]) + b]
    sr_bits = _ragged_gather(rows, n)

    return {"nspread": nspread, "u_row": u_row, "psel": psel,
            "hirep": hirep, "nfold": nfold,
            "linear": linear, "sr_bits": sr_bits}


def _bits_row(block16: np.ndarray) -> np.ndarray:
    """(16,) byte values -> (128,) LSB-first bit rows."""
    return np.unpackbits(block16.astype(np.uint8),
                         bitorder="little").astype(np.int32)


def _live_bits(last: int) -> List[int]:
    """Bit rows of a block's first ``last`` bytes (the rest is the dead
    region of a partial final block)."""
    return [8 * j + b for j in range(last) for b in range(8)]


def _emit_aes_rounds(b: pp.ProgramBuilder, plans: dict) -> None:
    """SubBytes/linear/AddRoundKey for rounds 1..10 on register 0, the
    round keys read from the lane's key material (key blocks 1..10)."""
    for rnd in range(1, aes_mod.ROUNDS + 1):
        b.permute(1, 0, plans["nspread"])
        b.eq_const(1, 1, plans["u_row"])
        b.permute(0, 1, plans["psel"])      # state dead: P -> r0
        b.permute(1, 1, plans["hirep"])     # one-hots dead after this
        b.and_(1, 0, 1)                     # t[b,h] = hi[h] & P[b,h]
        b.permute(0, 1, plans["nfold"])     # S(v) bits, full overwrite
        b.permute(0, 0,
                  plans["linear" if rnd < aes_mod.ROUNDS else "sr_bits"])
        b.xor_key(0, rnd)


def _coeff_row(e: int) -> int:
    """Block bit row of field coefficient ``e`` (and back: an
    involution): coefficient 8j + k is bit 7 - k of byte j."""
    return 8 * (e >> 3) + 7 - (e & 7)


def _reduction() -> List[List[int]]:
    """Per coefficient c < 128: the product coefficients k < 255 whose
    x^k mod GCM_POLY has coefficient c — the fixed reduction of CLMUL's
    output, as select lists."""
    rows: List[List[int]] = [[] for _ in range(128)]
    for k in range(255):
        v = 1 << k
        for e in range(254, 127, -1):
            if (v >> e) & 1:
                v ^= GCM_POLY << (e - 128)
        for c in range(128):
            if (v >> c) & 1:
                rows[c].append(k)
    return rows


def build_gcm_program(pt_len: int, aad_len: int, *,
                      open_mode: bool = False) -> Tuple[pp.PlanProgram,
                                                        dict]:
    """The one-launch seal/open schedule for one record geometry.

    Returns (program, layout).  The program maps an ``(n, B)`` 0/1 bit
    state (B records as payload lanes, packed by ``_pack_records``) and
    the lanes' key material (``KEY_ROWS`` rows, ``lane_keys``) to
    ``[ciphertext|plaintext bits, tag bits]`` in register 0.  No step
    depends on a key: AddRoundKey XORs key blocks 0..10, and every
    multiply by H is a CLMUL with key block 11 followed by a reduction
    the next gather folds in.
    """
    m, last, a = _geometry(pt_len, aad_len)
    lay = _layout(m, a)
    n = lay["n"]
    y = lay["y"]
    plans = _aes_bit_plans(n)
    red = _reduction()

    def factor(rows: List[List[int]], src: int, dead=()) -> None:
        """Y's factor rows (coefficient order) <- the reduced product in
        the window ^ the block at ``src`` (block order, ``dead`` bit
        rows dropped)."""
        for c in range(128):
            r = _coeff_row(c)
            rows[y + c] = [y + k for k in red[c]] + (
                [] if r in dead else [src + r])

    def keep(rows: List[List[int]], base: int, count: int) -> None:
        for i in range(base, base + count):
            rows[i] = [i]

    # d1: stream <- plaintext/ciphertext rows, the later AAD blocks in
    # place, and the first AAD block as the first factor of Y.
    rows: List[List[int]] = [[] for _ in range(n)]
    keep(rows, lay["stream"], 128 * m)
    keep(rows, lay["aad"] + 128, 128 * (a - 1))
    for c in range(128 if a else 0):
        rows[y + c] = [lay["aad"] + _coeff_row(c)]
    d1 = _ragged_gather(rows, n)

    def fold_aad(j: int) -> xb.PermutePlan:
        """Y's factor <- (Y so far) ^ AAD block j (1-based), keeping the
        stream and the blocks after j."""
        rows = [[] for _ in range(n)]
        keep(rows, lay["stream"], 128 * m)
        keep(rows, lay["aad"] + 128 * j, 128 * (a - j))
        factor(rows, lay["aad"] + 128 * (j - 1))
        return _ragged_gather(rows, n)

    # d2: keep IV and LEN in register 3.
    rows = [[] for _ in range(n)]
    keep(rows, lay["iv"], 96)
    keep(rows, lay["len"], 128)
    d2 = _ragged_gather(rows, n)

    # Per-trip counter load: IV bits to rows 0..95 (the 32-bit counter
    # arrives as the trip's constant row, the whitening key as key
    # block 0).
    rows = [[] for _ in range(n)]
    for i in range(96):
        rows[i] = [lay["iv"] + i]
    ctr = _ragged_gather(rows, n)

    def ctr_const(t: int) -> np.ndarray:
        row = np.zeros(n, np.int32)
        ctr_bytes = np.zeros(BLOCK, np.int32)
        ctr_bytes[12:] = np.frombuffer(int(t + 1).to_bytes(4, "big"),
                                       np.uint8)
        row[:128] = _bits_row(ctr_bytes)
        return row

    # Trip 0 epilogue: park E_K(J0) (the tag mask) in its stream rows.
    rows = [[] for _ in range(n)]
    for i in range(128):
        rows[lay["ej0"] + i] = [i]
    place_ej0 = _ragged_gather(rows, n)

    def absorb_plan(dead: List[int]) -> xb.PermutePlan:
        """shift stream + append C + keep E(J0) + Y's factor <- Y ^ C_t,
        all one gather, C_t in the stream's front block; ``dead`` C rows
        are dropped from absorb and append (partial final block)."""
        dead_set = set(dead)
        rows = [[] for _ in range(n)]
        for i in range(128 * (m - 1)):
            rows[lay["stream"] + i] = [lay["stream"] + 128 + i]
        for r in range(128):
            if r not in dead_set:
                rows[lay["stream"] + 128 * (m - 1) + r] = [
                    lay["stream"] + r]
        keep(rows, lay["ej0"], 128)
        factor(rows, lay["stream"], dead_set)
        return _ragged_gather(rows, n)

    def route_ks(dead: List[int]) -> xb.PermutePlan:
        """Open trips: keystream bits routed onto the appended C block's
        rows (the XOR that turns it into plaintext)."""
        dead_set = set(dead)
        rows = [[] for _ in range(n)]
        for r in range(128):
            if r not in dead_set:
                rows[lay["stream"] + 128 * (m - 1) + r] = [r]
        return _ragged_gather(rows, n)

    # Epilogue: Y's factor <- Y ^ LEN, then the output: ciphertext
    # stream + tag = reduced (Y ^ LEN)·H ^ E(J0), in block order.
    rows = [[] for _ in range(n)]
    keep(rows, lay["stream"], 128 * m)
    keep(rows, lay["ej0"], 128)
    factor(rows, lay["len"])
    fin = _ragged_gather(rows, n)
    rows = [[] for _ in range(n)]
    keep(rows, lay["stream"], 128 * m)
    for r in range(128):
        rows[128 * m + r] = ([y + k for k in red[_coeff_row(r)]]
                             + [lay["ej0"] + r])
    e2 = _ragged_gather(rows, n)

    dead_last = ([r for r in range(128) if r not in set(_live_bits(last))]
                 if m else [])

    b = pp.ProgramBuilder(
        f"gcm_{'open' if open_mode else 'seal'}_m{m}", n, n_regs=4)
    b.permute(2, 0, d1)
    b.permute(3, 0, d2)
    for j in range(1, a + 1):               # Y = (..(A_1·H ^ A_2)·H ..)·H
        if j > 1:
            b.permute(2, 2, fold_aad(j))
        b.clmul(2, H_BLOCK, y)
    for t in range(m + 1):
        b.permute(0, 3, ctr)
        b.xor_const(0, 0, ctr_const(t))
        b.xor_key(0, 0)
        _emit_aes_rounds(b, plans)
        if t == 0:
            b.permute(1, 0, place_ej0)
            b.xor(2, 2, 1)
        else:
            dead = dead_last if t == m else []
            if not open_mode:
                b.xor(1, 0, 2)      # rows 0..127: C_t = ks ^ pt front
                b.permute(2, 1, absorb_plan(dead))
                b.clmul(2, H_BLOCK, y)
            else:
                # Absorb the received C_t straight from the stream, then
                # overlay the keystream on the appended copy -> PT_t.
                b.permute(1, 2, absorb_plan([]))
                b.clmul(1, H_BLOCK, y)
                b.permute(0, 0, route_ks(dead))
                b.xor(2, 1, 0)
    b.xor(1, 2, 3)
    b.permute(1, 1, fin)
    b.clmul(1, H_BLOCK, y)
    b.permute(0, 1, e2)
    return b.build(), lay


def seal_device_fn(pt_len: int, aad_len: int, *, open_mode: bool = False):
    """(fn, layout) where ``fn(bits, keys)`` is the COMPLETE device
    portion of a fused seal/open — one program launch from packed record
    bits and the lanes' key material to ciphertext+tag bits.  This is
    the region ``REGISTRY.audit_constant_time`` abstract-evaluates:
    everything outside it is host marshalling of data the schedule never
    reads.
    """
    _, program, lay = gcm_program(pt_len, aad_len, open_mode=open_mode)

    def fn(bts: Array, keys: Array) -> Array:
        return pp.run_program(program, bts, keys=keys, backend="megakernel")

    return fn, lay


def _program_key(pt_len: int, aad_len: int, open_mode: bool) -> str:
    m, last, a = _geometry(pt_len, aad_len)
    mode = "open" if open_mode else "seal"
    return f"gcm/aes128/{mode}/m{m}.{last}a{a}"


def gcm_program(pt_len: int, aad_len: int, *,
                open_mode: bool = False) -> Tuple[str, pp.PlanProgram,
                                                  dict]:
    """Registry-cached fused program for one record geometry, whatever
    the keys; returns (registry key, program, row layout)."""
    prog_key = _program_key(pt_len, aad_len, open_mode)
    holder: dict = {}

    def build():
        program, lay = build_gcm_program(pt_len, aad_len,
                                         open_mode=open_mode)
        holder["lay"] = lay
        return program

    program = REGISTRY.get_or_register_program(prog_key, build)
    lay = holder.get("lay") or _layout(*_geometry(pt_len, aad_len)[::2])
    return prog_key, program, lay


# ---------------------------------------------------------------------------
# Record packing (host <-> bit-state marshalling)
# ---------------------------------------------------------------------------

def _bits_matrix(records: Sequence[bytes], nbytes: int) -> np.ndarray:
    """B same-geometry byte strings -> (8*nbytes, B) LSB-first bit rows
    (zero-padded to ``nbytes``)."""
    arr = np.zeros((len(records), nbytes), np.uint8)
    for i, rec in enumerate(records):
        arr[i, :len(rec)] = np.frombuffer(rec, np.uint8)
    return np.unpackbits(arr, axis=1, bitorder="little").T.astype(np.int32)


def _len_block(aad_len: int, pt_len: int) -> bytes:
    return (8 * aad_len).to_bytes(8, "big") + (8 * pt_len).to_bytes(8, "big")


def _pack_records(lay: dict, ivs: Sequence[bytes], data: Sequence[bytes],
                  aads: Sequence[bytes], pt_len: int,
                  aad_len: int) -> np.ndarray:
    m, _, a = _geometry(pt_len, aad_len)
    bts = np.zeros((lay["n"], len(ivs)), np.int32)
    if m:
        bts[lay["stream"]:lay["stream"] + 128 * m] = _bits_matrix(
            data, BLOCK * m)
    bts[lay["iv"]:lay["iv"] + 96] = _bits_matrix(ivs, IV_BYTES)
    lb = _len_block(aad_len, pt_len)
    bts[lay["len"]:lay["len"] + 128] = _bits_matrix(
        [lb] * len(ivs), BLOCK)
    if a:
        bts[lay["aad"]:lay["aad"] + 128 * a] = _bits_matrix(
            aads, BLOCK * a)
    return bts


def _unpack_records(out: np.ndarray, m: int, pt_len: int
                    ) -> Tuple[List[bytes], List[bytes]]:
    """(n, B) output bits -> per-record (body bytes, 16-byte tag)."""
    body_bits = out[:128 * m].T.astype(np.uint8)
    tag_bits = out[128 * m:128 * m + 128].T.astype(np.uint8)
    bodies = [np.packbits(row, bitorder="little")[:pt_len].tobytes()
              for row in body_bits]
    tags = [np.packbits(row, bitorder="little").tobytes()
            for row in tag_bits]
    return bodies, tags


# ---------------------------------------------------------------------------
# Fused batch seal/open
# ---------------------------------------------------------------------------

def _check_batch(ivs, records, aads):
    if not ivs:
        raise ValueError("empty record batch")
    if aads is None:
        aads = [b""] * len(ivs)
    if not (len(ivs) == len(records) == len(aads)):
        raise ValueError(
            f"batch length mismatch: {len(ivs)} IVs, {len(records)} "
            f"records, {len(aads)} AADs")
    for iv in ivs:
        if len(iv) != IV_BYTES:
            raise ValueError(f"GCM nonce must be {IV_BYTES} bytes "
                             f"(96-bit IV fast path), got {len(iv)}")
    if len({len(r) for r in records}) != 1 or len({len(x)
                                                   for x in aads}) != 1:
        raise ValueError(
            "fused GCM batches share one record geometry (same plaintext "
            "and AAD lengths); route mixed sizes through serve.batching "
            "buckets")
    return aads


def no_phase(name: Optional[str] = None) -> None:
    """The default ``phase`` hook of the batch seal: times nothing.

    A caller that times the stages of a seal passes a callable told the
    name of each stage as it begins (``launch``: the transfer and the
    program launch; ``sync``: the wait for the device's result;
    ``unpack``: the output bits back to bytes), which ends the stage
    before it; ``serve.batching`` makes its bucket spans from them.
    """


def _run_fused(lanes, ivs, records, aads, pt_len: int, aad_len: int,
               *, open_mode: bool, fixed_latency: bool,
               interpret: Optional[bool], phase=no_phase):
    m, _, a = _geometry(pt_len, aad_len)
    prog_key, program, lay = gcm_program(pt_len, aad_len,
                                         open_mode=open_mode)
    bits = _pack_records(lay, ivs, records, aads, pt_len, aad_len)
    phase("launch")
    bts = jnp.asarray(bits)
    op = "gcm_open" if open_mode else "gcm_seal"
    launches0 = pp.program_launch_count()
    passes0 = pp.passes_avoided_count()

    def run():
        with _obs.span(op, records=len(ivs), blocks=m, aad_blocks=a,
                       program=prog_key):
            return pp.run_program(program, bts, keys=lanes,
                                  backend="megakernel",
                                  interpret=interpret)

    if fixed_latency:
        with REGISTRY.observe(
                (op, m, a, pt_len % BLOCK),
                shapes=(tuple(bts.shape), str(bts.dtype)),
                backend="megakernel", program_keys=(prog_key,),
                expect_apply_calls=0, expect_program_launches=1):
            out = run()
    else:
        out = run()
    phase("sync")
    out_np = np.asarray(out)
    telemetry.incr(f"{op}_calls")
    telemetry.incr(f"{op}_records", len(ivs))
    telemetry.incr(f"{op}_launches",
                   pp.program_launch_count() - launches0)
    telemetry.incr("gcm_passes_avoided",
                   pp.passes_avoided_count() - passes0)
    phase("unpack")
    return _unpack_records(out_np, m, pt_len)


# ---------------------------------------------------------------------------
# Chained per-block lowering (the four-backend reference path)
# ---------------------------------------------------------------------------

def _seal_chained_core(key: bytes, iv: bytes, data: bytes, aad: bytes, *,
                       open_mode: bool, backend: str,
                       interpret: Optional[bool]
                       ) -> Tuple[bytes, bytes]:
    """(body, tag) via chained passes: one batched CTR keystream call
    (J0 and all block counters as payload width), then one GHASH Horner
    pass per block."""
    m = -(-len(data) // BLOCK)
    j0 = iv + b"\x00\x00\x00\x01"
    ks = aes_mod.aes128_ctr_keystream(key, j0, m + 1, backend=backend,
                                      interpret=interpret)
    tag_mask, ks = ks[:BLOCK], ks[BLOCK:]
    body = bytes(a ^ b for a, b in zip(data, ks))
    ct = data if open_mode else body
    h = _hash_key(key)
    pad_c = ct + b"\x00" * ((-len(ct)) % BLOCK)
    pad_a = aad + b"\x00" * ((-len(aad)) % BLOCK)
    s = ghash(h, pad_a + pad_c + _len_block(len(aad), len(data)),
              mode="horner", backend=backend, interpret=interpret)
    tag = bytes(a ^ b for a, b in zip(s, tag_mask))
    return body, tag


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def _record_keys(key, n: int) -> list:
    """One key for the batch, or one per record, as a list of n keys."""
    keys = [key] * n if isinstance(key, (bytes, bytearray)) else list(key)
    if len(keys) != n:
        raise ValueError(f"{len(keys)} keys for {n} records")
    return keys


def seal_lanes(lanes, ivs: Sequence[bytes], plaintexts: Sequence[bytes],
               aads: Optional[Sequence[bytes]] = None, *,
               fixed_latency: bool = False,
               interpret: Optional[bool] = None,
               phase=no_phase) -> List[bytes]:
    """The fused seal of B same-geometry records whose key material is
    already the key operand ``lanes`` ((KEY_ROWS, B) or padded to
    ``pp.lane_pad(B)`` lanes, lane i keying record i: ``key_lanes`` or
    ``session_keys``); one program launch."""
    aads = _check_batch(ivs, plaintexts, aads)
    bodies, tags = _run_fused(lanes, ivs, plaintexts, aads,
                              len(plaintexts[0]), len(aads[0]),
                              open_mode=False, fixed_latency=fixed_latency,
                              interpret=interpret, phase=phase)
    return [c + t for c, t in zip(bodies, tags)]


def aes128_gcm_seal_batch(key, ivs: Sequence[bytes],
                          plaintexts: Sequence[bytes],
                          aads: Optional[Sequence[bytes]] = None, *,
                          backend: str = "fused",
                          fixed_latency: bool = False,
                          interpret: Optional[bool] = None,
                          phase=no_phase) -> List[bytes]:
    """Seal B same-geometry records; returns ``ciphertext || tag`` each.

    ``key`` is one 16-byte key for the batch or a sequence of one per
    record.  backend='fused' runs the whole batch as ONE plan-program
    launch, the keys expanded on the host into its key operand; any
    crossbar backend name runs the chained per-block lowering per record
    (the CAVP reference path), which launches and syncs per block and so
    reads as one ``launch`` stage to ``phase`` (``no_phase`` says what
    it is told).
    """
    aads = _check_batch(ivs, plaintexts, aads)
    keys = _record_keys(key, len(ivs))
    if backend == "fused":
        return seal_lanes(key_lanes(keys), ivs, plaintexts, aads,
                          fixed_latency=fixed_latency, interpret=interpret,
                          phase=phase)
    phase("launch")
    out = []
    for k, iv, pt, aad in zip(keys, ivs, plaintexts, aads):
        c, t = _seal_chained_core(k, iv, pt, aad, open_mode=False,
                                  backend=backend, interpret=interpret)
        out.append(c + t)
    return out


def aes128_gcm_open_batch(key, ivs: Sequence[bytes],
                          ciphertexts: Sequence[bytes],
                          aads: Optional[Sequence[bytes]] = None, *,
                          backend: str = "fused",
                          fixed_latency: bool = False,
                          interpret: Optional[bool] = None) -> List[bytes]:
    """Open B sealed records (``ciphertext || tag`` each, ``key`` one
    key or one per record); raises ``InvalidTagError`` (with the failing
    indices) unless every tag verifies — no plaintext escapes a failed
    batch."""
    aads = _check_batch(ivs, ciphertexts, aads)
    keys = _record_keys(key, len(ivs))
    if any(len(c) < TAG_BYTES for c in ciphertexts):
        raise ValueError("sealed record shorter than the 16-byte tag")
    bodies_in = [c[:-TAG_BYTES] for c in ciphertexts]
    tags_in = [c[-TAG_BYTES:] for c in ciphertexts]
    pt_len, aad_len = len(bodies_in[0]), len(aads[0])
    if backend == "fused":
        bodies, tags = _run_fused(key_lanes(keys), ivs, bodies_in, aads,
                                  pt_len, aad_len, open_mode=True,
                                  fixed_latency=fixed_latency,
                                  interpret=interpret)
    else:
        bodies, tags = [], []
        for k, iv, ct, aad in zip(keys, ivs, bodies_in, aads):
            b_, t_ = _seal_chained_core(k, iv, ct, aad, open_mode=True,
                                        backend=backend,
                                        interpret=interpret)
            bodies.append(b_)
            tags.append(t_)
    bad = [i for i, (got, want) in enumerate(zip(tags, tags_in))
           if not hmac.compare_digest(got, want)]
    if bad:
        raise InvalidTagError(bad)
    return bodies


def aes128_gcm_seal(key: bytes, iv: bytes, plaintext: bytes,
                    aad: bytes = b"", *, backend: str = "fused",
                    fixed_latency: bool = False,
                    interpret: Optional[bool] = None) -> bytes:
    """Seal one record: returns ``ciphertext || 16-byte tag``."""
    return aes128_gcm_seal_batch(key, [iv], [plaintext], [aad],
                                 backend=backend,
                                 fixed_latency=fixed_latency,
                                 interpret=interpret)[0]


def aes128_gcm_open(key: bytes, iv: bytes, sealed: bytes,
                    aad: bytes = b"", *, backend: str = "fused",
                    fixed_latency: bool = False,
                    interpret: Optional[bool] = None) -> bytes:
    """Open one sealed record; raises ``InvalidTagError`` on a bad tag."""
    return aes128_gcm_open_batch(key, [iv], [sealed], [aad],
                                 backend=backend,
                                 fixed_latency=fixed_latency,
                                 interpret=interpret)[0]


# The lift cache backs every GHASH bit-lift the matmul backends run;
# export its occupancy lazily so dashboards see eviction pressure from
# many concurrent (H, width) lifts without a hot-path counter.
_obs.metrics.gauge_fn("ghash_lift_cache",
                      lambda: xb.lift_cache_info()["size"])
