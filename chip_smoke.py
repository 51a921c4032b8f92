"""Chip smoke test: drive the permutation engine's main paths once on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: mesh-sharded serving only

One process, the repo's normal entry points, phases in order; the first
failure ends the run with a non-zero exit and no result line.

1. device   - the backend is a TPU and Pallas kernels run compiled;
2. crossbar - vrgather/vcompress/vslideup through ``apply_plan`` on the
              ``kernel`` and ``sparse`` backends at n=4096, D=512 f32, and
              a T=4096, E=8, C=1024 MoE dispatch on ``sparse``, each
              bit-identical to ``backend="reference"``;
3. crypto   - a ``BatchingEngine`` with its default chain serves SHA3-256
              and AES-128-GCM seal requests; digests equal hashlib, seals
              equal the reference-backend lowering, and every bucket ran
              on the megakernel with no fault, retry or fallback;
4. model    - minicpm-2b at its published widths and depth, random
              weights, serves 4 prompts x 16 greedy tokens through
              ``ServingEngine``; each first token is checked against the
              model's own parallel forward over the prompt.

With ``--chips 4`` a ``BatchingEngine`` on a 4-device mesh answers the
phase-3 requests; results must equal hashlib, the reference seals and a
one-device engine's results, and the SHA3 shards must come back from all
four devices.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
N, D = 4096, 512
SHA3_REQUESTS, SHA3_MAX_BYTES = 256, 8192
GCM_SIZES, GCM_AAD, GCM_REQUESTS = (16, 256, 1024), 16, 66
GCM_KEY = bytes(range(16))


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def phase_device(chips: int):
    import jax
    from repro.kernels.ops import default_interpret

    devices = jax.devices()
    dev = devices[0]
    log("device", devices=devices, platform=dev.platform,
        kind=repr(dev.device_kind), count=len(devices))
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX platform is {dev.platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"--chips {chips} needs {chips} devices, "
                         f"found {len(devices)}")
    if default_interpret() is not False:
        raise SystemExit("Pallas would run in interpret mode on this host")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def phase_crossbar():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import moe_dispatch as md
    from repro.core import permute as P

    rng = np.random.default_rng(SEED)
    x = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    idx = jnp.asarray(rng.integers(-8, N + 8, N), jnp.int32)   # some OOB
    mask = jnp.asarray(rng.random(N) < 0.4)
    ops = {
        "vrgather": lambda b: P.vrgather(x, idx, backend=b),
        "vcompress": lambda b: P.vcompress(x, mask, backend=b),
        "vslideup": lambda b: P.vslideup(x, 37, backend=b),
    }
    for name, op in ops.items():
        want = np.asarray(op("reference"))
        for backend in ("kernel", "sparse"):
            first, t_first = timed(lambda: np.asarray(op(backend)))
            again, t_warm = timed(lambda: np.asarray(op(backend)))
            if not (np.array_equal(first, want)
                    and np.array_equal(again, want)):
                raise AssertionError(f"{name} on {backend} differs from "
                                     "the reference backend")
            log("crossbar", op=name, backend=backend, n=N, d=D,
                first_call_s=t_first, warm_s=t_warm)

    t, e, c = 4096, 8, 1024
    logits = jax.random.normal(jax.random.PRNGKey(SEED), (t, e))
    routing = md.make_routing(logits, num_experts=e, k=2, capacity=c)
    tokens = jnp.asarray(rng.standard_normal((t, D)), jnp.float32)
    want = np.asarray(md.dispatch(tokens, routing, backend="reference"))
    got, t_first = timed(lambda: np.asarray(
        md.dispatch(tokens, routing, backend="sparse")))
    if not np.array_equal(got, want):
        raise AssertionError("MoE dispatch on sparse differs from the "
                             "reference backend")
    log("crossbar", op="moe_dispatch", backend="sparse", tokens=t,
        experts=e, capacity=c, first_call_s=t_first)


def crypto_requests():
    import numpy as np

    rng = np.random.default_rng(SEED)
    sha3 = [rng.bytes(int(n)) for n in
            rng.integers(0, SHA3_MAX_BYTES + 1, SHA3_REQUESTS)]
    gcm = []
    for i in range(GCM_REQUESTS):
        size = GCM_SIZES[i % len(GCM_SIZES)]
        gcm.append((rng.bytes(12), rng.bytes(size), rng.bytes(GCM_AAD)))
    return sha3, gcm


def reference_seals(gcm_records):
    """The reference-backend lowering of every record, computed on the
    host's CPU device: an oracle independent of the chip, and not paced
    by one device dispatch per eager reference op."""
    import jax
    from repro.crypto import gcm as G

    out = []
    with jax.default_device(jax.devices("cpu")[0]):
        for size in GCM_SIZES:
            recs = [r for r in gcm_records if len(r[1]) == size]
            seals = G.aes128_gcm_seal_batch(
                GCM_KEY, [r[0] for r in recs], [r[1] for r in recs],
                [r[2] for r in recs], backend="reference")
            out.extend(zip(recs, seals))
    lookup = dict(out)
    return [lookup[r] for r in gcm_records]


def serve(engine, sha3, gcm_records):
    """Submit every request, wait for all; returns (digests, seals, reqs)."""
    from repro.serve.batching import encode_aead_record

    reqs = [engine.submit(m, op="sha3_256") for m in sha3]
    reqs += [engine.submit(encode_aead_record(*r), op="gcm_seal")
             for r in gcm_records]
    values = [r.result(timeout=900) for r in reqs]
    return values[:len(sha3)], values[len(sha3):], reqs


def check_served(engine, reqs, digests, seals, sha3, want_seals):
    from repro.core import telemetry

    for msg, got in zip(sha3, digests):
        if got != hashlib.sha3_256(msg).digest():
            raise AssertionError("a served SHA3-256 digest differs from "
                                 "hashlib")
    if seals != want_seals:
        raise AssertionError("a served GCM seal differs from the "
                             "reference-backend lowering")
    backends = {r.backend for r in reqs} | {e[2] for e in engine.batch_log}
    if backends != {"megakernel"}:
        raise AssertionError(f"buckets ran on {sorted(backends)}, not only "
                             "on the megakernel")
    snap = telemetry.snapshot()
    bad = {k: snap.get(k, 0) for k in ("resilience_faults",
                                       "resilience_retries",
                                       "resilience_fallbacks")}
    if any(bad.values()):
        raise AssertionError(f"the resilient executor degraded: {bad}")


def phase_crypto(sha3, gcm_records, want_seals):
    from repro.core import plan_program as pp
    from repro.core import telemetry
    from repro.serve.batching import BatchingEngine, BatchingOptions

    telemetry.reset()
    launches0 = pp.program_launch_count()
    engine = BatchingEngine(BatchingOptions(aead_key=GCM_KEY))
    try:
        (digests, seals, reqs), seconds = timed(
            lambda: serve(engine, sha3, gcm_records))
    finally:
        engine.close()
    check_served(engine, reqs, digests, seals, sha3, want_seals)
    log("crypto", sha3_requests=len(digests), gcm_requests=len(seals),
        buckets=len(engine.batch_log),
        program_launches=pp.program_launch_count() - launches0,
        program_cache=pp.program_cache_info(), wall_s=seconds)
    return digests, seals


def phase_model():
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import transformer as M
    from repro.models.model_zoo import build
    from repro.serve import ServeOptions, ServingEngine

    # f32 weights (10.9 GB) plus the decode step's bf16 copy of them do
    # not fit one v5e's 16 GB: hold the weights in bf16, the dtype every
    # matmul computes in anyway.
    cfg = dataclasses.replace(get_config("minicpm-2b"),
                              param_dtype="bfloat16")
    api = build(cfg)
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(SEED))
    param_bytes = sum(leaf.size * leaf.dtype.itemsize
                      for leaf in jax.tree.leaves(shapes))
    params, t_init = timed(lambda: jax.block_until_ready(
        jax.jit(api.init)(jax.random.PRNGKey(SEED))))
    log("model", arch=cfg.name, layers=cfg.num_layers, d_model=cfg.d_model,
        param_bytes=param_bytes, param_dtype=cfg.param_dtype,
        compute_dtype=cfg.compute_dtype, init_s=t_init)

    slots, prompt_len, new_tokens = 4, 8, 16
    rng = np.random.default_rng(SEED)
    prompts = rng.integers(0, cfg.vocab_size, (slots, prompt_len)).tolist()
    engine = ServingEngine(api, ServeOptions(batch_slots=slots,
                                             max_new_tokens=new_tokens),
                           max_seq=128, cache_dtype=jnp.float32)
    outs, t_gen = timed(lambda: engine.generate(
        params, prompts, key=jax.random.PRNGKey(SEED + 1)))
    if [len(o) for o in outs] != [new_tokens] * slots:
        raise AssertionError(f"generated lengths {[len(o) for o in outs]}")

    forward = jax.jit(lambda p, t: M.lm_logits(p, M.lm_hidden(p, t, cfg),
                                               cfg))
    logits, t_fwd = timed(lambda: np.asarray(
        forward(params, jnp.asarray(prompts, jnp.int32))[:, -1],
        np.float32))
    # Decode and the parallel forward round differently in bf16, so the
    # decoded token must be the forward's argmax or within 1% of the
    # logit range of it.
    exact, worst = 0, 0.0
    for row, out in zip(logits, outs):
        best = int(np.argmax(row))
        gap = float(row[best] - row[out[0]]) / float(row.max() - row.min())
        exact += best == out[0]
        worst = max(worst, gap)
        if not np.isfinite(row).all() or gap > 0.01:
            raise AssertionError(
                f"first token {out[0]} vs parallel argmax {best} "
                f"(gap {gap:.4f} of the logit range)")
    log("model", prompts=slots, new_tokens=new_tokens, generate_s=t_gen,
        forward_s=t_fwd, first_token_exact=f"{exact}/{slots}",
        worst_gap=worst)


def phase_mesh(sha3, gcm_records, want_seals):
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import telemetry
    from repro.serve.batching import BatchingEngine, BatchingOptions

    devices = jax.devices()[:4]
    single = BatchingEngine(BatchingOptions(aead_key=GCM_KEY))
    try:
        one_digests, one_seals, _ = serve(single, sha3, gcm_records)
    finally:
        single.close()

    telemetry.reset()
    mesh = Mesh(np.asarray(devices), ("data",))
    engine = BatchingEngine(BatchingOptions(aead_key=GCM_KEY, mesh=mesh))
    try:
        (digests, seals, reqs), seconds = timed(
            lambda: serve(engine, sha3, gcm_records))
    finally:
        engine.close()
    check_served(engine, reqs, digests, seals, sha3, want_seals)
    if digests != one_digests or seals != one_seals:
        raise AssertionError("mesh results differ from the one-device "
                             "engine's")
    snap = telemetry.snapshot()
    lanes = {d.id: snap.get(f"serve_lanes_device{d.id}", 0)
             for d in devices}
    if not all(lanes.values()):
        raise AssertionError(f"SHA3 shards did not come back from all four "
                             f"devices: lanes per device {lanes}")
    log("mesh", devices=len(devices), sha3_requests=len(digests),
        gcm_requests=len(seals), lanes_per_device=lanes,
        shard_launches=snap.get("serve_shard_launches", 0), wall_s=seconds)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    log("setup", compile_cache=use_compile_cache())
    device = phase_device(args.chips)
    sha3, gcm_records = crypto_requests()
    want_seals, t_ref = timed(lambda: reference_seals(gcm_records))
    log("reference", gcm_seals=len(want_seals), seconds=t_ref)
    if args.chips == 4:
        phase_mesh(sha3, gcm_records, want_seals)
    else:
        _, t = timed(phase_crossbar)
        log("crossbar", phase_s=t)
        _, t = timed(lambda: phase_crypto(sha3, gcm_records, want_seals))
        log("crypto", phase_s=t)
        _, t = timed(phase_model)
        log("model", phase_s=t)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
