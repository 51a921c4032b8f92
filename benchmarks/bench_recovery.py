"""Partial-batch recovery cost: replay-only vs whole-batch re-execution.

A device fault mid-batch on an S=8 host-platform mesh loses one shard's
lane window.  The serving engine's partial-results path salvages the
seven completed shards from the per-lane result journal and replays
ONLY the lost window on a survivor device; the pre-PR behaviour
(``BatchingOptions(partial_results=False)``) pays a full doomed attempt
plus a full re-execution on the survivor mesh.  This benchmark measures
both recoveries end-to-end through the real serving engine:

* **replay_only** — a real ``core.faults.inject_device_fault`` kills
  device 3 mid-batch; the timed drain covers salvage + force-trip +
  one-window replay.  Every digest is checked against hashlib.
* **whole_batch** — the whole-batch path cannot be interrupted
  mid-flight (it has no per-shard boundary, which is exactly the
  point), so its recovery is composed from its two real halves: one
  full-mesh batch (the doomed attempt whose results a fault would
  discard) plus one full re-execution on the survivor mesh after the
  device trip.  Both halves are measured, not modeled.

The interesting number is the ratio: replay-only re-executes 1/S of
the lanes instead of (S+S')/S, so recovery latency should drop well
below 2x a clean batch.  Payloads are ~15 keccak blocks each so
per-lane absorb compute dominates launch overhead — on the host
platform every "device" shares the same CPU, and with 1-block lanes
both regimes disappear into fixed dispatch cost.

The mesh is the process's devices: S=8 under ``JAX_PLATFORMS=cpu``
(forced host-platform devices, set by ``main`` before JAX starts) and
the chips elsewhere.  Results land in BENCH_recovery.json (quick:
BENCH_recovery_quick.json).

Usage: PYTHONPATH=src python -m benchmarks.bench_recovery [--quick]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import time

import numpy as np

from benchmarks.common import force_host_devices

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_JSON = os.path.join(REPO, "BENCH_recovery.json")
OUT_JSON_QUICK = os.path.join(REPO, "BENCH_recovery_quick.json")

SHARDS = 8
LANES = 64           # b_pad: 8 lanes per shard on the full mesh
PAYLOAD_BYTES = 4096  # ~30 absorb blocks/lane: compute-bound lanes
FAULT_DEVICE = 3

_TELEMETRY_KEYS = ("serve_shard_launches", "serve_shards_salvaged",
                   "lanes_replayed", "serve_partial_batches",
                   "serve_mesh_device_drops", "serve_completed")


def _payloads(n, seed=0):
    rng = np.random.default_rng(seed)
    # One geometry bucket: every lane the same block count.
    return [rng.bytes(PAYLOAD_BYTES) for _ in range(n)]


def _drain(eng, payloads):
    reqs = [eng.submit(p) for p in payloads]
    while eng.run_once():
        pass
    return reqs


def _check(reqs, payloads) -> bool:
    return all(r.result() == hashlib.sha3_256(p).digest()
               for p, r in zip(payloads, reqs))


def _heal(eng) -> None:
    """Rejoin every tripped device (between recovery iterations)."""
    eng.device_health.breaker.reset()


def _trip(eng, device) -> None:
    while eng.device_health.is_healthy(device):
        eng.report_device_fault(device)


def _stats(samples_ms) -> dict:
    arr = np.asarray(samples_ms)
    return {"iters": len(samples_ms),
            "mean_ms": round(float(arr.mean()), 3),
            "p50_ms": round(float(np.percentile(arr, 50)), 3),
            "p99_ms": round(float(np.percentile(arr, 99)), 3)}


def bench_inner(iters: int) -> dict:
    import jax
    from jax.sharding import Mesh
    from repro.core import faults, telemetry
    from repro.serve.batching import BatchingEngine, BatchingOptions

    assert len(jax.devices()) >= SHARDS, (
        f"need {SHARDS} devices, got {len(jax.devices())}: on the CPU run "
        "the module entry point under JAX_PLATFORMS=cpu, which forces "
        "host devices before JAX starts")
    mesh = Mesh(np.asarray(jax.devices()[:SHARDS]), ("data",))
    payloads = _payloads(LANES)

    def engine(partial):
        return BatchingEngine(
            BatchingOptions(max_batch=LANES, max_queue=4 * LANES,
                            mesh=mesh, double_buffer=False,
                            partial_results=partial),
            start=False)

    all_exact = True

    # -- replay-only: a real mid-batch device fault --------------------------
    eng = engine(partial=True)
    all_exact &= _check(_drain(eng, payloads), payloads)     # warm full mesh
    with faults.inject_device_fault(FAULT_DEVICE, max_fires=LANES):
        all_exact &= _check(_drain(eng, payloads), payloads)  # warm recovery
    _heal(eng)
    base = telemetry.snapshot()
    replay_ms = []
    for _ in range(iters):
        with faults.inject_device_fault(FAULT_DEVICE, max_fires=LANES):
            t0 = time.perf_counter()
            reqs = _drain(eng, payloads)
            replay_ms.append((time.perf_counter() - t0) * 1e3)
        all_exact &= _check(reqs, payloads)
        _heal(eng)
    snap = telemetry.snapshot()
    replay_tel = {k: snap.get(k, 0) - base.get(k, 0)
                  for k in _TELEMETRY_KEYS}

    # -- whole-batch: doomed full attempt + full survivor re-execution -------
    eng2 = engine(partial=False)
    all_exact &= _check(_drain(eng2, payloads), payloads)    # warm full mesh
    _trip(eng2, FAULT_DEVICE)
    all_exact &= _check(_drain(eng2, payloads), payloads)    # warm survivors
    _heal(eng2)
    whole_ms = []
    for _ in range(iters):
        t0 = time.perf_counter()
        _drain(eng2, payloads)               # the attempt a fault discards
        _trip(eng2, FAULT_DEVICE)
        reqs = _drain(eng2, payloads)        # whole-batch re-execution
        whole_ms.append((time.perf_counter() - t0) * 1e3)
        all_exact &= _check(reqs, payloads)
        _heal(eng2)

    replay = dict(_stats(replay_ms), regime="replay_only", shards=SHARDS,
                  lanes=LANES, lanes_reexecuted_per_fault=LANES // SHARDS,
                  telemetry=replay_tel)
    whole = dict(_stats(whole_ms), regime="whole_batch", shards=SHARDS,
                 lanes=LANES, lanes_reexecuted_per_fault=2 * LANES)
    return {"rows": [replay, whole], "all_exact": bool(all_exact),
            "devices": len(jax.devices())}


def run(quick: bool = False) -> dict:
    import jax
    from benchmarks.common import row

    iters = 2 if quick else 8
    # In this process, on the devices it has: a child started after JAX
    # is up could not share the chip.
    fragment = bench_inner(iters)
    replay, whole = fragment["rows"]
    for r in fragment["rows"]:
        row("recovery", regime=r["regime"], p50_ms=r["p50_ms"],
            p99_ms=r["p99_ms"],
            lanes_reexecuted=r["lanes_reexecuted_per_fault"])

    tel = replay["telemetry"]
    acceptance = {
        "criterion": f"a device fault mid-batch on an S={SHARDS} mesh "
                     "replays only the lost shard's lane window "
                     "(telemetry-asserted), every digest stays hashlib-"
                     "exact, and replay-only recovery beats whole-batch "
                     "re-execution",
        "replay_p50_ms": replay["p50_ms"],
        "replay_p99_ms": replay["p99_ms"],
        "whole_batch_p50_ms": whole["p50_ms"],
        "whole_batch_p99_ms": whole["p99_ms"],
        "speedup_replay_vs_whole_batch": round(
            whole["p50_ms"] / max(replay["p50_ms"], 1e-9), 3),
        "lanes_replayed_per_fault": LANES // SHARDS,
        "all_exact": fragment["all_exact"],
        # Telemetry ledger over the timed iterations: per fault, S
        # dispatches + 1 replay, S-1 shards salvaged, LANES/S lanes
        # replayed.
        "replay_only_launch_ledger_ok": bool(
            tel["serve_shard_launches"] == iters * (SHARDS + 1)
            and tel["serve_shards_salvaged"] == iters * (SHARDS - 1)
            and tel["lanes_replayed"] == iters * (LANES // SHARDS)),
    }
    acceptance["pass"] = bool(
        acceptance["all_exact"]
        and acceptance["replay_only_launch_ledger_ok"]
        and replay["p50_ms"] < whole["p50_ms"])
    report = {
        "benchmark": "recovery",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "jax_backend": jax.default_backend(),
        "quick": quick,
        "rows": fragment["rows"],
        "acceptance": acceptance,
    }
    out_path = OUT_JSON_QUICK if quick else OUT_JSON
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"# wrote {out_path}")
    print(f"# acceptance: {acceptance}")
    assert acceptance["pass"], acceptance
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    force_host_devices()
    run(quick=args.quick)


if __name__ == "__main__":
    main()
