"""Serving-path throughput/latency under the resilience stack.

Queues 10^4 SHA3-256 requests (mixed 1/2-block payloads) into the
continuous-batching engine and drains them synchronously, measuring
hashes/sec and p50/p99 request latency in two regimes:

* **no_fault** — the clean path: every bucket answered by the primary
  backend, zero degradations;
* **fault_1pct** — 1% of crossbar passes raise an injected launch
  failure (seed-deterministic, ``core.faults``): with 24 passes per
  permutation roughly a fifth of batches hit a fault, retry, and — when
  the retry also faults — fall back down the chain.  The acceptance
  criterion is that **every digest still equals hashlib** and the
  overhead is visible as retries/fallbacks in telemetry, not as wrong
  answers or hung requests.

Latency here is queue-drain latency (submit-all, then serve): p99 ≈
total drain time by construction; p50 is the half-queue point.  The
interesting quantities are throughput and the fault-regime *ratios*
(throughput and tail-latency cost of 1% injected faults).

Off-TPU the chain starts at einsum (``resilience.default_chain``), so
the numbers measure the XLA take-fastpath, not Pallas interpret mode.

When the process has more than one device, the full run adds the
**mesh regime**, in the same process: 10^6 requests through the
threaded engine with bucket sharding, double-buffered host→device
feeds, and the measured tuning table — sustained hashes/sec and p50/p99
appended alongside the single-device rows.  On the CPU the devices come
from ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` set before
the run; those host devices time-slice ``host_cores`` physical
core(s), and the mesh rows record that rather than claiming a
device-parallel speedup.  ``--mesh`` runs ONLY the mesh regime (the CI
mesh smoke job does this under that flag).

Results land in BENCH_serving.json (quick: BENCH_serving_quick.json so
CI smoke never clobbers the committed sweep).

Usage: PYTHONPATH=src python -m benchmarks.bench_serving
           [--quick] [--mesh] [--mesh-out PATH] [--mesh-requests N]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import time

import jax
import numpy as np

from benchmarks.common import row
from repro import obs
from repro.core import faults, telemetry
from repro.core.resilience import default_chain
from repro.serve.batching import BatchingEngine, BatchingOptions

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_JSON = os.path.join(REPO, "BENCH_serving.json")
OUT_JSON_QUICK = os.path.join(REPO, "BENCH_serving_quick.json")
MESH_REQUESTS = 1_000_000
MESH_MAX_BATCH = 1024

_TELEMETRY_KEYS = ("serve_batches", "serve_completed", "serve_failed",
                   "serve_padded_lanes", "resilience_retries",
                   "resilience_fallbacks", "resilience_faults",
                   "resilience_breaker_trips", "resilience_exhausted")


# The serving lifecycle stages the span layer breaks a request into
# (queue_wait/bucket_pack/device_absorb sum to ~the request wall; the
# request row is the end-to-end envelope).
_STAGE_SPANS = ("queue_wait", "bucket_pack", "device_absorb", "request")


def _stage_breakdown() -> dict:
    """Per-stage latency stats (ms) from the obs span histograms."""
    snap = obs.snapshot(include_telemetry=False)
    out = {}
    for name in _STAGE_SPANS:
        st = snap["histograms"].get(name)
        if st is None or not st["count"]:
            continue
        out[name] = {
            "count": st["count"],
            "total_s": round(st["sum_s"], 4),
            "mean_ms": round(st["mean_s"] * 1e3, 3),
            "p50_ms": round(st["p50_s"] * 1e3, 3),
            "p90_ms": round(st["p90_s"] * 1e3, 3),
            "p99_ms": round(st["p99_s"] * 1e3, 3),
            "max_ms": round(st["max_s"] * 1e3, 3),
        }
    return out


def _payloads(n, seed):
    """Deterministic mixed workload: ~85% 1-block, ~15% 2-block."""
    rng = np.random.default_rng(seed)
    lengths = np.where(rng.random(n) < 0.85,
                       rng.integers(1, 128, n),       # 1 sponge block
                       rng.integers(140, 260, n))     # 2 sponge blocks
    return [rng.bytes(int(l)) for l in lengths]


def bench_regime(name, payloads, *, max_batch, fault_rate, seed):
    eng = BatchingEngine(
        BatchingOptions(max_batch=max_batch, max_queue=len(payloads)),
        start=False)
    telemetry.reset()

    def drive():
        reqs = [eng.submit(p) for p in payloads]
        t0 = time.perf_counter()
        while eng.run_once():
            pass
        return reqs, time.perf_counter() - t0

    if fault_rate > 0.0:
        with faults.inject_faults(seed=seed, launch_rate=fault_rate) as inj:
            reqs, wall_s = drive()
        injected = inj.count
    else:
        reqs, wall_s = drive()
        injected = 0

    lat_ms = np.asarray([r.latency_s for r in reqs]) * 1e3
    exact = sum(r.result() == hashlib.sha3_256(p).digest()
                for p, r in zip(payloads, reqs))
    backends = sorted({r.backend for r in reqs})
    snap = telemetry.snapshot()

    rec = {
        "regime": name,
        "requests": len(payloads),
        "max_batch": max_batch,
        "injected_faults": injected,
        "bit_exact": exact,
        "all_exact": exact == len(payloads),
        "wall_s": round(wall_s, 3),
        "hashes_per_s": round(len(payloads) / wall_s, 1),
        "latency_ms": {"p50": round(float(np.percentile(lat_ms, 50)), 2),
                       "p99": round(float(np.percentile(lat_ms, 99)), 2),
                       "max": round(float(lat_ms.max()), 2)},
        "answering_backends": backends,
        "batches": len(eng.batch_log),
        "telemetry": {k: snap.get(k, 0) for k in _TELEMETRY_KEYS},
    }
    row(f"serving/{name}", hashes_per_s=rec["hashes_per_s"],
        p50_ms=rec["latency_ms"]["p50"], p99_ms=rec["latency_ms"]["p99"],
        exact=rec["all_exact"], faults=injected,
        fallbacks=rec["telemetry"]["resilience_fallbacks"])
    return rec


def bench_traced_stages(payloads, *, max_batch, seed=0):
    """The same clean-regime drain with spans ON: per-stage breakdown.

    Runs SEPARATELY from the headline regimes so their walls stay
    untraced — the disabled-by-default overhead guarantee is part of
    what this benchmark certifies, so the throughput rows must never
    pay for their own decomposition.  The stage rows replace nothing:
    they sit beside the old end-to-end numbers.
    """
    was_enabled = obs.enabled()
    obs.enable()
    try:
        rec = bench_regime("traced_stages", payloads, max_batch=max_batch,
                           fault_rate=0.0, seed=seed)
        rec["stage_breakdown"] = _stage_breakdown()
        rec["spans_recorded"] = len(obs.finished_spans())
        rec["spans_dropped"] = obs.dropped_count()
    finally:
        if not was_enabled:
            obs.disable()
    stages = rec["stage_breakdown"]
    row("serving/traced_stages",
        **{f"{k}_p50_ms": v["p50_ms"] for k, v in stages.items()})
    return rec


def bench_mesh_regime(n_requests, *, max_batch=MESH_MAX_BATCH, seed=3):
    """10^6-request sustained-throughput run on the full host mesh.

    Unlike ``bench_regime`` this drives the THREADED engine (worker +
    prep threads, double-buffered host->device feeds) with every bucket
    sharded across the mesh and the measured tuning table steering
    ``backend="auto"`` — i.e. the PR 7 serving path end to end.  The
    returned latencies are queue-drain latencies (submit-all then wait),
    same convention as the single-device rows.
    """
    from jax.sharding import Mesh
    from repro.core.tuning import TuningTable

    devices = jax.devices()
    mesh = Mesh(np.asarray(devices), ("data",))
    tuning = TuningTable()
    eng = BatchingEngine(
        BatchingOptions(max_batch=max_batch,
                        # warmup floods 2*max_batch before the timed
                        # queue; small --mesh-requests must not shed it
                        max_queue=max(n_requests, 4 * max_batch),
                        mesh=mesh, double_buffer=True, tuning=tuning),
        start=True)
    telemetry.reset()
    # telemetry.reset() uninstalls any tuning table; re-pin the engine's.
    from repro.core import crossbar as xb
    xb.set_tuning_table(tuning)
    try:
        # Warm the trace caches (per-bucket shapes) outside the timed
        # region so the sustained number is steady-state serving.
        warm = _payloads(2 * max_batch, seed=seed + 1)
        for r in [eng.submit(p) for p in warm]:
            r.result(timeout=600)

        payloads = _payloads(n_requests, seed=seed)
        t0 = time.perf_counter()
        reqs = [eng.submit(p) for p in payloads]
        for r in reqs:
            r.result(timeout=3600)
        wall_s = time.perf_counter() - t0

        lat_ms = np.asarray([r.latency_s for r in reqs]) * 1e3
        exact = sum(r.result() == hashlib.sha3_256(p).digest()
                    for p, r in zip(payloads, reqs))
        snap = telemetry.snapshot()
        stats = eng.stats()
    finally:
        eng.close()

    rec = {
        "regime": "mesh_no_fault",
        "requests": n_requests,
        "max_batch": max_batch,
        "devices": len(devices),
        "host_cores": os.cpu_count(),
        "double_buffer": True,
        "injected_faults": 0,
        "bit_exact": exact,
        "all_exact": exact == n_requests,
        "wall_s": round(wall_s, 3),
        "hashes_per_s": round(n_requests / wall_s, 1),
        "latency_ms": {"p50": round(float(np.percentile(lat_ms, 50)), 2),
                       "p99": round(float(np.percentile(lat_ms, 99)), 2),
                       "max": round(float(lat_ms.max()), 2)},
        "answering_backends": sorted({r.backend for r in reqs}),
        "tuning_entries": stats["tuning_entries"],
        "mesh_active": stats["mesh_active"],
        "telemetry": {k: snap.get(k, 0) for k in
                      _TELEMETRY_KEYS + ("serve_mesh_batches",
                                         "serve_mesh_device_drops",
                                         "serve_mesh_collapsed")},
    }
    row("serving/mesh_no_fault", devices=rec["devices"],
        hashes_per_s=rec["hashes_per_s"],
        p50_ms=rec["latency_ms"]["p50"], p99_ms=rec["latency_ms"]["p99"],
        exact=rec["all_exact"],
        mesh_batches=rec["telemetry"]["serve_mesh_batches"])
    return rec


def _trace_collective_probe():
    """One cross-shard ``apply_plan_sharded`` on the full mesh, so the
    traced artifacts contain the collective spans/histograms.

    The serving absorb itself is *collective-free by design* (the lane
    pattern shards elementwise work), so a pure serving trace would
    never show the instrumented collective path — this probe runs a
    rotation plan whose occupancy forces a real ppermute round.  One
    megakernel keccak-f follows so the launch histogram is populated
    too (off TPU the serving chain is einsum-first and would otherwise
    never launch a program).
    """
    from jax.sharding import Mesh
    from repro.core import crossbar as xb
    from repro.core.semiring import GF2
    from repro.crypto import keccak
    from repro.dist import mesh_exec

    devices = jax.devices()
    mesh = Mesh(np.asarray(devices), ("data",))
    s = len(devices)
    n = 16 * s
    idx = np.roll(np.arange(n), n // s)  # rotate one full shard
    plan = xb.gather_plan(np.asarray(idx)[:, None], n, semiring=GF2)
    x = np.arange(n, dtype=np.int32) % 2
    mesh_exec.apply_plan_sharded(plan, x, mesh)
    st = np.zeros((1, keccak.STATE_BITS), np.int32)
    keccak.keccak_f1600(st, backend="megakernel", batch_mode="payload",
                        fixed_latency=False)


def run_mesh(n_requests, out_path=None) -> dict:
    """Entry point for the --mesh subprocess / CI mesh smoke job.

    With tracing on (``REPRO_OBS=1``) the mesh run additionally exports
    the three observability artifacts — a Prometheus text snapshot
    (``OBS_mesh_prometheus.txt``), a Chrome/Perfetto trace
    (``OBS_mesh_trace.json``), and a drift-monitor report inline in the
    fragment — and validates the first two against their schemas.  The
    CI ``obs`` job runs exactly this under 8 forced host devices.
    """
    rec = bench_mesh_regime(n_requests)
    fragment = {
        "benchmark": "serving_mesh",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "jax_backend": jax.default_backend(),
        "rows": [rec],
    }
    if obs.enabled():
        _trace_collective_probe()
        rec["stage_breakdown"] = _stage_breakdown()
        rec["spans_recorded"] = len(obs.finished_spans())
        rec["spans_dropped"] = obs.dropped_count()
        prom = obs.prometheus_text()
        obs.validate_prometheus_text(prom)
        prom_path = os.path.join(REPO, "OBS_mesh_prometheus.txt")
        with open(prom_path, "w") as f:
            f.write(prom)
        trace_path = os.path.join(REPO, "OBS_mesh_trace.json")
        trace_obj = obs.export_chrome_trace(trace_path)
        obs.validate_chrome_trace(trace_obj)
        rec["drift_report"] = obs.drift_report()
        fragment["obs_artifacts"] = {"prometheus": prom_path,
                                     "chrome_trace": trace_path}
        print(f"# wrote {prom_path}")
        print(f"# wrote {trace_path}")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(fragment, f, indent=2)
            f.write("\n")
        print(f"# wrote {out_path}")
    assert rec["all_exact"], rec
    assert rec["telemetry"]["serve_mesh_batches"] > 0, rec
    return fragment


def run(quick: bool = False) -> dict:
    n = 200 if quick else 10_000
    max_batch = 16 if quick else 128
    payloads = _payloads(n, seed=0)
    # Warm the trace caches outside the timed region (both regimes then
    # measure steady-state serving, not XLA warmup).
    bench_regime("warmup", payloads[:2 * max_batch], max_batch=max_batch,
                 fault_rate=0.0, seed=0)

    clean = bench_regime("no_fault", payloads, max_batch=max_batch,
                         fault_rate=0.0, seed=0)
    chaos = bench_regime("fault_1pct", payloads, max_batch=max_batch,
                         fault_rate=0.01, seed=7)
    traced = bench_traced_stages(payloads, max_batch=max_batch, seed=0)

    # The mesh regime runs in this process, on the devices it already
    # has: a child started after JAX is up could not share the chip.
    mesh = None
    if not quick and len(jax.devices()) > 1:
        mesh = bench_mesh_regime(MESH_REQUESTS)
    elif not quick:
        print("# mesh regime skipped: one device (on the CPU, run under "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8)")

    acceptance = {
        "criterion": "10^4 queued SHA3-256 requests drain bit-exactly vs "
                     "hashlib in both regimes; 1% injected launch faults "
                     "cost retries/fallbacks (telemetry), never wrong "
                     "digests, hung requests, or poisoned caches",
        "requests": n,
        "all_exact_no_fault": clean["all_exact"],
        "all_exact_fault_1pct": chaos["all_exact"],
        "hashes_per_s_no_fault": clean["hashes_per_s"],
        "hashes_per_s_fault_1pct": chaos["hashes_per_s"],
        "p99_ms_no_fault": clean["latency_ms"]["p99"],
        "p99_ms_fault_1pct": chaos["latency_ms"]["p99"],
        "fault_overhead_x": round(
            clean["hashes_per_s"] / max(chaos["hashes_per_s"], 1e-9), 3),
        "faults_absorbed": chaos["injected_faults"],
        "pass": bool(clean["all_exact"] and chaos["all_exact"]
                     and chaos["injected_faults"] > 0
                     and chaos["telemetry"]["resilience_retries"]
                     + chaos["telemetry"]["resilience_fallbacks"] > 0),
    }
    # Per-stage headline rows (from the separate traced pass): where a
    # request's wall actually goes — queue wait vs host pack vs device
    # absorb — instead of one end-to-end number.
    stages = traced["stage_breakdown"]
    for stage_name, short in (("queue_wait", "queue_wait"),
                              ("bucket_pack", "pack"),
                              ("device_absorb", "absorb")):
        st = stages.get(stage_name)
        if st:
            acceptance[f"{short}_p50_ms"] = st["p50_ms"]
            acceptance[f"{short}_p99_ms"] = st["p99_ms"]
    acceptance["traced_all_exact"] = traced["all_exact"]
    acceptance["traced_hashes_per_s"] = traced["hashes_per_s"]
    acceptance["pass"] = bool(acceptance["pass"] and traced["all_exact"]
                              and len(stages) >= 3)
    if mesh is not None:
        acceptance.update({
            "mesh_requests": mesh["requests"],
            "mesh_devices": mesh["devices"],
            "mesh_host_cores": mesh["host_cores"],
            "mesh_all_exact": mesh["all_exact"],
            "mesh_hashes_per_s": mesh["hashes_per_s"],
            "mesh_p50_ms": mesh["latency_ms"]["p50"],
            "mesh_p99_ms": mesh["latency_ms"]["p99"],
            # Same physical host: the 8 host-platform devices time-slice
            # host_cores physical core(s), so this ratio measures the
            # serving-stack overhead of the mesh path (GSPMD dispatch,
            # staging), NOT device parallelism — expect <= 1.0 on a
            # 1-core host; the device-parallel scaling claim lives in
            # BENCH_mesh_sharded.json as modeled speedup.
            "mesh_throughput_vs_single_device_x": round(
                mesh["hashes_per_s"] / max(clean["hashes_per_s"], 1e-9),
                3),
            "pass": bool(acceptance["pass"] and mesh["all_exact"]
                         and mesh["telemetry"]["serve_mesh_batches"] > 0),
        })
    assert acceptance["pass"], acceptance

    rows = [clean, chaos, traced] + ([mesh] if mesh is not None else [])
    report = {
        "benchmark": "serving",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "jax_backend": jax.default_backend(),
        "chain": list(default_chain()),
        "quick": quick,
        "rows": rows,
        "acceptance": acceptance,
    }
    out_path = OUT_JSON_QUICK if quick else OUT_JSON
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"# wrote {out_path}")
    print(f"# acceptance: {acceptance}")
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small request count (CI smoke)")
    ap.add_argument("--mesh", action="store_true",
                    help="run ONLY the mesh regime (on the CPU, under "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8)")
    ap.add_argument("--mesh-out", default=None,
                    help="write the mesh JSON fragment here")
    ap.add_argument("--mesh-requests", type=int, default=None,
                    help="mesh regime request count "
                         f"(default {MESH_REQUESTS}; --quick: 2000)")
    args = ap.parse_args()
    if args.mesh:
        n = args.mesh_requests or (2000 if args.quick else MESH_REQUESTS)
        run_mesh(n, out_path=args.mesh_out)
    else:
        run(quick=args.quick)


if __name__ == "__main__":
    main()
