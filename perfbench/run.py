"""Run one benchmark cell on the chip and print its result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  One ``serve.batching.BatchingEngine`` in
threaded mode, with the engine's defaults except what the cell's
configuration fixes, serves the cell's traffic for ``--seconds``.  Set-up
warms every bucket shape the traffic can form (each request size at
each padded lane count), so nothing compiles in the window; compiles
that happen there anyway are counted and printed.  Every answer due in
the window is compared with the configuration's plain reference.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
records the device profile and the engine's spans over the window and
reports the per-layer metrics.  The last line of standard output is one
JSON object; the numbers compared and their limits are the last lines
of standard error and the last key of that object.  Without a TPU, or
with fewer chips than the cell asks for, the run exits with code 3 and
prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, ".perfbench")
# The first request is due this long after the engine starts.
LEAD_S = 0.05
# Send times and seeds of warm-up requests lie apart from the window's.
WARM_INDEX = 1 << 40


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Context:
    """What a metric reader (``metrics/<name>.py``) may read."""

    records: list
    t0: float
    t1: float
    setup_s: float
    counters: dict                 # telemetry deltas over the window
    spans: list                    # obs spans (trace runs)
    trace: Optional[object]        # trace.Summary (trace runs)
    work_bytes: object             # size -> bytes the request moves
    peak: dict


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def device_info(chips: int, *, require_tpu: bool = True) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < chips):
        raise NoDevice(f"the cell needs {chips} TPU chip(s); JAX found "
                       f"{len(devices)} {dev.platform} device(s)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def _compile_counter():
    """Counts backend compiles (cache hits included) while ``on``."""
    import jax
    from jax._src import dispatch

    state = {"on": False, "n": 0}

    def listener(event, duration, **_):
        if state["on"] and event == dispatch.BACKEND_COMPILE_EVENT:
            state["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return state


def _gc_pauses():
    """Collects the duration of each garbage collection of the oldest
    generation while ``on``: a pause of every thread, the feed's too."""
    state = {"on": False, "start": 0.0, "pauses": []}

    def callback(phase, info):
        if not state["on"] or info["generation"] != 2:
            return
        if phase == "start":
            state["start"] = time.perf_counter()
        else:
            state["pauses"].append(time.perf_counter() - state["start"])

    gc.callbacks.append(callback)
    return state


def warm_up(engine, client, sizes, max_batch: int) -> int:
    """Serve one bucket of every size at every padded lane count the
    window can form (1, 2, 4, ... max_batch), synchronously."""
    index, buckets = WARM_INDEX, 0
    for size in sizes:
        lanes = 1
        while lanes <= max_batch:
            reqs = []
            for _ in range(lanes):
                reqs.append(engine.submit(client.payload(index, size),
                                          op=client.op))
                index += 1
            engine.run_once()
            for r in reqs:
                r.result(timeout=0)
            buckets += 1
            lanes *= 2
    return buckets


def check(client, records) -> dict:
    """Compare every answer with the plain reference; the numbers and
    their limits."""
    answered = [r for r in records if r.value is not None]
    want = client.expected([(r.index, r.size) for r in answered])
    for r, w in zip(answered, want):
        r.correct = r.value == w
    return {
        "wrong_answers": {"value": sum(not r.correct for r in answered),
                          "limit": 0},
        "never_answered": {"value": sum(r.error == "NoAnswer"
                                        for r in records), "limit": 0},
    }


def run_cell(cell, *, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, engine_factory=None) -> dict:
    """Set up, drive the window, check, and return the result object.
    ``engine_factory(options, client)`` builds the engine in the
    program's place (the control, and tests)."""
    import jax

    from perfbench import drive, traffic
    from perfbench import trace as trace_mod
    from perfbench.spec import peak
    from repro import obs
    from repro.core import telemetry
    from repro.launch.compile_cache import use_compile_cache
    from repro.serve.batching import BatchingEngine, BatchingOptions

    device = device_info(cell.chips, require_tpu=require_tpu)
    log(f"[setup] device {device}")
    log(f"[setup] compile cache {use_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = _compile_counter()
    gc_state = _gc_pauses()

    mix = cell.traffic
    client = cell.client.Client(cell.config, seed, traffic.rng)
    options = BatchingOptions(**client.engine_options())
    if engine_factory is None:
        engine = BatchingEngine(options, start=False)
    else:
        engine = engine_factory(options, client)
    t_warm = time.perf_counter()
    buckets = warm_up(engine, client, traffic.geometries(mix),
                      options.max_batch)
    log(f"[setup] warmed {buckets} buckets in "
        f"{time.perf_counter() - t_warm:.3f} s")
    if mix["loop"] == "open":
        schedule = traffic.open_schedule(mix, seed, seconds)
        payloads = [client.payload(i, n) for i, (_, n) in enumerate(schedule)]

    before = telemetry.snapshot()
    trace_dir = os.path.join(OUT, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        obs.enable()
        obs.reset()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    engine.start()
    compiles["on"] = True
    gc_state["on"] = True
    t0 = time.perf_counter() + LEAD_S
    t1 = t0 + seconds
    setup_s = t0 - T_START
    if mix["loop"] == "open":
        records = drive.open_loop(engine, client.op, payloads, schedule, t0)
        time.sleep(max(0.0, t1 - time.perf_counter()))
    else:
        records = drive.closed_loop(
            engine, client.op, client.payload,
            traffic.closed_sizes(mix, seed), int(mix["clients"]), t0, t1)
    t_close = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    compiles["on"] = False
    gc_state["on"] = False
    after = telemetry.snapshot()
    drive.drain(records, t_close + drive.DRAIN_S)
    engine.close()
    if trace:
        obs.disable()

    stats_fn = getattr(jax.devices()[0], "memory_stats", None)
    mem = (stats_fn() or {}) if stats_fn else {}
    device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    checks = check(client, records)
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    summary = None
    if trace:
        summary = trace_mod.reduce(trace_dir, window_s=t_close - t0)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    counters = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    ctx = Context(records=records, t0=t0, t1=t1, setup_s=setup_s,
                  counters=counters,
                  spans=obs.finished_spans() if trace else [],
                  trace=summary,
                  work_bytes=lambda n: cell.work.request_bytes(
                      **client.geometry(n)),
                  peak=(peak(cell.peaks, device["kind"]) if trace
                        else {}))
    metrics = {}
    for entry, reader in (cell.per_layer if trace else cell.end_to_end):
        value = reader.read(ctx)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    late = sorted(r.sent - r.due for r in records)
    log(f"[window] {len(records)} requests, compiles in window "
        f"{compiles['n']}, generator lateness p95 "
        f"{late[int(0.95 * (len(late) - 1))] * 1e3:.3f} ms" if late else
        "[window] no requests")
    pauses = gc_state["pauses"]
    log(f"[window] full garbage collections {len(pauses)}, "
        f"longest {max(pauses, default=0.0) * 1e3:.3f} ms, "
        f"total {sum(pauses) * 1e3:.3f} ms")
    log("[window] errors " + json.dumps(
        {e: sum(r.error == e for r in records)
         for e in sorted({r.error for r in records} - {None})}))
    log("[window] engine " + json.dumps(
        {k: v for k, v in sorted(counters.items())
         if k.startswith(("serve_", "resilience_")) and v}))
    result = {
        "correct": correct,
        "attempted": len(records),
        "failed": sum(r.error is not None for r in records),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

    from perfbench.spec import load_cell

    cell = load_cell(args.workload, root=ROOT)
    try:
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace))
    except NoDevice as e:
        log(f"error: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
