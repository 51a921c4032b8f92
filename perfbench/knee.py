"""Find an open-loop cell's knee: the highest Poisson rate at which the
backlog does not grow over the window and nothing is shed.

    python3 perfbench/knee.py --workload <cell> --seed <n> \
        --seconds <s> --rates 10 20 40

One process and one engine: set-up and warm-up once, then one window
per rate, drained before the next.  For each rate it prints the
requests sent, shed and failed, p50 and p95 from the due time, the
median latency of the first and of the last fifth of the window's
requests, how long the last answer came after the window closed, and
the requests outstanding at the end of each fifth of the window.  The
backlog grows when those counts climb and the last fifth waits far
longer than the first.
The sweep stops at the first rate that sheds, or whose last answer
comes more than a window after the close.
Its lines feed the mix file's ``rate_per_s`` (0.8 of the knee) by hand;
the benchmark's runs never sweep.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

    from perfbench import drive, run, stats, traffic
    from perfbench.spec import load_cell
    from repro.launch.compile_cache import use_compile_cache
    from repro.serve.batching import BatchingEngine, BatchingOptions

    cell = load_cell(args.workload, root=ROOT)
    try:
        device = run.device_info(cell.chips)
    except run.NoDevice as e:
        run.log(f"error: {e}")
        return 3
    use_compile_cache()
    client = cell.client.Client(cell.config, args.seed, traffic.rng)
    options = BatchingOptions(**client.engine_options())
    engine = BatchingEngine(options, start=False)
    run.warm_up(engine, client, traffic.geometries(cell.traffic),
                options.max_batch)
    engine.start()
    base = 0
    rows = []
    for rate in args.rates:
        mix = dict(cell.traffic, rate_per_s=rate)
        schedule = traffic.open_schedule(mix, args.seed, args.seconds)
        payloads = [client.payload(base + i, n)
                    for i, (_, n) in enumerate(schedule)]
        t0 = time.perf_counter() + run.LEAD_S
        records = drive.open_loop(engine, client.op, payloads, schedule, t0)
        t_close = max(time.perf_counter(), t0 + args.seconds)
        drive.drain(records, t_close + drive.DRAIN_S)
        for r in records:
            r.index += base
        base += len(records)
        run.check(client, records)
        lat = stats.latencies_s(records, t0=t0, t1=t0 + args.seconds)
        fifth = max(1, len(records) // 5)
        ok_done = [r.t_done for r in records if r.t_done is not None]
        row = {
            "rate_per_s": rate, "sent": len(records),
            "shed": sum(r.error == "Overloaded" for r in records),
            "failed": sum(r.error is not None for r in records),
            "wrong": sum(r.correct is False for r in records),
            "p50_ms": stats.percentile(lat, 50) * 1e3,
            "p95_ms": stats.percentile(lat, 95) * 1e3,
            "first_fifth_p50_ms": stats.percentile(lat[:fifth], 50) * 1e3,
            "last_fifth_p50_ms": stats.percentile(lat[-fifth:], 50) * 1e3,
            "last_answer_after_close_s": max(ok_done) - t_close,
            "outstanding_at_fifths": [
                sum(r.due <= t < (r.t_done if r.t_done is not None
                                  else float("inf")) for r in records)
                for t in (t0 + args.seconds * k / 5 for k in range(1, 6))],
        }
        rows.append(row)
        run.log("[knee] " + json.dumps(row))
        if row["shed"] or row["last_answer_after_close_s"] > args.seconds:
            break   # past the knee: higher rates only queue longer
    engine.close()
    print(json.dumps({"workload": args.workload, "device": device,
                      "seconds": args.seconds, "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
