"""Order statistics of request latencies.

A request that failed, was refused or never answered counts as
infinitely late, so it sits above every real latency and a tail that
it reaches reads ``inf``.
"""

from __future__ import annotations

import math
import statistics


def percentile(values, p: float) -> float:
    """Nearest-rank ``p``-th percentile (0 < p <= 100) of ``values``."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    if not 0 < p <= 100:
        raise ValueError(f"percentile {p} outside (0, 100]")
    return xs[max(0, math.ceil(p / 100 * len(xs)) - 1)]


def latencies_s(records, *, t0: float, t1: float) -> list:
    """Latency from due time to result of every request due in
    ``[t0, t1)``; ``inf`` where it has no correct result."""
    out = []
    for r in records:
        if not t0 <= r.due < t1:
            continue
        ok = r.error is None and r.t_done is not None and r.correct
        out.append(r.t_done - r.due if ok else math.inf)
    return out


def spread(values) -> float:
    """Interquartile distance as a share of the median (the bound's
    measure of run-to-run noise)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
