"""95th percentile of the engine's own ``queue_wait`` spans (submit to
the take of the request's bucket), raw records of the window, not the
engine's bucketed histogram."""

from perfbench import stats


def read(ctx):
    waits = [s.t1 - s.t0 for s in ctx.spans
             if s.name == "queue_wait" and ctx.t0 <= s.t1 < ctx.t1]
    return stats.percentile(waits, 95) * 1e3 if waits else None
