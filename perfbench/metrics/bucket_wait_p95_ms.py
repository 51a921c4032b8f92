"""95th percentile, over the window's requests, of the ``bucket_wait``
of each request's bucket: from the end of the bucket's preparation on
the prep thread to its pick-up by the feed thread (blocked in the
staging put, then in the staging queue).  Each bucket's span counts
once per real lane, as ``queue_wait_p95_ms`` counts once per request."""

from perfbench import stats


def read(ctx):
    waits = []
    for s in ctx.spans:
        if s.name == "bucket_wait" and ctx.t0 <= s.t1 < ctx.t1:
            waits += [s.t1 - s.t0] * int(s.attrs.get("lanes", 1))
    return stats.percentile(waits, 95) * 1e3 if waits else None
