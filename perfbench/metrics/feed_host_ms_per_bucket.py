"""Host time of the feed thread per bucket: the sum over the window's
``bucket_feed`` spans of each one's length less the ``bucket_sync``
spans nested in it (the waits for the device's result), over the number
of ``bucket_feed`` spans.  The device idles while the feed thread does
this work, unless the double buffer hides it."""

import bisect


def read(ctx):
    feeds = sorted((s for s in ctx.spans if s.name == "bucket_feed"
                    and ctx.t0 <= s.t1 < ctx.t1),
                   key=lambda s: (s.thread_id, s.t0))
    if not feeds:
        return None
    keys = [(s.thread_id, s.t0) for s in feeds]
    host = [s.t1 - s.t0 for s in feeds]
    for s in ctx.spans:
        if s.name != "bucket_sync":
            continue
        i = bisect.bisect_right(keys, (s.thread_id, s.t0)) - 1
        if i >= 0 and feeds[i].thread_id == s.thread_id \
                and s.t1 <= feeds[i].t1:
            host[i] -= s.t1 - s.t0
    return sum(host) / len(feeds) * 1e3
