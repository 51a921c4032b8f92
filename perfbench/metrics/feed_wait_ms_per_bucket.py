"""Time the feed thread spent waiting for a prepared bucket (its
``feed_wait`` spans ending in the window), per ``bucket_feed`` span of
the window."""


def read(ctx):
    def in_window(s):
        return ctx.t0 <= s.t1 < ctx.t1

    feeds = sum(1 for s in ctx.spans
                if s.name == "bucket_feed" and in_window(s))
    if not feeds:
        return None
    waited = sum(s.t1 - s.t0 for s in ctx.spans
                 if s.name == "feed_wait" and in_window(s))
    return waited / feeds * 1e3
