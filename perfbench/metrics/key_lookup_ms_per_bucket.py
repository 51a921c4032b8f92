"""Host time per GCM bucket of looking up its lanes' session keys: the
mean length, in ms, of the window's ``key_lookup`` spans (one per
bucket on the feed thread: each lane's session read from its record,
the index vector built, and the gather from the device session table
dispatched).  A program without the span reads nothing."""


def read(ctx):
    spans = [s.t1 - s.t0 for s in ctx.spans
             if s.name == "key_lookup" and ctx.t0 <= s.t1 < ctx.t1]
    return sum(spans) / len(spans) * 1e3 if spans else None
