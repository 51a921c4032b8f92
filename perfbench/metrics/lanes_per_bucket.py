"""Real requests per bucket executed in the window, from the engine's
``serve_completed`` and ``serve_batches`` counters."""


def read(ctx):
    batches = ctx.counters.get("serve_batches", 0)
    if not batches:
        return None
    return ctx.counters.get("serve_completed", 0) / batches
