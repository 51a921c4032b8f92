"""What the per-metric readers in ``metrics/`` share.

A reader is ``read(ctx) -> number or None``; ``None`` means it found
nothing to read, and the harness then leaves the metric out of the line.
``ctx`` is ``run.Context``.
"""

from __future__ import annotations

from perfbench import stats

# The megakernel's device operation is the custom call of its Pallas
# kernel (unnamed today: "%_unknown_.1 = s32[...] custom-call(...)").  On
# the served path it is the only custom call: the other operations of a
# launch are XLA's (pad, transpose, slice, xor).  A bucket that falls
# back to the crossbar's Pallas rungs would add theirs; the run prints
# every fallback.
MEGAKERNEL = " custom-call("


def latency_ms(ctx, p: float):
    lat = stats.latencies_s(ctx.records, t0=ctx.t0, t1=ctx.t1)
    return stats.percentile(lat, p) * 1e3 if lat else None


def megakernel_ms(ctx):
    if ctx.trace is None:
        return None
    count, seconds = ctx.trace.kernel(MEGAKERNEL)
    return seconds / count * 1e3 if count else None


def megakernel_roofline(ctx):
    """Least time for the bytes that the window's answered requests
    moved, at the published HBM bandwidth, over the megakernel's time:
    a share of the memory-bound roofline (no integer-VPU peak is
    published for the chip)."""
    if ctx.trace is None:
        return None
    count, seconds = ctx.trace.kernel(MEGAKERNEL)
    if not count:
        return None
    moved = sum(ctx.work_bytes(r.size) for r in ctx.records
                if r.value is not None and ctx.t0 <= r.t_done < ctx.t1)
    return moved / ctx.peak["hbm_bytes_per_s"] / seconds * 100


def idle_share(ctx):
    if ctx.trace is None or not ctx.trace.n_devices:
        return None
    return (1 - ctx.trace.busy_s / ctx.trace.window_s) * 100
