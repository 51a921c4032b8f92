"""The megakernel's share of its memory-bound roofline where every
record brings its own key: ``megakernel_roofline``'s rule, with each
answered record's 16-byte AES key added to the bytes that
``work/gcm_seal.py`` counts (that file counts the key once per engine;
here each record's session key is read for it)."""

from perfbench.readers import MEGAKERNEL

KEY_BYTES = 16


def read(ctx):
    if ctx.trace is None:
        return None
    count, seconds = ctx.trace.kernel(MEGAKERNEL)
    if not count:
        return None
    moved = sum(ctx.work_bytes(r.size) + KEY_BYTES for r in ctx.records
                if r.value is not None and ctx.t0 <= r.t_done < ctx.t1)
    return moved / ctx.peak["hbm_bytes_per_s"] / seconds * 100
