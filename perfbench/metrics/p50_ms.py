"""Median latency from due time to answer, over every request due in
the window; a failed or refused one counts as infinitely late."""

from perfbench.readers import latency_ms


def read(ctx):
    return latency_ms(ctx, 50)
