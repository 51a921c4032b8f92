"""95th percentile of the latencies that ``p50_ms`` takes the median of."""

from perfbench.readers import latency_ms


def read(ctx):
    return latency_ms(ctx, 95)
