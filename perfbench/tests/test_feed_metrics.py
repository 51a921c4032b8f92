"""The readers of the feed thread's spans: ``bucket_wait_p95_ms``,
``feed_host_ms_per_bucket`` and ``feed_wait_ms_per_bucket``, on
synthetic spans and on the spans of a real engine."""

import os
import types

import pytest

from perfbench import spec

FEED, OTHER = 7, 8          # thread ids


def _reader(name):
    return spec.load_module(os.path.join(spec.HERE, "metrics",
                                         name + ".py"))


def _span(name, t0, t1, *, thread=FEED, **attrs):
    return types.SimpleNamespace(name=name, t0=t0, t1=t1, thread_id=thread,
                                 attrs=attrs)


def _ctx(spans, t0=10.0, t1=20.0):
    return types.SimpleNamespace(spans=spans, t0=t0, t1=t1)


bucket_wait = _reader("bucket_wait_p95_ms")
feed_host = _reader("feed_host_ms_per_bucket")
feed_wait = _reader("feed_wait_ms_per_bucket")


@pytest.mark.parametrize("reader", [bucket_wait, feed_host, feed_wait])
def test_no_span_reads_nothing(reader):
    assert reader.read(_ctx([])) is None
    # Spans of another name, or outside the window, are not read either.
    assert reader.read(_ctx([_span("queue_wait", 11.0, 12.0, lanes=8),
                             _span("bucket_feed", 1.0, 2.0),
                             _span("bucket_wait", 20.0, 21.0, lanes=8)
                             ])) is None


def test_bucket_wait_counts_each_bucket_once_per_lane():
    spans = [_span("bucket_wait", 11.0, 11.010, lanes=1),    # 10 ms
             _span("bucket_wait", 12.0, 12.002, lanes=19)]   # 2 ms
    # 20 lanes: 19 waited 2 ms, one 10 ms; the 95th is the 19th.
    assert bucket_wait.read(_ctx(spans)) == pytest.approx(2.0)
    spans[0].attrs["lanes"] = 2
    assert bucket_wait.read(_ctx(spans)) == pytest.approx(10.0)


def test_window_edges_follow_the_span_end():
    # Ends at the window's start: in; ends at the window's end: out.
    spans = [_span("bucket_wait", 9.0, 10.0, lanes=1),
             _span("bucket_wait", 19.5, 20.0, lanes=1)]
    assert bucket_wait.read(_ctx(spans)) == pytest.approx(1000.0)
    feeds = [_span("feed_wait", 9.9, 10.0),
             _span("bucket_feed", 10.0, 10.004),
             _span("feed_wait", 19.99, 20.0),
             _span("bucket_feed", 19.996, 20.0)]
    assert feed_host.read(_ctx(feeds)) == pytest.approx(4.0)
    assert feed_wait.read(_ctx(feeds)) == pytest.approx(100.0)


def test_feed_host_subtracts_only_the_nested_sync():
    spans = [
        _span("bucket_feed", 11.0, 11.010),
        _span("bucket_sync", 11.002, 11.008),      # nested: 6 ms off
        _span("bucket_feed", 12.0, 12.004),
        _span("bucket_sync", 12.005, 12.006),      # after it: not its own
        _span("bucket_sync", 12.001, 12.003, thread=OTHER),  # other thread
        _span("bucket_launch", 12.0, 12.001),
    ]
    assert feed_host.read(_ctx(spans)) == pytest.approx((4 + 4) / 2)


def test_feed_wait_is_per_bucket_fed():
    spans = [_span("bucket_feed", 11.0, 11.010),
             _span("bucket_feed", 12.0, 12.010),
             _span("feed_wait", 10.5, 11.0),
             _span("feed_wait", 11.010, 11.011)]
    assert feed_wait.read(_ctx(spans)) == pytest.approx((500 + 1) / 2)


def test_readers_find_the_engines_own_spans():
    import time

    from repro import obs
    from repro.serve.batching import BatchingEngine, BatchingOptions

    was = obs.enabled()
    obs.enable()
    obs.reset()
    try:
        eng = BatchingEngine(BatchingOptions(max_batch=4))
        t0 = time.perf_counter()
        reqs = [eng.submit(b"m%d" % i) for i in range(12)]
        for r in reqs:
            r.result(timeout=300)
        t1 = time.perf_counter()
        eng.close()
        spans = obs.finished_spans()
    finally:
        (obs.enable if was else obs.disable)()
        obs.reset()
    ctx = _ctx(spans, t0, t1)
    buckets = sum(s.name == "bucket_feed" for s in spans)
    assert buckets >= 3
    assert bucket_wait.read(ctx) >= 0
    host = feed_host.read(ctx)
    waited = feed_wait.read(ctx)
    assert host > 0 and waited >= 0
    # Host work and waits fit in the time the buckets took.
    assert (host + waited) * buckets <= (t1 - t0) * 1e3
