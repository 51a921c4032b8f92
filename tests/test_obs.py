"""Observability layer: spans, metrics, timeline, drift, thread safety.

Covers the ``repro.obs`` contract from ISSUE 8: spans are no-ops when
disabled (and still usable as timers), recorded spans propagate trace
ids across the serving engine's threads, the metrics registry exports
valid Prometheus text and Chrome trace JSON, the drift monitor warns on
timing drift before the structural contract trips, and the whole stack
survives an 8-thread hammer with exact final counts (chaos marker).
Also the ``telemetry.delta()`` mid-window-counter regression.
"""

import gc
import json
import sys
import threading
import time
import warnings

import numpy as np
import pytest

from repro import obs
from repro.core import crossbar as xb
from repro.core import telemetry
from repro.core.semiring import GF2
from repro.core.static_registry import StaticPlanRegistry
from repro.core.tuning import TuningTable
from repro.obs import drift as drift_mod
from repro.obs import tracing
from repro.serve.batching import (BatchingEngine, BatchingOptions,
                                  encode_aead_record)


@pytest.fixture(autouse=True)
def _obs_flag_guard():
    """Restore the enabled flag after each test (the conftest reset
    clears obs *data* but deliberately preserves the flag)."""
    was = obs.enabled()
    yield
    (obs.enable if was else obs.disable)()
    obs.reset()


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

class TestSpans:
    def test_disabled_records_nothing_but_still_times(self):
        obs.disable()
        n0 = obs.disabled_call_count()
        with obs.span("x", op="probe") as sp:
            time.sleep(0.001)
        assert sp.recording is False
        assert sp.duration_s >= 0.001
        assert obs.finished_spans() == []
        assert obs.disabled_call_count() == n0 + 1

    def test_enabled_records_with_attrs(self):
        obs.enable()
        with obs.span("work", op="sha3", k=3) as sp:
            sp.set(backend="einsum")
        spans = obs.finished_spans()
        assert [s.name for s in spans] == ["work"]
        assert spans[0].attrs == {"op": "sha3", "k": 3,
                                  "backend": "einsum"}
        assert spans[0].duration_s >= 0
        assert spans[0].trace_id is not None

    def test_nesting_inherits_parent_and_trace(self):
        obs.enable()
        with obs.span("outer") as outer:
            with obs.span("inner") as inner:
                pass
        assert inner.parent_id == outer.span_id
        assert inner.trace_id == outer.trace_id

    def test_explicit_trace_id_crosses_threads(self):
        obs.enable()
        tid = obs.new_trace_id()

        def work():
            with obs.span("stage_b", trace_id=tid):
                pass

        with obs.span("stage_a", trace_id=tid):
            pass
        t = threading.Thread(target=work)
        t.start()
        t.join()
        assert {s.trace_id for s in obs.finished_spans()} == {tid}

    def test_span_at_retroactive(self):
        obs.enable()
        t0 = time.perf_counter()
        t1 = t0 + 0.25
        obs.span_at("queue_wait", t0, t1, thread_name="elsewhere")
        (sp,) = obs.finished_spans()
        assert sp.duration_s == pytest.approx(0.25)
        assert sp.thread_name == "elsewhere"

    def test_exception_tagged_and_propagated(self):
        obs.enable()
        with pytest.raises(ValueError):
            with obs.span("boom"):
                raise ValueError("x")
        (sp,) = obs.finished_spans()
        assert sp.attrs["error"] == "ValueError"

    def test_ring_buffer_bounds_and_counts_drops(self):
        obs.enable()
        obs.set_buffer_capacity(8)
        try:
            for i in range(20):
                with obs.span("s"):
                    pass
            assert len(obs.finished_spans()) == 8
            assert obs.dropped_count() == 12
        finally:
            obs.set_buffer_capacity(tracing.DEFAULT_BUFFER_CAP)


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_histogram_quantiles_bound_samples(self):
        h = obs.Histogram()
        for v in [0.001] * 90 + [0.1] * 10:
            h.observe(v)
        st = h.stats()
        assert st["count"] == 100
        assert st["max_s"] == pytest.approx(0.1)
        # log-bucketed: quantile is an upper bucket bound >= true value
        assert 0.001 <= st["p50_s"] <= 0.002048
        assert st["p99_s"] >= 0.1 or st["p99_s"] == pytest.approx(0.1)

    def test_span_sink_feeds_histograms(self):
        obs.enable()
        with obs.span("fed"):
            pass
        assert obs.metrics.histogram("fed").n == 1

    def test_gauge_fn_lazy_and_survives_reset(self):
        calls = []

        def g():
            calls.append(1)
            return 7.0

        obs.metrics.gauge_fn("test_lazy", g)
        try:
            assert calls == []  # not evaluated until export
            snap = obs.snapshot(include_telemetry=False)
            assert snap["gauges"]["test_lazy"] == 7.0
            assert calls == [1]
            obs.reset()  # data clears, wiring survives
            snap = obs.snapshot(include_telemetry=False)
            assert snap["gauges"]["test_lazy"] == 7.0
        finally:
            obs.metrics.unregister_gauge_fn("test_lazy")

    def test_broken_gauge_fn_does_not_break_export(self):
        obs.metrics.gauge_fn("test_dead", lambda: 1 / 0)
        try:
            snap = obs.snapshot(include_telemetry=False)
            assert np.isnan(snap["gauges"]["test_dead"])
            obs.validate_prometheus_text(obs.prometheus_text())
        finally:
            obs.metrics.unregister_gauge_fn("test_dead")

    def test_prometheus_text_validates_and_has_counters(self):
        obs.enable()
        telemetry.incr("test_obs_counter", 3)
        with obs.span("apply_plan"):
            pass
        txt = obs.prometheus_text()
        summary = obs.validate_prometheus_text(txt)
        assert summary["samples"] > 0 and summary["histograms"] >= 1
        assert "repro_test_obs_counter_total 3" in txt
        assert 'repro_span_seconds_count{span="apply_plan"} 1' in txt

    def test_validator_rejects_malformed(self):
        with pytest.raises(ValueError, match="malformed"):
            obs.validate_prometheus_text("this is not{ a metric line\n")
        bad_hist = (
            '# TYPE repro_span_seconds histogram\n'
            'repro_span_seconds_bucket{span="x",le="0.1"} 5\n'
            'repro_span_seconds_bucket{span="x",le="+Inf"} 3\n')
        with pytest.raises(ValueError, match="decrease"):
            obs.validate_prometheus_text(bad_hist)


# ---------------------------------------------------------------------------
# Timeline
# ---------------------------------------------------------------------------

class TestTimeline:
    def test_chrome_trace_valid_and_complete(self, tmp_path):
        obs.enable()
        with obs.span("outer", op="sha3") as sp:
            sp.event("mark", detail=1)
        path = tmp_path / "trace.json"
        obj = obs.export_chrome_trace(str(path))
        summary = obs.validate_chrome_trace(obj)
        assert summary["complete"] == 1
        # instant event + thread-name metadata ride along
        phases = sorted(e["ph"] for e in obj["traceEvents"])
        assert phases == ["M", "X", "i"]
        on_disk = json.loads(path.read_text())
        assert obs.validate_chrome_trace(on_disk)["events"] == 3

    def test_validator_rejects_bad_events(self):
        with pytest.raises(ValueError, match="traceEvents"):
            obs.validate_chrome_trace({"foo": []})
        with pytest.raises(ValueError, match="bad dur"):
            obs.validate_chrome_trace({"traceEvents": [
                {"name": "x", "ph": "X", "pid": 1, "ts": 0.0, "dur": -1}]})


# ---------------------------------------------------------------------------
# Drift monitor
# ---------------------------------------------------------------------------

class TestDriftMonitor:
    def _mon(self):
        return drift_mod.DriftMonitor(baseline_n=4, recent_n=4)

    def test_stable_op_never_warns(self):
        m = self._mon()
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            for _ in range(20):
                m.observe("op", passes=3, fingerprint="f", wall_s=0.001)
        assert w == []
        rep = m.report()["op"]
        assert rep["drifting"] is False
        assert rep["structural_mismatches"] == 0

    def test_timing_drift_warns_once(self):
        m = self._mon()
        for _ in range(4):
            m.observe("op", passes=3, fingerprint="f", wall_s=0.001)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            for _ in range(10):
                m.observe("op", passes=3, fingerprint="f", wall_s=0.01)
        msgs = [x for x in w if "fixed-latency drift" in str(x.message)]
        assert len(msgs) == 1  # warn-once per op
        rep = m.report()["op"]
        assert rep["drifting"] is True
        assert rep["ratio"] == pytest.approx(10.0)

    def test_sub_floor_jitter_ignored(self):
        # 10x ratio but under the absolute noise floor: not drift.
        m = self._mon()
        for _ in range(4):
            m.observe("op", passes=3, fingerprint="f", wall_s=1e-6)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            for _ in range(10):
                m.observe("op", passes=3, fingerprint="f", wall_s=1e-5)
        assert w == []

    def test_structural_mismatch_counted(self):
        m = self._mon()
        m.observe("op", passes=3, fingerprint="f", wall_s=0.001)
        m.observe("op", passes=4, fingerprint="f", wall_s=0.001)
        assert m.report()["op"]["structural_mismatches"] == 1

    def test_registry_observe_feeds_monitor(self):
        reg = StaticPlanRegistry("t")
        idx = np.arange(8, dtype=np.int32)[:, None]
        plan = xb.gather_plan(idx, 8, semiring=GF2)
        reg.register("p", plan)
        x = np.arange(8, dtype=np.int32) % 2
        for _ in range(3):
            with reg.observe("probe", shapes=(8,), plan_keys=["p"]):
                xb.apply_plan(reg["p"], x)
        rep = obs.drift_report()
        assert "t:probe" in rep
        assert rep["t:probe"]["n_obs"] == 3
        assert rep["t:probe"]["passes"] == 1


# ---------------------------------------------------------------------------
# telemetry.delta regression (ISSUE 8 satellite)
# ---------------------------------------------------------------------------

class TestDeltaMidWindowCounters:
    def test_counter_created_inside_window_needs_no_guard(self):
        with telemetry.delta() as d:
            telemetry.incr("test_obs_brand_new", 5)
        out = d()
        assert out["test_obs_brand_new"] == 5

    def test_key_only_in_baseline_still_present(self):
        telemetry.incr("test_obs_doomed", 2)
        with telemetry.delta() as d:
            telemetry.reset()  # wipes _COUNTERS mid-window
        out = d()
        # pre-seeded to 0 on the missing side: visible as negative
        # flow, not a KeyError / silent omission
        assert out["test_obs_doomed"] == -2

    def test_sizes_report_end_state(self):
        with telemetry.delta() as d:
            telemetry.incr("whatever_size", 3)
        assert d()["whatever_size"] == 3  # level, not differenced


# ---------------------------------------------------------------------------
# Tuning feed
# ---------------------------------------------------------------------------

class TestTuningSpanFeed:
    def test_record_span_feeds_ewma_even_disabled(self):
        obs.disable()
        table = TuningTable()
        with obs.span("probe") as sp:
            time.sleep(0.002)
        table.record_span(sp, "op", (4, 1), "einsum")
        assert table.best("op", (4, 1)) == "einsum"


# ---------------------------------------------------------------------------
# Serving integration
# ---------------------------------------------------------------------------

class TestServingTrace:
    def test_request_lifecycle_spans_share_trace_id(self):
        obs.enable()
        eng = BatchingEngine(BatchingOptions(max_batch=4), start=False)
        reqs = [eng.submit(bytes([i]) * (i + 1)) for i in range(4)]
        while eng.run_once():
            pass
        for r in reqs:
            r.result(timeout=60)
        spans = obs.finished_spans()
        names = {s.name for s in spans}
        assert {"queue_wait", "bucket_pack", "device_absorb",
                "request"} <= names
        # the batch leader's trace id stitches all four stages
        leader = reqs[0].trace_id
        leader_stages = {s.name for s in spans if s.trace_id == leader}
        assert {"queue_wait", "bucket_pack", "device_absorb",
                "request"} <= leader_stages
        # every request got queue_wait + request spans on its own trace
        for r in reqs:
            stages = {s.name for s in spans if s.trace_id == r.trace_id}
            assert {"queue_wait", "request"} <= stages

    def test_serving_gauges_exported(self):
        eng = BatchingEngine(BatchingOptions(max_batch=4), start=False)
        eng.submit(b"pending")
        gauges = obs.snapshot(include_telemetry=False)["gauges"]
        assert gauges["serve_queue_depth"] == 1.0
        assert gauges["resilience_breaker_open"] == 0.0
        assert "compile_cache_size" in gauges

    def test_disabled_tracing_assigns_no_trace_ids(self):
        obs.disable()
        eng = BatchingEngine(BatchingOptions(max_batch=2), start=False)
        req = eng.submit(b"x")
        while eng.run_once():
            pass
        req.result(timeout=60)
        assert req.trace_id is None
        assert obs.finished_spans() == []


# ---------------------------------------------------------------------------
# Bucket lifecycle on the feed thread
# ---------------------------------------------------------------------------

PHASES = ("bucket_wait", "feed_wait", "bucket_feed", "bucket_pack",
          "bucket_launch", "bucket_sync", "bucket_unpack")
PREP, FEED = "batching-host-prep", "batching-device-feed"
AEAD_KEY = bytes(range(16))


@pytest.fixture(scope="module")
def served_spans():
    """A threaded, double-buffered engine on the megakernel serves a few
    SHA3 and GCM buckets with recording on: its spans and requests."""
    was = obs.enabled()
    obs.enable()
    obs.reset()
    try:
        eng = BatchingEngine(BatchingOptions(
            max_batch=4, chain=("megakernel",), aead_key=AEAD_KEY))
        reqs = [eng.submit(b"m%d" % i) for i in range(6)]
        reqs += [eng.submit(encode_aead_record(bytes([i]) * 12, b"x" * 16,
                                               b"hdr"), op="gcm_seal")
                 for i in range(5)]
        for r in reqs:
            r.result(timeout=600)
        eng.close()
        spans = obs.finished_spans()
    finally:
        (obs.enable if was else obs.disable)()
        obs.reset()
    return spans, reqs


def _by_name(spans, name):
    return [s for s in spans if s.name == name]


class TestBucketLifecycle:
    def test_each_bucket_has_one_span_of_each_phase(self, served_spans):
        spans, reqs = served_spans
        feeds = _by_name(spans, "bucket_feed")
        assert {f.attrs["op"] for f in feeds} == {"sha3_256", "gcm_seal"}
        assert sum(f.attrs["lanes"] for f in feeds) == len(reqs)
        for f in feeds:
            op = f.attrs["op"]
            mine = {n: [s for s in _by_name(spans, n)
                        if s.trace_id == f.trace_id] for n in PHASES}
            assert {n: len(v) for n, v in mine.items()} == dict.fromkeys(
                PHASES, 1), op
            for name, (sp,) in mine.items():
                on_prep = name == "bucket_pack" and op == "sha3_256"
                assert sp.thread_name == (PREP if on_prep else FEED), name
                assert sp.attrs["op"] == op
                assert sp.attrs["lanes"] == f.attrs["lanes"]
            # The feed thread's phases follow one another inside it; a
            # GCM bucket looks its lanes' session keys up first.
            inner = ["bucket_launch", "bucket_sync", "bucket_unpack"]
            if op == "gcm_seal":
                inner.insert(0, "bucket_pack")
                (lookup,) = [s for s in _by_name(spans, "key_lookup")
                             if s.trace_id == f.trace_id]
                assert lookup.thread_name == FEED
                assert lookup.attrs["lanes"] == f.attrs["lanes"]
                inner.insert(0, "key_lookup")
                mine["key_lookup"] = [lookup]
            seq = [mine[n][0] for n in inner]
            assert f.t0 <= seq[0].t0
            assert all(a.t1 <= b.t0 for a, b in zip(seq, seq[1:]))
            assert seq[-1].t1 <= f.t1
            assert mine["feed_wait"][0].t1 <= f.t0

    def test_request_phases_follow_and_add_up(self, served_spans):
        spans, reqs = served_spans
        feeds = {s.trace_id: s for s in _by_name(spans, "bucket_feed")}
        qw = {s.trace_id: s for s in _by_name(spans, "queue_wait")}
        # A bucket's spans carry its first request's trace id, and every
        # request of the bucket was taken at the same instant.
        head_at = {qw[t].t1: t for t in feeds}
        for r in reqs:
            (request,) = [s for s in _by_name(spans, "request")
                          if s.trace_id == r.trace_id]
            wait = qw[r.trace_id]
            head = head_at[wait.t1]

            def phase(name):
                (sp,) = [s for s in _by_name(spans, name)
                         if s.trace_id == head]
                return sp

            chain = [wait]
            if r.op == "sha3_256":          # packed on the prep thread
                chain.append(phase("bucket_pack"))
            chain += [phase("bucket_wait"), feeds[head]]
            assert all(a.t1 <= b.t0 for a, b in zip(chain, chain[1:]))
            assert chain[0].t0 == request.t0
            assert request.t1 <= chain[-1].t1
            total = sum(s.t1 - s.t0 for s in chain)
            assert total == pytest.approx(request.t1 - request.t0,
                                          rel=0.05)

    def test_disabled_builds_no_annotation_and_records_nothing(
            self, monkeypatch):
        import jax.profiler

        built = []

        class Probe:
            def __init__(self, name, **kw):
                built.append(name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                pass

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Probe)
        eng = BatchingEngine(BatchingOptions(max_batch=2), start=False)
        obs.disable()
        req = eng.submit(b"quiet")
        while eng.run_once():
            pass
        req.result(timeout=60)
        assert built == [] and obs.finished_spans() == []
        obs.enable()
        req = eng.submit(b"heard")
        while eng.run_once():
            pass
        req.result(timeout=60)
        assert {"bucket_launch", "bucket_sync", "bucket_unpack",
                "bucket_pack"} <= set(built)
        assert set(built) <= tracing.PROFILER_SPANS

    def test_gc_pause_recorded_while_enabled_only(self):
        obs.enable()
        assert tracing._gc_hook in gc.callbacks
        gc.collect()
        (pause,) = _by_name(obs.finished_spans(), "gc_pause")
        assert pause.thread_name == threading.current_thread().name
        assert pause.t1 >= pause.t0
        obs.disable()
        assert tracing._gc_hook not in gc.callbacks
        obs.reset()
        gc.collect()
        assert obs.finished_spans() == []

    def test_gc_pauses_filed_under_thread_churn(self):
        """With many threads recording spans while collections run
        at nearly every allocation, every span is filed and each
        collection of the oldest generation leaves one ``gc_pause``."""
        obs.enable()
        n_threads, n_iter, every = 16, 200, 50
        full0 = gc.get_stats()[2]["collections"]
        switch, thresholds = sys.getswitchinterval(), gc.get_threshold()
        sys.setswitchinterval(1e-6)
        gc.set_threshold(1, 1, 1)   # collections at nearly any allocation
        try:
            def work():
                for i in range(n_iter):
                    with obs.span("churn"):
                        junk = [[j] for j in range(20)]  # noqa: F841
                    if i % every == 0:
                        gc.collect()

            threads = [threading.Thread(target=work)
                       for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(switch)
            gc.set_threshold(*thresholds)
        spans = obs.finished_spans()
        full = gc.get_stats()[2]["collections"] - full0
        assert len(_by_name(spans, "churn")) == n_threads * n_iter
        assert len(_by_name(spans, "gc_pause")) == full > 0

    def test_set_adopts_a_trace_id(self):
        obs.enable()
        with obs.span("feed_wait") as sp:
            sp.set(trace_id=12345, op="sha3_256")
        assert sp.trace_id == 12345 and sp.attrs == {"op": "sha3_256"}
        obs.disable()
        with obs.span("feed_wait") as null:
            null.set(trace_id=1)
        assert null.trace_id is None


# ---------------------------------------------------------------------------
# Thread safety under load (chaos)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
class TestTelemetryThreadSafety:
    N_THREADS = 8
    N_ITER = 400

    def test_hammer_while_serving(self):
        obs.enable()
        eng = BatchingEngine(
            BatchingOptions(max_batch=8, max_queue=4096), start=True)
        errors = []

        def hammer(tid):
            try:
                for i in range(self.N_ITER):
                    telemetry.incr("chaos_hammer")
                    telemetry.incr(f"chaos_hammer_{tid}")
                    with obs.span("chaos_span", tid=tid):
                        pass
                    if i % 100 == 0:
                        # concurrent readers: consistent, never torn
                        snap = telemetry.snapshot()
                        assert snap["chaos_hammer"] >= 1
                        with telemetry.delta() as d:
                            telemetry.incr("chaos_probe")
                        assert d()["chaos_probe"] >= 1
                        obs.prometheus_text()
                        obs.snapshot()
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        threads = [threading.Thread(target=hammer, args=(t,))
                   for t in range(self.N_THREADS)]
        reqs = [eng.submit(b"p%d" % i) for i in range(64)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for r in reqs:
            r.result(timeout=120)
        eng.close()

        assert errors == []
        # exact final counts: no lost increments anywhere
        want = self.N_THREADS * self.N_ITER
        assert telemetry.counter("chaos_hammer") == want
        for tid in range(self.N_THREADS):
            assert telemetry.counter(f"chaos_hammer_{tid}") == self.N_ITER
        assert obs.metrics.histogram("chaos_span").n == want
        # the serving engine kept answering while being hammered
        assert telemetry.counter("serve_completed") == 64
