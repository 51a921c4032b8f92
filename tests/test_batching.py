"""Continuous-batching serving engine: admission, buckets, degradation.

Most tests drive the engine synchronously (``start=False`` +
``run_once()``) so batch composition is deterministic; one test runs
the real worker thread end-to-end.  Digest ground truth is hashlib.
"""

import hashlib
import os
import time

import numpy as np
import pytest

from repro.core import faults, integrity, telemetry
from repro.core.faults import InjectedLaunchFailure
from repro.core.resilience import (CircuitBreaker, LaunchFault,
                                   ResilientExecutor, RetryPolicy,
                                   TimeoutFault)
from repro.crypto.registry import REGISTRY
from repro.crypto import gcm
from repro.serve.batching import (BatchingEngine, BatchingOptions, Cancelled,
                                  Overloaded, _aead_bucket,
                                  _decode_aead_record, _dummy_payload,
                                  _gcm_registry_keys, _n_blocks,
                                  encode_aead_record)

pytestmark = pytest.mark.chaos


def _engine(**opts):
    opts.setdefault("chain", ("einsum", "reference"))
    return BatchingEngine(BatchingOptions(**opts), start=False)


def _drain(eng):
    while eng.run_once():
        pass


class TestBuckets:
    def test_n_blocks_matches_pad101(self):
        from repro.crypto import keccak
        for n in (0, 1, 135, 136, 137, 271, 272, 500):
            got = _n_blocks(n)
            assert got == keccak._pad101(b"\x00" * n, 136, 0x06).shape[0]

    def test_dummy_payload_lands_in_its_bucket(self):
        for nb in (1, 2, 5):
            assert _n_blocks(len(_dummy_payload(nb))) == nb

    def test_mixed_lengths_bit_exact(self):
        eng = _engine(max_batch=4)
        msgs = [b"", b"a", b"x" * 135, b"y" * 136, b"z" * 300, b"ab" * 80]
        reqs = [eng.submit(m) for m in msgs]
        _drain(eng)
        for m, r in zip(msgs, reqs):
            assert r.result(timeout=1) == hashlib.sha3_256(m).digest()
            assert r.backend == "einsum" and r.latency_s > 0

    def test_batches_are_bucket_aligned_and_pow2_padded(self):
        eng = _engine(max_batch=4)
        # 3 one-block + 1 two-block: one (4,1)-padded batch, one (1,2).
        for m in (b"a", b"b", b"c", b"x" * 140):
            eng.submit(m)
        _drain(eng)
        shapes = sorted(shape for _, shape, _, _ in eng.batch_log)
        assert shapes == [(1, 2), (4, 1)]
        assert telemetry.counter("serve_padded_lanes") == 1  # 3 -> 4 lanes
        assert telemetry.counter("serve_completed") == 4

    def test_fifo_within_bucket(self):
        eng = _engine(max_batch=2)
        reqs = [eng.submit(bytes([i])) for i in range(5)]
        assert eng.run_once() == 2               # oldest two first
        assert reqs[0].done() and reqs[1].done() and not reqs[2].done()
        _drain(eng)
        assert all(r.done() for r in reqs)


class TestBucketCapacity:
    """By default a bucket holds up to one megakernel lane block: the
    width every launch runs at, so a backlog rides one launch."""

    def test_default_cap_is_one_megakernel_lane_block(self):
        from repro.core import plan_program as pp
        from repro.kernels import plan_program_kernel as ppk
        assert BatchingOptions().max_batch == ppk.LANES == pp.lane_pad(1)

    def test_backlog_leaves_as_one_full_block(self):
        eng = _engine()
        msgs = [b"m%03d" % i for i in range(100)]
        reqs = [eng.submit(m) for m in msgs]
        assert eng.run_once() == 100
        assert [shape for _, shape, _, _ in eng.batch_log] == [(128, 1)]
        assert telemetry.counter("serve_padded_lanes") == 28
        for m, r in zip(msgs, reqs):
            assert r.result(timeout=0) == hashlib.sha3_256(m).digest()

    def test_bucket_never_waits_to_fill(self):
        eng = _engine()
        reqs = [eng.submit(bytes([i])) for i in range(3)]
        assert eng.run_once() == 3
        assert all(r.done() for r in reqs)
        assert [shape for _, shape, _, _ in eng.batch_log] == [(4, 1)]
        assert eng.queue_depth() == 0

    def test_sessions_backlog_seals_as_one_bucket(self):
        from cryptography.hazmat.primitives.ciphers.aead import AESGCM
        keys = [bytes((29 * s + 3 * i + 7) & 0xFF for i in range(16))
                for s in range(12)]
        eng = _engine(aead_keys=keys)
        recs = [(bytes([s + 1]) * 12, bytes([0x50 + s]) * 17,
                 b"\x17\x03\x03\x00\x21", s) for s in range(12)]
        reqs = [eng.submit(encode_aead_record(*r), op="gcm_seal")
                for r in recs]
        assert eng.run_once() == 12
        assert [shape for _, shape, _, _ in eng.batch_log] == [(16, 17, 5)]
        assert telemetry.counter("serve_padded_lanes") == 4
        for req, (nonce, pt, aad, s) in zip(reqs, recs):
            assert req.result(timeout=0) == AESGCM(keys[s]).encrypt(
                nonce, pt, aad)


class TestAdmission:
    def test_overload_sheds_with_typed_rejection(self):
        eng = _engine(max_queue=2)
        eng.submit(b"a")
        eng.submit(b"b")
        with pytest.raises(Overloaded, match="queue full"):
            eng.submit(b"c")
        assert telemetry.counter("serve_shed") == 1
        assert eng.queue_depth() == 2            # shed request never queued
        _drain(eng)

    def test_unsupported_op_rejected_at_submit(self):
        eng = _engine()
        with pytest.raises(ValueError, match="unsupported op"):
            eng.submit(b"x", op="md5")

    def test_expired_deadline_completes_with_timeout_fault(self):
        eng = _engine()
        req = eng.submit(b"late", timeout_s=0.0)
        time.sleep(0.01)
        eng.run_once()
        with pytest.raises(TimeoutFault, match="deadline expired"):
            req.result(timeout=1)
        assert telemetry.counter("serve_timeouts") == 1

    def test_cancel_before_dispatch(self):
        eng = _engine()
        a = eng.submit(b"keep")
        b = eng.submit(b"drop")
        assert b.cancel()
        _drain(eng)
        assert a.result(1) == hashlib.sha3_256(b"keep").digest()
        with pytest.raises(Cancelled):
            b.result(timeout=1)
        assert not b.cancel()                    # already completed
        assert telemetry.counter("serve_cancelled") == 1

    def test_result_timeout_while_queued(self):
        eng = _engine()
        req = eng.submit(b"never run")
        with pytest.raises(TimeoutFault, match="not ready"):
            req.result(timeout=0.01)
        assert not req.done()                    # still queued, not failed


class TestDegradation:
    def _chaos_engine(self):
        ex = ResilientExecutor(
            chain=("einsum", "reference"),
            retry=RetryPolicy(max_attempts=2, backoff_base_s=0.0),
            breaker=CircuitBreaker(threshold=10, clock=lambda: 0.0),
            sleep=lambda s: None, registry=REGISTRY)
        return BatchingEngine(
            BatchingOptions(max_batch=4, chain=("einsum", "reference")),
            executor=ex, start=False)

    def test_injected_faults_fall_back_bit_exactly(self):
        eng = self._chaos_engine()
        msgs = [b"alpha", b"beta", b"gamma"]
        with faults.inject_faults(seed=0, launch_rate=1.0,
                                  max_faults=2) as inj:
            reqs = [eng.submit(m) for m in msgs]
            _drain(eng)
        assert inj.count == 2                    # einsum's two attempts
        for m, r in zip(msgs, reqs):
            assert r.result(1) == hashlib.sha3_256(m).digest()
            assert r.backend == "reference"      # degraded, not wrong
        snap = telemetry.snapshot()
        assert snap["resilience_fallbacks"] == 1
        assert snap["resilience_retries"] == 1
        assert snap["serve_completed"] == 3
        log_backends = [b for _, _, b, _ in eng.batch_log]
        assert log_backends == ["reference"]

    def test_exhausted_chain_rejects_all_requests_typed(self):
        eng = self._chaos_engine()
        with faults.inject_faults(seed=0, launch_rate=1.0):
            reqs = [eng.submit(m) for m in (b"a", b"b")]
            _drain(eng)
        for r in reqs:
            # The engine surfaces the executor's typed fault (the
            # injected failure rides along as __cause__).
            with pytest.raises(LaunchFault) as ei:
                r.result(timeout=1)
            assert isinstance(ei.value.__cause__, InjectedLaunchFailure)
        assert telemetry.counter("serve_failed") == 2
        assert telemetry.counter("resilience_exhausted") == 1

    def test_drift_quarantine_inside_serving_path(self):
        eng = self._chaos_engine()
        eng.submit(b"warm the geometry")
        _drain(eng)
        assert faults.poison_observations(REGISTRY) > 0
        req = eng.submit(b"post-drift request")
        _drain(eng)
        assert req.result(1) == hashlib.sha3_256(
            b"post-drift request").digest()
        assert req.backend == "einsum"           # recovered, not degraded
        assert REGISTRY.quarantine_count("keccak/rho_pi") == 1
        assert telemetry.counter("resilience_quarantines") == 1

    def test_stats_exposes_counters_and_breakers(self):
        eng = self._chaos_engine()
        eng.submit(b"x")
        _drain(eng)
        stats = eng.stats()
        assert stats["queue_depth"] == 0
        assert stats["serve_completed"] == 1
        assert stats["breaker_open"] == []
        assert stats["resilience_backend_einsum"] == 1


class TestAEADRecords:
    """The gcm_seal op: (pt_len, aad_len)-geometry buckets sealing
    AEAD records through the same admission/degradation machinery."""

    KEY = bytes(range(16))

    def test_mixed_geometries_bucket_and_seal_bit_exactly(self):
        eng = _engine(aead_key=self.KEY, max_batch=8)
        recs = [(bytes([i]) * 12, bytes([0x40 + i]) * pt, b"ad" * i)
                for i, pt in enumerate((20, 20, 33, 33, 5))]
        reqs = [eng.submit(encode_aead_record(n, p, a), op="gcm_seal")
                for n, p, a in recs]
        _drain(eng)
        for req, (n, p, a) in zip(reqs, recs):
            want = gcm.aes128_gcm_seal(self.KEY, n, p, a,
                                       backend="einsum")
            assert req.result(timeout=5) == want

    def test_bucket_key_is_op_and_geometry(self):
        eng = _engine(aead_key=self.KEY, max_batch=2)
        same = [encode_aead_record(bytes([i]) * 12, b"x" * 24, b"aa")
                for i in range(2)]
        other = encode_aead_record(b"\x07" * 12, b"x" * 24)  # no AAD
        reqs = [eng.submit(r, op="gcm_seal") for r in same + [other]]
        eng.run_once()                           # full (24, 2) bucket
        assert reqs[0].done() and reqs[1].done() and not reqs[2].done()
        _drain(eng)
        assert reqs[2].done()

    def test_filler_records_never_leak_into_results(self):
        # 3 records pad to a 4-lane batch; the filler lane must not
        # perturb any real lane (sealed output is per-record exact).
        eng = _engine(aead_key=self.KEY, max_batch=8)
        recs = [(bytes([9 - i]) * 12, bytes(range(16)), b"")
                for i in range(3)]
        reqs = [eng.submit(encode_aead_record(n, p, a), op="gcm_seal")
                for n, p, a in recs]
        _drain(eng)
        for req, (n, p, a) in zip(reqs, recs):
            got = req.result(timeout=5)
            assert got[-16:] == gcm.aes128_gcm_seal(
                self.KEY, n, p, a, backend="einsum")[-16:]
            assert gcm.aes128_gcm_open(self.KEY, n, got) == p

    def test_sha3_and_gcm_interleave_in_one_engine(self):
        eng = _engine(aead_key=self.KEY, max_batch=8)
        msg = b"hash me"
        rec = encode_aead_record(b"\x01" * 12, b"seal me")
        h = eng.submit(msg)
        s = eng.submit(rec, op="gcm_seal")
        _drain(eng)
        assert h.result(timeout=5) == hashlib.sha3_256(msg).digest()
        assert s.result(timeout=5) == gcm.aes128_gcm_seal(
            self.KEY, b"\x01" * 12, b"seal me", backend="einsum")


class TestAEADSessions:
    """Per-session keys: the sessions live on the device, a record names
    its session, and one bucket's lanes seal under different keys."""

    KEYS = [bytes((17 * r + 5 * i + 1) & 0xFF for i in range(16))
            for r in range(8)]

    def _records(self, sessions):
        return [(bytes([s + 1]) * 12, bytes([0x30 + s]) * 17, b"\x17\x03\x03"
                 + bytes([0, 33]), s) for s in sessions]

    @pytest.mark.parametrize("chain", [("megakernel",), ("einsum",)])
    def test_one_bucket_seals_each_lane_under_its_session(self, chain):
        from cryptography.hazmat.primitives.ciphers.aead import AESGCM
        eng = _engine(aead_keys=self.KEYS, max_batch=8, chain=chain)
        recs = self._records([7, 3, 0, 5, 3, 1, 6, 2])
        before = telemetry.snapshot()
        reqs = [eng.submit(encode_aead_record(*r), op="gcm_seal")
                for r in recs]
        assert eng.run_once() == 8               # one bucket, one launch
        for req, (nonce, pt, aad, s) in zip(reqs, recs):
            assert req.result(timeout=5) == AESGCM(self.KEYS[s]).encrypt(
                nonce, pt, aad)
            assert req.backend == chain[0]
        snap = telemetry.snapshot()
        assert (snap["serve_bucket_sessions"]
                - before.get("serve_bucket_sessions", 0)) == 7
        assert len(eng.batch_log) == 1

    def test_record_without_session_uses_session_zero(self):
        from cryptography.hazmat.primitives.ciphers.aead import AESGCM
        before = telemetry.counter("serve_sessions_installed")
        eng = _engine(aead_keys=self.KEYS, max_batch=8,
                      chain=("megakernel",))
        assert telemetry.counter("serve_sessions_installed") - before == 8
        nonce, pt, aad, _ = self._records([0])[0]
        req = eng.submit(encode_aead_record(nonce, pt, aad), op="gcm_seal")
        _drain(eng)
        assert req.result(timeout=5) == AESGCM(self.KEYS[0]).encrypt(
            nonce, pt, aad)

    def test_unknown_session_is_refused_at_submit(self):
        eng = _engine(aead_keys=self.KEYS[:2])
        rec = encode_aead_record(b"\x00" * 12, b"x", session=2)
        with pytest.raises(ValueError, match="session 2"):
            eng.submit(rec, op="gcm_seal")
        assert eng.queue_depth() == 0

    def test_wire_format_round_trip(self):
        nonce, pt, aad = b"\x05" * 12, b"payload!", b"hdr"
        plain = encode_aead_record(nonce, pt, aad)
        assert plain == nonce + (3).to_bytes(4, "big") + aad + pt
        named = encode_aead_record(nonce, pt, aad, session=65535)
        assert _decode_aead_record(plain) == (nonce, pt, aad, 0)
        assert _decode_aead_record(named) == (nonce, pt, aad, 65535)
        assert _aead_bucket(plain) == _aead_bucket(named) == (8, 3)

    def test_registry_keys_follow_the_geometry_alone(self):
        keys = _gcm_registry_keys(17, 5)
        assert keys("megakernel") == (gcm._program_key(17, 5, False),)
        assert all(k.startswith("aes/") for k in keys("einsum"))


class TestAEADChaosSweep:
    """Satellite chaos sweep: 10^4 GCM records sealed through the
    serving engine under 1% injected megakernel faults plus silent
    cache corruption.  Every tag must be bit-exact (checked against the
    clean run, which is itself spot-verified against the pure-python
    oracle from ``test_gcm``), the integrity guards must catch the
    corruption before a poisoned tag is served, and a tampered tag must
    reject with a typed error that leaks no plaintext.

    ``CHAOS_AEAD_RECORDS`` shrinks the sweep for quick CI laps; the
    default is the full 10^4 of the acceptance criteria.
    """

    KEY = bytes(range(16))
    PT_LEN, AAD_LEN = 32, 8

    def _records(self, n):
        rng = np.random.default_rng(0xC0FFEE)
        return [(i.to_bytes(12, "big"), rng.bytes(self.PT_LEN),
                 rng.bytes(self.AAD_LEN)) for i in range(n)]

    def _seal_all(self, recs, mid_hook=None):
        """One fresh engine, fused-first chain, synchronous waves of
        max_batch so the whole sweep is (10^4/128) one-launch seals."""
        eng = _engine(aead_key=self.KEY, max_batch=128, max_queue=256,
                      chain=("megakernel", "einsum"))
        out = []
        step = 128
        for start in range(0, len(recs), step):
            if mid_hook is not None and start >= len(recs) // 2:
                mid_hook()
                mid_hook = None
            wave = recs[start:start + step]
            reqs = [eng.submit(encode_aead_record(n, p, a), op="gcm_seal")
                    for n, p, a in wave]
            _drain(eng)
            out.extend(r.result(timeout=120) for r in reqs)
        return out

    def test_chaos_sweep_bit_exact_tags(self):
        n = int(os.environ.get("CHAOS_AEAD_RECORDS", "10000"))
        recs = self._records(n)

        clean = self._seal_all(recs)
        # Independent oracle spot-check of the clean baseline: the
        # pure-python GCM from the CAVP suite (too slow for all 10^4).
        from test_gcm import gcm_ref
        for i in np.random.default_rng(7).choice(
                n, size=min(24, n), replace=False):
            nonce, pt, aad = recs[i]
            ct, tag = gcm_ref(self.KEY, nonce, pt, aad)
            assert clean[i] == ct + tag, f"oracle mismatch at record {i}"

        before = telemetry.snapshot()
        # Chaos pass: every cache hit digest-verified, ~1% of megakernel
        # launches die, the corrupt site flips cache bits at random, and
        # one guaranteed mid-sweep constants flip rides on top.
        with integrity.always_verify():
            with faults.inject_faults(seed=11, program_rate=0.01,
                                      corrupt_cache_rate=0.01,
                                      max_faults=8) as inj:
                chaotic = self._seal_all(
                    recs,
                    mid_hook=lambda: faults.corrupt_cache(
                        np.random.default_rng(5), target="const"))

        assert chaotic == clean                  # bit-exact through chaos
        snap = telemetry.snapshot()
        delta = {k: snap.get(k, 0) - before.get(k, 0)
                 for k in ("integrity_checks", "integrity_faults",
                           "resilience_quarantines", "resilience_retries",
                           "resilience_faults", "serve_completed")}
        assert delta["serve_completed"] == n
        # The guaranteed mid-sweep flip was caught and quarantined —
        # the poison was never served.
        assert delta["integrity_checks"] > 0
        assert delta["integrity_faults"] >= 1
        assert delta["resilience_quarantines"] >= 1
        # Injected launch faults (if the seed fired any at this sweep
        # size) were retried/degraded, never surfaced to a caller.
        fired = [s for s, _ in inj.injected if s == "program"]
        if fired:
            assert delta["resilience_faults"] >= len(fired)

    def test_tampered_tag_rejects_without_plaintext_leak(self):
        nonce, pt, aad = b"\x01" * 12, b"attack at dawn!!", b"hdr"
        sealed = gcm.aes128_gcm_seal(self.KEY, nonce, pt, aad,
                                     backend="einsum")
        tampered = sealed[:-1] + bytes([sealed[-1] ^ 1])
        with pytest.raises(gcm.InvalidTagError) as ei:
            gcm.aes128_gcm_open(self.KEY, nonce, tampered, aad,
                                backend="einsum")
        assert ei.value.indices == (0,)
        # The rejection carries indices only: no plaintext (or anything
        # derived from it) in the message or on the exception.
        leak_surface = repr(ei.value) + repr(vars(ei.value))
        assert pt.decode() not in leak_surface
        assert pt.hex() not in leak_surface


class TestWorkerThread:
    def test_threaded_end_to_end(self):
        eng = BatchingEngine(
            BatchingOptions(max_batch=4, chain=("einsum", "reference")))
        try:
            msgs = [bytes([i]) * (i + 1) for i in range(6)]
            digests = eng.map(msgs)
            assert digests == [hashlib.sha3_256(m).digest() for m in msgs]
        finally:
            eng.close()
        assert eng.check_workers() == []         # worker was beating

    def test_close_without_drain_cancels_pending(self):
        eng = _engine()                          # start=False: never runs
        req = eng.submit(b"doomed")
        eng.close(drain=False)
        with pytest.raises(Cancelled):
            req.result(timeout=1)

    def test_watchdog_reports_wedged_worker(self):
        eng = _engine(watchdog_miss_threshold=2)  # start=False: no beats
        assert eng.check_workers() == []
        assert eng.check_workers() == [0]
        assert telemetry.counter("serve_watchdog_misses") == 1
        eng.heartbeats.beat(0)                   # a beat recovers it
        assert eng.check_workers() == []
