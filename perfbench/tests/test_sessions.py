"""The ``tls13-aes128gcm-sessions`` configuration on the CPU: its client's
records on the wire, the comparison that catches a record sealed under
another session's key, the readers of ``key_lookup_ms_per_bucket`` and
``megakernel_roofline.sessions``, and a short run of the cell's harness
on the program's megakernel path."""

import dataclasses
import json
import os
import types

import pytest

from perfbench import drive, run, spec, traffic

SEED = 2**33 + 7
CONFIG = "tls13-aes128gcm-sessions"


def _config(**changes):
    with open(os.path.join(spec.HERE, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    cfg.update(changes)
    return cfg


def _module(*parts):
    return spec.load_module(os.path.join(spec.HERE, *parts))


sessions_client = _module("configs", CONFIG + ".py")


def _client(**changes):
    return sessions_client.Client(_config(**changes), SEED, traffic.rng)


def test_payloads_round_trip_through_the_engine_decoder():
    from repro.serve.batching import _aead_bucket, _decode_aead_record

    client = _client()
    assert len(client.engine_options()["aead_keys"]) == 65536
    seen = set()
    for index in range(200):
        nonce, pt, aad, session = client.record(index, 16)
        payload = client.payload(index, 16)
        assert _decode_aead_record(payload) == (nonce, pt, aad, session)
        assert _aead_bucket(payload) == (17, 5)
        assert dict(zip(("pt_len", "aad_len"), _aead_bucket(payload))) \
            == client.geometry(16)
        seen.add(session)
    # Zipf 0.99 over 65,536 sessions: most records of a bucket differ.
    assert len(seen) > 100
    # One record's session and nonce are a function of the record.
    assert _client().record(5, 16) == client.record(5, 16)


def _records(client, n=24):
    recs = []
    for index in range(n):
        rec = drive.Record(index=index, size=16, due=0.0)
        rec.value = client.expected([(index, 16)])[0]
        recs.append(rec)
    return recs


def test_wrong_session_and_the_control_are_caught():
    client = _client(sessions=64)
    recs = _records(client)
    assert run.check(client, recs)["wrong_answers"]["value"] == 0
    # One record sealed under its neighbour session's key.
    nonce, pt, aad, s = client.record(3, 16)
    recs[3].value = client._seal((s + 1) % 64, nonce, pt, aad)
    assert run.check(client, recs)["wrong_answers"]["value"] == 1
    control = _records(client)
    for rec, value in zip(control, client.control(
            [(r.index, r.size) for r in control])):
        rec.value = value
    wrong = run.check(client, control)["wrong_answers"]["value"]
    assert wrong == sum(client.session(r.index) != 0 for r in control) > 0


def _span(name, t0, t1):
    return types.SimpleNamespace(name=name, t0=t0, t1=t1, thread_id=1,
                                 attrs={})


def test_key_lookup_reader():
    reader = _module("metrics", "key_lookup_ms_per_bucket.py")
    ctx = types.SimpleNamespace(t0=10.0, t1=20.0, spans=[])
    assert reader.read(ctx) is None
    ctx.spans = [_span("key_lookup", 11.0, 11.001),
                 _span("key_lookup", 12.0, 12.003),
                 _span("key_lookup", 19.0, 20.5),      # ends past the window
                 _span("bucket_pack", 13.0, 14.0)]
    assert reader.read(ctx) == pytest.approx(2.0)


def test_roofline_reader_adds_each_record_key():
    reader = _module("metrics", "megakernel_roofline.sessions.py")
    work = _module("work", "gcm_seal.py")
    kernel = {"n": 4, "s": 0.002}
    trace = types.SimpleNamespace(
        kernel=lambda name: (kernel["n"], kernel["s"]))
    done = [types.SimpleNamespace(size=16, value=b"x", t_done=t)
            for t in (10.5, 11.0, 19.9, 20.0)]       # the last is late
    ctx = types.SimpleNamespace(
        t0=10.0, t1=20.0, records=done, trace=trace,
        work_bytes=lambda n: work.request_bytes(pt_len=n + 1, aad_len=5),
        peak={"hbm_bytes_per_s": 819e9})
    moved = 3 * (work.request_bytes(pt_len=17, aad_len=5) + 16)
    assert reader.read(ctx) == pytest.approx(moved / 819e9 / 0.002 * 100)
    kernel["n"] = 0
    assert reader.read(ctx) is None
    ctx.trace = None
    assert reader.read(ctx) is None


def test_cell_runs_correct_on_the_megakernel(tmp_path):
    """The harness end to end on a toy closed loop, the engine's fused
    seal in the program's place (interpreted on the CPU)."""
    from repro.serve.batching import BatchingEngine

    cell = spec.load_cell("gcm.tls.sessions",
                          root=os.path.dirname(spec.HERE))
    cell.traffic = dict(cell.traffic, clients=8)

    def factory(options, client):
        return BatchingEngine(dataclasses.replace(options,
                                                  chain=("megakernel",)),
                              start=False)

    res = run.run_cell(cell, seed=SEED, seconds=0.5, trace=False,
                       require_tpu=False, engine_factory=factory)
    assert res["correct"] is True and res["attempted"] >= 8
    assert res["checks"]["never_answered"]["value"] == 0
