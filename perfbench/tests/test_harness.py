"""The harness end to end on the CPU, at a size a test run holds.

The look for a chip is skipped (``require_tpu=False``); everything else
runs as on the chip: set-up and warm-up, the window through
``BatchingEngine.submit``, the drain, the comparison and the readers.
"""

import json
import os
import shutil

import pytest

from perfbench import control, run, spec

SEED = 2**31 + 101


def _toy_root(tmp_path, *, extra_metric: bool = False):
    """A checkout with the real configurations, readers and work files,
    a tiny closed-loop mix, and a BENCHMARK.json naming two toy cells."""
    bench = tmp_path / "perfbench"
    for part in ("configs", "metrics", "work"):
        shutil.copytree(os.path.join(spec.HERE, part), bench / part)
    shutil.copy(os.path.join(spec.HERE, "peaks.json"), bench / "peaks.json")
    (bench / "traffic").mkdir()
    (bench / "traffic" / "toy.closed.json").write_text(json.dumps(
        {"loop": "closed", "clients": 8, "sizes": [16], "weights": [1.0],
         "why": "test"}))
    per_layer = [{"name": "lanes_per_bucket", "unit": "lanes",
                  "better": "higher", "source": "program_counter",
                  "layer": "admission", "moves": "req_per_s"}]
    if extra_metric:
        (bench / "metrics" / "answers_total.py").write_text(
            "def read(ctx):\n"
            "    return sum(r.value is not None for r in ctx.records)\n")
        per_layer.append({"name": "answers_total", "unit": "req",
                          "better": "higher", "source": "program_counter",
                          "layer": "admission", "moves": "req_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [
            {"name": "toy.sha3", "config": "sha3_256-openssl",
             "traffic": "toy.closed", "chips": 1, "why": "test"},
            {"name": "toy.tls", "config": "tls13-aes128gcm",
             "traffic": "toy.closed", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "req_per_s", "unit": "req/s", "better": "higher",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": per_layer}))
    return str(tmp_path), str(bench)


def _run(tmp_path, name, *, trace=False, extra_metric=False, **kw):
    root, bench = _toy_root(tmp_path, extra_metric=extra_metric)
    cell = spec.load_cell(name, root=root, bench_dir=bench)
    return run.run_cell(cell, seed=SEED, seconds=1.0, trace=trace,
                        require_tpu=False, **kw)


def test_sound_run_is_correct_and_reports_its_metrics(tmp_path):
    res = _run(tmp_path, "toy.sha3")
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] >= 8
    assert set(res["metrics"]) == {"setup_s", "req_per_s"}
    assert res["metrics"]["req_per_s"]["value"] > 0
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    assert res["checks"]["wrong_answers"] == {"value": 0, "limit": 0}


def test_dropped_in_metric_is_found_by_name(tmp_path):
    res = _run(tmp_path, "toy.sha3", trace=False, extra_metric=True)
    assert res["correct"] is True
    root, bench = _toy_root(tmp_path / "again", extra_metric=True)
    cell = spec.load_cell("toy.sha3", root=root, bench_dir=bench)
    assert [m["name"] for m, _ in cell.per_layer] == [
        "lanes_per_bucket", "answers_total"]
    assert cell.traffic["clients"] == 8
    assert cell.config["name"] == "sha3_256-openssl"


def test_dropped_in_configuration_is_found_by_name(tmp_path):
    root, bench = _toy_root(tmp_path)
    cfg = json.load(open(os.path.join(bench, "configs",
                                      "sha3_256-openssl.json")))
    cfg["name"] = "sha3_256-tiny"
    cfg["message_bytes"] = [16]
    with open(os.path.join(bench, "configs", "sha3_256-tiny.json"),
              "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(bench, "configs", "sha3_256-openssl.py"),
                os.path.join(bench, "configs", "sha3_256-tiny.py"))
    spec_path = os.path.join(root, "BENCHMARK.json")
    b = json.load(open(spec_path))
    b["workloads"].append({"name": "tiny.sha3", "config": "sha3_256-tiny",
                           "traffic": "toy.closed", "chips": 1,
                           "why": "test"})
    with open(spec_path, "w") as f:
        json.dump(b, f)
    cell = spec.load_cell("tiny.sha3", root=root, bench_dir=bench)
    assert cell.config["message_bytes"] == [16]
    with pytest.raises(KeyError):
        spec.load_cell("no.such.cell", root=root, bench_dir=bench)


def test_control_in_the_program_place_is_not_correct(tmp_path):
    res = _run(tmp_path, "toy.sha3", engine_factory=control.ControlEngine)
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] == res["attempted"]


# -- the timed path broken underneath: each fault must read not correct --

def _flip_first(values):
    v = bytearray(values[0])
    v[0] ^= 1
    return [bytes(v)] + list(values[1:])


def _half_batch(values):
    half = (len(values) + 1) // 2
    return list(values[:half]) + list(values[:len(values) - half])


def _patch_answers(monkeypatch, name, fix):
    from repro.serve import batching

    real = getattr(batching, name)
    monkeypatch.setattr(batching, name,
                        lambda *a, **k: fix(real(*a, **k)))


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch",
                                   "state_unchanged"])
def test_sha3_fault_reads_not_correct(tmp_path, monkeypatch, fault):
    if fault == "state_unchanged":
        from repro.crypto import keccak
        monkeypatch.setattr(keccak, "keccak_f1600", lambda bits, **k: bits)
    else:
        _patch_answers(monkeypatch, "_absorb_digests",
                       _flip_first if fault == "answer_altered"
                       else _half_batch)
    res = _run(tmp_path, "toy.sha3")
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch"])
def test_tls_fault_reads_not_correct(tmp_path, monkeypatch, fault):
    _patch_answers(monkeypatch, "_bucket_seal",
                   _flip_first if fault == "answer_altered" else _half_batch)
    res = _run(tmp_path, "toy.tls")
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_tls_sound_run_is_correct(tmp_path):
    res = _run(tmp_path, "toy.tls")
    assert res["correct"] is True
    assert res["checks"]["wrong_answers"]["value"] == 0
