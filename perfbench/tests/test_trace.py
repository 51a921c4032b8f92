import gzip
import os

import pytest

from perfbench import readers, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
CHIP_TRACE = os.path.join(DATA, "sha3_small_closed.xplane.pb.gz")

KERNEL = "%_unknown_.1 = s32[1600,128]{1,0:T(8,128)} custom-call(s32[12] %a)"
PAD = "%pad.1 = s32[1600,128]{1,0:T(8,128)} pad(s32[1600,8] %copy)"


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert trace.union([]) == []


def test_summary_of_synthetic_planes():
    ms = 1_000_000
    planes = [
        ("/device:TPU:0", [
            ("XLA Modules", [("jit_x", 0, 100 * ms)]),     # not an op line
            ("XLA Ops", [(PAD, 0, 1 * ms), (KERNEL, 1 * ms, 6 * ms),
                         (PAD, 20 * ms, 1 * ms), (KERNEL, 21 * ms, 6 * ms),
                         (KERNEL, 24 * ms, 2 * ms)])]),
        ("/host:CPU", [("python", [
            ("np.asarray(jax.Array)", 6 * ms, 15 * ms),
            ("PjitFunction(pad)", 18 * ms, 3 * ms),
            ("Session", 0, 1000 * ms)])]),
    ]
    s = trace.summarize(planes, window_s=0.1)
    assert s.n_devices == 1
    assert s.busy_s == pytest.approx(0.014)     # [0, 7) and [20, 27) ms
    assert s.kernel(readers.MEGAKERNEL) == (3, pytest.approx(0.014))
    # The gap [7, 20) ms is named by the host event covering most of it.
    assert s.gaps == [["np.asarray(jax.Array)", pytest.approx(0.013)]]
    b = s.breakdown()
    assert b["device_ops"][0] == ["%_unknown_.1 custom-call",
                                  pytest.approx(0.014)]
    assert b["device_ops"][1] == ["%pad.1 pad", pytest.approx(0.002)]


def test_no_device_plane_reads_nothing():
    s = trace.summarize([("/host:CPU", [])], window_s=1.0)
    assert s.n_devices == 0 and s.busy_s == 0
    assert s.kernel(readers.MEGAKERNEL) == (0, 0.0)


def test_recorded_chip_trace():
    """A quarter-second window of a closed-loop SHA3-256 run of one-block
    messages, recorded on one TPU v5e: Keccak-f launches of 8-lane buckets, each a megakernel
    custom call and a few XLA ops around it."""
    import jax

    with gzip.open(CHIP_TRACE, "rb") as f:
        data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    planes = [(p.name, [(ln.name, [(e.name, e.start_ns, e.duration_ns)
                                   for e in ln.events]) for ln in p.lines])
              for p in data.planes]
    s = trace.summarize(planes, window_s=0.25)
    assert s.n_devices == 1
    count, seconds = s.kernel(readers.MEGAKERNEL)
    assert count > 5
    ms = seconds / count * 1e3
    assert 1 < ms < 50                      # one Keccak-f launch
    assert 0 < s.busy_s <= 0.25 * 1.05
    assert seconds <= s.busy_s
    names = [n for n, _ in s.breakdown()["device_ops"]]
    assert names[0].endswith("custom-call")
    assert all(sec > 0 for _, sec in s.gaps)
