"""Mesh-sharded plan execution: connectivity/schedule derivation,
shard-restricted plans, the tuning table, per-device health, and the
8-fake-device differential suites (bit-exactness vs single device,
collective-free HLO for lane-parallel programs, survivor-mesh serving)
run in subprocesses so XLA_FLAGS takes effect before jax import."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.core import crossbar as xb
from repro.core import plan_algebra as pa
from repro.core import telemetry
from repro.core.resilience import DeviceHealth
from repro.core.semiring import GF2, REAL
from repro.core.tuning import TuningTable, make_key
from repro.dist import mesh_exec as mx
from repro.dist import sharding as shd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))



def _run_sub(script, sentinel, timeout=600):
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")})
    assert sentinel in proc.stdout, (
        proc.stdout[-2000:] + proc.stderr[-2000:])


# ---------------------------------------------------------------------------
# Host-side derivation: occupancy -> connectivity -> collective schedule.
# ---------------------------------------------------------------------------

class TestShardConnectivity:
    def test_block_diag_is_diagonal(self):
        idx = jnp.arange(16, dtype=jnp.int32)[:, None]
        conn = mx.shard_connectivity(
            xb.gather_plan(idx, 16, semiring=GF2), 4)
        assert np.array_equal(conn != 0, np.eye(4, dtype=bool))
        assert mx.is_lane_parallel(xb.gather_plan(idx, 16, semiring=GF2), 4)

    def test_rotation_is_one_off_diagonal(self):
        n, s = 16, 4
        idx = ((jnp.arange(n) + n // s) % n).astype(jnp.int32)[:, None]
        conn = mx.shard_connectivity(xb.gather_plan(idx, n, semiring=GF2), s)
        # conn[dst, src]: dst block d reads from src block d+1
        want = np.roll(np.eye(s, dtype=bool), 1, axis=1)
        assert np.array_equal(conn != 0, want)

    def test_indivisible_rejected(self):
        idx = jnp.arange(10, dtype=jnp.int32)[:, None]
        plan = xb.gather_plan(idx, 10, semiring=GF2)
        with pytest.raises(ValueError, match="divide"):
            mx.shard_connectivity(plan, 4)


class TestCollectiveSchedule:
    def test_rotation_single_round(self):
        conn = np.roll(np.eye(8, dtype=np.int64), -1, axis=1)
        sched = mx.collective_schedule(conn)
        assert len(sched) == 1 and len(sched[0]) == 8

    def test_diagonal_empty_schedule(self):
        assert mx.collective_schedule(np.eye(8, dtype=np.int64)) == []

    def test_rounds_cover_all_edges_as_partial_permutations(self):
        rng = np.random.default_rng(0)
        conn = (rng.random((8, 8)) < 0.4).astype(np.int64)
        sched = mx.collective_schedule(conn)
        edges = {(s, d) for d in range(8) for s in range(8)
                 if s != d and conn[d, s]}
        covered = set()
        for rnd in sched:
            # each round is a partial permutation: src and dst unique
            srcs = [s for s, _ in rnd]
            dsts = [d for _, d in rnd]
            assert len(set(srcs)) == len(srcs)
            assert len(set(dsts)) == len(dsts)
            covered |= set(rnd)
        assert covered == edges

    def test_stats_beat_naive_on_skewed(self):
        conn = np.eye(8, dtype=np.int64)
        conn[0, 1] = conn[1, 0] = 1      # one cross pair
        st = mx.schedule_stats(conn)
        assert st["scheduled_block_transfers"] == 2
        assert st["naive_block_transfers"] == 56
        assert st["schedule_rounds"] < st["naive_rounds"]


class TestShardRestrict:
    def test_window_correctness(self):
        rng = np.random.default_rng(1)
        idx = jnp.asarray(rng.permutation(16).astype(np.int32))[:, None]
        plan = xb.gather_plan(idx, 16, semiring=GF2)
        x = jnp.asarray(rng.integers(0, 2, 16), jnp.int32)
        full = xb.apply_plan(plan, x, backend="einsum")
        # output window [8, 16), input window [0, 8): matches the full
        # result wherever the source index fell inside the window
        sub = pa.shard_restrict(plan, (8, 8), (0, 8))
        got = xb.apply_plan(sub, x[:8], backend="einsum")
        src = np.asarray(idx[8:16, 0])
        inside = src < 8
        np.testing.assert_array_equal(np.asarray(got)[inside],
                                      np.asarray(full)[8:][inside])
        assert not np.asarray(got)[~inside].any()

    def test_bad_windows_rejected(self):
        idx = jnp.arange(8, dtype=jnp.int32)[:, None]
        plan = xb.gather_plan(idx, 8, semiring=GF2)
        for ow, iw in (((0, 9), (0, 8)), ((4, 8), (0, 8)),
                       ((0, 8), (-1, 4)), ((0, 0), (0, 8))):
            with pytest.raises(ValueError):
                pa.shard_restrict(plan, ow, iw)


class TestInputValidation:
    def test_mesh_axis_size_unknown_axis(self):
        mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
        with pytest.raises(ValueError, match="not on the mesh"):
            shd.mesh_axis_size(mesh, ("model",))

    def test_require_divisible(self):
        # a 1-device mesh divides everything; the indivisible branch is
        # exercised on 8 devices in SHARDED_PROGRAM_SCRIPT below
        mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
        assert shd.require_divisible(8, mesh, ("data",)) == 8
        with pytest.raises(ValueError, match="not on the mesh"):
            shd.require_divisible(7, mesh, ("bogus",))

    def test_quantize_empty_rejected(self):
        from repro.dist.collectives import quantize_int8
        with pytest.raises(ValueError, match="empty"):
            quantize_int8(jnp.zeros((0,)))

    def test_compressed_psum_unbound_axis(self):
        from repro.dist.collectives import compressed_psum
        with pytest.raises(ValueError, match="not bound"):
            compressed_psum(jnp.ones((4,)), "nonexistent_axis")

    def test_sharded_apply_unknown_axis(self):
        mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
        idx = jnp.arange(8, dtype=jnp.int32)[:, None]
        plan = xb.gather_plan(idx, 8, semiring=GF2)
        with pytest.raises(ValueError, match="not on mesh"):
            mx.sharded_apply_fn(plan, mesh, axis="model")


# ---------------------------------------------------------------------------
# Tuning table: EWMA records, ranked chains, stable round-trip, auto wiring.
# ---------------------------------------------------------------------------

class TestTuningTable:
    def test_best_and_rank_chain(self):
        t = TuningTable()
        geo = (128, 1600)
        t.record("apply_plan", geo, "einsum", 2e-3)
        t.record("apply_plan", geo, "sparse", 1e-3)
        assert t.best("apply_plan", geo) == "sparse"
        chain = t.rank_chain("apply_plan", geo,
                             ("einsum", "kernel", "sparse", "reference"))
        assert chain[0] == "sparse" and chain[1] == "einsum"
        # unmeasured keep their original relative order
        assert chain[2:] == ("kernel", "reference")

    def test_mesh_key_separates_entries(self):
        t = TuningTable()
        t.record("apply_plan", (8, 8), "einsum", 1e-3)
        t.record("apply_plan", (8, 8), "sparse", 1e-4,
                 mesh_shape={"data": 8})
        assert t.best("apply_plan", (8, 8)) == "einsum"
        assert t.best("apply_plan", (8, 8),
                      mesh_shape={"data": 8}) == "sparse"
        assert make_key("apply_plan", (8, 8)) != make_key(
            "apply_plan", (8, 8), {"data": 8})

    def test_round_trip_stable(self):
        t = TuningTable()
        t.record("apply_plan", (64, 1600), "einsum", 3.3e-3)
        t.record("run_program", (64, 1600), "chained", 9e-2,
                 mesh_shape={"data": 8})
        text = t.to_json()
        again = TuningTable.from_json(text).to_json()
        assert text == again
        # and a second hop stays byte-identical (CI gate)
        assert TuningTable.from_json(again).to_json() == again

    def test_ewma_converges_to_new_regime(self):
        t = TuningTable(alpha=0.5)
        for _ in range(12):
            t.record("apply_plan", (8, 8), "einsum", 1e-3)
        for _ in range(12):
            t.record("apply_plan", (8, 8), "einsum", 5e-3)
        ewma = t.lookup("apply_plan", (8, 8))["einsum"]["ewma_s"]
        assert abs(ewma - 5e-3) < 1e-4

    def test_auto_backend_follows_table(self):
        telemetry.reset()
        idx = jnp.arange(64, dtype=jnp.int32)[:, None]
        plan = xb.gather_plan(idx, 64, semiring=GF2)
        x = jnp.ones(64, jnp.int32)
        t = TuningTable()
        t.record("apply_plan", xb.plan_geometry(plan), "reference", 1e-6)
        xb.set_tuning_table(t)
        try:
            # the table's pick (reference) overrides the CPU heuristic,
            # which would have said einsum
            assert xb._choose_backend(plan) == "reference"
            res = xb.apply_plan(plan, x, backend="auto")
            np.testing.assert_array_equal(np.asarray(res), np.ones(64))
            assert xb.get_tuning_table() is t
        finally:
            telemetry.reset()
        assert xb.get_tuning_table() is None  # reset() uninstalls


# ---------------------------------------------------------------------------
# Per-device health: trip, drop, cooldown probe, rejoin.
# ---------------------------------------------------------------------------

class TestDeviceHealth:
    def test_trip_and_rejoin(self):
        now = [0.0]
        dh = DeviceHealth(4, threshold=2, cooldown_s=10.0,
                          clock=lambda: now[0])
        assert dh.healthy() == [0, 1, 2, 3]
        dh.record_failure(2)
        dh.record_failure(2)
        assert dh.healthy() == [0, 1, 3] and dh.lost() == [2]
        assert not dh.is_healthy(2)
        # cooldown elapses -> half-open counts healthy again (probe)
        now[0] = 11.0
        assert dh.is_healthy(2)
        dh.record_success(2)
        assert dh.healthy() == [0, 1, 2, 3]

    def test_failure_below_threshold_keeps_device(self):
        dh = DeviceHealth(2, threshold=3)
        dh.record_failure(0)
        dh.record_failure(0)
        assert dh.is_healthy(0)

    def test_trip_counts_telemetry(self):
        telemetry.reset()
        dh = DeviceHealth(2, threshold=1)
        dh.record_failure(1)
        assert telemetry.snapshot().get("device_trips", 0) == 1
        telemetry.reset()


# ---------------------------------------------------------------------------
# 8-fake-device differential suites (subprocess: XLA_FLAGS before import).
# ---------------------------------------------------------------------------

SHARDED_APPLY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType
    import numpy as np
    from repro.core import crossbar as xb
    from repro.core.semiring import GF2, REAL
    from repro.dist import mesh_exec as mx

    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    rng = np.random.default_rng(0)
    n = 1600

    def check(name, plan, x):
        want = np.asarray(xb.apply_plan(plan, x, backend="einsum"))
        fn = mx.sharded_apply_fn(plan, mesh)
        got = np.asarray(fn(x))
        assert np.array_equal(got, want), name
        naive = np.asarray(mx.sharded_apply_naive_fn(plan, mesh)(x))
        assert np.array_equal(naive, want), name + "/naive"
        print("OK", name)

    xbits = jnp.asarray(rng.integers(0, 2, n), jnp.int32)

    # block-diagonal (lane-parallel): permute within each shard
    idx_bd = np.concatenate([
        200 * b + rng.permutation(200) for b in range(8)])
    check("block_diag",
          xb.gather_plan(jnp.asarray(idx_bd, jnp.int32)[:, None], n,
                         semiring=GF2), xbits)

    # rotation by one shard: single ppermute round
    idx_rot = (np.arange(n) + 200) % n
    plan_rot = xb.gather_plan(jnp.asarray(idx_rot, jnp.int32)[:, None], n,
                              semiring=GF2)
    assert len(mx.collective_schedule(
        mx.shard_connectivity(plan_rot, 8))) == 1
    check("rotation", plan_rot, xbits)

    # dense random permutation (every shard talks to every shard)
    check("random_perm",
          xb.gather_plan(jnp.asarray(rng.permutation(n),
                                     jnp.int32)[:, None], n,
                         semiring=GF2), xbits)

    # GF2 k=3 (parity fold across shard-crossing sources)
    idx_k3 = rng.integers(0, n, (n, 3)).astype(np.int32)
    check("gf2_k3", xb.gather_plan(jnp.asarray(idx_k3), n, semiring=GF2),
          xbits)

    # weighted REAL semiring
    idx_w = rng.integers(0, n, (n, 2)).astype(np.int32)
    w = rng.normal(size=(n, 2)).astype(np.float32)
    plan_w = xb.gather_plan(jnp.asarray(idx_w), n,
                            weights=jnp.asarray(w), semiring=REAL)
    xr = jnp.asarray(rng.normal(size=n), jnp.float32)
    want = np.asarray(xb.apply_plan(plan_w, xr, backend="einsum"))
    got = np.asarray(mx.sharded_apply_fn(plan_w, mesh)(xr))
    assert np.max(np.abs(got - want)) < 1e-4, "weighted"
    print("OK weighted")

    print("SHARDED-APPLY-OK")
""")


def test_sharded_apply_matches_single_device():
    """8 fake devices: every sharded regime (block-diag, rotation,
    random perm, GF2 k=3, weighted) bit-exact vs single-device
    apply_plan, for both the scheduled and the naive path."""
    _run_sub(SHARDED_APPLY_SCRIPT, "SHARDED-APPLY-OK")


SHARDED_PROGRAM_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType
    import numpy as np
    from repro.core import plan_program as pp
    from repro.crypto import keccak as kk
    from repro.dist import mesh_exec as mx

    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    prog = kk.megakernel_program()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 2, (1600, 16)), jnp.int32)

    want = np.asarray(pp.run_program(prog, x, backend="chained"))
    fn = mx.sharded_program_fn(prog, mesh)
    got = np.asarray(fn(x))
    assert np.array_equal(got, want), "sharded keccak program"

    # lane-parallel => compiled HLO must contain no collectives
    txt = fn.lower(x).compile().as_text()
    for coll in ("all-reduce", "all-gather", "all-to-all",
                 "collective-permute", "reduce-scatter"):
        assert coll not in txt, f"found {coll}"

    # column count not divisible by the mesh -> clear error, not a trace
    try:
        mx.run_program_sharded(prog, x[:, :5], mesh)
    except ValueError as e:
        assert "divide" in str(e)
    else:
        raise AssertionError("indivisible columns accepted")

    print("SHARDED-PROGRAM-OK")
""")


def test_sharded_program_collective_free():
    """8 fake devices: the full Keccak-f[1600] plan program sharded over
    payload columns is bit-exact vs single device and compiles with zero
    collectives (lane-parallel by construction)."""
    _run_sub(SHARDED_PROGRAM_SCRIPT, "SHARDED-PROGRAM-OK")


SURVIVOR_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import hashlib
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.serve.batching import BatchingEngine, BatchingOptions

    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    eng = BatchingEngine(
        BatchingOptions(max_batch=32, max_queue=256, mesh=mesh,
                        double_buffer=False),
        start=False)
    rng = np.random.default_rng(0)
    payloads = [rng.bytes(int(l)) for l in rng.integers(1, 200, 64)]

    def drain():
        reqs = [eng.submit(p) for p in payloads]
        while eng.run_once():
            pass
        return reqs

    reqs = drain()
    assert all(r.result() == hashlib.sha3_256(p).digest()
               for p, r in zip(payloads, reqs)), "full mesh"
    assert eng.stats()["mesh_active"] == 8

    # trip devices 2 and 5 -> survivor mesh keeps answering bit-exactly
    for d in (2, 5):
        for _ in range(3):
            eng.report_device_fault(d)
    assert sorted(eng.stats()["mesh_lost"]) == [2, 5]
    reqs = drain()
    assert all(r.result() == hashlib.sha3_256(p).digest()
               for p, r in zip(payloads, reqs)), "survivor mesh"
    assert 0 < eng.stats()["mesh_active"] < 8
    print("SURVIVOR-OK")
""")


def test_survivor_mesh_keeps_answering():
    """8 fake devices: tripping two devices re-homes serving onto a
    survivor mesh and every digest still equals hashlib."""
    _run_sub(SURVIVOR_SCRIPT, "SURVIVOR-OK")


PARTIAL_REPLAY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import hashlib
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import faults, telemetry
    from repro.serve.batching import BatchingEngine, BatchingOptions

    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    eng = BatchingEngine(
        BatchingOptions(max_batch=64, max_queue=256, mesh=mesh,
                        double_buffer=False),
        start=False)
    rng = np.random.default_rng(0)
    payloads = [rng.bytes(int(l)) for l in rng.integers(1, 100, 64)]

    def drain():
        reqs = [eng.submit(p) for p in payloads]
        while eng.run_once():
            pass
        return reqs

    def check(reqs, label):
        assert all(r.result() == hashlib.sha3_256(p).digest()
                   for p, r in zip(payloads, reqs)), label

    # Warm pass: 64 lanes over 8 devices = 8 per-shard launches, all
    # journaled per lane.
    check(drain(), "warm full mesh")
    assert telemetry.counter("serve_shard_launches") == 8
    assert telemetry.counter("serve_partial_batches") == 1

    # Kill device 3 mid-batch.  max_fires is generous: the dead device
    # must fail EVERY retry and fallback rung, or the shard would heal
    # in place and nothing would need replaying.
    base = telemetry.snapshot()
    with faults.inject_device_fault(3, max_fires=64) as state:
        reqs = drain()
    check(reqs, "post-fault results")
    snap = telemetry.snapshot()
    d = lambda k: snap.get(k, 0) - base.get(k, 0)
    # The launch-count ledger: 8 shard dispatches + exactly 1 replay of
    # the lost window — the 7 salvaged shards are NOT re-executed.
    assert d("serve_shard_launches") == 9, d("serve_shard_launches")
    assert d("serve_shards_salvaged") == 7
    assert d("lanes_replayed") == 8, d("lanes_replayed")
    assert d("serve_completed") == 64
    assert d("serve_mesh_device_drops") == 1
    assert state["fired"] >= 1
    assert eng.stats()["mesh_lost"] == [3]

    # The tripped device stays out: the next batch runs on the survivor
    # mesh with one launch per surviving shard and no replays.
    base = telemetry.snapshot()
    check(drain(), "survivor mesh")
    active = eng.stats()["mesh_active"]
    snap = telemetry.snapshot()
    d = lambda k: snap.get(k, 0) - base.get(k, 0)
    assert 0 < active < 8
    assert d("serve_shard_launches") == active, (active, snap)
    assert d("lanes_replayed") == 0
    print("PARTIAL-REPLAY-OK")
""")


def test_partial_batch_replay_after_device_fault():
    """8 fake devices: a device killed mid-batch loses exactly one
    shard; its lanes replay on a survivor while the 7 completed shards'
    results are salvaged from the per-lane journal — asserted through
    the launch-count ledger (8 + 1 launches, never 16)."""
    _run_sub(PARTIAL_REPLAY_SCRIPT, "PARTIAL-REPLAY-OK")
