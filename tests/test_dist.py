"""Distribution substrate: fault policies, compressed collectives,
sharding rules, and a multi-device (8 fake CPU devices) integration run
in a subprocess."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.dist import fault
from repro.dist.collectives import dequantize_int8, quantize_int8

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestElasticPolicy:
    def test_survivor_mesh_drops_pod_first(self):
        shape = {"pod": 2, "data": 16, "model": 16}
        got = fault.survivor_mesh_shape(shape, lost_devices=10)
        assert got == {"pod": 1, "data": 16, "model": 16}

    def test_survivor_mesh_halves_data(self):
        got = fault.survivor_mesh_shape({"data": 16, "model": 16},
                                        lost_devices=1)
        assert got == {"data": 8, "model": 16}

    def test_model_axis_never_shrinks(self):
        with pytest.raises(RuntimeError):
            fault.survivor_mesh_shape({"data": 1, "model": 16},
                                      lost_devices=8)

    def test_negative_losses_rejected(self):
        with pytest.raises(ValueError, match=">= 0"):
            fault.survivor_mesh_shape({"data": 4}, lost_devices=-1)

    def test_no_survivors_rejected(self):
        # Losing the whole fleet (or more) is not a shrink — there is
        # no mesh left; the old code looped shrinking forever.
        for lost in (4, 5):
            with pytest.raises(ValueError, match="no survivors"):
                fault.survivor_mesh_shape({"data": 2, "model": 2},
                                          lost_devices=lost)


class TestStragglerPolicy:
    def test_deadline_tracks_ewma(self):
        p = fault.StragglerPolicy(deadline_factor=2.0, ewma_alpha=1.0)
        p.observe(1.0)
        assert p.deadline == 2.0

    def test_drop_and_block_decisions(self):
        p = fault.StragglerPolicy(deadline_factor=2.0, ewma_alpha=1.0,
                                  min_alive_fraction=0.5)
        p.observe(1.0)
        alive, block = p.decide(np.array([1.0, 1.5, 5.0, 1.2]))
        assert list(alive) == [True, True, False, True] and not block
        # too many stragglers -> block instead of dropping half the fleet
        alive, block = p.decide(np.array([5.0, 5.0, 5.0, 1.0]))
        assert block and alive.all()

    def test_rescale_unbiased(self):
        grads = {"w": jnp.asarray([[2.0, 2.0], [4.0, 4.0], [6.0, 6.0]])}
        alive = jnp.asarray([True, True, False])
        out = fault.rescale_gradients(grads, alive)
        np.testing.assert_allclose(np.asarray(out["w"]), [3.0, 3.0])


class TestHeartbeat:
    def test_death_after_misses(self):
        hb = fault.HeartbeatTracker(hosts=3, miss_threshold=2)
        hb.tick()
        hb.beat(0)
        hb.beat(1)
        dead = hb.tick()          # host 2 missed twice
        assert dead == [2]

    def test_init_validation(self):
        with pytest.raises(ValueError, match="at least one host"):
            fault.HeartbeatTracker(hosts=0)
        with pytest.raises(ValueError, match="miss_threshold"):
            fault.HeartbeatTracker(hosts=2, miss_threshold=0)

    def test_out_of_range_beat_rejected(self):
        hb = fault.HeartbeatTracker(hosts=3)
        for host in (-1, 3):      # -1 would silently wrap to host 2
            with pytest.raises(ValueError, match="out of range"):
                hb.beat(host)
        hb.beat(2)                # valid edges still work
        hb.beat(0)


class TestInt8Compression:
    def test_quantize_roundtrip_error_bounded(self, rng):
        x = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))
        q, s = quantize_int8(x)
        err = np.abs(np.asarray(dequantize_int8(q, s) - x))
        assert err.max() <= float(s) * 0.5 + 1e-7

    def test_error_feedback_converges(self, rng):
        """Repeated compression of the same gradient with error feedback
        transmits the true value on average (bias -> 0)."""
        x = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
        err = jnp.zeros_like(x)
        acc = jnp.zeros_like(x)
        steps = 50
        for _ in range(steps):
            q, s = quantize_int8(x + err)
            sent = dequantize_int8(q, s)
            err = (x + err) - sent
            acc = acc + sent
        np.testing.assert_allclose(np.asarray(acc / steps), np.asarray(x),
                                   atol=float(s) + 1e-6)


class TestShardingRules:
    def test_param_rules_divisibility_fallback(self):
        from jax.sharding import PartitionSpec as P
        from repro.dist import sharding as shd
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        params = {"blocks": {"attn": {"wq": {"w": jnp.zeros((7, 13))}}}}
        sh = shd.param_shardings(params, mesh, None)
        # sizes 7/13 divide 1, so specs apply
        assert sh["blocks"]["attn"]["wq"]["w"].spec == P("data", "model")

    def test_cache_rules(self):
        from repro.dist import sharding as shd
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        caches = {"k": jnp.zeros((2, 4, 8, 2, 16))}
        sh = shd.cache_shardings(caches, mesh, None)
        assert sh["k"].spec is not None


MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.configs.base import ModelConfig
    from repro.models.model_zoo import build
    from repro.train import TrainOptions, make_train_step
    from repro.train.trainer import init_state
    from repro.dist import sharding as shd
    from repro.dist.annotate import logical_axes
    from repro.data import SyntheticLM

    cfg = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=128,
                      head_dim=8, compute_dtype="float32", remat="none",
                      attn_chunk=8)
    api = build(cfg)
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    pipe = SyntheticLM(vocab_size=128, seq_len=16, global_batch=8)
    params = api.init(jax.random.PRNGKey(0))
    state = init_state(params, jax.random.PRNGKey(0))
    batch = pipe.batch(0)

    step = make_train_step(api.loss_fn, TrainOptions(peak_lr=1e-3))
    # single-device reference
    s_ref, m_ref = jax.jit(step)(state, batch)

    psh = shd.param_shardings(params, mesh, cfg)
    state_sh = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), state)
    import repro.train.trainer as trn
    from repro.optim import AdamWState
    state_sh = trn.TrainState(params=psh,
        opt=AdamWState(step=NamedSharding(mesh, P()),
                       mu=jax.tree.map(lambda p: p, psh),
                       nu=jax.tree.map(lambda p: p, psh)),
        step=NamedSharding(mesh, P()), rng=NamedSharding(mesh, P()))
    bsh = shd.batch_shardings(batch, mesh)
    with mesh, logical_axes(mesh):
        sharded_step = jax.jit(step, in_shardings=(state_sh, bsh),
                               out_shardings=(state_sh, None))
        state_d = jax.device_put(state, state_sh)
        batch_d = jax.device_put(batch, bsh)
        s_got, m_got = sharded_step(state_d, batch_d)

    np.testing.assert_allclose(float(m_ref["loss"]), float(m_got["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s_ref.params),
                    jax.tree.leaves(s_got.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    print("MULTIDEV-OK")
""")


def test_sharded_train_step_matches_single_device():
    """8 fake devices, (4 data x 2 model): sharded step == local step."""
    proc = subprocess.run(
        [sys.executable, "-c", MULTIDEV_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")})
    assert "MULTIDEV-OK" in proc.stdout, proc.stderr[-2000:]


COMPRESSED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from functools import partial
    from jax.sharding import AxisType, Mesh, PartitionSpec as P
    from repro.dist.collectives import compressed_psum

    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

    @partial(jax.shard_map, mesh=mesh, in_specs=P("data", None),
             out_specs=(P("data", None), P("data", None)))
    def reduce_compressed(gs):
        mean, err = compressed_psum(gs[0], "data")
        return mean[None], err[None]

    got, err = reduce_compressed(g)
    want = jnp.mean(g, axis=0)
    rel = float(jnp.linalg.norm(got[0] - want) / jnp.linalg.norm(want))
    assert rel < 0.05, rel
    print("COMPRESSED-OK", rel)
""")


def test_compressed_psum_shardmap():
    proc = subprocess.run(
        [sys.executable, "-c", COMPRESSED_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")})
    assert "COMPRESSED-OK" in proc.stdout, proc.stderr[-2000:]


KECCAK_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import hashlib, time
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.crypto import keccak as kk
    from repro.dist.annotate import logical_axes

    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))

    # End-to-end: B=8 sponge lanes sharded one per device via the
    # "batch" annotation in sha3_256_batched; digests must stay exact.
    msgs = [bytes([i]) * 200 for i in range(8)]
    with logical_axes(mesh):
        got = kk.sha3_256_batched(msgs, batch_mode="payload")
    assert got == [hashlib.sha3_256(m).digest() for m in msgs], "digests"

    # Collective-free scaling: the compiled sharded permutation must
    # contain no cross-device collectives at any lane count (the lanes
    # are independent sponges; the payload batch keeps them lane-local).
    for b in (8, 16, 32):
        states = jax.device_put(
            jnp.zeros((b, 1600), jnp.int32),
            NamedSharding(mesh, P("data", None)))
        with logical_axes(mesh):
            fn = jax.jit(lambda s: kk.keccak_f1600(s,
                                                   batch_mode="payload"))
            txt = fn.lower(states).compile().as_text()
        for coll in ("all-reduce", "all-gather", "all-to-all",
                     "collective-permute", "reduce-scatter"):
            assert coll not in txt, f"B={b}: found {coll}"
        t0 = time.time()
        fn(states).block_until_ready()
        t0 = time.time()
        fn(states).block_until_ready()
        print(f"LANES B={b} warm {1e3*(time.time()-t0):.1f}ms")
    print("KECCAK-SHARDED-OK")
""")


def test_sharded_keccak_lanes_collective_free():
    """8 fake devices: batched sponge lanes shard over the data axis,
    digests match hashlib, and the compiled permutation has no
    collectives at B in {8, 16, 32} (embarrassingly parallel scaling)."""
    proc = subprocess.run(
        [sys.executable, "-c", KECCAK_SHARDED_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")})
    assert "KECCAK-SHARDED-OK" in proc.stdout, (
        proc.stdout[-2000:] + proc.stderr[-2000:])
