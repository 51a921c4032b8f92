"""Tile-skipping sparse crossbar: schedule compilation + differential
execution against the einsum and reference backends.

The sparse backend must be bit-identical to 'reference' for unweighted
plans (selection sums are exact in f32) and within f32 accumulation
tolerance for weighted plans.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import crossbar as xb
from repro.core import moe_dispatch as md

KEY = jax.random.PRNGKey(0)


def assert_matches(plan, x, *, merge=None, out_mask=None, exact):
    got = xb.apply_plan(plan, x, backend="sparse", merge=merge,
                        out_mask=out_mask)
    want = xb.apply_plan(plan, x, backend="reference", merge=merge,
                         out_mask=out_mask)
    want_e = xb.apply_plan(plan, x, backend="einsum", merge=merge,
                           out_mask=out_mask)
    if exact:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want_e))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want_e),
                                   rtol=1e-5, atol=1e-5)


def sparse_gather_idx(n_out, n_in, k, *, oob=False, seed=0):
    """Banded indices -> few occupied tiles; optionally OOB-heavy."""
    key = jax.random.PRNGKey(seed)
    base = (jnp.arange(n_out, dtype=jnp.int32) % n_in)
    idx = (base[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]) % n_in
    if oob:
        drop = jax.random.bernoulli(key, 0.7, idx.shape)
        bad = jax.random.randint(key, idx.shape, -n_in, 3 * n_in,
                                 dtype=jnp.int32)
        bad = jnp.where(jnp.abs(bad) < n_in, bad + n_in, bad)  # force OOB
        idx = jnp.where(drop, jnp.where(bad < n_in, -1 - jnp.abs(bad), bad),
                        idx)
    return idx


class TestCompiledPlan:
    def test_occupancy_matches_bruteforce(self):
        n = 300
        idx = jax.random.randint(KEY, (n, 2), -20, n + 20, dtype=jnp.int32)
        plan = xb.gather_plan(idx, n)
        cp = xb.compile_plan(plan, block_o=128, block_n=128)
        dense = np.asarray(xb.build_onehot(plan))
        to, tn = cp.n_o_tiles, cp.n_n_tiles
        padded = np.zeros((to * 128, tn * 128), np.float32)
        padded[:n, :n] = dense
        brute = (padded.reshape(to, 128, tn, 128).sum((1, 3)) > 0)
        np.testing.assert_array_equal(np.asarray(cp.occupancy), brute)
        assert cp.is_static
        assert cp.num_active == int(brute.sum())

    def test_schedule_is_o_major_and_in_range(self):
        n = 512
        idx = jax.random.randint(KEY, (n, 1), 0, n, dtype=jnp.int32)
        cp = xb.compile_plan(xb.gather_plan(idx, n))
        po = np.asarray(cp.pair_o)
        pn = np.asarray(cp.pair_n)
        act = np.asarray(cp.active)
        num = cp.num_active
        assert act[:num].all() and not act[num:].any()
        # active prefix sorted o-major; tail clamped in range
        keys = po[:num] * cp.n_n_tiles + pn[:num]
        assert (np.diff(keys) > 0).all()
        assert (po >= 0).all() and (po < cp.n_o_tiles).all()
        assert (pn >= 0).all() and (pn < cp.n_n_tiles).all()

    def test_lru_cache_identity_and_identical_results(self):
        xb.clear_compile_cache()
        n = 300
        idx = jax.random.randint(KEY, (n, 1), 0, n, dtype=jnp.int32)
        plan = xb.gather_plan(idx, n)
        x = jax.random.normal(KEY, (n, 64))
        out1 = xb.apply_plan(plan, x, backend="sparse")
        info1 = xb.compile_cache_info()
        out2 = xb.apply_plan(plan, x, backend="sparse")
        info2 = xb.compile_cache_info()
        assert info2["hits"] > info1["hits"]
        assert info2["misses"] == info1["misses"]
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
        # same index VALUES in a different array -> different identity,
        # recompile (no stale aliasing), same results
        plan_b = xb.gather_plan(jnp.array(np.asarray(idx)), n)
        out3 = xb.apply_plan(plan_b, x, backend="sparse")
        assert xb.compile_cache_info()["misses"] == info2["misses"] + 1
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out3))

    def test_foreign_compiled_schedule_is_rejected(self):
        """A schedule built from another plan must not drive execution."""
        from repro.kernels import ops
        n = 300
        idx_a = sparse_gather_idx(n, n, 1, seed=1)
        idx_b = jax.random.randint(KEY, (n, 1), 0, n, dtype=jnp.int32)
        plan_a = xb.gather_plan(idx_a, n)
        plan_b = xb.gather_plan(idx_b, n)
        x = jax.random.normal(KEY, (n, 32))
        got = ops.crossbar_permute_sparse(plan_a, x,
                                          compiled=xb.compile_plan(plan_b))
        want = xb.apply_plan(plan_a, x, backend="reference")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_traced_plan_compiles_without_cache(self):
        n = 256

        @jax.jit
        def run(idx):
            cp = xb.compile_plan(xb.gather_plan(idx, n))
            return cp.num_active

        idx = jax.random.randint(KEY, (n, 1), 0, n, dtype=jnp.int32)
        num = int(run(idx))
        assert num == xb.compile_plan(xb.gather_plan(idx, n)).num_active


class TestCacheability:
    """``xb.is_cacheable``: a cache may be keyed only outside every trace
    and only on concrete operands — the verdicts the trace-state checks
    it replaced gave under jit, vmap and compile-time evaluation."""

    def test_concrete_outside_any_trace(self):
        x = jnp.arange(4)
        assert xb.is_cacheable()
        assert xb.is_cacheable(x, None)

    @pytest.mark.parametrize("transform", ["jit", "vmap"])
    def test_live_trace_is_not_cacheable(self, transform):
        x = jnp.arange(4)
        seen = {}

        def probe(y):
            seen["bare"] = xb.is_cacheable()
            seen["concrete"] = xb.is_cacheable(x)
            seen["traced"] = xb.is_cacheable(y)
            return y

        wrap = jax.jit if transform == "jit" else jax.vmap
        wrap(probe)(jnp.ones(4))
        assert seen == {"bare": False, "concrete": False, "traced": False}

    def test_compile_time_eval_is_cacheable(self):
        x = jnp.arange(4)
        with jax.ensure_compile_time_eval():
            assert xb.is_cacheable(x)

    def test_in_trace_schedule_is_not_stored(self):
        xb.clear_compile_cache()
        n = 256
        idx = jax.random.randint(KEY, (n, 1), 0, n, dtype=jnp.int32)
        plan = xb.gather_plan(idx, n)
        jax.jit(lambda: xb.compile_plan(plan).occupancy)()
        assert xb.compile_cache_info()["size"] == 0
        assert xb.compile_plan(plan).is_static
        assert xb.compile_cache_info()["size"] == 1


class TestSparseDifferential:
    @pytest.mark.parametrize("mode", ["gather", "scatter"])
    @pytest.mark.parametrize("weighted", [False, True])
    def test_modes_weighted(self, mode, weighted):
        n_out, n_in, d, k = 384, 300, 96, 2
        n_ctrl = n_out if mode == "gather" else n_in
        if mode == "gather":
            idx = jax.random.randint(KEY, (n_ctrl, k), -8, n_in + 8,
                                     dtype=jnp.int32)
        else:
            # Collision-free destinations (MoE-dispatch shape): every
            # output row receives <=1 contribution, so even the
            # unweighted sums are order-independent and bit-exact.
            # Colliding scatters are covered (in tolerance) below.
            perm = jax.random.permutation(KEY, n_ctrl * k + 16) - 8
            idx = perm[:n_ctrl * k].reshape(n_ctrl, k).astype(jnp.int32)
        w = (jax.random.normal(KEY, (n_ctrl, k)).astype(jnp.float32)
             if weighted else None)
        plan = xb.PermutePlan(mode, idx, n_in, n_out, w)
        x = jax.random.normal(KEY, (n_in, d))
        assert_matches(plan, x, exact=not weighted)

    @pytest.mark.parametrize("use_mask", [False, True])
    def test_merge_and_mask(self, use_mask):
        n = 270
        idx = sparse_gather_idx(n, n, 1)
        plan = xb.gather_plan(idx, n)
        x = jax.random.normal(KEY, (n, 40))
        merge = jax.random.normal(jax.random.PRNGKey(1), (n, 40))
        mask = (jax.random.bernoulli(jax.random.PRNGKey(2), 0.6, (n,))
                if use_mask else None)
        assert_matches(plan, x, merge=merge, out_mask=mask, exact=True)

    def test_fully_empty_plan(self):
        n = 256
        plan = xb.gather_plan(jnp.full((n,), -1, jnp.int32), n)
        assert xb.compile_plan(plan).num_active == 0
        x = jax.random.normal(KEY, (n, 32))
        merge = jax.random.normal(jax.random.PRNGKey(1), (n, 32))
        assert_matches(plan, x, exact=True)
        assert_matches(plan, x, merge=merge, exact=True)

    def test_single_tile_plan(self):
        n = 64  # everything inside one 128x128 tile
        idx = jax.random.randint(KEY, (n, 1), 0, n, dtype=jnp.int32)
        plan = xb.gather_plan(idx, n)
        cp = xb.compile_plan(plan)
        assert cp.num_active == 1
        x = jax.random.normal(KEY, (n, 16))
        assert_matches(plan, x, exact=True)

    def test_oob_drop_heavy_plan(self):
        n = 384
        idx = sparse_gather_idx(n, n, 2, oob=True)
        plan = xb.gather_plan(idx, n)
        x = jax.random.normal(KEY, (n, 48))
        merge = jax.random.normal(jax.random.PRNGKey(3), (n, 48))
        assert_matches(plan, x, merge=merge, exact=True)

    def test_scatter_drop_heavy_colliding(self):
        # Colliding destinations: many addends per output row, so the
        # backends' different reduction orders only agree in tolerance.
        n_in, n_out = 400, 300
        dest = jax.random.randint(KEY, (n_in, 1), -n_out, 3 * n_out,
                                  dtype=jnp.int32)
        plan = xb.scatter_plan(dest, n_out)
        x = jax.random.normal(KEY, (n_in, 24))
        assert_matches(plan, x, exact=False)

    def test_guarded_path_under_jit(self):
        """Traced plan -> full-grid pl.when-guarded skip, same results."""
        n = 384
        idx = sparse_gather_idx(n, n, 1)
        x = jax.random.normal(KEY, (n, 32))

        @jax.jit
        def run(idx, x):
            return xb.apply_plan(xb.gather_plan(idx, n), x,
                                 backend="sparse")

        got = run(idx, x)
        want = xb.apply_plan(xb.gather_plan(idx, n), x, backend="reference")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestMoEDispatchSparse:
    def test_dispatch_combine_sparse_vs_einsum(self):
        t, e, k, cap, d = 256, 8, 2, 64, 32
        logits = jax.random.normal(KEY, (t, e))
        x = jax.random.normal(KEY, (t, d))
        r = md.make_routing(logits, num_experts=e, k=k, capacity=cap)
        buf_s = md.dispatch(x, r, backend="sparse")
        buf_e = md.dispatch(x, r, backend="einsum")
        np.testing.assert_array_equal(np.asarray(buf_s), np.asarray(buf_e))
        y_s = md.combine(buf_s, r, backend="sparse")
        y_e = md.combine(buf_e, r, backend="einsum")
        np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_e),
                                   rtol=1e-5, atol=1e-6)

    def test_auto_backend_matches(self):
        t, e, k, cap, d = 256, 8, 2, 64, 32
        logits = jax.random.normal(KEY, (t, e))
        x = jax.random.normal(KEY, (t, d))
        r = md.make_routing(logits, num_experts=e, k=k, capacity=cap)
        np.testing.assert_array_equal(
            np.asarray(md.dispatch(x, r, backend="auto")),
            np.asarray(md.dispatch(x, r, backend="einsum")))


class TestIntPayloadGuard:
    def test_exact_below_bound(self):
        n = 64
        x = jax.random.randint(KEY, (n, 8), 0, 1 << 20, dtype=jnp.int32)
        idx = jax.random.randint(KEY, (n, 1), 0, n, dtype=jnp.int32)
        plan = xb.gather_plan(idx, n)
        got = xb.apply_plan(plan, x, backend="kernel")
        want = xb.apply_plan(plan, x, backend="reference")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_rejects_above_bound(self):
        from repro.kernels import ops
        n = 128
        x = jnp.full((n, 4), 1 << 25, jnp.int32)
        idx = jnp.arange(n, dtype=jnp.int32)
        plan = xb.gather_plan(idx, n)
        with pytest.raises(ValueError, match="2\\^24"):
            ops.crossbar_permute(plan, x)
        with pytest.raises(ValueError, match="2\\^24"):
            ops.crossbar_permute_sparse(plan, x)

    def test_rejects_large_negative(self):
        from repro.kernels import ops
        n = 128
        x = jnp.full((n, 4), -(1 << 26), jnp.int32)
        plan = xb.gather_plan(jnp.arange(n, dtype=jnp.int32), n)
        with pytest.raises(ValueError, match="2\\^24"):
            ops.crossbar_permute(plan, x)
