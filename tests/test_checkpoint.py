"""Checkpointing: atomicity, keep-k, async manager, elastic restore."""

import json
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, latest_step, restore, save


def tree():
    return {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.int32)}}


def test_roundtrip():
    with tempfile.TemporaryDirectory() as d:
        save(tree(), d, 7)
        got, step, _ = restore(d, tree())
        assert step == 7
        np.testing.assert_array_equal(np.asarray(got["a"]),
                                      np.asarray(tree()["a"]))
        np.testing.assert_array_equal(np.asarray(got["b"]["c"]),
                                      np.asarray(tree()["b"]["c"]))


def test_latest_and_keep_k():
    with tempfile.TemporaryDirectory() as d:
        for s in (1, 2, 3, 4):
            save(tree(), d, s, keep=2)
        assert latest_step(d) == 4
        kept = sorted(os.listdir(d))
        assert kept == ["step_00000003", "step_00000004"]


def test_atomic_no_partial_visible():
    """A stale tmp dir never shadows a committed checkpoint."""
    with tempfile.TemporaryDirectory() as d:
        save(tree(), d, 1)
        os.makedirs(os.path.join(d, "step_00000002.tmp-999"))
        assert latest_step(d) == 1
        got, step, _ = restore(d, tree())
        assert step == 1


def test_extra_payload():
    with tempfile.TemporaryDirectory() as d:
        save(tree(), d, 3, extra={"data_cursor": 123})
        _, _, extra = restore(d, tree())
        assert extra["data_cursor"] == 123


def test_async_manager():
    with tempfile.TemporaryDirectory() as d:
        m = CheckpointManager(d, keep=2)
        for s in (1, 2, 3):
            m.save_async(tree(), s)
        m.wait()
        assert m.latest_step() == 3
        assert len(os.listdir(d)) == 2


def test_restore_with_new_shardings():
    """Elastic restore: leaves re-placed with provided shardings."""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), tree())
    with tempfile.TemporaryDirectory() as d:
        save(tree(), d, 1)
        got, _, _ = restore(d, tree(), shardings=sh)
        assert got["a"].sharding == NamedSharding(mesh, P())


def test_dtype_preserved_via_template():
    t = {"w": jnp.ones((3,), jnp.bfloat16)}
    with tempfile.TemporaryDirectory() as d:
        save(t, d, 1)
        got, _, _ = restore(d, t)
        assert got["w"].dtype == jnp.bfloat16
