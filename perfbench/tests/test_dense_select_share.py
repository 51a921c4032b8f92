"""The ``dense_select_share.*`` readers on given counters, and on the
counters of a real megakernel launch."""

import os
import types

import numpy as np
import pytest

from perfbench import spec


def _reader(name):
    return spec.load_module(os.path.join(spec.HERE, "metrics",
                                         name + ".py"))


READERS = [_reader("dense_select_share.open"),
           _reader("dense_select_share.closed")]


def _ctx(**counters):
    return types.SimpleNamespace(counters=counters)


@pytest.mark.parametrize("reader", READERS)
def test_share_of_given_counters(reader):
    ctx = _ctx(megakernel_entries_dense=422400,
               megakernel_entries_walked=76800, serve_batches=3)
    assert reader.read(ctx) == pytest.approx(84.61538461538461)
    assert reader.read(_ctx(megakernel_entries_walked=960)) == 0.0
    assert reader.read(_ctx(megakernel_entries_dense=5)) == 100.0


@pytest.mark.parametrize("reader", READERS)
def test_no_counter_reads_nothing(reader):
    assert reader.read(_ctx()) is None
    assert reader.read(_ctx(serve_batches=4, serve_completed=32)) is None
    # Counted, but no launch in the window.
    assert reader.read(_ctx(megakernel_entries_dense=0,
                            megakernel_entries_walked=0)) is None


def test_reads_a_keccak_launch():
    import jax.numpy as jnp

    from repro.core import plan_program as pp
    from repro.core import telemetry
    from repro.crypto import keccak

    x = jnp.asarray(np.zeros((1600, 1)), jnp.int32)
    with telemetry.delta() as d:
        pp.run_program(keccak.megakernel_program(), x, backend="megakernel")
    assert READERS[0].read(_ctx(**d())) == pytest.approx(
        100 * 17600 / (17600 + 2 * 1600))
