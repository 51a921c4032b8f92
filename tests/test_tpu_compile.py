"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

Nothing runs here: each test lowers a kernel at its served size and
compiles it for a described (not attached) v5e chip, so a kernel that
Mosaic or the TPU compiler would refuse — an unaligned slice, more VMEM
or SMEM than the chip has, an unsupported primitive — fails on a CPU
host.  The topology is described inside a module-scoped fixture: only
the process that runs these tests loads the TPU compiler library.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import plan_program as pp
from repro.crypto import gcm, keccak
from repro.kernels import crossbar_permute as cp
from repro.kernels import plan_program_kernel as ppk

N, D = 4096, 512          # crossbar kernels: the chip smoke's served size


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return compiled


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_dense_crossbar_compiles(one_chip):
    fn = functools.partial(cp.crossbar_permute_pallas, mode="gather",
                           n_out=N, n_in_valid=N)
    _compile(fn, _spec((N, 1), jnp.int32, one_chip),
             _spec((N, D), jnp.float32, one_chip))


def test_sparse_crossbar_compiles(one_chip):
    pairs = N // cp.DEFAULT_BO
    fn = functools.partial(cp.crossbar_permute_sparse_pallas, mode="gather",
                           n_out=N)
    _compile(fn, *(_spec((pairs,), jnp.int32, one_chip),) * 3,
             _spec((N, 1), jnp.int32, one_chip),
             _spec((N, D), jnp.float32, one_chip))


def _compile_program(program, lanes, sharding, n_dense=None):
    """Compile ``program`` at ``lanes`` lanes, with its key operand when
    it reads one.  Where ``n_dense`` is given, check that as many dense
    plans ride as the tables operand and that their VMEM need, and the
    key operand's, fits one block of ``lanes``."""
    n_pad, control, static = pp.encode_program(program)
    if n_dense is not None:
        assert len(control) == 4 + bool(n_dense)
    if n_dense:
        assert control[-1].shape == (n_dense, n_pad, n_pad)
        assert ppk.lane_block(n_pad, lanes, program.n_regs, 4, n_dense,
                              program.key_rows) == lanes
    keyed = bool(program.key_rows)
    specs = [_spec((n_pad, lanes), jnp.int32, sharding)]
    specs += [_spec(c.shape, c.dtype, sharding) for c in control]
    if keyed:
        specs.append(_spec((program.key_rows, lanes), jnp.int32, sharding))

    def fn(state, *rest):
        ctrl, keys = (rest[:-1], rest[-1]) if keyed else (rest, None)
        return ppk.plan_program_pallas(state, *ctrl, keys=keys, **static)

    return _compile(fn, *specs)


def test_megakernel_keccak_compiles(one_chip):
    _compile_program(keccak.megakernel_program(), 128, one_chip, n_dense=1)


def test_megakernel_gcm_seal_compiles(one_chip):
    _, program, _ = gcm.gcm_program(1024, 16)
    # 9,056 rows: one table would pass the dense budget, so all walk.
    _compile_program(program, 128, one_chip, n_dense=0)


def test_megakernel_gcm_seal_dense_compiles(one_chip):
    """The served TLS record geometry (17 B, 5 B header): the S-box
    plan runs as a product, with the lanes' key operand and CLMUL, at
    the widest lane block."""
    _, program, _ = gcm.gcm_program(17, 5)
    _compile_program(program, 1024, one_chip, n_dense=1)


def test_megakernel_custom_call_is_named(one_chip):
    """The profile names the megakernel's device operation after the
    kernel (``%plan_program.N = ... custom-call(``), not ``%_unknown_``."""
    text = _compile_program(keccak.megakernel_program(), 128,
                            one_chip).as_text()
    calls = [ln for ln in text.splitlines() if " custom-call(" in ln]
    assert calls and all("%plan_program" in ln for ln in calls), calls
