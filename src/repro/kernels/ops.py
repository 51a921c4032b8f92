"""jit'd public wrappers around the Pallas kernels.

Responsibilities:
  * pad every axis to kernel block multiples (padding is semantically
    inert by construction: padded control indices are DROP, padded input
    rows route nowhere, padded outputs are sliced off);
  * pick interpret mode automatically (CPU backend -> interpret=True, so
    the whole suite runs on this container; on TPU the same call sites
    compile to Mosaic);
  * accept ``PermutePlan``s from repro.core so the crossbar engine can be
    switched to the kernel paths with ``backend='kernel'`` (dense grid) or
    ``backend='sparse'`` (tile-skipping grid over the CompiledPlan
    schedule).
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from repro.kernels.crossbar_permute import (crossbar_permute_pallas,
                                            crossbar_permute_sparse_pallas)
from repro.kernels.fused_compress import fused_vcompress_pallas
from repro.kernels.moe_route import moe_route_transform_pallas

DROP = -1

# Integer payloads route through the f32 MXU datapath, which represents
# integers exactly only up to 2^24.  Larger magnitudes would silently
# round; the wrappers below reject them when the payload is concrete.
_F32_EXACT_INT_BOUND = 1 << 24


class KernelLaunchError(RuntimeError):
    """A Pallas crossbar kernel failed to build or launch.

    Raised with the plan geometry and kernel name attached so the
    resilience layer (``core.resilience.classify`` -> ``LaunchFault``)
    and operators see *which* kernel at *which* shape died, instead of a
    bare Mosaic/interpreter traceback.  The original exception rides
    along as ``__cause__``.
    """


@contextlib.contextmanager
def _surface_kernel_errors(kernel: str, plan):
    """Rebrand kernel-internal failures with plan-geometry context.

    Input-validation errors raised by the wrappers themselves (payload
    bound checks, semiring routing) are *not* kernel failures and pass
    through untouched — only exceptions escaping the Pallas call are
    wrapped.
    """
    try:
        yield
    except Exception as e:  # noqa: BLE001 — annotate and re-raise
        raise KernelLaunchError(
            f"{kernel} failed for plan (mode={plan.mode}, "
            f"{plan.n_in}->{plan.n_out}, k={plan.idx.shape[-1]}, "
            f"semiring={plan.semiring.name}): {type(e).__name__}: {e}"
        ) from e


def default_interpret(interpret=None) -> bool:
    """Pallas interpret mode for a kernel launch: an explicit choice
    wins; otherwise compiled on a TPU backend and interpreted anywhere
    else."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _as_f32_payload(x):
    """Cast integer/bool payloads to f32 for the MXU crossbar.

    Contract: integer payloads must fit in f32 exactly, i.e. |x| < 2^24
    (token ids, slot indices, and routing metadata all do).  The bound is
    checked eagerly for concrete arrays; traced payloads are the caller's
    responsibility — the check cannot run at trace time.
    """
    if not (jnp.issubdtype(x.dtype, jnp.integer) or x.dtype == jnp.bool_):
        return x
    if (x.dtype != jnp.bool_ and x.dtype.itemsize > 2
            and not isinstance(x, jax.core.Tracer) and x.size):
        # min/max separately: abs() of the most negative int overflows.
        hi, lo = int(jnp.max(x)), int(jnp.min(x))
        if hi >= _F32_EXACT_INT_BOUND or -lo >= _F32_EXACT_INT_BOUND:
            raise ValueError(
                f"integer payload magnitude {max(hi, -lo)} >= 2^24: the "
                "crossbar kernels route integers through f32, which is "
                "only exact below 2^24. Split the payload or use the "
                "'einsum' backend (int32 accumulation).")
    return x.astype(jnp.float32)


def _pad_to(x, mult, axis, value=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _semiring_fold(plan):
    """The kernel-level accumulate mode of a plan's semiring.

    REAL accumulates natively; GF2 folds the exact f32 sum mod 2 at
    emission.  GF2_8 plans never reach the kernels directly — the
    crossbar engine lowers them through their GF(2) bit lift first
    (``core.crossbar.lift_gf2_8``), so seeing one here is a bug.
    """
    sr = plan.semiring
    if sr.mod2_fold:
        return True
    if sr.name == "real":
        return False
    raise ValueError(
        f"semiring {sr.name!r} has no direct kernel path; execute via "
        "core.crossbar.apply_plan (which lifts it to GF(2) bit rows)")


def crossbar_permute(plan, x, *, merge=None, interpret=None,
                     block_o=128, block_n=128, block_d=128):
    """Execute a repro.core PermutePlan via the Pallas crossbar kernel.

    x: (n_in, D). Returns (n_out, D).
    """
    from repro.core import crossbar as xb  # avoid import cycle at load time

    interpret = default_interpret(interpret)
    fold_mod2 = _semiring_fold(plan)
    n_in, n_out = plan.n_in, plan.n_out
    mode = "gather" if plan.mode == xb.GATHER else "scatter"

    orig_dtype = x.dtype
    x = _as_f32_payload(x)

    xp = _pad_to(_pad_to(x, block_n, 0), block_d, 1)
    # Padded control rows select nothing (DROP).
    ctrl_block = block_o if mode == "gather" else block_n
    idxp = _pad_to(plan.idx, ctrl_block, 0, value=DROP)
    wp = (None if plan.weights is None
          else _pad_to(plan.weights, ctrl_block, 0))
    mp = None
    if merge is not None:
        merge = merge.astype(xp.dtype)
        mp = _pad_to(_pad_to(merge, block_o, 0), block_d, 1)

    n_out_pad = n_out + ((-n_out) % block_o)
    with _surface_kernel_errors("dense crossbar kernel", plan):
        out = crossbar_permute_pallas(
            idxp, xp, mode=mode, n_out=n_out_pad, weights=wp, merge=mp,
            n_in_valid=n_in, fold_mod2=fold_mod2,
            block_o=block_o, block_n=block_n, block_d=block_d,
            interpret=interpret)
    out = out[:n_out, :x.shape[1]]
    return out.astype(orig_dtype)


def crossbar_permute_sparse(plan, x, *, compiled=None, interpret=None,
                            block_o=128, block_n=128, block_d=128):
    """Execute a PermutePlan via the tile-skipping sparse crossbar kernel.

    x: (n_in, D). Returns (n_out, D).  Rows belonging to output tiles the
    plan never touches are left unwritten by the kernel (zeros here, since
    the padded output buffer starts empty in interpret mode, but
    *unspecified* in general) — core.crossbar.apply_plan overlays
    merge/zero from the plan's coverage; use that entry point unless you
    only consume covered rows.

    ``compiled`` may carry a pre-built CompiledPlan (matching blocking);
    otherwise the plan is compiled here — a cache hit when the same
    concrete plan was executed before.
    """
    from repro.core import crossbar as xb  # avoid import cycle at load time

    interpret = default_interpret(interpret)
    fold_mod2 = _semiring_fold(plan)
    n_in, n_out = plan.n_in, plan.n_out
    mode = "gather" if plan.mode == xb.GATHER else "scatter"

    orig_dtype = x.dtype
    x = _as_f32_payload(x)

    # A schedule from a different plan (or blocking) would silently skip
    # tiles this plan occupies — only trust one built from this very idx.
    if (compiled is None or compiled.block_o != block_o
            or compiled.block_n != block_n
            or compiled.plan.idx is not plan.idx):
        compiled = xb.compile_plan(plan, block_o=block_o, block_n=block_n)

    xp = _pad_to(_pad_to(x, block_n, 0), block_d, 1)
    ctrl_block = block_o if mode == "gather" else block_n
    idxp = _pad_to(plan.idx, ctrl_block, 0, value=DROP)
    wp = (None if plan.weights is None
          else _pad_to(plan.weights, ctrl_block, 0))
    n_out_pad = n_out + ((-n_out) % block_o)

    if compiled.is_static:
        num = compiled.num_active
        if num == 0:
            out = jnp.zeros((n_out_pad, xp.shape[1]), xp.dtype)
        else:
            # Compact grid: exactly the occupied pairs, no guards.
            with _surface_kernel_errors("sparse crossbar kernel", plan):
                out = crossbar_permute_sparse_pallas(
                    compiled.pair_o[:num], compiled.pair_n[:num],
                    compiled.active[:num], idxp, xp,
                    mode=mode, n_out=n_out_pad, weights=wp, guard=False,
                    fold_mod2=fold_mod2,
                    block_o=block_o, block_n=block_n, block_d=block_d,
                    interpret=interpret)
    else:
        # Traced schedule: full pair list, pl.when-guarded tile skip.
        with _surface_kernel_errors("sparse crossbar kernel", plan):
            out = crossbar_permute_sparse_pallas(
                compiled.pair_o, compiled.pair_n, compiled.active, idxp, xp,
                mode=mode, n_out=n_out_pad, weights=wp, guard=True,
                fold_mod2=fold_mod2,
                block_o=block_o, block_n=block_n, block_d=block_d,
                interpret=interpret)
    out = out[:n_out, :x.shape[1]]
    return out.astype(orig_dtype)


def fused_vcompress(mask, x, *, tail="zero", interpret=None, block_d=128):
    """Fused mask->transform->crossbar compress. x: (N, D) -> (N, D)."""
    interpret = default_interpret(interpret)
    orig_dtype = x.dtype
    x = _as_f32_payload(x)
    d = x.shape[1]
    xp = _pad_to(x, block_d, 1)
    out = fused_vcompress_pallas(mask, xp, tail=tail, block_d=block_d,
                                 interpret=interpret)
    return out[:, :d].astype(orig_dtype)


# -- sub-element-width pack/permute/unpack helpers --------------------------
# The paper's Table 1 shows crossbar cost collapsing as the minimum
# movable element (SEW) grows; these helpers turn the knob the other way:
# elements *narrower* than a payload word (bit permutations in PRESENT/
# GIFT-style ciphers) are exposed by unpacking each word into `width`
# 0/1 rows, permuting at bit granularity, and packing back.  Both
# directions are branch-free shift/mask arithmetic (fixed latency) and
# exact for values in [0, 2**width).

_MAX_PACK_WIDTH = 31  # packed words accumulate in int32


def unpack_bits(x, width, *, axis=0):
    """Split each integer element into ``width`` 0/1 int32 rows (LSB-first).

    ``(..., N, ...) -> (..., N*width, ...)`` along ``axis``: element i's
    bits occupy rows ``[i*width, (i+1)*width)``, least-significant first
    (the SHA-3 / RVV bit-numbering convention).  Values must lie in
    ``[0, 2**width)``; width is capped at 31 so the packed round-trip is
    int32-exact.
    """
    if not 1 <= width <= _MAX_PACK_WIDTH:
        raise ValueError(f"unpack width must be in [1, {_MAX_PACK_WIDTH}], "
                         f"got {width}")
    x = jnp.asarray(x)
    if not (jnp.issubdtype(x.dtype, jnp.integer) or x.dtype == jnp.bool_):
        raise ValueError(f"unpack_bits needs an integer payload, got "
                         f"{x.dtype}")
    axis = axis % x.ndim
    xe = jnp.expand_dims(x.astype(jnp.int32), axis + 1)
    shifts = jnp.arange(width, dtype=jnp.int32).reshape(
        (1,) * (axis + 1) + (width,) + (1,) * (x.ndim - axis - 1))
    bits = (jnp.right_shift(xe, shifts)) & 1
    shape = x.shape[:axis] + (x.shape[axis] * width,) + x.shape[axis + 1:]
    return bits.reshape(shape)


def pack_bits(bits, width, *, axis=0, dtype=jnp.int32):
    """Inverse of :func:`unpack_bits`: fold ``width`` 0/1 rows per word.

    ``(..., N*width, ...) -> (..., N, ...)`` along ``axis``.  Exact for
    any bit pattern with ``width <= 31``.
    """
    if not 1 <= width <= _MAX_PACK_WIDTH:
        raise ValueError(f"pack width must be in [1, {_MAX_PACK_WIDTH}], "
                         f"got {width}")
    bits = jnp.asarray(bits)
    axis = axis % bits.ndim
    n = bits.shape[axis]
    if n % width:
        raise ValueError(f"pack_bits: axis length {n} is not a multiple "
                         f"of width {width}")
    shape = bits.shape[:axis] + (n // width, width) + bits.shape[axis + 1:]
    grouped = bits.astype(jnp.int32).reshape(shape)
    weights = (jnp.int32(1) << jnp.arange(width, dtype=jnp.int32)).reshape(
        (1,) * (axis + 1) + (width,) + (1,) * (bits.ndim - axis - 1))
    return jnp.sum(grouped * weights, axis=axis + 1).astype(dtype)


def bits_roundtrip(x, width, *, axis=0):
    """``pack_bits(unpack_bits(x))`` — the identity for in-range payloads.

    Exists to make the sub-element path's overhead measurable in
    isolation (benchmarks/bench_crypto.py width sweep) and its exactness
    assertable in tests without involving a crossbar pass.
    """
    return pack_bits(unpack_bits(x, width, axis=axis), width, axis=axis,
                     dtype=jnp.asarray(x).dtype)


def moe_route_transform(expert_ids, *, num_experts, capacity,
                        interpret=None, block_t=256):
    """Fused MoE position/destination transform. (T,K) -> (pos, dest)."""
    interpret = default_interpret(interpret)
    t = expert_ids.shape[0]
    idp = _pad_to(expert_ids, block_t, 0, value=DROP)
    pos, dest = moe_route_transform_pallas(
        idp, num_experts=num_experts, capacity=capacity, block_t=block_t,
        interpret=interpret)
    return pos[:t], dest[:t]
