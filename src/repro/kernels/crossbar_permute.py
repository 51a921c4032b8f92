"""Pallas TPU kernel: the unified permutation crossbar.

This is the MXU-native form of the paper's AND-OR crossbar (Fig. 2):
``out = P @ x`` where ``P`` is the one-hot select operator.  The crucial
structural property — mirrored from the hardware, where one-hot selects are
decoded *at* the multiplexers and never stored — is that **P is never
materialised in HBM**: each grid step rebuilds the (BO, BN) one-hot tile in
VMEM/registers from the int32 index tile (an iota compare, the SAD
fused add-and-decode analogue) and feeds it straight into the MXU matmul.

HBM traffic is therefore ``N*K*4`` index bytes + the data tiles — not the
``N_out*N_in`` operator — so arithmetic intensity scales with D like a
dense matmul while memory traffic stays permutation-sized.

Grid: ``(n_out/BO, D/BD, n_in/BN)`` with the reduction axis innermost;
a (BO, BD) f32 accumulator lives in VMEM scratch across reduction steps.

Both control modes run on the same kernel (the paper's unification):
  * gather  (output-driven, vrgather):  onehot[o, i] = (idx[o,k] == i)
  * scatter (input-driven, vcompress/vslide after the Sec. III-B transform):
            onehot[o, i] = (idx[i,k] == o)
Out-of-range indices match no iota — the all-zeros SAD row — so dropped
elements (slide-out, MoE capacity overflow) cost nothing and need no branch.

Optional per-select weights turn the crossbar into the weighted MoE
combine; optional merge input provides the RVV tail/masked-undisturbed
policy, fused at the final reduction step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BO = 128
DEFAULT_BN = 128
DEFAULT_BD = 128


def _onehot_tile(idx_blk, w_blk, o_base, n_base, bo, bn, mode, compute_dtype):
    """Build the (BO, BN) crossbar tile: fused decode of the index block.

    gather:  idx_blk (BO, K); tile[o, i] = sum_k w[o,k] * (idx[o,k]==n_base+i)
    scatter: idx_blk (BN, K); tile[o, i] = sum_k w[i,k] * (idx[i,k]==o_base+o)
    """
    k = idx_blk.shape[-1]
    tile = jnp.zeros((bo, bn), dtype=compute_dtype)
    if mode == "gather":
        col = jax.lax.broadcasted_iota(jnp.int32, (bo, bn), 1) + n_base
        for j in range(k):
            sel = (idx_blk[:, j][:, None] == col)
            wj = (w_blk[:, j][:, None].astype(compute_dtype)
                  if w_blk is not None else None)
            contrib = sel.astype(compute_dtype)
            tile = tile + (contrib * wj if wj is not None else contrib)
    else:
        row = jax.lax.broadcasted_iota(jnp.int32, (bo, bn), 0) + o_base
        for j in range(k):
            sel = (idx_blk[:, j][None, :] == row)
            wj = (w_blk[:, j][None, :].astype(compute_dtype)
                  if w_blk is not None else None)
            contrib = sel.astype(compute_dtype)
            tile = tile + (contrib * wj if wj is not None else contrib)
    return tile


def _exact_dot(tile, x):
    """One-hot tile times payload, accumulated in f32.  An f32 payload
    asks for full-precision passes: the MXU's default rounds f32
    operands to bf16, which would not route an f32 value exactly."""
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else None)
    return jax.lax.dot(tile, x, precision=precision,
                       preferred_element_type=jnp.float32)


def _kernel(idx_ref, x_ref, *refs, mode, weighted, use_merge,
            bo, bn, n_tiles, n_in_valid, fold_mod2=False):
    """One grid step of the crossbar contraction."""
    if weighted and use_merge:
        w_ref, merge_ref, out_ref, acc_ref, cov_ref = refs
    elif weighted:
        w_ref, out_ref, acc_ref, cov_ref = refs
        merge_ref = None
    elif use_merge:
        merge_ref, out_ref, acc_ref, cov_ref = refs
        w_ref = None
    else:
        out_ref, acc_ref, cov_ref = refs
        w_ref = merge_ref = None

    o_i = pl.program_id(0)
    n_i = pl.program_id(2)

    @pl.when(n_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        cov_ref[...] = jnp.zeros(cov_ref.shape, cov_ref.dtype)

    x_blk = x_ref[...]
    idx_blk = idx_ref[...]
    w_blk = w_ref[...] if w_ref is not None else None

    compute_dtype = (x_blk.dtype if x_blk.dtype in (jnp.bfloat16, jnp.float32)
                     else jnp.float32)
    tile = _onehot_tile(idx_blk, w_blk, o_i * bo, n_i * bn, bo, bn, mode,
                        compute_dtype)

    acc_ref[...] += _exact_dot(tile, x_blk.astype(compute_dtype))

    # Coverage (unweighted hit count per output row) for merge semantics.
    if mode == "gather":
        # valid source anywhere in [0, n_in_total): independent of n-step,
        # but accumulate only once (at step 0) to keep the scratch pattern.
        @pl.when(n_i == 0)
        def _cov():
            valid = ((idx_blk >= 0) & (idx_blk < n_in_valid))
            cov_ref[...] += jnp.sum(valid.astype(jnp.float32), axis=-1,
                                    keepdims=True)
    else:
        row = jax.lax.broadcasted_iota(jnp.int32, (bo, bn), 0) + o_i * bo
        hits = jnp.zeros((bo, bn), dtype=jnp.float32)
        for j in range(idx_blk.shape[-1]):
            hits += (idx_blk[:, j][None, :] == row).astype(jnp.float32)
        cov_ref[...] += jnp.sum(hits, axis=-1, keepdims=True)

    @pl.when(n_i == n_tiles - 1)
    def _emit():
        result = acc_ref[...]
        if fold_mod2:
            # GF(2) accumulate: the f32 sum of 0/1 AND-products is exact
            # below 2^24, and its parity IS the XOR accumulation.
            result = result - 2.0 * jnp.floor(result * 0.5)
        if merge_ref is not None:
            covered = cov_ref[...] > 0.0
            result = jnp.where(covered, result,
                               merge_ref[...].astype(jnp.float32))
        out_ref[...] = result.astype(out_ref.dtype)


def crossbar_permute_pallas(
    idx: jax.Array,
    x: jax.Array,
    *,
    mode: str,
    n_out: int,
    weights: jax.Array | None = None,
    merge: jax.Array | None = None,
    n_in_valid: int | None = None,
    fold_mod2: bool = False,
    block_o: int = DEFAULT_BO,
    block_n: int = DEFAULT_BN,
    block_d: int = DEFAULT_BD,
    interpret: bool = False,
) -> jax.Array:
    """Raw kernel entry; shapes must already be block-aligned.

    idx: (n_ctrl, K) int32;  x: (n_in, D);  weights: like idx (f32);
    merge: (n_out, D) or None.  ``fold_mod2`` reduces the accumulated
    sum mod 2 at emission — the GF(2) semiring's XOR accumulation on
    0/1 payloads/weights.  Returns (n_out, D) in x.dtype.
    """
    n_in, d = x.shape
    assert n_in % block_n == 0 and n_out % block_o == 0 and d % block_d == 0, (
        "pad shapes before calling the raw kernel")
    k = idx.shape[1]
    n_tiles = n_in // block_n
    grid = (n_out // block_o, d // block_d, n_tiles)

    # Control-block geometry differs per mode: per-output vs per-input.
    if mode == "gather":
        idx_spec = pl.BlockSpec((block_o, k), lambda o, dd, n: (o, 0))
    else:
        idx_spec = pl.BlockSpec((block_n, k), lambda o, dd, n: (n, 0))

    in_specs = [idx_spec,
                pl.BlockSpec((block_n, block_d), lambda o, dd, n: (n, dd))]
    operands = [idx, x]
    if weights is not None:
        in_specs.append(idx_spec)
        operands.append(weights.astype(jnp.float32))
    if merge is not None:
        in_specs.append(
            pl.BlockSpec((block_o, block_d), lambda o, dd, n: (o, dd)))
        operands.append(merge)

    kernel = functools.partial(
        _kernel, mode=mode, weighted=weights is not None,
        use_merge=merge is not None, bo=block_o, bn=block_n,
        n_tiles=n_tiles, fold_mod2=fold_mod2,
        n_in_valid=n_in if n_in_valid is None else n_in_valid)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_o, block_d), lambda o, dd, n: (o, dd)),
        out_shape=jax.ShapeDtypeStruct((n_out, d), x.dtype),
        scratch_shapes=[
            # f32 accumulator tile + per-row coverage counter, in VMEM.
            pltpu.VMEM((block_o, block_d), jnp.float32),
            pltpu.VMEM((block_o, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# Tile-skipping sparse path
# ---------------------------------------------------------------------------
#
# A permutation touches at most N·K operator tiles; the dense grid above
# visits all n_out/BO × n_in/BN of them.  The sparse path iterates a grid
# over the *active-pair schedule* computed by core.crossbar.compile_plan:
# scalar-prefetched (o_tile, n_tile) coordinates drive the BlockSpec index
# maps, so only occupied tiles are ever DMA'd or multiplied.  Pairs arrive
# o-major-sorted, so all reduction steps of one output tile are consecutive
# grid steps and a single VMEM accumulator suffices; the kernel detects
# o-run boundaries by comparing neighbouring schedule entries (branch-free,
# pl.when-predicated).
#
# With a static schedule (plan concrete at trace time) the grid is exactly
# num_active pairs — true tile skipping.  With a traced schedule the grid
# spans the full pair list and inactive slots are skipped behind pl.when
# guards (no DMA savings, but the MXU work is still predicated off).


def _sparse_kernel(po_ref, pn_ref, act_ref, idx_ref, x_ref, *refs,
                   mode, weighted, bo, bn, num_pairs, guard,
                   fold_mod2=False):
    """One grid step over (d_tile, schedule_slot)."""
    if weighted:
        w_ref, out_ref, acc_ref = refs
    else:
        out_ref, acc_ref = refs
        w_ref = None

    p = pl.program_id(1)
    o_cur = po_ref[p]
    prev_o = po_ref[jnp.maximum(p - 1, 0)]
    nxt = jnp.minimum(p + 1, num_pairs - 1)
    is_first = (p == 0) | (prev_o != o_cur)
    is_last = (p == num_pairs - 1) | (po_ref[nxt] != o_cur)
    if guard:
        # Inactive slots are clamped onto the last active pair, so the last
        # *active* slot of an o-run is also followed by an inactive slot.
        is_last = is_last | (act_ref[nxt] == 0)
        is_active = act_ref[p] != 0
    else:
        is_active = None

    @pl.when(is_first)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    def _accumulate():
        x_blk = x_ref[...]
        idx_blk = idx_ref[...]
        w_blk = w_ref[...] if w_ref is not None else None
        compute_dtype = (x_blk.dtype
                         if x_blk.dtype in (jnp.bfloat16, jnp.float32)
                         else jnp.float32)
        tile = _onehot_tile(idx_blk, w_blk, o_cur * bo, pn_ref[p] * bn,
                            bo, bn, mode, compute_dtype)
        acc_ref[...] += _exact_dot(tile, x_blk.astype(compute_dtype))

    if guard:
        pl.when(is_active)(_accumulate)
    else:
        _accumulate()

    emit = (is_last & is_active) if guard else is_last

    @pl.when(emit)
    def _emit():
        result = acc_ref[...]
        if fold_mod2:
            # GF(2) accumulate: parity of the exact f32 0/1-product sum.
            result = result - 2.0 * jnp.floor(result * 0.5)
        out_ref[...] = result.astype(out_ref.dtype)


def crossbar_permute_sparse_pallas(
    pair_o: jax.Array,
    pair_n: jax.Array,
    active: jax.Array,
    idx: jax.Array,
    x: jax.Array,
    *,
    mode: str,
    n_out: int,
    weights: jax.Array | None = None,
    guard: bool = False,
    fold_mod2: bool = False,
    block_o: int = DEFAULT_BO,
    block_n: int = DEFAULT_BN,
    block_d: int = DEFAULT_BD,
    interpret: bool = False,
) -> jax.Array:
    """Raw tile-skipping kernel; shapes must already be block-aligned.

    pair_o / pair_n / active: (num_pairs,) schedule from compile_plan —
    o-major sorted, inactive tail clamped in-range.  ``guard=False``
    asserts every slot is active (statically compacted schedule);
    ``guard=True`` predicates each slot on ``active`` instead.
    idx: (n_ctrl, K) int32; x: (n_in, D).  Returns (n_out, D) in x.dtype;
    rows of output tiles absent from the schedule are NOT written — the
    caller overlays merge/zero from the plan's coverage.
    """
    n_in, d = x.shape
    assert n_in % block_n == 0 and n_out % block_o == 0 and d % block_d == 0, (
        "pad shapes before calling the raw kernel")
    num_pairs = pair_o.shape[0]
    assert num_pairs >= 1, "empty schedules are handled by the wrapper"
    k = idx.shape[1]

    # Index maps receive the scalar-prefetch refs after the grid indices;
    # the schedule drives which blocks get DMA'd each step.
    if mode == "gather":
        idx_spec = pl.BlockSpec((block_o, k),
                                lambda dd, p, po, pn, act: (po[p], 0))
    else:
        idx_spec = pl.BlockSpec((block_n, k),
                                lambda dd, p, po, pn, act: (pn[p], 0))
    in_specs = [idx_spec,
                pl.BlockSpec((block_n, block_d),
                             lambda dd, p, po, pn, act: (pn[p], dd))]
    operands = [idx, x]
    if weights is not None:
        in_specs.append(idx_spec)
        operands.append(weights.astype(jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(d // block_d, num_pairs),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_o, block_d),
                               lambda dd, p, po, pn, act: (po[p], dd)),
        scratch_shapes=[pltpu.VMEM((block_o, block_d), jnp.float32)],
    )
    kernel = functools.partial(
        _sparse_kernel, mode=mode, weighted=weights is not None,
        bo=block_o, bn=block_n, num_pairs=num_pairs, guard=guard,
        fold_mod2=fold_mod2)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_out, d), x.dtype),
        interpret=interpret,
    )(pair_o.astype(jnp.int32), pair_n.astype(jnp.int32),
      active.astype(jnp.int32), *operands)
