"""The one-hot crossbar: the universal executor of the unified datapath.

The paper's crossbar (Sec. III-A, Fig. 2) is a matrix of AND-OR multiplexers:
output ``o`` receives ``sum_i onehot[o, i] * x[i]``.  On a TPU the natural —
and fast — form of that computation is a dense matmul against a one-hot
operator matrix, executed on the MXU.  This module provides:

* ``PermutePlan`` — the compiled control information of a permutation:
  either *gather* form (per-output source indices — output-driven
  instructions) or *scatter* form (per-input destination indices —
  input-driven instructions after core/transform.py pre-processing).
  Plans support multi-index selections with optional per-select weights,
  which is what lets the same crossbar implement weighted MoE combine
  (a crossbar whose AND-OR selects carry gate scalars).  The algebra
  weights accumulate in is pluggable per plan (``core.semiring``):
  REAL multiply-add, GF(2) XOR/AND (parity-folded integer contraction),
  or GF(2^8) field arithmetic (executed as a cached GF(2) bit lift —
  AES MixColumns is a crossbar whose weights are field coefficients).

* ``build_onehot``  — materialise the (n_out, n_in) operator (reference /
  small sizes / tests).

* ``CompiledPlan``  — the *schedule* of a plan: which (output-tile,
  input-tile) blocks of the crossbar operator are actually occupied, and
  a compacted o-major list of those active pairs.  Compiling a plan is
  itself branch-free log-depth work (scatter-add + stable argsort), so it
  stays jittable; an LRU cache keyed on plan identity makes repeated
  executions (serving, training steps with static routing geometry) pay
  compilation once.

* ``apply_plan``    — execute the crossbar.  This is the single point
  every permutation in the repo lowers through: the RVV ops in
  ``core/permute.py`` build plans (eagerly, or lazily fused through
  ``core/plan_algebra.py`` so a whole chain costs one call), MoE
  dispatch/combine derive their plans by transposition, and batched
  per-row ops arrive as one block-diagonal plan.  An invocation counter
  (``apply_call_count``, surfaced by ``core/telemetry.py``) makes the
  one-pass property assertable.  Backends:
    - 'einsum':  XLA dense path — builds one-hot and contracts; XLA fuses
      the iota-compare into the matmul producer. Default, always available.
    - 'kernel':  Pallas kernel (kernels/crossbar_permute.py) that builds
      one-hot *tiles* in VMEM on the fly — the operator never exists in HBM.
    - 'sparse':  tile-skipping Pallas kernel driven by the CompiledPlan
      schedule — cost scales with the number of *occupied* tiles (N·K
      selects), not the full n_out×n_in grid.
    - 'auto':    measured-density heuristic picking between the above.
    - 'reference': jnp.take-based oracle (the "separate datapath" world);
      used for differential testing.

Fixed-latency property: every backend is branch-free and fixed-shape.  Out
of range indices produce all-zero one-hot rows/columns (the SAD
out-of-bounds drop), never an error and never a data-dependent branch.
"""

from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro import obs as _obs
from repro.core import integrity as _integrity
from repro.core import semiring as sr_mod
from repro.core import transform as _t
from repro.core.semiring import GF2, GF2_8, REAL, Semiring

Array = jax.Array

GATHER = "gather"    # output-driven: idx[o, k] = source of output o
SCATTER = "scatter"  # input-driven:  idx[i, k] = destination of input i


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PermutePlan:
    """Control information for one crossbar evaluation.

    Attributes:
      mode: GATHER (output-driven) or SCATTER (input-driven).
      idx:  int32 (n_ctrl, k) — multi-index selects.  In gather mode
            n_ctrl == n_out; in scatter mode n_ctrl == n_in.  Entries
            outside the valid range are dropped (match nothing).
      weights: optional (n_ctrl, k) — per-select scaling (MoE gates,
            GF(2^8) MixColumns coefficients).  None means the semiring's
            multiplicative identity everywhere.
      n_in / n_out: crossbar geometry.
      semiring: the (add, mul, zero, one) the pass accumulates in
            (``core.semiring``).  REAL is the classic multiply-add;
            GF2/GF2_8 make the same crossbar a finite-field linear
            layer.  Interned singleton — part of every cache key.
    """

    mode: str
    idx: Array
    n_in: int
    n_out: int
    weights: Optional[Array] = None
    semiring: Semiring = REAL

    def __post_init__(self):
        if self.mode not in (GATHER, SCATTER):
            raise ValueError(f"bad mode {self.mode!r}")
        if not isinstance(self.semiring, Semiring):
            raise ValueError(f"bad semiring {self.semiring!r}; use the "
                             "core.semiring singletons")
        if self.idx.ndim == 1:
            self.idx = self.idx[:, None]
        if self.weights is not None and self.weights.ndim == 1:
            self.weights = self.weights[:, None]

    # -- pytree plumbing so plans can cross jit boundaries ----------------
    # The semiring is aux data (static): an interned singleton, never a
    # tracer, and part of the trace-level identity of the plan.
    def tree_flatten(self):
        children = (self.idx, self.weights)
        aux = (self.mode, self.n_in, self.n_out, self.semiring)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        idx, weights = children
        mode, n_in, n_out, semiring = aux
        obj = object.__new__(cls)
        obj.mode, obj.idx, obj.n_in, obj.n_out, obj.weights = (
            mode, idx, n_in, n_out, weights)
        obj.semiring = semiring
        return obj

    @property
    def k(self) -> int:
        return self.idx.shape[-1]

    @property
    def neutral_semiring(self) -> bool:
        """True when the plan is pure routing: unweighted REAL-default.

        Such a plan means the same thing in every semiring (selects
        carry the multiplicative identity), so combining it with a
        finite-field plan adopts the other operand's algebra.
        """
        return self.weights is None and self.semiring is REAL


def gather_plan(src_idx: Array, n_in: int, *, weights: Array | None = None,
                semiring: Semiring = REAL) -> PermutePlan:
    """Output-driven plan: ``out[o] = sum_k w[o,k] * x[src_idx[o,k]]``."""
    return PermutePlan(GATHER, src_idx.astype(jnp.int32), n_in,
                       src_idx.shape[0], weights, semiring)


def scatter_plan(dest_idx: Array, n_out: int, *, weights: Array | None = None,
                 semiring: Semiring = REAL) -> PermutePlan:
    """Input-driven plan: input i lands at ``dest_idx[i,k]`` (OOB drops)."""
    return PermutePlan(SCATTER, dest_idx.astype(jnp.int32), dest_idx.shape[0],
                       n_out, weights, semiring)


def transpose_plan(plan: PermutePlan) -> PermutePlan:
    """The inverse-direction crossbar (operator transpose).

    One-hot operators with one-hot rows are partial isometries: the
    transposed plan routes data back.  Used for MoE combine (= dispatchᵀ
    with gate weights) and for gradients.
    """
    mode = SCATTER if plan.mode == GATHER else GATHER
    return PermutePlan(mode, plan.idx, plan.n_out, plan.n_in, plan.weights,
                       plan.semiring)


def build_onehot(plan: PermutePlan, dtype=None) -> Array:
    """Materialise the (n_out, n_in) crossbar operator.

    ``P[o, i] = SUM_k w[., k] * [idx[., k] selects (o, i)]`` where SUM and
    * are the plan's semiring (REAL sums; GF2/GF2_8 XOR-fold, so two
    selects landing on the same cell cancel instead of doubling).

    ``dtype`` defaults to f32 for REAL plans and the semiring's weight
    dtype (int32) for finite-field plans.

    Reference path — the Pallas kernel never materialises this matrix.
    """
    sr = plan.semiring
    if sr.limbs:
        raise ValueError(
            f"wide {sr.name} plans have no dense one-hot form; they "
            "execute through lift_gf2_k")
    if dtype is None:
        dtype = jnp.float32 if sr is REAL else sr.weight_dtype
    if plan.mode == GATHER:
        # idx: (n_out, k); P[o, i] = SUM_k w[o,k] * (idx[o,k] == i)
        iota = jnp.arange(plan.n_in, dtype=jnp.int32)
        sel = (plan.idx[:, :, None] == iota[None, None, :])  # (n_out, k, n_in)
        w = (jnp.ones_like(plan.idx, dtype=dtype) if plan.weights is None
             else plan.weights.astype(dtype))
        if sr is REAL:
            return jnp.sum(sel.astype(dtype) * w[:, :, None], axis=1)
        terms = sr.mul(w[:, :, None], sel.astype(dtype))
        return sr.reduce(terms, axis=1)
    else:
        # idx: (n_in, k); P[o, i] = SUM_k w[i,k] * (idx[i,k] == o)
        iota = jnp.arange(plan.n_out, dtype=jnp.int32)
        sel = (plan.idx[:, :, None] == iota[None, None, :])  # (n_in, k, n_out)
        w = (jnp.ones_like(plan.idx, dtype=dtype) if plan.weights is None
             else plan.weights.astype(dtype))
        if sr is REAL:
            return jnp.sum(sel.astype(dtype) * w[:, :, None], axis=1).T
        terms = sr.mul(w[:, :, None], sel.astype(dtype))
        return sr.reduce(terms, axis=1).T


def coverage(plan: PermutePlan) -> Array:
    """(n_out,) bool — which outputs receive at least one input.

    Uncovered outputs take the merge value (RVV tail/masked-off policy).
    Unweighted on purpose: a zero-gate selection still *covers* its output.
    """
    if plan.mode == GATHER:
        valid = (plan.idx >= 0) & (plan.idx < plan.n_in)  # (n_out, k)
        return jnp.any(valid, axis=-1)
    # Scatter: O(N*K) scatter-add, not an (n_in, k, n_out) hit tensor —
    # this runs per apply_plan call on the dispatch hot path.
    valid = (plan.idx >= 0) & (plan.idx < plan.n_out)
    hits = jnp.zeros((plan.n_out,), jnp.int32).at[
        jnp.clip(plan.idx, 0, plan.n_out - 1).ravel()].add(
        valid.ravel().astype(jnp.int32), mode="drop")
    return hits > 0


# ---------------------------------------------------------------------------
# Plan compilation: occupancy maps and active-tile schedules
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CompiledPlan:
    """The tile schedule of a plan under a (block_o, block_n) blocking.

    A permutation with N control rows and K selects touches at most N·K of
    the n_o_tiles × n_n_tiles operator blocks; every other block is exactly
    zero and contributes nothing to the contraction.  ``CompiledPlan``
    records which blocks are occupied and a compacted, o-major-sorted list
    of the occupied (o_tile, n_tile) pairs — the iteration schedule of the
    tile-skipping kernel.

    Attributes:
      plan:        the PermutePlan this schedule was compiled from.
      block_o/block_n: operator blocking (output rows / input rows per tile).
      n_o_tiles/n_n_tiles: padded grid extents (ceil divisions).
      occupancy:   (n_o_tiles, n_n_tiles) bool — block is touched by >=1
                   valid select.
      pair_o/pair_n: (n_pairs,) int32 — active pairs first, o-major order
                   (all n-tiles of one output tile are consecutive, so the
                   kernel can keep one VMEM accumulator per o-run).  The
                   inactive tail is clamped to the last active pair so
                   index maps always stay in range.
      active:      (n_pairs,) bool — schedule-slot validity.
      num_active:  Python int when the plan was concrete at compile time
                   (the compacted grid can then be sliced statically — true
                   tile skipping); a traced scalar otherwise (the kernel
                   falls back to ``pl.when``-guarded skipping over the full
                   pair list).
    """

    plan: PermutePlan
    block_o: int
    block_n: int
    n_o_tiles: int
    n_n_tiles: int
    occupancy: Array
    pair_o: Array
    pair_n: Array
    active: Array
    num_active: Union[int, Array]

    # -- pytree plumbing ----------------------------------------------------
    # num_active travels as a child: crossing a jit boundary naturally
    # demotes a static (int) count to a traced scalar, and is_static is
    # derived from its type at use time.
    def tree_flatten(self):
        children = (self.plan, self.occupancy, self.pair_o, self.pair_n,
                    self.active, self.num_active)
        aux = (self.block_o, self.block_n, self.n_o_tiles, self.n_n_tiles)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        plan, occ, po, pn, act, num = children
        bo, bn, to, tn = aux
        return cls(plan, bo, bn, to, tn, occ, po, pn, act, num)

    @property
    def n_pairs(self) -> int:
        """Full grid size (schedule capacity)."""
        return self.n_o_tiles * self.n_n_tiles

    @property
    def is_static(self) -> bool:
        """True when the active count is a Python int (compact grid)."""
        return isinstance(self.num_active, int)

    @property
    def density(self) -> Union[float, Array]:
        """Fraction of operator tiles occupied (the heuristic's input)."""
        if self.n_pairs == 0:
            return 1.0
        return self.num_active / self.n_pairs


def _tile_occupancy(plan: PermutePlan, block_o: int, block_n: int) -> Array:
    """(n_o_tiles, n_n_tiles) bool occupancy of the blocked operator.

    Branch-free: one scatter-add over the N·K select entries (invalid
    selects drop), so it traces cleanly inside jit.
    """
    to = -(-plan.n_out // block_o)
    tn = -(-plan.n_in // block_n)
    n_ctrl = plan.idx.shape[0]
    ctrl_tile = jnp.arange(n_ctrl, dtype=jnp.int32)
    if plan.mode == GATHER:
        valid = (plan.idx >= 0) & (plan.idx < plan.n_in)
        o_t = jnp.broadcast_to((ctrl_tile // block_o)[:, None], plan.idx.shape)
        n_t = jnp.clip(plan.idx, 0, plan.n_in - 1) // block_n
    else:
        valid = (plan.idx >= 0) & (plan.idx < plan.n_out)
        o_t = jnp.clip(plan.idx, 0, plan.n_out - 1) // block_o
        n_t = jnp.broadcast_to((ctrl_tile // block_n)[:, None], plan.idx.shape)
    occ = jnp.zeros((to, tn), jnp.int32)
    occ = occ.at[o_t.ravel(), n_t.ravel()].add(
        valid.ravel().astype(jnp.int32), mode="drop")
    return occ > 0


def _compile_schedule(plan: PermutePlan, block_o: int, block_n: int):
    """Jittable core of compile_plan (log-depth, branch-free)."""
    occ = _tile_occupancy(plan, block_o, block_n)
    to, tn = occ.shape
    flat = occ.reshape(-1)
    # Stable argsort on the negated flags: active pairs first, each group
    # in row-major (o-major) order — log-depth sorting network on device.
    order = jnp.argsort(jnp.logical_not(flat), stable=True).astype(jnp.int32)
    num = jnp.sum(flat.astype(jnp.int32))
    # Clamp the inactive tail onto the last active pair (or pair 0 for the
    # fully-empty plan) so BlockSpec index maps never go out of range.
    last = order[jnp.maximum(num - 1, 0)]
    fill = jnp.where(num > 0, last, 0)
    slot = jnp.arange(flat.shape[0], dtype=jnp.int32)
    sel = jnp.where(slot < num, order, fill)
    pair_o = sel // tn
    pair_n = sel % tn
    active = slot < num
    return occ, pair_o, pair_n, active, num


# Plan-identity LRU: repeated executions of the same concrete plan
# (serving, static routing geometry) fetch the schedule instead of
# recomputing it.  Keyed on the identities of the index *and* weight
# arrays — plans produced by the plan algebra (compose/transpose/batch)
# share idx arrays across differently-weighted variants, so both must
# key the entry.  The cache entry holds strong references to them, so the
# ids cannot be recycled while the entry is alive; the ``is`` checks make
# aliasing impossible.  The plan algebra memoises its own constructions
# (plan_algebra._memo) so a recomposed plan arrives here with the same
# array identities and hits.
#
# Static plans (crypto permutation layers, any plan whose control is a
# program constant registered in a ``core.static_registry``) bypass the
# LRU via ``compile_plan(..., pin=True)``: their schedules live in
# ``_PINNED_COMPILE``, are checked first on lookup, and are never
# evicted — transient traffic (serving routing churn) cannot push a
# fixed-latency plan's schedule out from under it.
_COMPILE_CACHE: "OrderedDict[tuple, CompiledPlan]" = OrderedDict()
_COMPILE_CACHE_CAPACITY = 64
_COMPILE_CACHE_STATS = {"hits": 0, "misses": 0}
_PINNED_COMPILE: "dict[tuple, CompiledPlan]" = {}


def compile_cache_info() -> dict:
    return dict(_COMPILE_CACHE_STATS, size=len(_COMPILE_CACHE),
                capacity=_COMPILE_CACHE_CAPACITY,
                pinned=len(_PINNED_COMPILE))


# Cache occupancy as export-time gauges (read lazily at metrics dump).
_obs.metrics.gauge_fn("compile_cache_size", lambda: len(_COMPILE_CACHE))
_obs.metrics.gauge_fn("compile_cache_pinned", lambda: len(_PINNED_COMPILE))


def _schedule_parts(compiled: "CompiledPlan") -> tuple:
    """The digest-relevant content of a cached schedule: everything the
    sparse kernel's launch geometry and tile routing are derived from.
    The embedded plan arrays are deliberately excluded — they are the
    *source* the schedule would be recompiled from, and are covered by
    the registry fingerprint / drift checks instead."""
    return (compiled.block_o, compiled.block_n, compiled.n_o_tiles,
            compiled.n_n_tiles, compiled.occupancy, compiled.pair_o,
            compiled.pair_n, compiled.active,
            compiled.num_active if isinstance(compiled.num_active, int)
            else None)


def clear_compile_cache() -> None:
    for key in list(_COMPILE_CACHE):
        _integrity.SCHEDULE_GUARD.drop(key)
    for key in list(_PINNED_COMPILE):
        _integrity.SCHEDULE_GUARD.drop(key)
    _COMPILE_CACHE.clear()
    _PINNED_COMPILE.clear()
    _COMPILE_CACHE_STATS.update(hits=0, misses=0)


def unpin_plan(plan: "PermutePlan") -> int:
    """Drop every pinned compiled schedule built from this plan's arrays.

    The quarantine path (``core.resilience``): a drifted static plan's
    pinned schedule must not survive eviction from its registry, or the
    next registration would resurrect the poisoned schedule via the
    identity-keyed pinned cache.  Returns the number of entries removed.
    """
    removed = 0
    for key, compiled in list(_PINNED_COMPILE.items()):
        if (compiled.plan.idx is plan.idx
                and compiled.plan.weights is plan.weights):
            del _PINNED_COMPILE[key]
            _integrity.SCHEDULE_GUARD.drop(key)
            removed += 1
    return removed


def is_cacheable(*arrays) -> bool:
    """No live trace, and every operand (``None`` allowed) concrete.

    The condition for *storing* into a cross-call cache keyed on these
    arrays, and for branching on their values on the host.  The trace-state check matters: under omnistaging, jnp ops
    run inside a jit/vmap/grad trace are staged and return tracers even
    when every operand is concrete, so a schedule compiled there is
    trace-local — caching it (or calling ``int()`` on its count) would
    leak tracers out of the trace.  ``ensure_compile_time_eval`` counts
    as no trace: its ops evaluate eagerly.  Cache *lookups* for concrete
    plans are still allowed under a trace (see compile_plan): a stored
    schedule is concrete and folds into the trace as constants.
    """
    return jax.core.trace_ctx.is_top_level() and all(
        a is None or not isinstance(a, jax.core.Tracer) for a in arrays)


def _is_concrete_array(x) -> bool:
    """Concrete array, regardless of trace state (cache-lookup eligible)."""
    return x is not None and not isinstance(x, jax.core.Tracer)


def compile_plan(plan: PermutePlan, *, block_o: int = 128,
                 block_n: int = 128, pin: bool = False) -> CompiledPlan:
    """Compile a plan's active-tile schedule for a given blocking.

    Concrete plans (outside jit) produce a *static* ``num_active`` — the
    sparse kernel then launches a grid of exactly the occupied pairs — and
    are memoised in an LRU keyed on the index array's identity.  Traced
    plans compile inline (the schedule ops are jittable) with a traced
    count; the kernel skips inactive pairs with ``pl.when`` guards instead
    of shrinking the grid.

    ``pin=True`` is the static-plan fast path: the schedule is stored in
    (or promoted to) the pinned cache, which is consulted before the LRU
    and never evicted — the contract backing ``core.static_registry``
    plans, whose schedules must stay resident for the fixed-latency
    guarantee to be checkable cheaply on every call.
    """
    # Lookup eligibility only needs concrete operands: an entry stored by
    # a previous out-of-trace compile is concrete, and returning it under
    # a live trace constant-folds the schedule into the trace — this is
    # what lets a pre-compiled static-routing plan keep its sparse
    # schedule inside a jitted step.
    keyable = _is_concrete_array(plan.idx) and (
        plan.weights is None or _is_concrete_array(plan.weights))
    key = None
    if keyable:
        # The semiring is part of the key: identical idx/weight arrays
        # under different semirings are different plans (the cached
        # CompiledPlan embeds its PermutePlan, semiring included), and
        # must never alias — in the LRU or the pinned static cache.
        key = (plan.mode, plan.n_in, plan.n_out, plan.semiring.name,
               block_o, block_n, id(plan.idx),
               id(plan.weights) if plan.weights is not None else None)
        hit = _PINNED_COMPILE.get(key)
        in_lru = False
        if hit is None:
            hit = _COMPILE_CACHE.get(key)
            in_lru = hit is not None
        if (hit is not None and hit.plan.idx is plan.idx
                and hit.plan.weights is plan.weights
                and hit.plan.semiring is plan.semiring):
            # Sampled digest check of the cached schedule content; a
            # mismatch evicts the entry and raises IntegrityError (the
            # executor retries, which recompiles from the plan arrays).
            _integrity.SCHEDULE_GUARD.verify(
                key, lambda: _schedule_parts(hit),
                evict=lambda: (_PINNED_COMPILE.pop(key, None),
                               _COMPILE_CACHE.pop(key, None)))
            _COMPILE_CACHE_STATS["hits"] += 1
            if in_lru:
                if pin:  # promote: from now on immune to LRU churn
                    del _COMPILE_CACHE[key]
                    _PINNED_COMPILE[key] = hit
                else:
                    _COMPILE_CACHE.move_to_end(key)
            return hit
    _COMPILE_CACHE_STATS["misses"] += 1

    with _obs.span("compile_plan", mode=plan.mode, n_out=plan.n_out,
                   n_in=plan.n_in, block_o=block_o, block_n=block_n,
                   pin=pin):
        occ, pair_o, pair_n, active, num = _compile_schedule(
            plan, block_o, block_n)
    to = -(-plan.n_out // block_o)
    tn = -(-plan.n_in // block_n)
    # Storing (and the int() demotion) additionally require a clean trace
    # state — under omnistaging the schedule arrays above are tracers
    # inside a jit trace even for concrete plans.
    cacheable = keyable and is_cacheable()
    num_active: Union[int, Array] = num
    if cacheable:
        num_active = int(num)
    compiled = CompiledPlan(plan, block_o, block_n, to, tn, occ,
                            pair_o, pair_n, active, num_active)
    if cacheable:
        _integrity.SCHEDULE_GUARD.seal(key, _schedule_parts(compiled))
        if pin:
            _PINNED_COMPILE[key] = compiled
        else:
            _COMPILE_CACHE[key] = compiled
            while len(_COMPILE_CACHE) > _COMPILE_CACHE_CAPACITY:
                evicted_key, _ = _COMPILE_CACHE.popitem(last=False)
                _integrity.SCHEDULE_GUARD.drop(evicted_key)
    return compiled


# apply_plan invocation counters: the observable the plan algebra's
# "K-deep chain == one crossbar pass" guarantee is asserted against
# (core/telemetry.py aggregates it with the cache counters).  The total
# is additionally split by *resolved* backend ('auto' counts under the
# backend it picked): the plan-program megakernel's "passes avoided"
# claim is only measurable if einsum passes and Pallas-kernel passes are
# distinguishable — a megakernel launch must show up as zero of either.
_APPLY_CALLS = 0
_APPLY_CALLS_BY_BACKEND: "dict[str, int]" = {}
# Increments hold _COUNT_LOCK: the serving layer executes passes on a
# device-feed thread while its admission thread reads telemetry.
_COUNT_LOCK = threading.Lock()


def apply_call_count() -> int:
    with _COUNT_LOCK:
        return _APPLY_CALLS


def apply_calls_by_backend() -> dict:
    """Pass counts keyed by the backend that actually executed them."""
    with _COUNT_LOCK:
        return dict(_APPLY_CALLS_BY_BACKEND)


def reset_apply_call_count() -> None:
    global _APPLY_CALLS
    with _COUNT_LOCK:
        _APPLY_CALLS = 0
        _APPLY_CALLS_BY_BACKEND.clear()


def _canon_2d(x: Array) -> tuple[Array, tuple]:
    """Flatten trailing dims: (N, ...) -> (N, D)."""
    shp = x.shape
    if x.ndim == 1:
        return x[:, None], shp
    return x.reshape(shp[0], -1), shp


# Auto heuristic: below this occupied-tile fraction the tile-skipping
# kernel wins over dense contraction (measured by
# benchmarks/bench_sparse_crossbar.py; see BENCH_sparse_crossbar.json).
AUTO_SPARSE_DENSITY = 0.25
# Below this operator size the einsum path's fused iota-compare beats any
# kernel launch; a single 128x128 tile has nothing to skip.
AUTO_MIN_CELLS = 128 * 128


# Optional measured tuning table (core/tuning.py): when installed,
# backend='auto' prefers what the table has SEEN win for this plan
# geometry over the density prior below.  Module-level because the
# choice point is deep inside apply_plan; serving installs its table at
# engine start and persists it across processes.
_TUNING_TABLE = None
_VALID_AUTO_BACKENDS = frozenset({"einsum", "kernel", "sparse", "reference"})


def set_tuning_table(table) -> None:
    """Install (or clear, with None) the measured backend tuning table."""
    global _TUNING_TABLE
    _TUNING_TABLE = table


def get_tuning_table():
    return _TUNING_TABLE


def plan_geometry(plan: PermutePlan) -> tuple:
    """The tuning-table geometry key for a plan: everything that shapes
    backend-relative performance without looking at control values."""
    return (plan.mode, plan.n_out, plan.n_in, plan.k, plan.semiring.name)


def _choose_backend(plan: PermutePlan) -> str:
    """Measured-density heuristic behind ``backend='auto'``.

    Traced plans cannot be measured at trace time — they fall back to the
    dense einsum path, which is always available and shape-static.
    Concrete plans *inside* a jit trace can be measured only when a prior
    out-of-trace compile left a static schedule in the LRU (compile it
    before jitting to opt a static-routing plan into the sparse path);
    otherwise they too fall back to einsum.  Off TPU both Pallas paths
    run in interpret mode and lose to the fused einsum at every density
    (see BENCH_sparse_crossbar.json), so 'auto' only routes to a kernel
    on real TPU hardware; pass backend='sparse' explicitly to exercise
    the tile-skipping path elsewhere.
    """
    if not _is_concrete_array(plan.idx):
        return "einsum"
    if _TUNING_TABLE is not None:
        measured = _TUNING_TABLE.best("apply_plan", plan_geometry(plan))
        if measured in _VALID_AUTO_BACKENDS:
            return measured
    if jax.default_backend() != "tpu":
        return "einsum"
    if plan.n_out * plan.n_in <= AUTO_MIN_CELLS:
        return "einsum"
    compiled = compile_plan(plan)
    if not compiled.is_static:
        # In-trace compile with no cached schedule: density is a tracer.
        return "einsum"
    if compiled.num_active == 0 or compiled.density <= AUTO_SPARSE_DENSITY:
        return "sparse"
    # Dense regime: the Pallas kernel still avoids materialising the
    # operator in HBM.
    return "kernel"


def apply_plan(
    plan: PermutePlan,
    x: Array,
    *,
    merge: Array | None = None,
    backend: str = "einsum",
    out_mask: Array | None = None,
    interpret: bool | None = None,
) -> Array:
    """Execute the crossbar: ``out = P @ x`` with merge semantics.

    Args:
      plan:  the control information (gather or scatter form).
      x:     (n_in, ...) data; trailing dims are the payload ("element
             width" in the paper — arbitrarily wide here).
      merge: optional (n_out, ...) old-destination values; outputs not
             covered by the plan (and outputs masked off by ``out_mask``)
             take these (RVV undisturbed policy).  Default: zeros.
      backend: 'einsum' | 'kernel' | 'sparse' | 'auto' | 'reference'.
      out_mask: optional (n_out,) bool — the RVV ``v0`` mask: False rows
             keep merge values (mask applies to *destination* elements).
      interpret: Pallas interpret-mode override (kernel/sparse backends).
    Returns:
      (n_out, ...) permuted data.
    """
    global _APPLY_CALLS
    with _COUNT_LOCK:
        _APPLY_CALLS += 1
    x2, xshape = _canon_2d(x)
    out_trailing = xshape[1:]
    n_out = plan.n_out

    if merge is not None:
        merge2, _ = _canon_2d(merge)
    else:
        merge2 = None

    requested = backend
    if backend == "auto":
        backend = _choose_backend(plan)
    if backend in ("einsum", "kernel", "sparse", "reference"):
        with _COUNT_LOCK:
            _APPLY_CALLS_BY_BACKEND[backend] = (
                _APPLY_CALLS_BY_BACKEND.get(backend, 0) + 1)

    sr = plan.semiring
    if sr.integer_carrier and not (jnp.issubdtype(x2.dtype, jnp.integer)
                                   or x2.dtype == jnp.bool_):
        raise ValueError(
            f"semiring {sr.name!r} carries small integers; got payload "
            f"dtype {x2.dtype} — cast to an integer type first")

    # One coverage computation serves both the sparse backend's zero
    # pinning and the merge/mask logic (for scatter plans it materialises
    # an (n_in, k, n_out) hit tensor — not something to do twice, and
    # skipped entirely when nothing needs it).  The GF(2^k) matmul paths
    # pin zeros from the *lifted* plan's coverage inside _run_lifted.
    need_cov = ((backend == "sparse" and not sr.is_gf2k)
                or merge2 is not None or out_mask is not None)
    cov = coverage(plan) if need_cov else None

    with _obs.span("apply_plan", backend=backend, requested=requested,
                   mode=plan.mode, n_out=plan.n_out, n_in=plan.n_in,
                   semiring=sr.name):
        if backend == "reference":
            out2 = _apply_reference(plan, x2)
        elif sr.limbs and backend in ("einsum", "kernel", "sparse"):
            # Wide GF(2^width) (GHASH's GF(2^128)): elements ride as
            # trailing byte-limb axes, the pass executes as ONE lifted
            # GF(2) crossbar evaluation over width·n bit rows.
            out2 = _apply_gf2k_wide(plan, x2, backend, interpret)
        elif sr.is_gf2k and backend in ("einsum", "kernel", "sparse"):
            # GF(2^k)-weighted plans execute as their GF(2) bit lift on
            # the chosen backend: one crossbar evaluation over width·x
            # the rows.  The take lowering only substitutes for the
            # einsum backend — an explicitly requested Pallas backend
            # runs its kernel.
            fast = _take_fastpath(plan, x2) if backend == "einsum" else None
            out2 = fast if fast is not None else _apply_gf2k(
                plan, x2, backend, interpret)
        elif backend == "kernel":
            from repro.kernels import ops as _kops  # kernels optional
            out2 = _kops.crossbar_permute(plan, x2, interpret=interpret)
        elif backend == "sparse":
            from repro.kernels import ops as _kops
            out2 = _kops.crossbar_permute_sparse(plan, x2,
                                                 interpret=interpret)
            # The tile-skipping kernel never visits unoccupied output
            # tiles, so their rows hold whatever was in the buffer —
            # pin them to the exact zeros every other backend produces.
            # Redundant when merge is given: the merge select below
            # overwrites those rows anyway.
            if merge2 is None:
                out2 = jnp.where(cov[:, None], out2, 0)
        elif backend == "einsum":
            out2 = _apply_einsum(plan, x2)
        else:
            raise ValueError(f"unknown backend {backend!r}")

    if out_mask is not None:
        cov = cov & out_mask.astype(bool)
        # masked-off outputs must not expose routed data
        out2 = jnp.where(out_mask.astype(bool)[:, None], out2, 0)
    if merge2 is not None:
        out2 = jnp.where(cov[:, None], out2, merge2.astype(out2.dtype))
    # else uncovered rows are already exact zeros by construction

    out = out2.reshape((n_out,) + out_trailing) if out_trailing else out2[:, 0]
    return out.astype(x.dtype)


# Take-based einsum fast path: a concrete, unweighted, single-select
# gather plan is a pure row routing — ``jnp.take`` with DROP masking is
# semantically identical to the one-hot contraction (exact in every
# semiring, since each output receives at most one unscaled pick) and
# sidesteps the pathological XLA-CPU lowering of rank-1 integer
# contractions fed by elementwise producers (BENCH_crypto.json
# keccak_fuse D=1 vs D=8).  Module-level switch so the regression
# benchmark can measure both lowerings.
EINSUM_TAKE_FASTPATH = True


def _take_fastpath(plan: PermutePlan, x2: Array) -> Optional[Array]:
    """The take lowering, or None when the plan is not eligible."""
    if not (EINSUM_TAKE_FASTPATH and plan.mode == GATHER and plan.k == 1
            and plan.weights is None and _is_concrete_array(plan.idx)):
        return None
    src = plan.idx[:, 0]
    valid = (src >= 0) & (src < plan.n_in)
    picked = jnp.take(x2, jnp.clip(src, 0, plan.n_in - 1), axis=0)
    if plan.semiring.carrier_mask is not None:
        # Keep the lowerings value-identical even for payloads outside
        # the carrier range: the matmul/lift paths fold their single
        # pick into the field's carrier set, so the take path must too.
        picked = picked.astype(jnp.int32) & plan.semiring.carrier_mask
    return jnp.where(valid[:, None], picked, 0).astype(x2.dtype)


def _apply_einsum(plan: PermutePlan, x2: Array) -> Array:
    """Dense XLA path: one-hot build + MXU contraction.

    REAL: f32 (or int32) accumulation — numerically *exact* for
    unweighted plans (each output row sums at most k one-hot picks).
    GF2: the same integer contraction with a parity fold — a sum of
    0/1 AND-products reduced mod 2 IS the XOR accumulation.
    GF2_8 never reaches here; apply_plan routes it through the bit lift.
    """
    fast = _take_fastpath(plan, x2)
    if fast is not None:
        return fast
    sr = plan.semiring
    if jnp.issubdtype(x2.dtype, jnp.integer) or x2.dtype == jnp.bool_:
        p = build_onehot(plan, dtype=jnp.int32)
        out = jax.lax.dot(p, x2.astype(jnp.int32),
                          preferred_element_type=jnp.int32)
        if sr.mod2_fold:
            out = out & 1
        return out.astype(x2.dtype)
    # Float payloads only reach here for REAL plans: apply_plan rejects
    # them for every integer-carrier semiring up front.
    p = build_onehot(plan, dtype=x2.dtype)
    out = jax.lax.dot(p, x2, preferred_element_type=jnp.float32)
    return out.astype(x2.dtype)


# ---------------------------------------------------------------------------
# GF(2^8) execution: the GF(2) bit lift
# ---------------------------------------------------------------------------
#
# Multiplication by a constant is GF(2)-linear, so a GF2_8-weighted plan
# over n byte rows is *exactly* an unweighted GF2 plan over 8n bit rows:
# each select (o <- i, weight w) becomes, for output bit b, the selects
# {8i + j : bit b of w·2^j == 1} — up to 8 bit selects per byte select,
# DROP elsewhere.  The lifted plan runs on the ordinary 0/1-exact
# crossbar (any matmul backend, parity fold at emission); payloads are
# unpacked to LSB-first bit rows around the pass.  Lifts are memoised on
# the source plan's array identities so the lifted plan — and therefore
# its CompiledPlan schedule — stays cache-stable across calls.

_LIFT_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_LIFT_CACHE_CAPACITY = 32
_LIFT_STATS = {"hits": 0, "misses": 0}


def lift_cache_info() -> dict:
    return dict(_LIFT_STATS, size=len(_LIFT_CACHE),
                capacity=_LIFT_CACHE_CAPACITY)


def clear_lift_cache() -> None:
    for key in list(_LIFT_CACHE):
        _integrity.LIFT_GUARD.drop(key)
    _LIFT_CACHE.clear()
    _LIFT_STATS.update(hits=0, misses=0)


def lift_gf2_k(plan: PermutePlan) -> PermutePlan:
    """The GF(2) bit-level plan equivalent to a GF(2^width) plan.

    Generalises the GF(2^8) lift to every family width (4, 8, 16, ...
    128): each select ``(o <- i, weight w)`` becomes, for output bit
    ``b``, the selects ``{width·i + j : M_w[b, j] = 1}`` where ``M_w``
    is the constant's bit matrix, assembled from the 8-bit-tile table
    ``semiring.gf2k_tile_table`` — ``M_w[b, j] = XOR_t E[limb_t, b,
    j + 8t]`` — so the table stays 256 rows at any width.  Wide widths
    (limbed weights, GHASH's GF(2^128)) use the same assembly with the
    limbs read from the weights' trailing axis.

    The lift preserves the plan's mode: a scatter plan lifts to a
    scatter plan (input bit row ``width·i+j`` lands on the output bits
    ``{width·o+b : M_w[b,j]=1}``), NOT to its gather normal form —
    gather normalisation is only exact for output-injective scatters,
    while the lifted scatter accumulates colliding destinations exactly
    on every backend (XOR is per-bit parity).
    """
    sr = plan.semiring
    if not sr.is_gf2k:
        raise ValueError(f"lift_gf2_k needs a GF(2^k) plan (width >= 2), "
                         f"got {sr.name!r}")
    width = sr.width

    keyable = _is_concrete_array(plan.idx) and (
        plan.weights is None or _is_concrete_array(plan.weights))
    key = None
    if keyable:
        # The semiring name is part of the key: two plans sharing the
        # SAME idx/weight arrays under different widths (with_semiring
        # rebinds for free) must never collide on a lifted plan.
        key = (plan.mode, plan.n_in, plan.n_out, sr.name, id(plan.idx),
               id(plan.weights) if plan.weights is not None else None)
        hit = _LIFT_CACHE.get(key)
        if (hit is not None and hit[1] is plan.idx
                and hit[2] is plan.weights):
            # Sampled digest check of the lifted bit plan's arrays —
            # the key ids reference the *source* arrays, so a flipped
            # bit in the lifted idx keeps hitting this entry and must
            # be caught here, not by a key miss.
            lifted_hit = hit[0]
            _integrity.LIFT_GUARD.verify(
                key, lambda: (lifted_hit.idx, lifted_hit.weights),
                evict=lambda: _LIFT_CACHE.pop(key, None))
            _LIFT_CACHE.move_to_end(key)
            _LIFT_STATS["hits"] += 1
            return hit[0]
    _LIFT_STATS["misses"] += 1

    idx = plan.idx                                      # (n_ctrl, k)
    bound = plan.n_in if plan.mode == GATHER else plan.n_out
    valid = (idx >= 0) & (idx < bound)
    n_tiles = sr.limbs if sr.limbs else (width + 7) // 8
    if plan.weights is None:
        limbs = [jnp.full(idx.shape, 1 if t == 0 else 0, jnp.int32)
                 for t in range(n_tiles)]
    elif sr.limbs:
        w = plan.weights
        if w.ndim != 3 or w.shape[:2] != idx.shape \
                or w.shape[-1] != sr.limbs:
            raise ValueError(
                f"wide {sr.name} weights must be shaped "
                f"{idx.shape + (sr.limbs,)} (idx + limb axis), got "
                f"{w.shape}")
        limbs = [w[..., t].astype(jnp.int32) & 0xFF
                 for t in range(n_tiles)]
    else:
        w = plan.weights.astype(jnp.int32) & sr.carrier_mask
        limbs = [(w >> (8 * t)) & 0xFF for t in range(n_tiles)]
    table = jnp.asarray(sr_mod.gf2k_tile_table(width, sr.poly))
    m = None                                   # (n_ctrl, k, width b, width j)
    for t in range(n_tiles):
        mt = jnp.take(table, limbs[t], axis=0)[..., 8 * t: 8 * t + width]
        m = mt if m is None else m ^ mt
    keep = valid[:, :, None, None] & (m != 0)
    safe = jnp.clip(idx, 0, bound - 1)
    if plan.mode == GATHER:
        # out bit width·o+b selects in bits {width·i+j : M[b,j]=1}.
        src = (width * safe)[:, :, None, None] \
            + jnp.arange(width, dtype=jnp.int32)[None, None, None, :]
        bit_idx = jnp.where(keep, src, _t.DROP)         # (n_out, k, b, j)
        bit_idx = jnp.transpose(bit_idx, (0, 2, 1, 3)).reshape(
            width * plan.n_out, width * plan.k)
        lifted = gather_plan(bit_idx, width * plan.n_in, semiring=GF2)
    else:
        # in bit width·i+j lands on out bits {width·o+b : M[b,j]=1}.
        dst = (width * safe)[:, :, None, None] \
            + jnp.arange(width, dtype=jnp.int32)[None, None, :, None]
        bit_idx = jnp.where(keep, dst, _t.DROP)         # (n_in, k, b, j)
        bit_idx = jnp.transpose(bit_idx, (0, 3, 1, 2)).reshape(
            width * plan.n_in, width * plan.k)
        lifted = scatter_plan(bit_idx, width * plan.n_out, semiring=GF2)

    if keyable and is_cacheable():
        _integrity.LIFT_GUARD.seal(key, (lifted.idx, lifted.weights))
        _LIFT_CACHE[key] = (lifted, plan.idx, plan.weights)
        while len(_LIFT_CACHE) > _LIFT_CACHE_CAPACITY:
            evicted_key, _ = _LIFT_CACHE.popitem(last=False)
            _integrity.LIFT_GUARD.drop(evicted_key)
    return lifted


def lift_gf2_8(plan: PermutePlan) -> PermutePlan:
    """The original GF(2^8)-only entry point; now the width-8 instance
    of ``lift_gf2_k`` (same construction, same cached plans)."""
    if plan.semiring is not GF2_8:
        raise ValueError(f"lift_gf2_8 needs a GF2_8 plan, got "
                         f"{plan.semiring.name!r}")
    return lift_gf2_k(plan)


def _run_lifted(lifted: PermutePlan, bits: Array, backend: str,
                interpret) -> Array:
    """Execute a lifted GF(2) bit plan on the chosen matmul backend."""
    if backend == "einsum":
        return _apply_einsum(lifted, bits)
    if backend == "kernel":
        from repro.kernels import ops as _kops
        return _kops.crossbar_permute(lifted, bits, interpret=interpret)
    if backend == "sparse":
        from repro.kernels import ops as _kops
        out_bits = _kops.crossbar_permute_sparse(lifted, bits,
                                                 interpret=interpret)
        return jnp.where(coverage(lifted)[:, None], out_bits, 0)
    raise ValueError(f"no GF(2^k) path for backend {backend!r}")


def _apply_gf2k(plan: PermutePlan, x2: Array, backend: str,
                interpret) -> Array:
    """Scalar-carried GF(2^width): unpack elements to bit rows -> run
    the lifted GF2 plan -> pack back."""
    width = plan.semiring.width
    lifted = lift_gf2_k(plan)
    shifts = jnp.arange(width, dtype=jnp.int32)
    bits = ((x2.astype(jnp.int32)[:, None, :] >> shifts[None, :, None]) & 1)
    bits = bits.reshape(width * plan.n_in, x2.shape[1])
    out_bits = _run_lifted(lifted, bits, backend, interpret)
    out_bits = out_bits.astype(jnp.int32).reshape(plan.n_out, width, -1)
    out = jnp.sum(out_bits << shifts[None, :, None], axis=1)
    return out.astype(x2.dtype)


def _wide_unpack(x2: Array, n: int, limbs: int) -> Array:
    """(n, D·L) canonical payload -> (width·n, D) bit rows.

    The wide-payload convention: the trailing payload axis is the limb
    axis (length L, fastest-varying), so bit row ``width·i + 8r + b``
    is bit ``b`` of limb ``r`` of element ``i``.
    """
    d = x2.shape[1] // limbs
    x3 = x2.astype(jnp.int32).reshape(n, d, limbs)
    shifts = jnp.arange(8, dtype=jnp.int32)
    bits = ((jnp.transpose(x3, (0, 2, 1))[:, :, None, :]
             >> shifts[None, None, :, None]) & 1)       # (n, L, 8, D)
    return bits.reshape(8 * limbs * n, d)


def _wide_pack(bits: Array, n_out: int, limbs: int, dtype) -> Array:
    """(width·n_out, D) bit rows -> (n_out, D·L) canonical payload."""
    shifts = jnp.arange(8, dtype=jnp.int32)
    b4 = bits.astype(jnp.int32).reshape(n_out, limbs, 8, -1)
    packed = jnp.sum(b4 << shifts[None, None, :, None], axis=2)
    return jnp.transpose(packed, (0, 2, 1)).reshape(
        n_out, -1).astype(dtype)


def _apply_gf2k_wide(plan: PermutePlan, x2: Array, backend: str,
                     interpret) -> Array:
    """Wide (limbed) GF(2^width): elements ride as trailing byte-limb
    axes; one lifted-GF(2) crossbar evaluation over width·n bit rows."""
    sr = plan.semiring
    if x2.shape[1] % sr.limbs:
        raise ValueError(
            f"wide {sr.name} payloads need a trailing limb axis of "
            f"{sr.limbs}; flattened payload width {x2.shape[1]} is not "
            "divisible by it")
    bits = _wide_unpack(x2, plan.n_in, sr.limbs)
    out_bits = _run_lifted(lift_gf2_k(plan), bits, backend, interpret)
    return _wide_pack(out_bits, plan.n_out, sr.limbs, x2.dtype)


def _apply_gf2k_wide_reference(plan: PermutePlan, x2: Array) -> Array:
    """Direct limbed-arithmetic oracle for wide gather plans (no lift
    machinery involved); wide scatters run the lifted plan's reference
    path (per-bit parity scatter-add — itself lift-independent)."""
    sr = plan.semiring
    limbs = sr.limbs
    if plan.mode != GATHER:
        bits = _wide_unpack(x2, plan.n_in, limbs)
        out_bits = _apply_reference(lift_gf2_k(plan), bits)
        return _wide_pack(out_bits, plan.n_out, limbs, x2.dtype)
    d = x2.shape[1] // limbs
    x3 = x2.astype(jnp.int32).reshape(plan.n_in, d, limbs) & 0xFF
    acc = jnp.zeros((plan.n_out, d, limbs), jnp.int32)
    for j in range(plan.k):
        src = plan.idx[:, j]
        valid = (src >= 0) & (src < plan.n_in)
        vals = jnp.take(x3, jnp.clip(src, 0, plan.n_in - 1), axis=0)
        if plan.weights is None:
            prod = vals
        else:
            wj = plan.weights[:, j].astype(jnp.int32) & 0xFF  # (n_out, L)
            prod = sr.mul(wj[:, None, :], vals)
        acc = acc ^ jnp.where(valid[:, None, None], prod, 0)
    return acc.reshape(plan.n_out, -1).astype(x2.dtype)


def _apply_reference(plan: PermutePlan, x2: Array) -> Array:
    """jnp.take oracle — the 'separate datapath' semantics, for testing.

    Independent of the matmul/lift machinery on purpose: the finite-field
    paths here accumulate with direct semiring arithmetic (gather) or
    per-bit parity scatter-adds (scatter), so they differentially check
    the mod-2 folds and the GF2_8 bit lift used by the other backends.
    """
    k = plan.k
    w = plan.weights
    sr = plan.semiring
    if sr is REAL:
        if plan.mode == GATHER:
            acc = jnp.zeros((plan.n_out, x2.shape[1]), dtype=jnp.float32)
            for j in range(k):
                src = plan.idx[:, j]
                valid = (src >= 0) & (src < plan.n_in)
                vals = jnp.take(x2, jnp.clip(src, 0, plan.n_in - 1), axis=0)
                wj = 1.0 if w is None else w[:, j].astype(jnp.float32)[:, None]
                acc = acc + jnp.where(valid[:, None],
                                      vals.astype(jnp.float32) * wj, 0.0)
            return acc.astype(x2.dtype)
        acc = jnp.zeros((plan.n_out, x2.shape[1]), dtype=jnp.float32)
        for j in range(k):
            dest = plan.idx[:, j]
            valid = (dest >= 0) & (dest < plan.n_out)
            wj = 1.0 if w is None else w[:, j].astype(jnp.float32)[:, None]
            contrib = jnp.where(valid[:, None], x2.astype(jnp.float32) * wj,
                                0.0)
            acc = acc.at[jnp.clip(dest, 0, plan.n_out - 1)].add(
                contrib, mode="drop", unique_indices=False)
            # clip+where keeps OOB rows from landing anywhere real:
            # contributions for invalid dests were zeroed above.
        return acc.astype(x2.dtype)

    if sr.limbs:
        return _apply_gf2k_wide_reference(plan, x2)
    # Finite fields: XOR accumulation of semiring products.  Payloads
    # and weights are folded into the carrier up front so the oracle
    # agrees with the lift/matmul/take lowerings even for out-of-range
    # values (the same fold the bit decomposition applies implicitly).
    cmask = sr.carrier_mask
    xi = x2.astype(jnp.int32) & cmask
    if plan.mode == GATHER:
        acc = jnp.zeros((plan.n_out, x2.shape[1]), jnp.int32)
        for j in range(k):
            src = plan.idx[:, j]
            valid = (src >= 0) & (src < plan.n_in)
            vals = jnp.take(xi, jnp.clip(src, 0, plan.n_in - 1), axis=0)
            wj = (jnp.ones((plan.n_out, 1), jnp.int32) if w is None
                  else w[:, j].astype(jnp.int32)[:, None] & cmask)
            acc = acc ^ jnp.where(valid[:, None], sr.mul(wj, vals), 0)
        return acc.astype(x2.dtype)
    # Scatter: XOR has no native scatter op, but XOR accumulation is
    # per-bit parity — scatter-add each contribution's bit planes, fold
    # mod 2, repack.  Exact for arbitrary (non-injective) scatters.
    nbits = max(sr.width, 1)
    shifts = jnp.arange(nbits, dtype=jnp.int32)
    acc = jnp.zeros((plan.n_out, x2.shape[1], nbits), jnp.int32)
    for j in range(k):
        dest = plan.idx[:, j]
        valid = (dest >= 0) & (dest < plan.n_out)
        wj = (jnp.ones((plan.n_in, 1), jnp.int32) if w is None
              else w[:, j].astype(jnp.int32)[:, None] & cmask)
        contrib = jnp.where(valid[:, None], sr.mul(wj, xi), 0)
        bitplanes = (contrib[:, :, None] >> shifts) & 1
        acc = acc.at[jnp.clip(dest, 0, plan.n_out - 1)].add(
            bitplanes, mode="drop", unique_indices=False)
    out = jnp.sum((acc & 1) << shifts, axis=-1)
    return out.astype(x2.dtype)


# ---------------------------------------------------------------------------
# Plan constructors for the three RVV instruction classes (Sec. II-A)
# ---------------------------------------------------------------------------

def vrgather_plan(src_idx: Array, n_in: int) -> PermutePlan:
    """Output-driven: per-output source indices straight to the crossbar."""
    return gather_plan(src_idx, n_in)


def vcompress_plan(mask: Array) -> PermutePlan:
    """Input-driven: mask bits -> bijective destinations -> crossbar."""
    dest = _t.compress_destinations(mask)
    n = mask.shape[-1]
    return scatter_plan(dest, n)


def vslide_plan(n: int, offset, *, up: bool) -> PermutePlan:
    """Input-driven, degenerate transform: index +- offset (no prefix sums)."""
    dest = _t.slide_destinations(n, offset, up=up)
    return scatter_plan(dest, n)
