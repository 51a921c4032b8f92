"""Plan algebra: compose, transpose, and batch permutations.

The paper's central object — the one-hot crossbar operator — is closed
under three algebraic operations, and all three are computable on
*control information alone* (int index arithmetic, no payload movement):

* **composition**  ``compose(p2, p1)``: applying ``p1`` then ``p2`` is the
  operator product ``P2 @ P1``, itself a (weighted, partial) permutation.
  A K-deep chain of ``vrgather``/``vslide``/``vcompress``/``vexpand``
  therefore collapses to ONE crossbar evaluation — one HBM round-trip of
  the payload instead of K.  The product is taken over the operands'
  weight semiring (``core.semiring``): path weights fold with its
  ``mul``, composed selects accumulate with its ``add`` at apply time,
  so the same compose fuses MoE gate scaling (REAL) and AES
  ShiftRows∘MixColumns (GF(2^8)) alike; unweighted pure-routing plans
  are semiring-neutral and adopt the other operand's algebra.
* **transposition** ``transpose(p)``: the gather↔scatter duality of
  Sec. III-B.2 (vertical one-hots re-read as horizontal one-hots).  MoE
  combine is *derived* from dispatch this way rather than rebuilt.
* **direct sum** ``block_diag(plans)`` / ``batch(plan, b)``: a batch of
  per-row plans becomes one block-diagonal plan on the flattened axis.
  Its tile occupancy is 1/B, so the sparse backend (PR 1) skips the
  off-diagonal tiles for free — one crossbar pass replaces B.

Composition works in **gather-normal form**: every plan is first rewritten
as an output-driven gather (``to_gather``), then indices chain by lookup
and per-select weights multiply.  Scatter plans normalise exactly when
they are *output-injective* (at most one valid select lands on each
destination) — true by construction for every plan the control transforms
emit: compress destinations are bijective (Sec. III-B.1), slides are
injective, and MoE dispatch assigns unique buffer slots.

``PlanExpr`` is the lazy front-end: ``lazy(x)`` in ``core/permute.py``
wraps a payload, the RVV ops append symbolic nodes instead of executing,
and ``.apply()`` lowers the whole chain — after algebraic simplification
(slide∘slide = summed-offset slide, gather-of-iota elimination, weight
folding) — to exactly one ``apply_plan`` call.

Plans built from concrete (non-traced) control are memoised in an LRU
keyed on the identities of their input arrays, so repeated construction
(serving decode steps, static routing) returns the *same* ``PermutePlan``
object and the downstream ``CompiledPlan`` schedule cache hits as well.
Cache counters are exposed via ``core/telemetry.py``.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import crossbar as xb
from repro.core import semiring as sr_mod
from repro.core import transform as _t
from repro.core.semiring import REAL, Semiring

Array = jax.Array

DROP = _t.DROP


def _join(p2: xb.PermutePlan, p1: xb.PermutePlan) -> Semiring:
    """The semiring two plans combine under (see ``semiring.join``)."""
    return sr_mod.join(p2.semiring, p1.semiring,
                       neutral1=p2.neutral_semiring,
                       neutral2=p1.neutral_semiring)


# ---------------------------------------------------------------------------
# Plan-construction memo: stable identity for composed/batched plans
# ---------------------------------------------------------------------------
# compose()/batch()/block_diag() build fresh idx arrays; without memoisation
# every serving step would re-derive them and the CompiledPlan LRU (keyed on
# index-array identity) would never hit.  The memo holds strong references
# to the *input* arrays of each construction, so their ids cannot be
# recycled while the entry lives; an ``is`` check per operand makes
# aliasing impossible.

_PLAN_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_PLAN_CACHE_CAPACITY = 128
_PLAN_CACHE_STATS = {"hits": 0, "misses": 0}


def plan_cache_info() -> dict:
    return dict(_PLAN_CACHE_STATS, size=len(_PLAN_CACHE),
                capacity=_PLAN_CACHE_CAPACITY)


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()
    _PLAN_CACHE_STATS.update(hits=0, misses=0)


def _memo(op: str, operands: tuple, static: tuple, build):
    """Memoised plan construction keyed on operand identity + static args.

    ``operands`` are the arrays whose identity keys the entry (None allowed);
    traced operands bypass the cache entirely.
    """
    if not xb.is_cacheable(*operands):
        _PLAN_CACHE_STATS["misses"] += 1
        return build()
    key = (op, static, tuple(id(a) for a in operands))
    hit = _PLAN_CACHE.get(key)
    if hit is not None and all(a is b for a, b in zip(hit[1], operands)):
        _PLAN_CACHE.move_to_end(key)
        _PLAN_CACHE_STATS["hits"] += 1
        return hit[0]
    _PLAN_CACHE_STATS["misses"] += 1
    plan = build()
    _PLAN_CACHE[key] = (plan, operands)
    while len(_PLAN_CACHE) > _PLAN_CACHE_CAPACITY:
        _PLAN_CACHE.popitem(last=False)
    return plan


# ---------------------------------------------------------------------------
# Normal forms and elementary rewrites
# ---------------------------------------------------------------------------

def to_gather(plan: xb.PermutePlan) -> xb.PermutePlan:
    """Rewrite a plan in gather-normal form (per-output sources).

    Gather plans pass through unchanged.  Scatter plans are transposed on
    control information only — the software form of the paper's wire
    reshuffling (Sec. III-B.2): one O(N·K) scatter-add per field, no
    payload touched.  Exact when the scatter plan is output-injective
    (<=1 valid select per destination) — the invariant every
    ``core/transform.py`` product satisfies; outputs nothing routes to
    become DROP rows, reproducing the SAD all-zeros decode.
    """
    if plan.mode == xb.GATHER:
        return plan

    def build():
        idx, n_out = plan.idx, plan.n_out
        valid = (idx >= 0) & (idx < n_out)
        safe = jnp.clip(idx, 0, n_out - 1)
        n_in, k = idx.shape
        src_of = jnp.broadcast_to(
            jnp.arange(n_in, dtype=jnp.int32)[:, None], idx.shape)
        hits = jnp.zeros((n_out,), jnp.int32).at[safe.ravel()].add(
            valid.ravel().astype(jnp.int32), mode="drop")
        src = jnp.zeros((n_out,), jnp.int32).at[safe.ravel()].add(
            jnp.where(valid, src_of, 0).ravel(), mode="drop")
        src = jnp.where(hits > 0, src, DROP).astype(jnp.int32)
        weights = None
        if plan.weights is not None:
            # Output-injectivity means at most one valid contribution per
            # destination, so the scatter-add never actually combines two
            # weights — exact in every semiring (0 is each one's additive
            # identity).
            w = jnp.zeros((n_out,), plan.weights.dtype).at[safe.ravel()].add(
                jnp.where(valid, plan.weights, 0).ravel(), mode="drop")
            weights = w[:, None]
        return xb.gather_plan(src, plan.n_in, weights=weights,
                              semiring=plan.semiring)

    return _memo("to_gather", (plan.idx, plan.weights),
                 (plan.n_in, plan.n_out, plan.semiring.name), build)


def with_weights(plan: xb.PermutePlan, weights: Array, *,
                 semiring: Optional[Semiring] = None) -> xb.PermutePlan:
    """Same routing, new per-select weights (broadcast to the idx shape).

    ``semiring`` rebinds the algebra alongside the weights (e.g. byte
    coefficients over GF2_8); default keeps the plan's.
    """
    w = jnp.asarray(weights)
    if w.ndim == 1:
        w = w[:, None]
    return xb.PermutePlan(plan.mode, plan.idx, plan.n_in, plan.n_out, w,
                          semiring or plan.semiring)


def with_semiring(plan: xb.PermutePlan, semiring: Semiring) -> xb.PermutePlan:
    """Same routing and weights, different accumulation algebra."""
    return xb.PermutePlan(plan.mode, plan.idx, plan.n_in, plan.n_out,
                          plan.weights, semiring)


def transpose(plan: xb.PermutePlan) -> xb.PermutePlan:
    """Gather↔scatter duality: the inverse-direction crossbar.

    Alias of ``crossbar.transpose_plan`` — re-exported here so the algebra
    is closed in one namespace.  Zero-cost: the idx array is shared, so
    the CompiledPlan cache keys the transposed plan off the same identity.
    """
    return xb.transpose_plan(plan)


def identity_plan(n: int) -> xb.PermutePlan:
    """The unit of composition: gather-of-iota."""
    return xb.gather_plan(jnp.arange(n, dtype=jnp.int32), n)


def is_identity(plan: xb.PermutePlan) -> bool:
    """True iff the plan is provably (concretely) the identity."""
    if plan.n_in != plan.n_out or plan.k != 1:
        return False
    if not xb.is_cacheable(plan.idx, plan.weights):
        return False
    if plan.weights is not None and not bool(
            (np.asarray(plan.weights) == 1.0).all()):
        return False
    g = to_gather(plan)
    if not xb.is_cacheable(g.idx):
        return False
    return bool(np.array_equal(np.asarray(g.idx[:, 0]),
                               np.arange(plan.n_in)))


# ---------------------------------------------------------------------------
# Composition
# ---------------------------------------------------------------------------

def compose(p2: xb.PermutePlan, p1: xb.PermutePlan) -> xb.PermutePlan:
    """Operator product: ``apply(compose(p2, p1), x) == apply(p2, apply(p1, x))``.

    Both plans are gather-normalised; composed selects chain by index
    lookup (``idx[o, (a, b)] = g1.idx[g2.idx[o, a], b]``) and weights
    multiply.  DROP propagates: an invalid outer select, or an inner DROP
    reached through it, yields a DROP select — exactly the zero the
    sequential pipeline would have routed (uncovered intermediates read
    as 0 under merge-free apply).  The result has ``k = k2 * k1`` selects;
    weight folding keeps ``weights=None`` when both operands are unweighted.
    """
    if p1.n_out != p2.n_in:
        raise ValueError(
            f"compose: p1 produces {p1.n_out} elements but p2 consumes "
            f"{p2.n_in}")
    sr = _join(p2, p1)  # raises early on a genuine algebra mismatch

    def build():
        # Algebraic fast path: the identity is the unit.  Checked inside
        # the memoised builder because is_identity reads index values off
        # device — a blocking sync repeated calls must not pay.
        if is_identity(p1):
            return p2 if p2.semiring is sr else with_semiring(p2, sr)
        if is_identity(p2):
            return p1 if p1.semiring is sr else with_semiring(p1, sr)
        g2 = to_gather(p2)
        g1 = to_gather(p1)
        mid = p1.n_out
        outer_valid = (g2.idx >= 0) & (g2.idx < mid)          # (n_out, k2)
        safe = jnp.clip(g2.idx, 0, mid - 1)
        inner = jnp.take(g1.idx, safe, axis=0)                # (n_out, k2, k1)
        idx = jnp.where(outer_valid[:, :, None], inner, DROP)
        idx = idx.reshape(p2.n_out, g2.k * g1.k)
        weights = None
        if g2.weights is not None or g1.weights is not None:
            # Path weights fold with the joined semiring's product; the
            # k2*k1 composed selects accumulate with its add at apply
            # time, so compose(p2,p1) distributes exactly like P2 @ P1
            # over the semiring.
            wdt = sr.weight_dtype
            w2 = (sr.ones(tuple(g2.idx.shape)) if g2.weights is None
                  else g2.weights.astype(wdt))
            w1 = (sr.ones((mid, g1.k)) if g1.weights is None
                  else g1.weights.astype(wdt))
            # Wide fields carry a trailing limb axis through the fold:
            # broadcasting aligns it, the reshape preserves it.
            w = sr.mul(w2[:, :, None], jnp.take(w1, safe, axis=0))
            weights = w.reshape((p2.n_out, g2.k * g1.k) + w.shape[3:])
        return xb.gather_plan(idx, p1.n_in, weights=weights, semiring=sr)

    return _memo("compose", (p2.idx, p2.weights, p1.idx, p1.weights),
                 (p2.mode, p2.n_in, p2.n_out, p2.semiring.name,
                  p1.mode, p1.n_in, p1.n_out, p1.semiring.name),
                 build)


def compose_all(plans: Sequence[xb.PermutePlan], *,
                n: Optional[int] = None) -> xb.PermutePlan:
    """Fold a pipeline [first, ..., last] into one plan.

    The empty pipeline is the unit of composition, but its crossbar
    length cannot be inferred from zero operands: pass ``n`` to get
    ``identity_plan(n)`` back, otherwise the empty case raises a
    ``ValueError`` (it would previously fall through to an undefined
    reduction).  When ``n`` is given alongside a non-empty pipeline it is
    validated against the first plan's input length.
    """
    plans = list(plans)
    if not plans:
        if n is None:
            raise ValueError(
                "compose_all: empty pipeline has no inferable length; "
                "pass n=<crossbar length> to get the identity plan")
        return identity_plan(n)
    if n is not None and plans[0].n_in != n:
        raise ValueError(
            f"compose_all: first plan consumes {plans[0].n_in} elements "
            f"but n={n} was declared")
    fused = plans[0]
    for p in plans[1:]:
        fused = compose(p, fused)
    return fused


def compact_selects(plan: xb.PermutePlan) -> xb.PermutePlan:
    """Pack each row's valid selects to the front; trim all-DROP columns.

    Lifted GF(2^k) plans spread their selects over ``width · k`` slots
    with DROP wherever the constant's bit matrix has a zero — typically
    most of them (a MixColumns bit row keeps ~7 of 32 slots; a GHASH
    multiply-by-H row ~64 of 128).  Select order within a row is free
    (semiring addition commutes), so stable-sorting valid selects to
    the front and cutting the all-DROP tail shrinks ``k`` to the true
    maximum row weight — which is exactly what the megakernel's gather
    loop and the stacked plan tables pay for.  Traced plans pass
    through unchanged (compaction is value-dependent).
    """
    g = to_gather(plan)
    if not xb.is_cacheable(g.idx, g.weights):
        return g

    def build():
        idx = np.asarray(g.idx)
        valid = (idx >= 0) & (idx < g.n_in)
        order = np.argsort(~valid, axis=1, kind="stable")
        idx2 = np.where(np.take_along_axis(valid, order, axis=1),
                        np.take_along_axis(idx, order, axis=1), DROP)
        k_new = max(1, int(valid.sum(axis=1).max(initial=0)))
        idx2 = idx2[:, :k_new]
        weights = None
        if g.weights is not None:
            w = np.asarray(g.weights)
            ord_w = order[..., None] if w.ndim == 3 else order
            weights = jnp.asarray(
                np.take_along_axis(w, ord_w, axis=1)[:, :k_new])
        return xb.gather_plan(jnp.asarray(idx2, jnp.int32), g.n_in,
                              weights=weights, semiring=g.semiring)

    return _memo("compact_selects", (g.idx, g.weights),
                 (g.n_in, g.n_out, g.semiring.name), build)


# ---------------------------------------------------------------------------
# Direct sums: block-diagonal batching
# ---------------------------------------------------------------------------

def block_diag(plans: Sequence[xb.PermutePlan]) -> xb.PermutePlan:
    """Direct sum of plans: one crossbar over the concatenated axes.

    Row b's selects are offset into its own input segment; everything off
    the diagonal is structurally zero, so the occupancy map compiled by
    ``compile_plan`` is block-diagonal and the sparse backend skips the
    off-diagonal tiles entirely.
    """
    plans = list(plans)
    if not plans:
        # No well-defined geometry exists for a 0-plan direct sum (a
        # (0, 0) plan breaks every downstream shape contract), so this is
        # an explicit error rather than whatever an empty reduction would
        # produce.  The composition unit lives in compose_all(n=...).
        raise ValueError(
            "block_diag: empty plan list has no well-defined geometry; "
            "the direct sum needs at least one plan")
    gs = [to_gather(p) for p in plans]
    kmax = max(g.k for g in gs)
    sr, neutral_so_far = REAL, True
    for g in gs:
        sr = sr_mod.join(sr, g.semiring, neutral1=neutral_so_far,
                         neutral2=g.neutral_semiring)
        neutral_so_far = neutral_so_far and g.neutral_semiring

    def build():
        rows, ws = [], []
        weighted = any(g.weights is not None for g in gs)
        off = 0
        for g in gs:
            valid = (g.idx >= 0) & (g.idx < g.n_in)
            idx = jnp.where(valid, g.idx + off, DROP)
            if g.k < kmax:
                idx = jnp.pad(idx, ((0, 0), (0, kmax - g.k)),
                              constant_values=DROP)
            rows.append(idx)
            if weighted:
                w = (sr.ones(g.idx.shape) if g.weights is None
                     else g.weights.astype(sr.weight_dtype))
                if g.k < kmax:
                    # Padded selects are DROP; their weight value is inert.
                    w = jnp.pad(w, ((0, 0), (0, kmax - g.k)))
                ws.append(w)
            off += g.n_in
        idx = jnp.concatenate(rows, axis=0)
        weights = jnp.concatenate(ws, axis=0) if weighted else None
        return xb.gather_plan(idx, off, weights=weights, semiring=sr)

    operands = tuple(g.idx for g in gs) + tuple(g.weights for g in gs)
    static = tuple((g.n_in, g.n_out, g.semiring.name) for g in gs)
    return _memo("block_diag", operands, static, build)


def batch(plan: xb.PermutePlan, b: int) -> xb.PermutePlan:
    """``block_diag([plan] * b)``, vectorised (no Python loop over rows)."""
    g = to_gather(plan)

    def build():
        valid = (g.idx >= 0) & (g.idx < g.n_in)
        offs = (jnp.arange(b, dtype=jnp.int32) * g.n_in)[:, None, None]
        idx = jnp.where(valid[None], g.idx[None] + offs, DROP)
        idx = idx.reshape(b * g.n_out, g.k)
        weights = None
        if g.weights is not None:
            weights = jnp.tile(g.weights, (b, 1))
        return xb.gather_plan(idx, b * g.n_in, weights=weights,
                              semiring=g.semiring)

    return _memo("batch", (g.idx, g.weights),
                 (b, g.n_in, g.n_out, g.semiring.name), build)


def shard_restrict(plan: xb.PermutePlan, out_window: tuple[int, int],
                   in_window: tuple[int, int]) -> xb.PermutePlan:
    """Restrict a plan to an (output-window, input-window) sub-operator.

    ``out_window``/``in_window`` are ``(start, size)`` half-open ranges on
    the gather-normal axes.  The result is the ``size_out x size_in``
    block of the operator matrix in *local* coordinates: selects whose
    source falls outside the input window become DROP (their contribution
    belongs to a different block), surviving selects are rebased by the
    window start, and weights ride along unchanged.  Summing the blocks
    of a full tiling over the plan's semiring reconstitutes the original
    operator — the identity mesh-sharded execution relies on.
    """
    g = to_gather(plan)
    o0, o_sz = out_window
    i0, i_sz = in_window
    if o0 < 0 or o_sz <= 0 or o0 + o_sz > g.n_out:
        raise ValueError(
            f"shard_restrict: output window ({o0}, {o_sz}) out of range "
            f"for n_out={g.n_out}")
    if i0 < 0 or i_sz <= 0 or i0 + i_sz > g.n_in:
        raise ValueError(
            f"shard_restrict: input window ({i0}, {i_sz}) out of range "
            f"for n_in={g.n_in}")

    def build():
        idx = g.idx[o0:o0 + o_sz]
        inside = (idx >= i0) & (idx < i0 + i_sz)
        local = jnp.where(inside, idx - i0, DROP).astype(jnp.int32)
        weights = None
        if g.weights is not None:
            weights = g.weights[o0:o0 + o_sz]
        return xb.gather_plan(local, i_sz, weights=weights,
                              semiring=g.semiring)

    return _memo("shard_restrict", (g.idx, g.weights),
                 (o0, o_sz, i0, i_sz, g.n_in, g.n_out, g.semiring.name),
                 build)


def batched_gather_plan(idx: Array, n_in: int, *,
                        weights: Array | None = None,
                        semiring: Semiring = REAL) -> xb.PermutePlan:
    """Distinct per-row gathers -> one block-diagonal plan.

    ``idx`` is (B, n_out) or (B, n_out, k), each row indexing its own
    ``n_in``-element segment; out-of-range entries DROP per row.
    """
    b, n_out = idx.shape[:2]
    k = idx.shape[2] if idx.ndim == 3 else 1

    def build():
        # ndim normalisation happens here, after the memo key is taken
        # from the caller's array — reshaping first would mint a fresh
        # identity per call and the memo could never hit.
        idx3 = idx if idx.ndim == 3 else idx[:, :, None]
        valid = (idx3 >= 0) & (idx3 < n_in)
        offs = (jnp.arange(b, dtype=jnp.int32) * n_in)[:, None, None]
        flat = jnp.where(valid, idx3.astype(jnp.int32) + offs, DROP)
        w = None if weights is None else weights.reshape(b * n_out, k)
        return xb.gather_plan(flat.reshape(b * n_out, k), b * n_in,
                              weights=w, semiring=semiring)

    return _memo("batched_gather", (idx, weights), (n_in, semiring.name),
                 build)


def batched_scatter_plan(dest: Array, n_out: int, *,
                         weights: Array | None = None,
                         semiring: Semiring = REAL) -> xb.PermutePlan:
    """Distinct per-row scatters -> one block-diagonal plan.

    ``dest`` is (B, n_in) or (B, n_in, k); row b's destinations land in
    output segment ``[b*n_out, (b+1)*n_out)``, OOB entries DROP per row.
    """
    b, n_in = dest.shape[:2]
    k = dest.shape[2] if dest.ndim == 3 else 1

    def build():
        # Normalise ndim inside the builder (see batched_gather_plan).
        dest3 = dest if dest.ndim == 3 else dest[:, :, None]
        valid = (dest3 >= 0) & (dest3 < n_out)
        offs = (jnp.arange(b, dtype=jnp.int32) * n_out)[:, None, None]
        flat = jnp.where(valid, dest3.astype(jnp.int32) + offs, DROP)
        w = None if weights is None else weights.reshape(b * n_in, k)
        return xb.scatter_plan(flat.reshape(b * n_in, k), b * n_out,
                               weights=w, semiring=semiring)

    return _memo("batched_scatter", (dest, weights), (n_out, semiring.name),
                 build)


# ---------------------------------------------------------------------------
# Lazy expression front-end
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LazyOp:
    """One symbolic permutation node in a PlanExpr chain.

    kind: 'gather' | 'compress' | 'expand' | 'slide' | 'plan'.
    n:    crossbar length the op consumes (filled in by PlanExpr.then).
    mask: gather/slide — the RVV v0 destination mask (False rows -> 0,
          folded into the plan as DROP selects); compress/expand — the
          element mask that *is* the control information.
    """

    kind: str
    n: int
    idx: Optional[Array] = None
    mask: Optional[Array] = None
    offset: Any = None
    up: bool = True
    tail: str = "zero"
    plan: Optional[xb.PermutePlan] = None

    @property
    def n_out(self) -> int:
        if self.kind == "gather":
            return self.idx.shape[0]
        if self.kind == "plan":
            return self.plan.n_out
        return self.n

    def lower(self) -> xb.PermutePlan:
        """Gather-normal PermutePlan with destination masking folded in."""
        if self.kind == "gather":
            plan = to_gather(xb.gather_plan(self.idx.astype(jnp.int32),
                                            self.n))
            out_mask = self.mask
        elif self.kind == "compress":
            plan = to_gather(xb.vcompress_plan(self.mask))
            if self.tail == "bijective":
                out_mask = None
            else:  # 'zero'
                k = _t.compress_keep_count(self.mask)
                out_mask = jnp.arange(self.n, dtype=jnp.int32) < k
        elif self.kind == "expand":
            plan = to_gather(xb.transpose_plan(xb.vcompress_plan(self.mask)))
            out_mask = self.mask
        elif self.kind == "slide":
            plan = to_gather(xb.vslide_plan(self.n, self.offset, up=self.up))
            out_mask = self.mask
        elif self.kind == "plan":
            plan = to_gather(self.plan)
            out_mask = self.mask
        else:
            raise ValueError(f"unknown lazy op kind {self.kind!r}")
        if out_mask is not None:
            # A masked-off destination under merge-free semantics is an
            # exact zero — the same thing a DROP select produces.
            keep = out_mask.astype(bool)[:, None]
            plan = xb.gather_plan(jnp.where(keep, plan.idx, DROP),
                                  plan.n_in, weights=plan.weights,
                                  semiring=plan.semiring)
        return plan


def _simplify_ops(ops: list) -> list:
    """Peephole rewrites on the symbolic chain before lowering.

    * slide∘slide with the *same direction* and no v0 masks folds into a
      single summed-offset slide (same-direction drops compose exactly:
      an element sliding out of the first hop is out of the sum too).
      Opposite directions do NOT fold — the intermediate boundary drops
      elements a net offset would keep — and are left for index
      composition, which handles them exactly.
    * gather-of-iota (concrete identity gather, unmasked) is eliminated.
    """
    out: list = []
    for op in ops:
        if (op.kind == "gather" and op.mask is None
                and op.idx.shape[0] == op.n
                and xb.is_cacheable(op.idx)
                and bool(np.array_equal(np.asarray(op.idx),
                                        np.arange(op.n)))):
            continue
        prev = out[-1] if out else None
        if (prev is not None and op.kind == "slide" and prev.kind == "slide"
                and op.up == prev.up and op.mask is None
                and prev.mask is None):
            out[-1] = dataclasses.replace(
                prev, offset=jnp.asarray(prev.offset, jnp.int32)
                + jnp.asarray(op.offset, jnp.int32))
            continue
        out.append(op)
    return out


class PlanExpr:
    """A payload plus a pending chain of symbolic permutation ops.

    Built by ``core.permute.lazy(x)``; the RVV ops in ``core/permute.py``
    recognise a PlanExpr input and append to the chain instead of
    executing.  ``apply()`` fuses the chain — simplification, then
    left-fold of ``compose`` — into ONE PermutePlan and makes exactly one
    ``apply_plan`` call regardless of chain depth.
    """

    def __init__(self, x: Array, ops: Sequence[LazyOp] = (),
                 group: int = 1, backend: Optional[str] = None):
        self.x = x
        self.ops = list(ops)
        self.group = group
        # Per-op backend requests are collected as the chain's default
        # execution backend ('einsum', the ops' default, is "no request").
        # Conflicting non-default requests are an error — a fused chain
        # runs on exactly one backend.
        self.backend = backend

    @property
    def _n0(self) -> int:
        n = self.x.shape[0]
        if n % self.group:
            raise ValueError(f"group {self.group} does not divide N={n}")
        return n // self.group

    @property
    def n_current(self) -> int:
        """Crossbar length the next op must consume."""
        return self.ops[-1].n_out if self.ops else self._n0

    def then(self, op: LazyOp, *, group: int = 1,
             backend: str = "einsum") -> "PlanExpr":
        if self.ops and group != self.group:
            raise ValueError(
                f"lazy chain grouped by {self.group} cannot take an op "
                f"with group={group}; evaluate first")
        hint = self.backend
        if backend != "einsum":
            if hint is not None and hint != backend:
                raise ValueError(
                    f"lazy chain already requested backend {hint!r}; a "
                    f"fused chain runs on one backend (got {backend!r})")
            hint = backend
        g = group if not self.ops else self.group
        expr = PlanExpr(self.x, self.ops, g, hint)
        op = dataclasses.replace(op, n=expr.n_current)
        if op.kind == "gather" and op.idx.ndim != 1:
            raise ValueError("lazy vrgather needs a 1-D index vector")
        expr.ops.append(op)
        return expr

    def plan(self) -> xb.PermutePlan:
        """The fused plan of the whole chain (identity if empty)."""
        ops = _simplify_ops(self.ops)
        if not ops:
            return identity_plan(self._n0)
        return compose_all([op.lower() for op in ops])

    def apply(self, *, backend: str | None = None,
              interpret: bool | None = None) -> Array:
        """Evaluate the chain with a single crossbar pass.

        ``backend`` defaults to the chain's collected per-op backend
        request (or 'einsum' when none was made); passing it explicitly
        overrides.
        """
        backend = backend or self.backend or "einsum"
        g = self.group
        shape = self.x.shape
        xg = self.x.reshape(shape[0] // g, -1) if g > 1 or self.x.ndim > 1 \
            else self.x
        plan = self.plan()
        out = xb.apply_plan(plan, xg, backend=backend, interpret=interpret)
        return out.reshape((plan.n_out * g,) + shape[1:])
