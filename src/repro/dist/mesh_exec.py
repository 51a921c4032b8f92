"""Mesh-sharded execution of plans and plan programs.

Everything below PR 6 runs a plan inside ONE device: the crossbar's
occupancy map says which (output-tile, input-tile) pairs carry traffic,
and the sparse backend skips the rest.  This module reads the *same*
occupancy map at mesh granularity: compile the plan with shard-sized
blocks and the (S, S) occupancy matrix IS the shard connectivity graph —
entry (d, s) says device d's output window reads device s's input
window.

Three regimes fall out, cheapest first:

* **lane-parallel** (``run_program_sharded``): plan *programs* route
  along the control axis and broadcast over payload columns (every
  PERMUTE/ROTLV/XOR step is elementwise in the payload lane), so
  splitting payload columns across devices needs NO collectives at all —
  the PR 5 sharded-SHA3 pattern, now available for every program.
* **block-local plans** (``is_lane_parallel``): a ``block_diag``/
  ``batch`` plan whose shard-blocked occupancy is diagonal executes as S
  independent local crossbars — ``apply_plan_sharded`` compiles
  collective-free.
* **genuinely cross-shard plans**: off-diagonal occupancy entries become
  a *collective schedule* — a greedy edge-colouring groups the required
  (src -> dst) block transfers into rounds of partial permutations, each
  round ONE ``jax.lax.ppermute``.  Rounds == max degree of the
  connectivity graph, so a shifted/block-sparse operator (MoE dispatch
  with locality, slides, butterfly stages) moves only the blocks that
  carry traffic, vs the naive all-gather baseline that always moves
  S - 1 blocks through every device (``apply_plan_sharded_naive``).

Control stays concrete host-side: per-device restricted plans are built
with ``plan_algebra.shard_restrict`` (so they hit the plan/compile
caches), stacked along a leading mesh axis, and shard_map slices each
device its own block — one trace serves all devices with
device-dependent control.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs as _obs
from repro.core import crossbar as xb
from repro.core import plan_algebra as pa
from repro.core import plan_program as pp
from repro.core import telemetry
from repro.core.semiring import GF2, REAL
from repro.dist import sharding as shd

Array = jax.Array


# ---------------------------------------------------------------------------
# Shard connectivity: the occupancy map at mesh granularity
# ---------------------------------------------------------------------------

def shard_connectivity(plan: xb.PermutePlan, n_shards: int) -> np.ndarray:
    """(S, S) bool: does shard d's output window read shard s's inputs?

    This is literally the plan's CompiledPlan occupancy under a
    shard-sized blocking — the same data structure the sparse backend
    tile-skips with, reused as the inter-device traffic matrix.
    Requires concrete control and shard-divisible geometry.
    """
    g = pa.to_gather(plan)
    if isinstance(g.idx, jax.core.Tracer):
        raise ValueError(
            "shard_connectivity: traced control has no concrete occupancy; "
            "mesh scheduling needs host-known plans")
    if n_shards <= 0:
        raise ValueError(f"shard_connectivity: n_shards={n_shards} must be "
                         "positive")
    if g.n_out % n_shards or g.n_in % n_shards:
        raise ValueError(
            f"shard_connectivity: geometry ({g.n_out} out, {g.n_in} in) "
            f"does not divide into {n_shards} shards")
    compiled = xb.compile_plan(g, block_o=g.n_out // n_shards,
                               block_n=g.n_in // n_shards)
    return np.asarray(compiled.occupancy)


def is_lane_parallel(plan: xb.PermutePlan, n_shards: int) -> bool:
    """True when the shard-blocked occupancy is (a subset of) diagonal —
    every device's outputs read only its own inputs, so sharded execution
    is collective-free."""
    conn = shard_connectivity(plan, n_shards)
    off = conn & ~np.eye(n_shards, dtype=bool)
    return not off.any()


def collective_schedule(conn: np.ndarray) -> list[list[tuple[int, int]]]:
    """Greedy edge-colouring of the off-diagonal traffic graph.

    Input: (S, S) bool connectivity, conn[dst, src].  Output: rounds of
    (src, dst) transfers where each round is a partial permutation (every
    device sends to at most one peer and receives from at most one peer),
    i.e. exactly one ``jax.lax.ppermute``.  Diagonal entries are local
    and never scheduled.  The greedy colouring needs at most
    2 * max_degree - 1 rounds and hits max_degree on the structured
    graphs plans produce (shifts, butterflies, block-banded MoE) — vs the
    all-gather baseline's fixed S - 1 full-ring rounds.
    """
    conn = np.asarray(conn, dtype=bool)
    s = conn.shape[0]
    if conn.shape != (s, s):
        raise ValueError(f"collective_schedule: connectivity must be "
                         f"square, got {conn.shape}")
    edges = [(src, dst) for dst in range(s) for src in range(s)
             if conn[dst, src] and src != dst]
    # Longest-queue-first over destinations keeps the colouring near the
    # degree bound: pick each round as a maximal matching.
    rounds: list[list[tuple[int, int]]] = []
    remaining = list(edges)
    while remaining:
        used_src: set[int] = set()
        used_dst: set[int] = set()
        this_round: list[tuple[int, int]] = []
        rest: list[tuple[int, int]] = []
        for src, dst in remaining:
            if src not in used_src and dst not in used_dst:
                this_round.append((src, dst))
                used_src.add(src)
                used_dst.add(dst)
            else:
                rest.append((src, dst))
        rounds.append(this_round)
        remaining = rest
    return rounds


def schedule_stats(conn: np.ndarray) -> dict:
    """Traffic accounting for a connectivity matrix: scheduled rounds and
    moved blocks vs the naive all-gather baseline."""
    conn = np.asarray(conn, dtype=bool)
    s = conn.shape[0]
    sched = collective_schedule(conn)
    off_edges = int(conn.sum()) - int(np.diag(conn).sum())
    return {
        "n_shards": s,
        "off_diag_edges": off_edges,
        "schedule_rounds": len(sched),
        "scheduled_block_transfers": sum(len(r) for r in sched),
        "naive_rounds": s - 1,
        "naive_block_transfers": s * (s - 1),
    }


# ---------------------------------------------------------------------------
# Sharded apply_plan
# ---------------------------------------------------------------------------

def _stack_restricted(plan: xb.PermutePlan, n_shards: int):
    """Per-device restricted controls, stacked for shard_map slicing.

    Returns (idx, weights, k, semiring) where idx is
    (S_src, S_dst, n_out_local, k): block [s, d] routes device s's input
    window into device d's output window in local coordinates.  Stacking
    along TWO leading axes lets one shard_map body index 'which source
    block am I combining' with a fori-style loop while the mesh axis
    slices the destination.
    """
    g = pa.to_gather(plan)
    n_o_loc = g.n_out // n_shards
    n_i_loc = g.n_in // n_shards
    restricted = [[pa.shard_restrict(g, (d * n_o_loc, n_o_loc),
                                     (s * n_i_loc, n_i_loc))
                   for d in range(n_shards)] for s in range(n_shards)]
    kmax = max(r.k for row in restricted for r in row)
    weighted = any(r.weights is not None for row in restricted for r in row)

    def pad(r):
        idx = np.asarray(r.idx)
        if idx.shape[1] < kmax:
            idx = np.pad(idx, ((0, 0), (0, kmax - idx.shape[1])),
                         constant_values=pa.DROP)
        return idx

    idx = np.stack([np.stack([pad(r) for r in row]) for row in restricted])
    weights = None
    if weighted:
        def padw(r):
            if r.weights is None:
                w = np.ones(np.asarray(r.idx).shape,
                            dtype=g.semiring.weight_dtype)
            else:
                w = np.asarray(r.weights)
                if w.shape[1] < np.asarray(r.idx).shape[1]:
                    w = np.broadcast_to(w, np.asarray(r.idx).shape)
            if w.shape[1] < kmax:
                w = np.pad(w, ((0, 0), (0, kmax - w.shape[1])))
            return w
        weights = np.stack([np.stack([padw(r) for r in row])
                            for row in restricted])
    return jnp.asarray(idx), (None if weights is None
                              else jnp.asarray(weights)), kmax, g.semiring


def _local_apply(idx_block, w_block, x_block, n_i_loc, semiring, backend):
    """Apply one restricted (n_out_local, k) control block to a local
    payload, accumulating in the semiring's carrier (int sums for GF2;
    the mod-2 fold happens once, after all blocks are summed)."""
    plan = xb.gather_plan(idx_block, n_i_loc, weights=w_block,
                          semiring=semiring)
    if semiring is GF2:
        # Defer the parity fold: run the block in REAL over int payloads
        # so cross-block accumulation is a plain integer sum and the
        # caller folds &1 exactly once.  (GF2 weights are 0/1 so the
        # weighted product is the same integer product.)
        plan = pa.with_semiring(plan, REAL)
    return xb.apply_plan(plan, x_block, backend=backend)


def shard_bounds(n: int, n_shards: int) -> list:
    """Per-shard ``(lo, hi)`` row boundaries of an evenly sharded axis.

    The slicing contract the serving layer's partial-batch recovery is
    built on: shard ``s`` of a mesh-sharded batch owns exactly rows
    ``[lo, hi)`` of the padded batch axis, so a completed shard's rows
    can be salvaged — and a lost shard's rows replayed — by plain
    slicing, without re-deriving any device placement.
    """
    if n_shards < 1:
        raise ValueError(f"shard_bounds: n_shards={n_shards} must be >= 1")
    if n % n_shards:
        raise ValueError(f"shard_bounds: axis size {n} not divisible by "
                         f"{n_shards} shards")
    per = n // n_shards
    return [(s * per, (s + 1) * per) for s in range(n_shards)]


def _collective_round(round_index: int, pairs: tuple) -> None:
    """Per-round hook on the host-side collective schedule derivation.

    A no-op in production; ``core.faults.inject_faults`` patches this
    module attribute to raise ``InjectedCollectiveFailure`` at
    seed-chosen rounds, so collective-bearing mesh plans have a chaos
    interception point just like apply/compile/megakernel do.
    """


def sharded_apply_fn(plan: xb.PermutePlan, mesh: Mesh, *,
                     axis: str = "data", backend: str = "einsum"):
    """Build the jit-able mesh executor for a plan: ``fn(x) -> out``.

    All host-side derivation — occupancy at shard granularity, the
    ppermute schedule, the stacked per-device restricted controls —
    happens HERE, eagerly, exactly once; the returned function is pure
    device execution and can be jitted, timed, and ``.lower()``-ed (the
    collective-free property of block-local plans is assertable from
    its compiled HLO).
    """
    g = pa.to_gather(plan)
    if g.semiring.name == "gf2_8":
        raise NotImplementedError(
            "sharded_apply_fn: lift GF2_8 plans to GF(2) bits first "
            "(crossbar.lift_gf2_8)")
    if axis not in mesh.axis_names:
        raise ValueError(f"sharded_apply_fn: axis {axis!r} not on mesh "
                         f"{tuple(mesh.axis_names)}")
    s = shd.mesh_axis_size(mesh, axis)
    shd.require_divisible(g.n_out, mesh, axis, what="plan output axis")
    shd.require_divisible(g.n_in, mesh, axis, what="plan input axis")
    if s == 1:
        return jax.jit(lambda x: xb.apply_plan(g, x, backend=backend))

    # Host-side schedule derivation happens once per builder call; the
    # per-round device work is inside jit and cannot carry host spans,
    # so this span (with rounds/shards attrs) is the traced unit.
    with _obs.span("sharded_schedule_derive", shards=s, axis=axis,
                   n_out=g.n_out, n_in=g.n_in) as _sp:
        conn = shard_connectivity(g, s)
        schedule = collective_schedule(conn)
        for r_i, rnd in enumerate(schedule):
            if len(rnd):
                _collective_round(r_i, tuple(rnd))
        _sp.set(rounds=sum(1 for r in schedule if len(r)))
        n_i_loc = g.n_in // s
        n_in = g.n_in
        idx, weights, _, semiring = _stack_restricted(g, s)
    diag = bool(np.diag(conn).any())
    fold_mod2 = semiring is GF2
    # Per-round receive routing, precomputed: src_of[r][dst] = which
    # source block lands on dst in round r (-1: none).
    src_of_rounds = []
    for rnd in schedule:
        src_of = np.full((s,), -1, dtype=np.int32)
        for src, dst in rnd:
            src_of[dst] = src
        src_of_rounds.append(jnp.asarray(src_of))

    def body(idx_l, w_l, x_l):
        # idx_l: (S_src, 1, n_o_loc, k) — this device's destination
        # column of every source block.  x_l: (n_i_loc, ...) local rows.
        my = jax.lax.axis_index(axis)
        acc = None
        if diag:
            w_d = None if w_l is None else w_l[:, 0][my]
            acc = _local_apply(idx_l[:, 0][my], w_d, x_l, n_i_loc,
                               semiring, backend)
        for rnd, src_of in zip(schedule, src_of_rounds):
            recv = jax.lax.ppermute(x_l, axis, list(rnd))
            src_id = src_of[my]
            has = src_id >= 0
            safe_src = jnp.maximum(src_id, 0)
            w_b = None if w_l is None else w_l[:, 0][safe_src]
            part = _local_apply(idx_l[:, 0][safe_src], w_b, recv, n_i_loc,
                                semiring, backend)
            part = jnp.where(has, part, jnp.zeros((), part.dtype))
            acc = part if acc is None else acc + part
        if acc is None:
            n_o_loc = idx_l.shape[2]
            acc = jnp.zeros((n_o_loc,) + x_l.shape[1:], x_l.dtype)
        if fold_mod2 and jnp.issubdtype(acc.dtype, jnp.integer):
            acc = acc & 1
        return acc.astype(x_l.dtype) if jnp.issubdtype(
            x_l.dtype, jnp.integer) else acc

    def apply(x):
        if x.shape[0] != n_in:
            raise ValueError(
                f"sharded apply: payload leading dim {x.shape[0]} != "
                f"plan n_in {n_in}")
        trailing = (None,) * (x.ndim - 1)
        ctrl_spec = P(None, axis, None, None)
        if weights is None:
            fn = jax.shard_map(lambda i, xv: body(i, None, xv), mesh=mesh,
                               in_specs=(ctrl_spec, P(axis, *trailing)),
                               out_specs=P(axis, *trailing))
            return fn(idx, x)
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(ctrl_spec, ctrl_spec,
                                     P(axis, *trailing)),
                           out_specs=P(axis, *trailing))
        return fn(idx, weights, x)

    return jax.jit(apply)


def apply_plan_sharded(plan: xb.PermutePlan, x: Array, mesh: Mesh, *,
                       axis: str = "data",
                       backend: str = "einsum") -> Array:
    """Run ``apply_plan`` with payload rows sharded over a mesh axis.

    The control axis (plan rows) is split evenly over ``axis``; trailing
    payload columns replicate into every shard.  Collective structure is
    derived from the plan's occupancy at shard granularity:

    * diagonal occupancy -> pure local crossbars, zero collectives;
    * off-diagonal blocks -> the minimal ppermute schedule from
      ``collective_schedule`` (each round moves only blocks that carry
      traffic), with per-(src, dst) restricted plans applied locally and
      accumulated in the semiring.

    Bit-exact vs single-device ``apply_plan`` for REAL and GF2 plans.
    GF2_8 plans should be bit-lifted (``crossbar.lift_gf2_8``) first.
    Repeated execution should reuse ``sharded_apply_fn`` directly (the
    host-side schedule derivation is cached only via the plan memo).
    """
    g = pa.to_gather(plan)
    if x.shape[0] != g.n_in:
        raise ValueError(
            f"apply_plan_sharded: payload leading dim {x.shape[0]} != "
            f"plan n_in {g.n_in}")
    fn = sharded_apply_fn(g, mesh, axis=axis, backend=backend)
    s = shd.mesh_axis_size(mesh, axis)
    rounds = 0
    if s > 1:
        rounds = sum(1 for r in collective_schedule(shard_connectivity(g, s))
                     if len(r))
    with _obs.span("collective_apply", shards=s, rounds=rounds,
                   axis=axis, backend=backend, n_out=g.n_out,
                   n_in=g.n_in):
        out = fn(x)
    telemetry.incr("mesh_apply_calls")
    if s > 1 and rounds == 0:
        telemetry.incr("mesh_apply_collective_free")
    return out


def sharded_apply_naive_fn(plan: xb.PermutePlan, mesh: Mesh, *,
                           axis: str = "data", backend: str = "einsum"):
    """Builder for the all-gather baseline executor: every device pulls
    the FULL payload, then runs its restricted rows locally.  Always
    moves (S-1) blocks per device regardless of the plan's structure —
    the thing the scheduled path beats whenever occupancy is sparse at
    shard granularity."""
    g = pa.to_gather(plan)
    if axis not in mesh.axis_names:
        raise ValueError(f"sharded_apply_naive_fn: axis {axis!r} not on "
                         f"mesh {tuple(mesh.axis_names)}")
    s = shd.mesh_axis_size(mesh, axis)
    shd.require_divisible(g.n_out, mesh, axis, what="plan output axis")
    shd.require_divisible(g.n_in, mesh, axis, what="plan input axis")
    n_o_loc = g.n_out // s
    n_in = g.n_in
    # Stack each device's restricted-row plan (full input window).
    rows = [pa.shard_restrict(g, (d * n_o_loc, n_o_loc), (0, g.n_in))
            for d in range(s)]
    kmax = max(r.k for r in rows)

    def pad(r):
        i = np.asarray(r.idx)
        if i.shape[1] < kmax:
            i = np.pad(i, ((0, 0), (0, kmax - i.shape[1])),
                       constant_values=pa.DROP)
        return i

    idx = jnp.asarray(np.stack([pad(r) for r in rows]))
    weighted = any(r.weights is not None for r in rows)
    weights = None
    if weighted:
        ws = []
        for r in rows:
            w = (np.ones(np.asarray(r.idx).shape,
                         dtype=g.semiring.weight_dtype)
                 if r.weights is None else np.asarray(r.weights))
            if w.shape[1] < kmax:
                w = np.pad(w, ((0, 0), (0, kmax - w.shape[1])))
            ws.append(w)
        weights = jnp.asarray(np.stack(ws))

    semiring = g.semiring

    def body(idx_l, w_l, x_l):
        full = jax.lax.all_gather(x_l, axis, tiled=True)
        w_b = None if w_l is None else w_l[0]
        plan_l = xb.gather_plan(idx_l[0], n_in, weights=w_b,
                                semiring=semiring)
        return xb.apply_plan(plan_l, full, backend=backend)

    def apply(x):
        if x.shape[0] != n_in:
            raise ValueError(
                f"sharded apply (naive): payload leading dim {x.shape[0]} "
                f"!= plan n_in {n_in}")
        trailing = (None,) * (x.ndim - 1)
        if weights is None:
            fn = jax.shard_map(lambda i, xv: body(i, None, xv), mesh=mesh,
                               in_specs=(P(axis, None, None),
                                         P(axis, *trailing)),
                               out_specs=P(axis, *trailing))
            return fn(idx, x)
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(axis, None, None), P(axis, None, None),
                                     P(axis, *trailing)),
                           out_specs=P(axis, *trailing))
        return fn(idx, weights, x)

    return jax.jit(apply)


def apply_plan_sharded_naive(plan: xb.PermutePlan, x: Array, mesh: Mesh, *,
                             axis: str = "data",
                             backend: str = "einsum") -> Array:
    """One-shot wrapper around ``sharded_apply_naive_fn``."""
    return sharded_apply_naive_fn(plan, mesh, axis=axis, backend=backend)(x)


# ---------------------------------------------------------------------------
# Sharded plan programs (lane-parallel over payload columns)
# ---------------------------------------------------------------------------

def run_program_sharded(program, x: Array, mesh: Mesh, *,
                        axis: str = "data", backend: str = "chained",
                        pass_backend: str = "einsum",
                        interpret: Optional[bool] = None) -> Array:
    """Run a PlanProgram with payload COLUMNS sharded over a mesh axis.

    Every program step (PERMUTE, XOR, ANDN, ADD, ROTLV, XOR_CONST)
    routes along the control axis and is elementwise across payload
    columns, so column sharding is collective-free by construction: each
    device runs the complete program on its own column slice.  This is
    the PR 5 sharded-SHA3 lane pattern promoted to a first-class
    executor for arbitrary programs — near-linear scaling is structural,
    not a tuning outcome.
    """
    if x.ndim != 2:
        raise ValueError(
            f"run_program_sharded: payload must be (n, D) to shard "
            f"columns, got shape {x.shape}")
    if axis not in mesh.axis_names:
        raise ValueError(f"run_program_sharded: axis {axis!r} not on mesh "
                         f"{tuple(mesh.axis_names)}")
    shd.require_divisible(x.shape[1], mesh, axis,
                          what="program payload column axis")
    fn = sharded_program_fn(program, mesh, axis=axis, backend=backend,
                            pass_backend=pass_backend, interpret=interpret)
    telemetry.incr("mesh_program_launches")
    with _obs.span("collective_program", program=program.name,
                   shards=shd.mesh_axis_size(mesh, axis), axis=axis,
                   backend=backend, columns=x.shape[1]):
        return fn(x)


def sharded_program_fn(program, mesh: Mesh, *, axis: str = "data",
                       backend: str = "chained",
                       pass_backend: str = "einsum",
                       interpret: Optional[bool] = None):
    """The jit-able column-sharded program executor (exposed separately so
    tests and benchmarks can ``.lower()`` it and assert the compiled HLO
    contains no collectives)."""

    def local(x_l):
        return pp.run_program(program, x_l, backend=backend,
                              pass_backend=pass_backend,
                              interpret=interpret)

    body = jax.shard_map(local, mesh=mesh, in_specs=P(None, axis),
                         out_specs=P(None, axis))
    return jax.jit(body)
