"""Plan programs: whole permutation *schedules* as one compiled object.

The plan algebra (``core.plan_algebra``) collapses a chain of pure
permutations into one crossbar pass — but real permutation workloads
are not pure chains.  A Keccak round is a linear crossbar pass *plus*
branch-free XOR/AND arithmetic; a ChaCha double round interleaves lane
rotations with 32-bit adds and word rotates.  Executed step-by-step,
every round pays an HBM round-trip of the state between the crossbar
pass and the elementwise arithmetic (23 avoidable trips per
Keccak-f[1600], per the ROADMAP).

``PlanProgram`` is the IR that closes that gap: an ordered sequence of

* ``PERMUTE``   — a full crossbar pass of a static ``PermutePlan``
                  (k-select gather, semiring accumulation: REAL add or
                  GF(2) XOR),
* ``XOR/AND/ANDN/ADD`` — branch-free elementwise steps between two
                  registers (``ANDN`` is χ's ``(~a) & b``; ``ADD`` is
                  the wrapping 32-bit add of ARX ciphers),
* ``ROTLV``     — per-row bitwise rotate-left by a *static* amount
                  vector (a constants-table row; rows that must not
                  rotate carry amount 0),
* ``XOR_CONST`` — XOR with a constants-table row broadcast over the
                  payload (ι round constants, pre-scheduled keys),
* ``EQ_CONST``  — 0/1 equality mask against a constants-table row: the
                  one-hot *encode* primitive (a byte state compared to
                  row ``u`` is value ``u``'s indicator lane, so table
                  lookups become PERMUTE gathers in-register),
* ``XOR_KEY``   — XOR a 128-row window with one block of the launch's
                  per-lane *key operand* (AddRoundKey with a key per
                  payload lane),
* ``CLMUL``     — the carry-less product of a 128-row window of bits
                  with a key-operand block, lane by lane: GF(2^128)
                  multiplication by a per-lane factor before its fixed
                  reduction, which an ordinary GF(2) PERMUTE does,

over a small register file of ``(n, D)`` state buffers.  All control
information — plans, constants, rotation amounts, the step list itself
— is concrete program data; payload values (key material included:
the key operand is payload, one column per lane) never influence which
steps run (the fixed-latency property, now checkable for a whole *schedule*
via ``StaticPlanRegistry.register_program`` / ``program_fingerprint``).

Two executors share the IR:

* ``backend='chained'`` — the reference lowering: one
  ``crossbar.apply_plan`` call per PERMUTE step and XLA elementwise ops
  between them (state bounces through HBM each step).  This is the
  differential baseline and the pass-count ledger.
* ``backend='megakernel'`` — ONE ``pl.pallas_call``
  (``kernels.plan_program_kernel``): the state is loaded into VMEM
  once, every step executes on the VMEM-resident registers (in-VMEM
  gathers, integer-exact XOR folds), and the result is written back
  once.  A Keccak-f[1600] is 24 rounds — 72 would-be crossbar passes —
  in a single launch.

Compiled megakernel executables are cached on (program identity,
payload geometry, interpret mode); ``core.telemetry`` counts program
launches and the crossbar passes they avoided, so "one launch per
permutation" is assertable the same way "one pass per chain" is.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from collections import OrderedDict
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as _obs
from repro.core import crossbar as xb
from repro.core import integrity as _integrity
from repro.core import plan_algebra as pa
from repro.core.semiring import GF2, REAL

Array = jax.Array

# Step opcodes.  Kept as strings (not an enum) so step tuples print
# readably in fingerprints and error messages.
PERMUTE = "permute"      # dst = plans[plan] @ regs[a]
XOR = "xor"              # dst = regs[a] ^ regs[b]
AND = "and"              # dst = regs[a] & regs[b]
ANDN = "andn"            # dst = (~regs[a]) & regs[b]     (χ's not-and)
ADD = "add"              # dst = regs[a] + regs[b]        (wrapping)
ROTLV = "rotlv"          # dst = rotl(regs[a], consts[const])  per-row
XOR_CONST = "xor_const"  # dst = regs[a] ^ consts[const][:, None]
EQ_CONST = "eq_const"    # dst = (regs[a] == consts[const][:, None])  0/1
XOR_KEY = "xor_key"      # regs[a][row:row+128] ^= keys[key block]
CLMUL = "clmul"          # regs[a][row:row+256] = regs[a][row:row+128]
                         #   (x) keys[key block], carry-less, per lane

_BINARY_OPS = (XOR, AND, ANDN, ADD)
# New ops ride last so pre-existing encoded step streams (and the
# kernel's switch branch numbering) keep their opcode values.
_CONST_OPS = (ROTLV, XOR_CONST, EQ_CONST)
_KEY_OPS = (XOR_KEY, CLMUL)
OPS = (PERMUTE,) + _BINARY_OPS + _CONST_OPS + _KEY_OPS

# The key operand is read in blocks of KEY_BLOCK rows (one 128-bit
# value, a bit per row); XOR_KEY rewrites KEY_BLOCK rows of its
# register and CLMUL 2 * KEY_BLOCK.
KEY_BLOCK = 128
_KEY_WINDOW = {XOR_KEY: KEY_BLOCK, CLMUL: 2 * KEY_BLOCK}


@dataclasses.dataclass(frozen=True)
class Step:
    """One program step.  ``a``/``b`` are register indices; ``plan`` and
    ``const`` index the program's plan and constants tables; ``key`` is
    the key-operand block and ``row`` the first state row of the window
    a key step rewrites in place (``dst == a``)."""

    op: str
    dst: int
    a: int
    b: int = -1
    plan: int = -1
    const: int = -1
    key: int = -1
    row: int = 0


@dataclasses.dataclass(frozen=True)
class PlanProgram:
    """A validated, immutable schedule over ``n``-row states.

    Attributes:
      name:   diagnostic label (registry keys carry the real identity).
      n:      state rows — every plan is an (n -> n) crossbar.
      steps:  the ordered step tuple of ONE round.
      plans:  plan table, gather-normal form, concrete control.
      consts: (n_consts, n) int32 table (ι masks, rotation amounts);
              None when no step references a constant.
      n_regs: register-file size (register 0 is the state in/out).
      rounds: trip count — the step tuple executes ``rounds`` times.
              Round structure is *first-class* rather than unrolled:
              the megakernel compiles one round body inside a
              ``fori_loop`` (XLA-CPU's gather fusion is exponential in
              unrolled multi-select gather chains — measured: 4
              unrolled Keccak rounds already blow the compile budget),
              and the trip count is part of the program's fingerprint.
      const_stride: per-round advance of every constant reference —
              step ``const`` reads row ``const + round * const_stride``
              (stride 1 walks Keccak's 24 ι rows; stride 0 reuses
              ChaCha's rotation-amount rows every round).
    """

    name: str
    n: int
    steps: Tuple[Step, ...]
    plans: Tuple[xb.PermutePlan, ...]
    consts: Optional[np.ndarray]
    n_regs: int
    rounds: int = 1
    const_stride: int = 0

    def __post_init__(self):
        n_consts = 0 if self.consts is None else self.consts.shape[0]
        if self.rounds < 1:
            raise ValueError(f"rounds must be >= 1, got {self.rounds}")
        for i, plan in enumerate(self.plans):
            if plan.mode != xb.GATHER:
                raise ValueError(
                    f"program {self.name!r} plan slot {i} is in scatter "
                    "form; gather-normalise with plan_algebra.to_gather "
                    "before building the program")
            if plan.n_in != self.n or plan.n_out != self.n:
                raise ValueError(
                    f"program {self.name!r} plan slot {i} is "
                    f"{plan.n_in}->{plan.n_out}, not {self.n}->{self.n}: "
                    "program plans must preserve the state geometry")
            if plan.semiring not in (REAL, GF2):
                raise ValueError(
                    f"program {self.name!r} plan slot {i} uses semiring "
                    f"{plan.semiring.name!r}; the megakernel's integer "
                    "datapath executes REAL and GF2 plans only")
            if isinstance(plan.idx, jax.core.Tracer) or isinstance(
                    plan.weights, jax.core.Tracer):
                raise ValueError(
                    f"program {self.name!r} plan slot {i} has traced "
                    "control; programs are static schedules")
        for s, step in enumerate(self.steps):
            if step.op not in OPS:
                raise ValueError(f"step {s}: unknown op {step.op!r}")
            regs = [step.dst, step.a] + (
                [step.b] if step.op in _BINARY_OPS else [])
            if not all(0 <= r < self.n_regs for r in regs):
                raise ValueError(
                    f"step {s} ({step.op}): register out of range "
                    f"(n_regs={self.n_regs})")
            if step.op == PERMUTE and not 0 <= step.plan < len(self.plans):
                raise ValueError(f"step {s}: plan slot {step.plan} out of "
                                 f"range ({len(self.plans)} plans)")
            if step.op in _CONST_OPS:
                last = step.const + (self.rounds - 1) * self.const_stride
                if not (0 <= step.const < n_consts and 0 <= last < n_consts):
                    raise ValueError(
                        f"step {s} ({step.op}): const rows "
                        f"[{step.const}, {last}] out of range ({n_consts} "
                        f"rows, stride {self.const_stride} x "
                        f"{self.rounds} rounds)")
            if step.op in _KEY_OPS:
                span = _KEY_WINDOW[step.op]
                if (step.dst != step.a or step.key < 0 or step.row % 8
                        or not 0 <= step.row <= self.n - span):
                    raise ValueError(
                        f"step {s} ({step.op}): a key step rewrites rows "
                        f"[row, row + {span}) of one register in place "
                        f"from key block >= 0, row a multiple of 8; got "
                        f"dst={step.dst} a={step.a} key={step.key} "
                        f"row={step.row} in {self.n} rows")

    @property
    def key_rows(self) -> int:
        """Rows of the per-lane key operand a launch must supply: its
        highest key block read, plus one, times ``KEY_BLOCK``; 0 when no
        step reads it."""
        return KEY_BLOCK * (1 + max((s.key for s in self.steps
                                     if s.op in _KEY_OPS), default=-1))

    @property
    def passes(self) -> int:
        """Crossbar passes a chained execution would issue (PERMUTE steps
        per round times the trip count)."""
        return self.rounds * sum(1 for s in self.steps if s.op == PERMUTE)

    @property
    def total_steps(self) -> int:
        return self.rounds * len(self.steps)

    @property
    def uses_rotlv(self) -> bool:
        return any(s.op == ROTLV for s in self.steps)

    def unroll(self) -> "PlanProgram":
        """The explicit single-trip form: every round's steps spelled out
        with their constant references resolved.  Semantically identical;
        used by the differential suite to truncate at arbitrary step
        counts (``prefix``)."""
        steps = []
        for r in range(self.rounds):
            off = r * self.const_stride
            for s in self.steps:
                steps.append(s if s.const < 0 else
                             dataclasses.replace(s, const=s.const + off))
        return PlanProgram(f"{self.name}[unrolled]", self.n, tuple(steps),
                           self.plans, self.consts, self.n_regs)

    def prefix(self, n_steps: int) -> "PlanProgram":
        """The program truncated to its first ``n_steps`` steps.

        Shares the plan and constants tables (and therefore their
        compiled schedules); used by the differential suite to check
        the megakernel against the chained path at every step count.
        Only defined for single-trip programs — ``unroll()`` first.
        """
        if self.rounds != 1:
            raise ValueError("prefix() needs a single-trip program; call "
                             ".unroll() first")
        if not 0 <= n_steps <= len(self.steps):
            raise ValueError(f"prefix length {n_steps} out of range "
                             f"(program has {len(self.steps)} steps)")
        return PlanProgram(f"{self.name}[:{n_steps}]", self.n,
                           self.steps[:n_steps], self.plans, self.consts,
                           self.n_regs)


class ProgramBuilder:
    """Incremental ``PlanProgram`` construction with table dedup.

    Plans are deduplicated by object identity (the plan algebra's memo
    already makes recomposed plans identity-stable), constants by
    value, so a 24-round loop referencing the same linear plan emits
    one table entry.
    """

    def __init__(self, name: str, n: int, *, n_regs: int = 4):
        self.name = name
        self.n = n
        self.n_regs = n_regs
        self._steps: List[Step] = []
        self._plans: List[xb.PermutePlan] = []
        self._consts: List[np.ndarray] = []

    def plan_slot(self, plan: xb.PermutePlan) -> int:
        if plan.mode != xb.GATHER:
            plan = pa.to_gather(plan)
        for i, p in enumerate(self._plans):
            if p is plan:
                return i
        self._plans.append(plan)
        return len(self._plans) - 1

    def const_slot(self, row) -> int:
        row = np.asarray(row, np.int32).reshape(-1)
        if row.shape[0] != self.n:
            raise ValueError(f"const row has {row.shape[0]} entries, "
                             f"state has {self.n} rows")
        for i, c in enumerate(self._consts):
            if np.array_equal(c, row):
                return i
        self._consts.append(row)
        return len(self._consts) - 1

    def permute(self, dst: int, a: int, plan: xb.PermutePlan) -> None:
        self._steps.append(Step(PERMUTE, dst, a, plan=self.plan_slot(plan)))

    def xor(self, dst: int, a: int, b: int) -> None:
        self._steps.append(Step(XOR, dst, a, b))

    def and_(self, dst: int, a: int, b: int) -> None:
        self._steps.append(Step(AND, dst, a, b))

    def andn(self, dst: int, a: int, b: int) -> None:
        self._steps.append(Step(ANDN, dst, a, b))

    def add(self, dst: int, a: int, b: int) -> None:
        self._steps.append(Step(ADD, dst, a, b))

    def rotlv(self, dst: int, a: int, amounts) -> None:
        self._steps.append(
            Step(ROTLV, dst, a, const=self.const_slot(amounts)))

    def xor_const(self, dst: int, a: int, row) -> None:
        self._steps.append(
            Step(XOR_CONST, dst, a, const=self.const_slot(row)))

    def xor_const_at(self, dst: int, a: int, slot: int) -> None:
        """XOR with a pre-placed constant row (``add_const_rows``) — the
        form strided per-round constants use."""
        self._steps.append(Step(XOR_CONST, dst, a, const=slot))

    def rotlv_at(self, dst: int, a: int, slot: int) -> None:
        self._steps.append(Step(ROTLV, dst, a, const=slot))

    def eq_const(self, dst: int, a: int, row) -> None:
        """dst = 0/1 mask of where ``regs[a]`` equals the constant row
        broadcast over the payload — the one-hot *encode* primitive (a
        byte state compared against row u yields the indicator lane for
        value u, turning table lookups into PERMUTE gathers)."""
        self._steps.append(
            Step(EQ_CONST, dst, a, const=self.const_slot(row)))

    def eq_const_at(self, dst: int, a: int, slot: int) -> None:
        self._steps.append(Step(EQ_CONST, dst, a, const=slot))

    def xor_key(self, reg: int, key: int, row: int = 0) -> None:
        """regs[reg] rows [row, row + 128) ^= key-operand block ``key``."""
        self._steps.append(Step(XOR_KEY, reg, reg, key=key, row=row))

    def clmul(self, reg: int, key: int, row: int) -> None:
        """regs[reg] rows [row, row + 256) = the carry-less product of
        bit 0 of rows [row, row + 128) (coefficient i at row + i) and of
        key block ``key`` (coefficient j at its row j), lane by lane:
        coefficient k at row + k, row + 255 zero."""
        self._steps.append(Step(CLMUL, reg, reg, key=key, row=row))

    def build(self, *, rounds: int = 1,
              const_stride: int = 0) -> PlanProgram:
        consts = (np.stack(self._consts).astype(np.int32)
                  if self._consts else None)
        return PlanProgram(self.name, self.n, tuple(self._steps),
                           tuple(self._plans), consts, self.n_regs,
                           rounds, const_stride)

    def add_const_rows(self, rows) -> int:
        """Append a block of constant rows verbatim (no dedup); returns
        the first row's index.  Strided round constants (Keccak's 24 ι
        rows) need their table order preserved exactly."""
        rows = np.asarray(rows, np.int32)
        if rows.ndim != 2 or rows.shape[1] != self.n:
            raise ValueError(f"const block must be (rows, {self.n}), got "
                             f"{rows.shape}")
        base = len(self._consts)
        self._consts.extend(rows)
        return base


# ---------------------------------------------------------------------------
# Telemetry: program launches and the passes they replaced
# ---------------------------------------------------------------------------

_PROGRAM_LAUNCHES = 0
_PASSES_AVOIDED = 0
# Launch-counter increments hold _COUNT_LOCK (the serving layer's
# device-feed thread races its admission thread's telemetry reads).
_COUNT_LOCK = threading.Lock()


def program_launch_count() -> int:
    with _COUNT_LOCK:
        return _PROGRAM_LAUNCHES


def passes_avoided_count() -> int:
    """Crossbar passes that would have been issued by chained execution
    of every megakernel launch so far (the fusion ledger)."""
    with _COUNT_LOCK:
        return _PASSES_AVOIDED


def reset_program_counters() -> None:
    global _PROGRAM_LAUNCHES, _PASSES_AVOIDED
    with _COUNT_LOCK:
        _PROGRAM_LAUNCHES = 0
        _PASSES_AVOIDED = 0


# ---------------------------------------------------------------------------
# Megakernel executable cache
# ---------------------------------------------------------------------------
# One compiled (jitted pallas_call closure) per (program identity,
# padded payload geometry, dtype, interpret mode).  Entries hold a
# strong reference to the program so ids cannot be recycled, mirroring
# the CompiledPlan LRU contract.

_EXEC_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_EXEC_CACHE_CAPACITY = 16
_EXEC_STATS = {"hits": 0, "misses": 0}


def program_cache_info() -> dict:
    return dict(_EXEC_STATS, size=len(_EXEC_CACHE),
                capacity=_EXEC_CACHE_CAPACITY)


_obs.metrics.gauge_fn("program_exec_cache_size", lambda: len(_EXEC_CACHE))


def clear_program_cache() -> None:
    for key in list(_EXEC_CACHE):
        _integrity.PROGRAM_GUARD.drop(key)
    _EXEC_CACHE.clear()
    _EXEC_STATS.update(hits=0, misses=0)


def _control_digest(program: "PlanProgram") -> str:
    """Digest of the control content a cached executable was built from
    (step stream, constants, plan idx/weight arrays).  The kernel owns
    the digest recipe so the opcode numbering and the dense-plan rule
    salt it: the dense tables are a function of the sealed plan arrays
    and step stream under that rule, as the entry list is."""
    from repro.kernels import plan_program_kernel as ppk  # lazy: kernels opt.
    parts = []
    for plan in program.plans:
        parts.append(plan.idx)
        parts.append(plan.weights)
    return ppk.control_digest(encode_steps(program), program.consts, parts)


def _plan_fold(plan: xb.PermutePlan) -> str:
    return "xor" if plan.semiring is GF2 else "add"


_OPCODE = {op: i for i, op in enumerate(OPS)}


def encode_steps(program: PlanProgram) -> np.ndarray:
    """One round's step stream as (n_steps, 8) int32 rows — the VM's
    bytecode: (opcode, dst, a, b, plan, const, key, row).  Unused
    operand fields are clamped to 0 so traced register/table indexing
    stays in range (the dispatched branch never reads them)."""
    rows = []
    for s in program.steps:
        rows.append((_OPCODE[s.op], s.dst, s.a, max(s.b, 0),
                     max(s.plan, 0), max(s.const, 0), max(s.key, 0),
                     s.row))
    return np.asarray(rows, np.int32)


def _live_entries(program: PlanProgram) -> list:
    """Per plan: its live selects (0 <= src < n) as (dst, src, weight)
    int32 arrays, weight 1 for unweighted plans.  DROP and out-of-range
    selects contribute nothing, so they are not encoded."""
    out = []
    for plan in program.plans:
        idx = np.asarray(plan.idx, np.int32)
        dst, col = np.nonzero((idx >= 0) & (idx < program.n))
        w = (np.ones(dst.shape, np.int32) if plan.weights is None
             else np.asarray(plan.weights, np.int32)[dst, col])
        out.append((dst.astype(np.int32), idx[dst, col], w))
    return out


def _plan_uses(program: PlanProgram) -> list:
    """Per plan: the PERMUTE steps of one round that apply it."""
    uses = [0] * len(program.plans)
    for s in program.steps:
        if s.op == PERMUTE:
            uses[s.plan] += 1
    return uses


def dense_slots(program: PlanProgram) -> tuple:
    """Per plan: its dense table slot, or -1 where the megakernel walks
    its select entries.

    A plan runs as one MXU product when its semiring is GF2 (XOR fold),
    it has at least ``DENSE_MIN_SELECTS_PER_ROW`` live selects per state
    row, and its table fits: plans are taken in order of live entries x
    uses, each once, while the tables stay within
    ``DENSE_VMEM_BUDGET_BYTES`` and, with a 128-lane register file of
    4-byte words, within ``VMEM_CAP_BYTES``.  REAL plans and sparse GF2
    plans walk.  The choice reads only the plans and the step stream.
    """
    from repro.kernels import plan_program_kernel as ppk  # lazy: kernels opt.
    n_mm = program.n + (-program.n) % ppk.LANES
    table = ppk.dense_table_bytes(n_mm)
    uses = _plan_uses(program)
    cost = [len(e[0]) * u for e, u in zip(_live_entries(program), uses)]
    slots = [-1] * len(program.plans)
    taken = 0
    for p in sorted(range(len(program.plans)), key=lambda p: -cost[p]):
        if (program.plans[p].semiring is GF2 and uses[p]
                and cost[p] >= ppk.DENSE_MIN_SELECTS_PER_ROW * program.n
                * uses[p]
                and (taken + 1) * table <= ppk.DENSE_VMEM_BUDGET_BYTES
                and ppk.vmem_bytes(n_mm, ppk.LANES, program.n_regs, 4,
                                   taken + 1) <= ppk.VMEM_CAP_BYTES):
            slots[p] = taken
            taken += 1
    return tuple(slots)


def _encode_plans(program: PlanProgram, slots: tuple, n_pad: int,
                  ppk) -> tuple:
    """The program's plans as the kernel's flat select-entry list, and
    the dense plans' tables.

    Every live select ``idx[i, j]`` becomes one ``(dst=i, src, weight)``
    triple.  Each plan's run starts on an HBM_ALIGN-word boundary and
    the list carries one ENTRY_CHUNK of tail padding, so the kernel's
    fixed-size chunk DMAs stay in bounds.  A dense plan's table holds
    ``T[i, s]`` = the parity of ``weight & 1`` over its entries
    ``(i, s)``: what the walk's ``acc ^= (x[s] * w) & 1`` computes,
    duplicates and even weights included.  Returns (entries, meta,
    tables) with meta = per plan (word offset, entry count, GF(2) XOR
    fold, dense slot or -1), and tables None when no plan is dense.
    """
    runs, meta, off = [], [], 0
    n_dense = max(slots, default=-1) + 1
    tables = (np.zeros((n_dense, n_pad, n_pad), np.uint8) if n_dense
              else None)
    for plan, (dst, src, w), slot in zip(program.plans,
                                         _live_entries(program), slots):
        if slot >= 0:
            np.add.at(tables[slot], (dst, src), (w & 1).astype(np.uint8))
        run = np.stack([dst, src, w], axis=1).reshape(-1)
        run = np.pad(run, (0, (-run.size) % ppk.HBM_ALIGN))
        meta.append((off, dst.size, int(_plan_fold(plan) == "xor"), slot))
        runs.append(run)
        off += run.size
    runs.append(np.zeros(ppk.ENTRY_WORDS * ppk.ENTRY_CHUNK, np.int32))
    meta = np.asarray(meta or [(0, 0, 0, -1)], np.int32).reshape(-1)
    if tables is not None:
        tables = (tables & 1).astype(ppk.DENSE_DTYPE)
    return np.concatenate(runs), meta, tables


def _encode_consts(program: PlanProgram, n_pad: int, ppk) -> np.ndarray:
    """Constants lane-transposed into (blocks, n_pad, 128): constant
    row ``c`` is lane ``c % 128`` of block ``c // 128``, so a step reads
    it as a per-row column without a transpose on the chip."""
    consts = (np.zeros((1, program.n), np.int32) if program.consts is None
              else np.asarray(program.consts, np.int32))
    c, n = consts.shape
    padded = np.zeros((c + (-c) % ppk.LANES, n_pad), np.int32)
    padded[:c, :n] = consts
    return np.ascontiguousarray(
        padded.reshape(-1, ppk.LANES, n_pad).transpose(0, 2, 1))


def encode_program(program: PlanProgram) -> tuple:
    """The megakernel's operands for one program.

    Control information is encoded once here: the step stream (padded
    to whole SMEM chunks), every plan's live selects as a flat
    (dst, src, weight) entry list with per-plan offset/count/fold/slot
    metadata — the work of a walked PERMUTE is its live selects, not
    rows x k of DROP padding — the lane-transposed constants table,
    and, when ``dense_slots`` picks any, the dense plans' 0/1 tables.
    Returns ``(n_pad, control, static)``: the state's padded row count,
    the control arrays the kernel takes after the state, and its static
    keyword arguments.
    """
    from repro.kernels import plan_program_kernel as ppk  # lazy: kernels opt.

    # The step-stream opcodes index the kernel's op bodies; the two
    # orderings must never drift apart.
    assert ppk.OPCODES == OPS and ppk.KEY_BLOCK == KEY_BLOCK, (
        f"kernel opcode table {ppk.OPCODES} or key block "
        f"{ppk.KEY_BLOCK} drifted from the IR's {OPS}, {KEY_BLOCK}")

    steps = encode_steps(program)
    n_steps = steps.shape[0]
    words = np.zeros((n_steps + (-n_steps) % ppk.STEP_CHUNK,
                      ppk.STEP_WORDS), np.int32)
    words[:n_steps, :steps.shape[1]] = steps
    # Rows: n rounded up to ROW_TILE, or to 128 when a plan runs dense
    # (the tables' lane dimension).  Padded rows read as zero.
    slots = dense_slots(program)
    mult = ppk.LANES if max(slots, default=-1) >= 0 else ppk.ROW_TILE
    n_pad = program.n + (-program.n) % mult
    entries, meta, tables = _encode_plans(program, slots, n_pad, ppk)
    control = (words.reshape(-1), entries, meta,
               _encode_consts(program, n_pad, ppk))
    if tables is not None:
        control += (tables,)
    static = dict(n_steps=n_steps, n_regs=program.n_regs,
                  rounds=program.rounds, const_stride=program.const_stride)
    return n_pad, control, static


@dataclasses.dataclass(frozen=True)
class _Exec:
    """One cached megakernel executable and what a launch of it does:
    the state's padded rows, and the live select entries its PERMUTE
    steps run as products and walk, rounds included."""

    run: object
    n_pad: int
    entries_dense: int
    entries_walked: int


def _build_exec(program: PlanProgram, interpret: bool) -> _Exec:
    """Megakernel closure for one program: the control arrays go to the
    device once and ride as arguments of one jitted launch (not as
    constants folded into the executable)."""
    from repro.kernels import plan_program_kernel as ppk  # lazy: kernels opt.

    n_pad, control, static = encode_program(program)
    meta = control[2].reshape(-1, ppk.META_WORDS)
    dense = walked = 0
    for p, uses in enumerate(_plan_uses(program)):
        n_entries = int(meta[p, 1]) * uses * program.rounds
        if meta[p, 3] >= 0:
            dense += n_entries
        else:
            walked += n_entries
    control = tuple(jnp.asarray(c) for c in control)
    launch = jax.jit(functools.partial(ppk.plan_program_pallas,
                                       interpret=interpret, **static))

    def run(xp, keys):
        return launch(xp, *control, keys=keys)

    return _Exec(run, n_pad, dense, walked)


def lane_pad(d: int) -> int:
    """Payload lanes rounded up to whole megakernel lane blocks: the
    width a launch runs at.  A key operand of this width rides as it
    is; one of the payload's width is padded per launch."""
    from repro.kernels import plan_program_kernel as ppk  # lazy: kernels opt.
    return d + (-d) % ppk.LANES


def _run_megakernel(program: PlanProgram, x2: Array, keys: Optional[Array],
                    interpret: Optional[bool]) -> Array:
    global _PROGRAM_LAUNCHES, _PASSES_AVOIDED
    from repro.core import telemetry  # lazy: telemetry imports this module
    from repro.kernels import plan_program_kernel as ppk  # lazy: kernels opt.
    from repro.kernels.ops import default_interpret
    interpret = default_interpret(interpret)
    n, d = x2.shape
    d_pad = lane_pad(d)
    key = (id(program), d_pad, str(x2.dtype), bool(interpret))
    hit = _EXEC_CACHE.get(key)
    cache_hit = hit is not None and hit[0] is program
    if cache_hit:
        # Sampled re-digest of the program's control content (steps,
        # consts, plan arrays) against the seal taken at insert — a
        # flipped const bit keeps the id-keyed hit alive, so only a
        # content check can catch it before launch.
        _integrity.PROGRAM_GUARD.verify(
            key, digest_fn=lambda: _control_digest(program),
            evict=lambda: _EXEC_CACHE.pop(key, None))
        _EXEC_STATS["hits"] += 1
        _EXEC_CACHE.move_to_end(key)
        ex = hit[1]
    else:
        _EXEC_STATS["misses"] += 1
        ex = _build_exec(program, interpret)
        _integrity.PROGRAM_GUARD.seal(
            key, digest=_control_digest(program))
        _EXEC_CACHE[key] = (program, ex)
        while len(_EXEC_CACHE) > _EXEC_CACHE_CAPACITY:
            evicted_key, _ = _EXEC_CACHE.popitem(last=False)
            _integrity.PROGRAM_GUARD.drop(evicted_key)
    with _COUNT_LOCK:
        _PROGRAM_LAUNCHES += 1
        _PASSES_AVOIDED += program.passes
    telemetry.incr("megakernel_entries_dense", ex.entries_dense)
    telemetry.incr("megakernel_entries_walked", ex.entries_walked)
    xp = x2
    if (ex.n_pad, d_pad) != (n, d):
        xp = jnp.pad(x2, ((0, ex.n_pad - n), (0, d_pad - d)))
    if keys is not None:
        if keys.dtype != x2.dtype:
            keys = keys.astype(x2.dtype)
        if keys.shape[1] != d_pad:
            keys = jnp.pad(keys, ((0, 0), (0, d_pad - keys.shape[1])))
    with _obs.span("program_launch", program=program.name,
                   passes=program.passes, n=n, d=d,
                   exec_cache_hit=cache_hit):
        return ex.run(xp, keys)[:n, :d]


# ---------------------------------------------------------------------------
# Chained reference executor
# ---------------------------------------------------------------------------

def _rotlv_host(v: Array, amt: Array) -> Array:
    bits = jnp.iinfo(v.dtype).bits
    a = amt.astype(v.dtype)[:, None]
    return (v << a) | (v >> ((bits - a) & (bits - 1)))


def _apply_pass(plan: xb.PermutePlan, v: Array, pass_backend: str,
                interpret) -> Array:
    # uint32 payloads (ARX words) bitcast around the pass: apply_plan's
    # integer path accumulates in int32, and routing is bit-exact at any
    # magnitude under the bitcast (never under a value cast).
    if v.dtype == jnp.uint32:
        vi = jax.lax.bitcast_convert_type(v, jnp.int32)
        out = xb.apply_plan(plan, vi, backend=pass_backend,
                            interpret=interpret)
        return jax.lax.bitcast_convert_type(out, jnp.uint32)
    return xb.apply_plan(plan, v, backend=pass_backend, interpret=interpret)


def _clmul_host(x: Array, h: Array) -> Array:
    """(128, D) x (128, D) bit columns -> (256, D): row k is the parity
    of x[i] & h[j] over i + j = k, lane by lane."""
    i = np.arange(KEY_BLOCK)
    terms = (x & 1)[:, None, :] * (h & 1)[None, :, :]
    prod = jnp.zeros((2 * KEY_BLOCK, x.shape[1]), jnp.int32).at[
        (i[:, None] + i[None, :]).reshape(-1)].add(
            terms.reshape(-1, x.shape[1]).astype(jnp.int32))
    return prod & 1


def _run_chained(program: PlanProgram, x2: Array, keys: Optional[Array],
                 pass_backend: str, interpret) -> Array:
    regs = [x2] + [jnp.zeros_like(x2)
                   for _ in range(program.n_regs - 1)]
    consts = (None if program.consts is None
              else jnp.asarray(program.consts, jnp.int32))
    for r in range(program.rounds):
        off = r * program.const_stride
        for step in program.steps:
            a = regs[step.a]
            if step.op == PERMUTE:
                val = _apply_pass(program.plans[step.plan], a, pass_backend,
                                  interpret)
            elif step.op == XOR:
                val = a ^ regs[step.b]
            elif step.op == AND:
                val = a & regs[step.b]
            elif step.op == ANDN:
                val = ~a & regs[step.b]
            elif step.op == ADD:
                val = a + regs[step.b]
            elif step.op == ROTLV:
                val = _rotlv_host(a, consts[step.const + off])
            elif step.op == EQ_CONST:
                val = (a == consts[step.const + off].astype(a.dtype)[:, None]
                       ).astype(a.dtype)
            elif step.op in _KEY_OPS:
                block = keys[KEY_BLOCK * step.key:KEY_BLOCK * (step.key + 1)]
                win = a[step.row:step.row + KEY_BLOCK]
                val = (win ^ block if step.op == XOR_KEY
                       else _clmul_host(win, block)).astype(a.dtype)
                val = a.at[step.row:step.row + val.shape[0]].set(val)
            else:  # XOR_CONST
                val = a ^ consts[step.const + off].astype(a.dtype)[:, None]
            regs[step.dst] = val
    return regs[0]


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def run_program(
    program: PlanProgram,
    x: Array,
    *,
    keys: Optional[Array] = None,
    backend: str = "megakernel",
    pass_backend: str = "einsum",
    interpret: Optional[bool] = None,
) -> Array:
    """Execute a plan program over an ``(n,)`` or ``(n, D)`` payload.

    Args:
      keys: the per-lane key operand, ``(program.key_rows, D)`` integer
        bits, or already ``lane_pad(D)`` lanes wide (lanes past D are
        not read), for a program whose key steps read it; None
        otherwise.
      backend: 'megakernel' (one VMEM-resident Pallas launch) or
        'chained' (one ``apply_plan`` per PERMUTE step with XLA
        elementwise between — the reference lowering and the
        differential baseline).
      pass_backend: crossbar backend for the chained lowering's passes.
      interpret: Pallas interpret-mode override (megakernel); defaults
        to interpret off-TPU like every other kernel wrapper.
    Returns:
      Register 0 after the last step, in the input's shape and dtype.
    """
    x = jnp.asarray(x)
    single = x.ndim == 1
    x2 = x[:, None] if single else x
    if x2.ndim != 2 or x2.shape[0] != program.n:
        raise ValueError(f"program {program.name!r} runs on ({program.n}, D) "
                         f"states, got payload shape {x.shape}")
    if not jnp.issubdtype(x2.dtype, jnp.integer):
        raise ValueError(f"plan programs carry integer states, got "
                         f"{x2.dtype}")
    if program.uses_rotlv and not jnp.issubdtype(x2.dtype, jnp.unsignedinteger):
        raise ValueError(
            "ROTLV needs an unsigned payload (logical right shift); got "
            f"{x2.dtype} — bitcast ARX states to uint32 first")
    if program.key_rows:
        keys = jnp.asarray(keys) if keys is not None else None
        lanes = (x2.shape[1], lane_pad(x2.shape[1]))
        if (keys is None or keys.ndim != 2
                or keys.shape[0] != program.key_rows
                or keys.shape[1] not in lanes
                or not jnp.issubdtype(keys.dtype, jnp.integer)):
            raise ValueError(
                f"program {program.name!r} reads a ({program.key_rows}, "
                f"{' or '.join(map(str, lanes))}) integer key operand, "
                f"got {None if keys is None else (keys.shape, keys.dtype)}")
    elif keys is not None:
        raise ValueError(f"program {program.name!r} reads no key operand")
    if backend == "megakernel":
        out2 = _run_megakernel(program, x2, keys, interpret)
    elif backend == "chained":
        out2 = _run_chained(program, x2,
                            None if keys is None else keys[:, :x2.shape[1]],
                            pass_backend, interpret)
    else:
        raise ValueError(f"unknown program backend {backend!r}")
    return out2[:, 0] if single else out2
