"""Compile-once plan registries and the fixed-latency execution contract.

The paper's unified datapath exists to give every permutation the same,
data-independent schedule — a microarchitectural property this repo's
crossbar engine provides implicitly (every backend is branch-free and
fixed-shape) but, until now, nothing *consumed*.  Cryptographic
permutation layers are that consumer: their control information is a
program constant (Keccak ρ∘π, ChaCha diagonalisation, AES ShiftRows,
PRESENT's bit pLayer), their schedules must never vary with the data
being permuted, and timing-side-channel hygiene demands the invariance
be *asserted*, not assumed.

Two pieces:

* ``StaticPlanRegistry`` — named ``PermutePlan``s whose control is
  checked concrete (a traced plan is by definition not static) and whose
  tile schedules are compiled once through ``compile_plan(pin=True)``,
  the pinned fast path that is immune to LRU churn.  Plans register
  eagerly (``register``) or lazily (``get_or_register``, used for
  batch-width variants built on demand with ``plan_algebra.batch``).

* ``StaticPlanRegistry.observe`` — the fixed-latency contract
  checker.  An observed block's *signature* — crossbar pass
  count (via ``core.telemetry``) plus the schedule fingerprint
  (geometry, select count, occupied-tile count) of every plan it
  declares — is recorded on first execution for each (op, payload
  shapes, backend) key and must be bit-identical on every later call.

Whole ``core.plan_program.PlanProgram`` schedules are first-class
citizens of the same contract: ``register_program`` /
``get_or_register_program`` hold them (pinning every referenced plan's
tile schedule), ``program_fingerprint`` folds the per-step
fingerprints *and the step order* into one value, and
``observe(program_keys=..., expect_program_launches=...)`` extends the
signature with megakernel launch counts — so fixed-latency drift
detection covers the fused single-launch path exactly like the
chained per-pass path.
  Payload values never enter the signature, so a violation means the
  implementation's schedule depends on data — exactly the bug class the
  paper's fixed-latency datapath exists to exclude.  Violations raise
  ``FixedLatencyError`` (an ``AssertionError``: this is a contract
  check, not a recoverable condition).
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax

from repro import obs as _obs
from repro.obs import drift as _drift
from repro.core import crossbar as xb
from repro.core import integrity as _integrity
from repro.core import plan_program as pp
from repro.core import telemetry


class FixedLatencyError(AssertionError):
    """A fixed-latency operation changed schedule/pass-count across calls."""


def _require_static(plan: xb.PermutePlan, key: str) -> None:
    if isinstance(plan.idx, jax.core.Tracer) or isinstance(
            plan.weights, jax.core.Tracer):
        raise ValueError(
            f"static registry plan {key!r} has traced control information; "
            "static plans must be built from concrete (program-constant) "
            "indices")


def schedule_fingerprint(plan: xb.PermutePlan, *, block_o: int = 128,
                         block_n: int = 128) -> tuple:
    """Value-level identity of a plan's compiled schedule.

    Deliberately *not* keyed on object identity: cache clears between
    calls (test isolation) rebuild equal schedules, and equality of
    (geometry, selects, occupied-tile count) is what fixed latency
    means.  Compiling here is a pinned-cache hit in the steady state.
    """
    compiled = xb.compile_plan(plan, block_o=block_o, block_n=block_n,
                               pin=True)
    fp = (plan.mode, plan.n_in, plan.n_out, plan.k, plan.semiring.name,
          compiled.n_o_tiles, compiled.n_n_tiles,
          int(compiled.num_active))
    if plan.semiring.is_gf2k:
        # The matmul backends never execute the element-level schedule
        # of a GF(2^k) plan — they run its GF(2) bit lift.  Fingerprint
        # (and pin) that executed schedule too, or the contract would
        # be checking a plan the datapath never touches while the real
        # one sits in the evictable LRU.
        lifted = xb.lift_gf2_k(plan)
        lc = xb.compile_plan(lifted, block_o=block_o, block_n=block_n,
                             pin=True)
        fp = fp + (("lift", lifted.n_in, lifted.n_out, lifted.k,
                    lc.n_o_tiles, lc.n_n_tiles, int(lc.num_active)),)
    return fp


def program_step_fingerprint(program: "pp.PlanProgram", step) -> tuple:
    """Value-level identity of one program step.

    PERMUTE steps carry their plan's full ``schedule_fingerprint`` (so
    a re-tiled or re-weighted plan is a different step even at the same
    slot); arithmetic steps are identified by opcode, register wiring,
    key block and row window (key steps), and constant-row slot (row
    *contents* enter the program fingerprint through the constants-table
    digest, which also covers the strided rows a per-round constant
    walks).
    """
    if step.op == "permute":
        return (step.op, step.dst, step.a,
                schedule_fingerprint(program.plans[step.plan]))
    if step.key >= 0:
        return (step.op, step.dst, step.a, step.key, step.row)
    if step.const >= 0:
        return (step.op, step.dst, step.a, step.const)
    return (step.op, step.dst, step.a, step.b)


class StaticPlanRegistry:
    """Named static plans, compiled once, executed under a latency contract."""

    def __init__(self, name: str):
        self.name = name
        self._plans: Dict[str, xb.PermutePlan] = {}
        self._programs: Dict[str, "pp.PlanProgram"] = {}
        self._observed: Dict[tuple, tuple] = {}
        self._quarantined: Dict[str, int] = {}

    # -- registration -------------------------------------------------------

    def register(self, key: str, plan: xb.PermutePlan, *,
                 precompile: bool = True) -> xb.PermutePlan:
        """Register a static plan under ``key`` (double-register is an error).

        ``precompile`` pins the tile schedule immediately so the first
        execution is already on the warm path.
        """
        if key in self._plans:
            raise ValueError(
                f"plan {key!r} already registered in {self.name!r}; "
                "static plans are immutable — use a new key")
        _require_static(plan, key)
        self._plans[key] = plan
        if precompile:
            # Compile-time eval: registration may be reached from inside
            # a jit trace (first use of a lazily-built cipher layer in a
            # jitted step); the schedule of a concrete plan is itself
            # concrete and must not be staged into that trace.
            with jax.ensure_compile_time_eval():
                xb.compile_plan(plan, pin=True)
                if plan.semiring.is_gf2k:
                    # Pin the executed (bit-lifted) schedule as well.
                    xb.compile_plan(xb.lift_gf2_k(plan), pin=True)
        return plan

    def get_or_register(self, key: str,
                        builder: Callable[[], xb.PermutePlan], *,
                        precompile: bool = True) -> xb.PermutePlan:
        """Idempotent registration: build only if ``key`` is absent.

        The builder runs under ``jax.ensure_compile_time_eval()`` so a
        static plan first touched inside a jit trace is still built from
        concrete arrays (index arithmetic on program constants must
        never be staged into the caller's trace).
        """
        plan = self._plans.get(key)
        if plan is None:
            with jax.ensure_compile_time_eval():
                built = builder()
            plan = self.register(key, built, precompile=precompile)
        return plan

    def __contains__(self, key: str) -> bool:
        return key in self._plans

    def __getitem__(self, key: str) -> xb.PermutePlan:
        try:
            return self._plans[key]
        except KeyError:
            raise KeyError(
                f"no plan {key!r} in static registry {self.name!r} "
                f"(registered: {sorted(self._plans)})") from None

    def keys(self):
        return self._plans.keys()

    def batch_variant(self, key: str, b: int) -> Tuple[xb.PermutePlan, str]:
        """The width-``b`` block-diagonal variant of a registered plan.

        Registered lazily under ``"<key>_x<b>"`` (``b=1`` returns the
        base plan and key unchanged).  Returns ``(plan, variant_key)``
        so fixed-latency observers can declare the exact plan they
        executed — the key derivation lives in one place.
        """
        base = self[key]
        if b == 1:
            return base, key
        from repro.core import plan_algebra as pa
        variant_key = f"{key}_x{b}"
        return self.get_or_register(
            variant_key, lambda: pa.batch(base, b)), variant_key

    def compiled(self, key: str) -> xb.CompiledPlan:
        """The pinned schedule of a registered plan (re-pins after clears)."""
        return xb.compile_plan(self[key], pin=True)

    def fingerprint(self, key: str) -> tuple:
        return schedule_fingerprint(self[key])

    # -- whole-program registration ----------------------------------------

    def register_program(self, key: str, program: "pp.PlanProgram", *,
                         precompile: bool = True) -> "pp.PlanProgram":
        """Register a static ``PlanProgram`` (double-register is an error).

        The program's *plans* stay program-private (they are slots, not
        registry keys), but every one of them gets its tile schedule
        pinned, so the fused path's control information is as eviction-
        proof as a registered plan's.
        """
        if key in self._programs:
            raise ValueError(
                f"program {key!r} already registered in {self.name!r}; "
                "static programs are immutable — use a new key")
        for i, plan in enumerate(program.plans):
            _require_static(plan, f"{key}[plan {i}]")
        self._programs[key] = program
        # Seal the constants table at registration: ``program()`` hits
        # re-verify on the sampling knob, so an in-place bit flip in the
        # consts block is caught before the program fingerprint (which
        # embeds a consts digest) would even be recomputed.
        _integrity.CONST_GUARD.seal((self.name, key), (program.consts,))
        if precompile:
            with jax.ensure_compile_time_eval():
                for plan in program.plans:
                    xb.compile_plan(plan, pin=True)
        return program

    def get_or_register_program(self, key: str, builder: Callable, *,
                                precompile: bool = True) -> "pp.PlanProgram":
        """Idempotent program registration (build under compile-time eval,
        like ``get_or_register`` — first touch inside jit stays concrete)."""
        program = self._programs.get(key)
        if program is None:
            with jax.ensure_compile_time_eval():
                built = builder()
            program = self.register_program(key, built,
                                            precompile=precompile)
        else:
            self._verify_program(key, program)
        return program

    def program(self, key: str) -> "pp.PlanProgram":
        try:
            program = self._programs[key]
        except KeyError:
            raise KeyError(
                f"no program {key!r} in static registry {self.name!r} "
                f"(registered: {sorted(self._programs)})") from None
        self._verify_program(key, program)
        return program

    def _verify_program(self, key: str, program: "pp.PlanProgram") -> None:
        """Sampled consts-digest check on program lookup.  A mismatch
        evicts the program (no quarantine tick — the IntegrityError
        reaches ``ResilientExecutor``, whose quarantine call records the
        single count that keeps the first retry free) and raises."""
        _integrity.CONST_GUARD.verify(
            (self.name, key), lambda: (program.consts,),
            evict=lambda: self._evict_program(key))

    def _evict_program(self, key: str) -> None:
        program = self._programs.pop(key, None)
        if program is not None:
            for plan in program.plans:
                xb.unpin_plan(plan)

    def program_fingerprint(self, key: str) -> tuple:
        """Value-level identity of a whole program's schedule.

        Per-step fingerprints *in step order*, plus the trip count,
        constant stride, and a digest of the constants table:
        reordering two steps, swapping a plan's schedule, changing the
        round count, or editing a constant row all change the
        fingerprint — the program-level analogue of
        ``schedule_fingerprint``, consumed by ``observe``.
        """
        import hashlib
        program = self.program(key)
        consts_digest = (None if program.consts is None else
                         hashlib.sha256(
                             program.consts.tobytes()).hexdigest()[:16])
        return (program.n, program.n_regs, program.rounds,
                program.const_stride, len(program.steps), consts_digest,
                tuple(program_step_fingerprint(program, s)
                      for s in program.steps))

    def info(self) -> dict:
        return {"name": self.name, "plans": len(self._plans),
                "programs": len(self._programs),
                "observed_signatures": len(self._observed),
                "quarantines": sum(self._quarantined.values())}

    # -- quarantine ---------------------------------------------------------

    def quarantine(self, key: str) -> int:
        """Evict a (possibly drifted) entry without poisoning the caches.

        Removes the plan/program registered under ``key`` — and every
        derived batch variant (``"<key>_x<B>"``) — from the registry,
        drops their pinned tile schedules (``crossbar.unpin_plan``), and
        forgets *all* recorded fixed-latency signatures (they may embed
        fingerprints of the evicted schedules, so partial retention
        would compare fresh schedules against stale baselines).

        The next ``get_or_register``/``get_or_register_program`` for the
        key rebuilds and re-registers from scratch: one observed drift
        costs one re-registration, not a permanently poisoned pinned
        cache.  Returns the total number of quarantines recorded for
        ``key`` so callers (``core.resilience``) can escalate instead of
        retrying when the same entry keeps drifting.
        """
        evicted: list = []
        for k in list(self._plans):
            if k == key or k.startswith(key + "_x"):
                evicted.append(self._plans.pop(k))
        for k in list(self._programs):
            if k == key or k.startswith(key + "_x"):
                evicted.extend(self._programs.pop(k).plans)
                _integrity.CONST_GUARD.drop((self.name, k))
        for plan in evicted:
            xb.unpin_plan(plan)
        self._observed.clear()
        self._quarantined[key] = self._quarantined.get(key, 0) + 1
        return self._quarantined[key]

    def quarantine_count(self, key: str) -> int:
        """How many times ``key`` has been quarantined since the last
        ``reset_observations``."""
        return self._quarantined.get(key, 0)

    # -- fixed-latency contract --------------------------------------------

    def reset_observations(self) -> None:
        """Forget recorded signatures and quarantine history (test
        isolation), keep the plans."""
        self._observed.clear()
        self._quarantined.clear()

    @contextlib.contextmanager
    def observe(self, name: Any, *, shapes: Sequence = (),
                backend: Optional[str] = None,
                plan_keys: Sequence[str] = (),
                program_keys: Sequence[str] = (),
                expect_apply_calls: Optional[int] = None,
                expect_program_launches: Optional[int] = None,
                audit_host_syncs: bool = False):
        """Assert the wrapped block's schedule signature is call-invariant.

        ``name``/``shapes``/``backend`` key the signature: a different
        payload geometry or backend is a different static configuration
        and gets its own recorded signature.  Within one key, the pass
        count and every declared plan's schedule fingerprint must match
        the first observation exactly — for any payload *values*.
        ``expect_apply_calls`` additionally hard-checks the pass count
        (e.g. 24 for fused-ρπ Keccak-f[1600]: one crossbar pass per
        round).

        ``program_keys`` declares registered ``PlanProgram``s executed
        inside the block: their whole-program fingerprints — and the
        megakernel launch count — join the signature, and
        ``expect_program_launches`` hard-checks the latter (e.g. 1 for
        a megakernel Keccak-f[1600], alongside
        ``expect_apply_calls=0``: the fused path must issue *no*
        per-pass crossbar calls at all).

        ``audit_host_syncs=True`` additionally forbids value-dependent
        host syncs inside the block: a disallowed device->host transfer
        (caught by JAX's transfer guard on accelerators, where a sync is
        a real copy) or an ``int()`` / ``np.asarray()`` on a *traced*
        value (JAX's own concretization errors) raises
        ``FixedLatencyError``.  Schedule invariance says latency didn't
        drift *between* these calls; the audit says nothing inside the
        region could have read payload values to make it drift.  On CPU
        hosts device->host views are zero-copy and invisible to the
        transfer guard — use ``audit_constant_time`` (abstract tracing)
        for a backend-independent static check.
        """
        audit = (telemetry.no_host_sync() if audit_host_syncs
                 else contextlib.nullcontext())
        t0 = time.perf_counter()
        try:
            with _obs.span("registry_observe", op=str(name),
                           registry=self.name, backend=backend or ""), \
                    telemetry.delta() as d, audit:
                yield
        except telemetry.HostSyncError as e:
            raise FixedLatencyError(
                f"{self.name}:{name}: value-dependent host sync inside "
                f"an observed fixed-latency region — {e}") from e
        except jax.errors.JAXTypeError as e:
            if not audit_host_syncs:
                raise
            raise FixedLatencyError(
                f"{self.name}:{name}: traced-value concretization "
                f"(int()/np.asarray() on a tracer) inside an observed "
                f"fixed-latency region — {e}") from e
        delta = d()
        calls = delta["apply_calls"]
        if expect_apply_calls is not None and calls != expect_apply_calls:
            raise FixedLatencyError(
                f"{self.name}:{name}: expected {expect_apply_calls} "
                f"crossbar passes, executed {calls}")
        launches = delta["program_launches"]
        if (expect_program_launches is not None
                and launches != expect_program_launches):
            raise FixedLatencyError(
                f"{self.name}:{name}: expected {expect_program_launches} "
                f"program launches, executed {launches}")
        sig = (calls, tuple(self.fingerprint(k) for k in plan_keys))
        if program_keys or expect_program_launches is not None:
            # Extended only when programs are in play, so plan-only
            # observers keep their recorded (calls, fingerprints) shape.
            sig = sig + (launches,
                         tuple(self.program_fingerprint(k)
                               for k in program_keys))
        # Feed the streaming drift monitor BEFORE the signature
        # comparison: a drifting observation must be visible even when
        # this very call is about to raise FixedLatencyError.
        _drift.MONITOR.observe(f"{self.name}:{name}",
                               passes=calls, fingerprint=sig[1:],
                               wall_s=time.perf_counter() - t0)
        key = (name, tuple(shapes), backend)
        prev = self._observed.get(key)
        if prev is None:
            self._observed[key] = sig
        elif prev != sig:
            raise FixedLatencyError(
                f"{self.name}:{name} violated the fixed-latency contract "
                f"for shapes={tuple(shapes)} backend={backend!r}: first "
                f"call signature {prev} != this call {sig} (pass count, "
                "(mode, n_in, n_out, k, o_tiles, n_tiles, active_tiles) "
                "per plan)")

    def audit_constant_time(self, name: Any, fn: Callable, *example_args,
                            **example_kwargs):
        """Statically assert ``fn``'s schedule cannot read payload values.

        The region is abstract-evaluated (``jax.eval_shape``) with every
        array argument replaced by a tracer: any value-dependent host
        sync in the implementation — ``int(tracer)``, ``np.asarray`` on
        a traced value, a data-dependent Python branch — necessarily
        concretizes a tracer and raises, which is converted to
        ``FixedLatencyError``.  Backend-independent (works on CPU hosts
        where zero-copy device->host views evade the transfer guard)
        and free: abstract evaluation moves no data and runs no FLOPs.

        Returns the abstract output (ShapeDtypeStructs) on success, so
        callers can additionally pin the output geometry.
        """
        try:
            return jax.eval_shape(fn, *example_args, **example_kwargs)
        except jax.errors.JAXTypeError as e:
            raise FixedLatencyError(
                f"{self.name}:{name}: implementation performs a "
                f"value-dependent host sync (int()/np.asarray()/branch "
                f"on payload values) — schedule is not a function of "
                f"static control information alone. Root cause: {e}"
            ) from e

    # -- execution ----------------------------------------------------------

    def execute(self, key: str, x: jax.Array, *,
                merge: Optional[jax.Array] = None,
                backend: str = "einsum",
                out_mask: Optional[jax.Array] = None,
                interpret: Optional[bool] = None,
                fixed_latency: bool = False,
                audit_host_syncs: bool = False) -> jax.Array:
        """One crossbar pass of a registered plan over ``x``.

        With ``fixed_latency=True`` the pass is observed: exactly one
        ``apply_plan`` call, schedule fingerprint invariant across calls
        for this (key, payload shape/dtype, backend);
        ``audit_host_syncs=True`` additionally forbids device->host
        syncs during the pass (see ``observe``).
        """
        plan = self[key]
        if not fixed_latency:
            return xb.apply_plan(plan, x, merge=merge, backend=backend,
                                 out_mask=out_mask, interpret=interpret)
        with self.observe(("execute", key),
                          shapes=(tuple(x.shape), str(x.dtype)),
                          backend=backend, plan_keys=(key,),
                          expect_apply_calls=1,
                          audit_host_syncs=audit_host_syncs):
            out = xb.apply_plan(plan, x, merge=merge, backend=backend,
                                out_mask=out_mask, interpret=interpret)
        return out
