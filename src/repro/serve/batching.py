"""Continuous request batching over the fixed-latency crypto engine.

``serve/engine.py`` batches *tokens* for a model; this module batches
*requests* for the permutation engine's crypto workloads — many clients
submitting variable-length payloads to be hashed, served from a bounded
admission queue by a single device-feed worker thread.  The design goal
is the ROADMAP's serving-scale item hardened by ``core.resilience``:
every answer is bit-exact or a clean typed rejection, never a hang.

* **Padded bucket shapes.**  Requests are bucketed by sponge geometry
  (``n_blocks`` of the SHA3-256 rate) and the batch axis is padded to
  the next power of two (dummy lanes route through the same schedule
  and are discarded).  Each bucket shape is therefore one of a small,
  fixed set of payload geometries — the fixed-latency contract holds
  *per bucket*, and ``StaticPlanRegistry.observe`` checks it on every
  batch when ``fixed_latency=True``.

* **Admission control.**  The queue is bounded: past ``max_queue``
  pending requests, ``submit`` sheds load with a typed ``Overloaded``
  rejection instead of growing latency without bound.  Per-request
  deadlines are enforced at dispatch (an expired request is completed
  with ``TimeoutFault``, never silently dropped) and requests can be
  cancelled while queued.

* **Degradation.**  Batch execution goes through
  ``resilience.ResilientExecutor``: megakernel/kernel/einsum faults
  retry, fall back down the chain, trip per-(op, geometry, backend)
  circuit breakers, and quarantine drifted registry entries — the
  telemetry counters (``serve_*``, ``resilience_*``) record every
  decision.

* **Watchdog.**  The worker thread heartbeats through
  ``dist.fault.HeartbeatTracker``; ``check_workers()`` is the
  supervisor hook (tick + report).  ``dist.fault.StragglerPolicy``
  tracks batch wall times so slow batches are visible as stragglers.

* **Mesh scale-out.**  With ``BatchingOptions(mesh=...)`` each padded
  bucket's batch axis is sharded over a mesh axis (the collective-free
  sharded-SHA3 lane pattern — every absorb step is elementwise across
  lanes, so GSPMD partitions without communication).  Per-DEVICE health
  (``resilience.DeviceHealth``) sits beside the per-backend breaker: a
  sick device drops out of the mesh via ``dist.fault.
  survivor_mesh_shape`` and batches keep flowing on the survivors,
  rejoining automatically after its breaker cooldown.  Host→device
  feeds are double-buffered: a prep thread packs/pads the next bucket
  while the feed thread's absorb is still executing, so admission
  overlaps device work.

* **Measured backend tuning.**  Every bucket execution records its wall
  time into a ``core.tuning.TuningTable`` keyed by (op, padded
  geometry, mesh shape); the table rank-orders the fallback chain
  measured-fastest-first and is installed into ``crossbar`` so
  ``backend="auto"`` inside any pass consults the measurements.  The
  table serialises deterministically for warm restarts.

Synchronous use (tests, benchmarks) can construct the engine with
``start=False`` and call ``run_once()`` to process one batch
deterministically on the caller's thread.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import math
import queue as queue_mod
import threading
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs as _obs
from repro.core import crossbar as xb
from repro.core import plan_program as pp
from repro.core import telemetry
from repro.core.resilience import (DeviceHealth, Fault, ResilientExecutor,
                                   TimeoutFault, default_chain)
from repro.core.tuning import TuningTable
from repro.crypto import aes as aes_mod
from repro.crypto import gcm, keccak
from repro.crypto.registry import REGISTRY
from repro.dist.fault import (HeartbeatTracker, StragglerPolicy,
                              survivor_mesh_shape)
from repro.dist import mesh_exec as mx

_RATE_BYTES = 136  # SHA3-256 sponge rate


class Overloaded(RuntimeError):
    """The admission queue is full; the request was shed, not queued."""


class Cancelled(RuntimeError):
    """The request was cancelled before execution."""


# ---------------------------------------------------------------------------
# Requests
# ---------------------------------------------------------------------------

_SUPPORTED_OPS = ("sha3_256", "gcm_seal")


def _n_blocks(payload_len: int) -> int:
    """Sponge blocks absorbed for a payload of this length (pad10*1
    always appends at least the domain byte, so the count is exact)."""
    return (payload_len + 1 + _RATE_BYTES - 1) // _RATE_BYTES


def _dummy_payload(n_blocks: int) -> bytes:
    """A payload whose padded form occupies exactly ``n_blocks``."""
    return b"\x00" * (_RATE_BYTES * n_blocks - 1)


# AEAD records ride the same byte-payload admission path as digests.
# Wire format for op="gcm_seal": nonce(12) || aad_len:u32be ||
# [session:u32be] || aad || plaintext, the session index present when
# the top bit of the length word is set; the result is ciphertext ||
# 16-byte tag.  The bucket key is the exact (pt_len, aad_len) record
# geometry — that is what one fused GCM program covers, whatever the
# keys, so a bucket maps 1:1 onto ONE program launch with the batch as
# payload lanes, each lane keyed by its record's session.

_SESSION_FLAG = 1 << 31


def encode_aead_record(nonce: bytes, plaintext: bytes, aad: bytes = b"",
                       session: Optional[int] = None) -> bytes:
    """Pack one seal request for ``submit(..., op='gcm_seal')``, sealed
    under the engine's session ``session`` (session 0 when None)."""
    if len(nonce) != gcm.IV_BYTES:
        raise ValueError(f"AEAD nonce must be {gcm.IV_BYTES} bytes")
    if session is None:
        return nonce + len(aad).to_bytes(4, "big") + aad + plaintext
    return (nonce + (len(aad) | _SESSION_FLAG).to_bytes(4, "big")
            + session.to_bytes(4, "big") + aad + plaintext)


def _aead_header(payload: bytes) -> tuple:
    """(aad_len, session, offset of the AAD) of one wire record."""
    word = int.from_bytes(payload[12:16], "big")
    if word & _SESSION_FLAG:
        return (word ^ _SESSION_FLAG,
                int.from_bytes(payload[16:20], "big"), 20)
    return word, 0, 16


def _decode_aead_record(payload: bytes) -> tuple:
    """(nonce, plaintext, aad, session) of one wire record."""
    aad_len, session, off = _aead_header(payload)
    return (payload[:12], payload[off + aad_len:],
            payload[off:off + aad_len], session)


def _aead_bucket(payload: bytes) -> tuple:
    aad_len, _, off = _aead_header(payload)
    return (len(payload) - off - aad_len, aad_len)   # (pt_len, aad_len)


_RID_COUNTER = itertools.count(1)


class Request:
    """One submitted payload: a thread-safe future with a deadline."""

    __slots__ = ("op", "payload", "deadline", "backend", "_event", "_value",
                 "_exc", "_lock", "t_submit", "t_done", "trace_id", "rid")

    def __init__(self, payload: bytes, op: str,
                 deadline: Optional[float]):
        self.op = op
        self.payload = payload
        self.deadline = deadline
        # Process-unique request id: the key of the partial-batch
        # result journal (idempotent replay needs an identity that
        # survives requeue/recovery, which list position does not).
        self.rid = next(_RID_COUNTER)
        self.backend: Optional[str] = None
        self._event = threading.Event()
        self._value: Optional[bytes] = None
        self._exc: Optional[BaseException] = None
        self._lock = threading.Lock()
        self.t_submit = time.perf_counter()
        self.t_done: Optional[float] = None
        # Request-scoped trace id: every span this request touches —
        # queue wait on the admission side, pack on the prep thread,
        # absorb on the device-feed thread — carries it, so a timeline
        # groups one request's whole lifecycle across threads.
        self.trace_id = _obs.new_trace_id() if _obs.enabled() else None

    @property
    def bucket(self) -> tuple:
        if self.op == "gcm_seal":
            return (self.op,) + _aead_bucket(self.payload)
        return (self.op, _n_blocks(len(self.payload)))

    def done(self) -> bool:
        return self._event.is_set()

    @property
    def latency_s(self) -> Optional[float]:
        if self.t_done is None:
            return None
        return self.t_done - self.t_submit

    def _finish(self, *, value: Optional[bytes] = None,
                exc: Optional[BaseException] = None,
                backend: Optional[str] = None) -> bool:
        with self._lock:
            if self._event.is_set():
                return False
            self._value, self._exc, self.backend = value, exc, backend
            self.t_done = time.perf_counter()
            self._event.set()
        # Retroactive lifecycle span (outside the lock): submit ->
        # completion, tagged with the terminal outcome.
        _obs.span_at("request", self.t_submit, self.t_done,
                     trace_id=self.trace_id, op=self.op,
                     outcome=("ok" if exc is None
                              else type(exc).__name__),
                     backend=backend or "")
        return True

    def cancel(self) -> bool:
        """Cancel a queued request; False if it already completed."""
        cancelled = self._finish(exc=Cancelled("request cancelled"))
        if cancelled:
            telemetry.incr("serve_cancelled")
        return cancelled

    def result(self, timeout: Optional[float] = None) -> bytes:
        """Block for the digest; raises the typed completion error."""
        if not self._event.wait(timeout):
            raise TimeoutFault(
                f"result not ready within {timeout}s (request still "
                "queued or executing)")
        if self._exc is not None:
            raise self._exc
        return self._value


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BatchingOptions:
    """Admission + execution knobs.

    ``chain=None`` resolves to ``resilience.default_chain()`` (einsum-
    first off TPU, megakernel-first on TPU).  ``fixed_latency=True``
    runs every bucket under the crypto registry's observation contract;
    drift then surfaces as ``DriftFault`` and is quarantined rather
    than poisoning the pinned caches.

    ``max_batch`` caps the live requests of one bucket at one
    megakernel lane block (``pp.lane_pad(1)`` = 128 lanes): a launch
    runs a whole block whatever its live lanes, so a smaller cap pays
    a full launch and a full feed cycle for part of a block.  A bucket
    still takes only what is queued and never waits to fill.
    """

    max_batch: int = pp.lane_pad(1)
    max_queue: int = 1024
    default_timeout_s: Optional[float] = None
    poll_interval_s: float = 0.02
    fixed_latency: bool = True
    chain: Optional[tuple] = None
    watchdog_miss_threshold: int = 3
    batch_log_cap: int = 256
    # Mesh scale-out: a jax.sharding.Mesh shards each bucket's batch
    # axis over ``mesh_axis``; None keeps the single-device path.
    mesh: Optional[object] = None
    mesh_axis: str = "data"
    # Overlap host-side packing with device absorb (threaded mode only;
    # run_once() stays synchronous regardless).
    double_buffer: bool = True
    # Measured backend table; None creates a fresh engine-local one.
    tuning: Optional[TuningTable] = None
    # AES-128 keys of the op="gcm_seal" sessions, installed on the
    # device when the engine is built: session i is aead_keys[i], or,
    # with no aead_keys, session 0 is aead_key.  A record names its
    # session (encode_aead_record) or is sealed under session 0; one
    # bucket's lanes may each use another session.
    aead_key: bytes = b"\x00" * 16
    aead_keys: Sequence[bytes] = ()
    # Partial-batch recovery on a mesh: execute each shard's lane
    # window as its own journaled unit, so a device fault mid-batch
    # salvages completed shards and replays only the lost lanes on the
    # survivors.  False restores whole-batch sharded execution.
    partial_results: bool = True
    # Result-journal capacity (completed lanes kept for idempotent
    # replay; oldest entries age out).
    journal_cap: int = 4096


class ResultJournal:
    """Completed-lane journal for partial-batch recovery.

    Maps request id -> result bytes for lanes whose shard completed,
    so a replay after a mid-batch device fault is idempotent: windows
    whose live lanes are all journaled are skipped, and a lane that
    somehow replays anyway just re-records the same bytes.  Bounded
    (FIFO aging) — the journal is a recovery scratchpad, not a cache.
    """

    def __init__(self, cap: int = 4096):
        if cap < 1:
            raise ValueError(f"journal cap must be >= 1, got {cap}")
        self.cap = cap
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[int, bytes]" = \
            collections.OrderedDict()

    def record(self, rid: int, value: bytes) -> None:
        with self._lock:
            self._entries[rid] = value
            self._entries.move_to_end(rid)
            while len(self._entries) > self.cap:
                self._entries.popitem(last=False)

    def get(self, rid: int) -> Optional[bytes]:
        with self._lock:
            return self._entries.get(rid)

    def forget(self, rid: int) -> None:
        with self._lock:
            self._entries.pop(rid, None)

    def depth(self) -> int:
        with self._lock:
            return len(self._entries)


def _shard_probe(shard_index: int, device_index: int) -> None:
    """Per-shard dispatch hook, called just before a shard's lanes
    execute on ``device_index``.  A no-op in production;
    ``core.faults.inject_device_fault`` patches this module attribute
    to kill a chosen device mid-batch."""


def _staging_put(queue, item) -> None:
    """Staging-queue insertion hook (prep thread -> device feed).  A
    plain ``put`` in production; ``core.faults.inject_faults`` patches
    this module attribute to stall or drop prepared batches."""
    queue.put(item)


def _pack_blocks(payloads: Sequence[bytes]) -> np.ndarray:
    """Host-side half of a bucket execution: pad10*1 every payload and
    stack the full-state absorb blocks, (B, n_blocks, STATE_BITS).

    Pure numpy so the prep thread can run it while the feed thread's
    previous absorb still owns the device — the double-buffering split.
    """
    blocks = np.stack([keccak._pad101(m, _RATE_BYTES, 0x06)
                       for m in payloads])          # (B, n_blocks, rate bits)
    b, n_blocks = blocks.shape[:2]
    pad_tail = np.zeros((b, n_blocks, keccak.STATE_BITS - _RATE_BYTES * 8),
                        np.int32)
    return np.concatenate([blocks, pad_tail], axis=2)


def _absorb_digests(blocks: np.ndarray, backend: str, *,
                    fixed_latency: bool,
                    interpret: Optional[bool] = None,
                    mesh=None, mesh_axis: str = "data",
                    device=None, phase=gcm.no_phase) -> list:
    """Device-side half: sponge-absorb pre-packed blocks, one
    ``keccak_f1600`` per block, and squeeze the digests.  ``phase`` is
    told as each stage begins: ``launch`` (the transfers and dispatches
    of every block), ``sync`` (the wait for the states), ``unpack`` (the
    squeeze).

    With ``mesh`` set, the batch axis is sharded over ``mesh_axis`` —
    every absorb step (XOR + keccak_f1600 with B as payload width) is
    elementwise across lanes, so GSPMD compiles it collective-free per
    shard (the PR 5 sharded-SHA3 pattern).  The megakernel backend runs
    its own Pallas launch and keeps the unsharded path.  ``device``
    pins the whole absorb to ONE device instead — the partial-batch
    recovery path executes each shard's lane window as its own
    journaled unit this way.
    """
    phase("launch")
    b, n_blocks = blocks.shape[:2]
    states = jnp.zeros((b, keccak.STATE_BITS), jnp.int32)
    shard = mesh is not None and backend != "megakernel" and b > 1
    if shard:
        sharding = NamedSharding(mesh, P(mesh_axis, None))
        states = jax.device_put(states, sharding)
    elif device is not None:
        states = jax.device_put(states, device)
    for i in range(n_blocks):
        block = jnp.asarray(blocks[:, i])
        if shard:
            block = jax.device_put(block, sharding)
        elif device is not None:
            block = jax.device_put(block, device)
        states = states ^ block
        states = keccak.keccak_f1600(states, backend=backend,
                                     batch_mode="payload",
                                     fixed_latency=fixed_latency,
                                     interpret=interpret)
    # Lanes per device that produced them: where the shards really ran.
    on = states.devices()
    for dev in on:
        telemetry.incr(f"serve_lanes_device{dev.id}", b // len(on))
    phase("sync")
    host = np.asarray(states)
    phase("unpack")
    return [keccak._squeeze(host[i], _RATE_BYTES)[:32] for i in range(b)]


def _bucket_digests(payloads: Sequence[bytes], backend: str, *,
                    fixed_latency: bool,
                    interpret: Optional[bool] = None,
                    mesh=None, mesh_axis: str = "data") -> list:
    """SHA3-256 of a padded bucket on one backend (ragged-capable).

    Unlike ``keccak.sha3_256_batched`` the lanes need not share a byte
    length — only a padded *block count* (the bucket invariant), which
    is what schedule alignment actually requires.  B rides as payload
    width (``batch_mode='payload'``), so the per-round plan is the
    single-state ρ∘π plan for every bucket width and the megakernel
    program handles the batch natively.
    """
    return _absorb_digests(_pack_blocks(payloads), backend,
                           fixed_latency=fixed_latency, interpret=interpret,
                           mesh=mesh, mesh_axis=mesh_axis)


def _keccak_registry_keys(backend: str) -> tuple:
    """The static-registry entries a bucket execution depends on —
    what drift quarantine must evict for the given backend."""
    if backend == "megakernel":
        return (keccak.MEGAKERNEL_PROGRAM_KEY,)
    return ("keccak/rho_pi",)


class SessionTable:
    """The AEAD sessions' key material on the device, installed once:
    ``gcm.key_material`` rows (round keys and H per session), and the
    keys on the host for the chained rungs."""

    def __init__(self, keys: Sequence[bytes]):
        self.keys = tuple(bytes(k) for k in keys)
        self.device = jnp.asarray(gcm.key_material(self.keys))
        telemetry.incr("serve_sessions_installed", len(self.keys))

    def __len__(self) -> int:
        return len(self.keys)


def _bucket_seal(payloads: Sequence[bytes], backend: str,
                 sessions: SessionTable, live: int, *,
                 fixed_latency: bool,
                 interpret: Optional[bool] = None,
                 phase=gcm.no_phase) -> list:
    """Seal one AEAD bucket: look up each lane's session, decode the
    wire records and run the whole batch as ONE fused GCM program launch
    (backend='megakernel'), its lanes' key material gathered from the
    device session table, or the chained per-block lowering on a
    crossbar backend when degraded.  ``phase("key_lookup")`` opens the
    lookup and ``phase("pack")`` the decode, which the seal's own bit
    packing continues; the seal tells ``phase`` the stages after it.
    ``live`` leading lanes are requests, the rest padding."""
    phase("key_lookup")
    idx = np.zeros(pp.lane_pad(len(payloads)), np.int32)
    idx[:len(payloads)] = [_aead_header(p)[1] for p in payloads]
    telemetry.incr("serve_bucket_sessions", len(set(idx[:live].tolist())))
    megakernel = backend == "megakernel"
    lanes = (gcm.session_keys(sessions.device, idx) if megakernel
             else [sessions.keys[i] for i in idx[:len(payloads)]])
    phase("pack")
    recs = [_decode_aead_record(p) for p in payloads]
    args = ([r[0] for r in recs], [r[1] for r in recs],
            [r[2] for r in recs])
    if megakernel:
        return gcm.seal_lanes(lanes, *args, fixed_latency=fixed_latency,
                              interpret=interpret, phase=phase)
    return gcm.aes128_gcm_seal_batch(lanes, *args, backend=backend,
                                     interpret=interpret, phase=phase)


def _gcm_registry_keys(pt_len: int, aad_len: int):
    """Quarantine targets for a gcm_seal bucket: the fused program of
    its geometry on the megakernel rung, the AES plans on the chained
    rungs (their GHASH plans are per session key)."""
    def keys(backend: str) -> tuple:
        if backend == "megakernel":
            return (gcm._program_key(pt_len, aad_len, False),)
        return aes_mod._ensure_plans(False, True)
    return keys


class _Phases:
    """The host phases of one bucket execution on the feed thread, as
    one sequence of spans: ``phase("launch")`` ends the open phase and
    opens ``bucket_launch``; ``phase()`` ends the last one.  A phase is
    ended by the next one starting rather than by a ``with`` block
    because the stages of a GCM seal live in two modules (the record
    decode here, the bit packing in ``crypto.gcm``) and make one phase.
    ``phase("key_lookup")``, a GCM bucket's first phase, opens the span
    ``key_lookup``.
    """

    __slots__ = ("_attrs", "_open")

    def __init__(self, **attrs):
        self._attrs = attrs
        self._open = None

    def __call__(self, name: Optional[str] = None) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        if name is not None:
            self._open = _obs.span(
                name if name == "key_lookup" else "bucket_" + name,
                **self._attrs)
            self._open.__enter__()


class BatchingEngine:
    """Bounded-queue continuous batching with graceful degradation."""

    def __init__(self, options: BatchingOptions = BatchingOptions(), *,
                 executor: Optional[ResilientExecutor] = None,
                 interpret: Optional[bool] = None, start: bool = True):
        self.opt = options
        self.chain = (tuple(options.chain) if options.chain is not None
                      else default_chain())
        self.executor = executor if executor is not None else (
            ResilientExecutor(chain=self.chain, registry=REGISTRY))
        self.interpret = interpret
        self._queue: "collections.deque[Request]" = collections.deque()
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._running = False
        self._worker: Optional[threading.Thread] = None
        self._prep: Optional[threading.Thread] = None
        # Double-buffer staging between the prep (pack/pad) thread and
        # the device-feed thread: depth 2 means the next bucket's host
        # work happens while the current absorb owns the device.
        self._staging: "queue_mod.Queue" = queue_mod.Queue(maxsize=2)
        # Mesh scale-out state.  Device index d on the full mesh maps to
        # ``_mesh_devices[d]``; DeviceHealth tracks per-index breakers
        # and the active mesh is rebuilt from survivors on demand.
        self.device_health: Optional[DeviceHealth] = None
        self._mesh_devices: list = []
        self._survivor_cache: dict = {}
        if options.mesh is not None:
            self._mesh_devices = list(np.asarray(
                options.mesh.devices).reshape(-1))
            self.device_health = DeviceHealth(len(self._mesh_devices))
        # Partial-batch recovery journal: completed lanes by request id.
        self.journal = ResultJournal(cap=options.journal_cap)
        # The gcm_seal sessions, on the device from here on.
        self.sessions = SessionTable(tuple(options.aead_keys)
                                     or (options.aead_key,))
        # Measured backend tuning (core/tuning.py): records every bucket
        # wall time, rank-orders the fallback chain, and backs
        # crossbar's backend="auto" for the passes inside each absorb.
        self.tuning = options.tuning if options.tuning is not None \
            else TuningTable()
        xb.set_tuning_table(self.tuning)
        # Worker watchdog + straggler tracking (reusing the dist-layer
        # policies: the serving worker is host 0 of a 1-host fleet).
        self.heartbeats = HeartbeatTracker(
            1, miss_threshold=options.watchdog_miss_threshold)
        self.straggler = StragglerPolicy()
        # Rolling ledger of executed buckets: (op, bucket_shape, backend,
        # live_requests) — tests and the benchmark read it.
        self.batch_log: "collections.deque[tuple]" = collections.deque(
            maxlen=options.batch_log_cap)
        # Export-time gauges: lazy callables evaluated only when a
        # metrics snapshot/exposition is taken — the admission and
        # dispatch paths never pay for them.  A newer engine replaces
        # an older one's registrations (latest engine wins).
        _obs.metrics.gauge_fn("serve_queue_depth", self.queue_depth)
        _obs.metrics.gauge_fn(
            "resilience_breaker_open",
            lambda: len(self.executor.breaker.open_keys()))
        _obs.metrics.gauge_fn("serve_tuning_entries",
                              lambda: len(self.tuning))
        _obs.metrics.gauge_fn("serve_staging_depth",
                              self._staging.qsize)
        _obs.metrics.gauge_fn("serve_journal_depth", self.journal.depth)
        if self.device_health is not None:
            def _mesh_active() -> int:
                mesh = self._active_mesh()
                return 0 if mesh is None else int(np.prod(list(
                    dict(mesh.shape).values())))
            _obs.metrics.gauge_fn("serve_mesh_active", _mesh_active)
            _obs.metrics.gauge_fn(
                "serve_mesh_lost",
                lambda: len(self.device_health.lost()))
        if start:
            self.start()

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        if self._worker is not None and self._worker.is_alive():
            return
        self._running = True
        if self.opt.double_buffer:
            self._prep = threading.Thread(target=self._prep_loop,
                                          name="batching-host-prep",
                                          daemon=True)
            self._prep.start()
        self._worker = threading.Thread(target=self._worker_loop,
                                        name="batching-device-feed",
                                        daemon=True)
        self._worker.start()

    def close(self, *, drain: bool = True, timeout: Optional[float] = None
              ) -> None:
        """Stop the worker(s).  ``drain=True`` finishes queued work first;
        otherwise pending requests complete with ``Cancelled``."""
        with self._work:
            if not drain:
                while self._queue:
                    self._queue.popleft().cancel()
            self._running = False
            self._work.notify_all()
        if self._prep is not None:
            self._prep.join(timeout)
            self._prep = None
        if self._worker is not None:
            self._worker.join(timeout)
            self._worker = None

    def __enter__(self) -> "BatchingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close(drain=not any(exc))

    # -- admission ----------------------------------------------------------

    def submit(self, payload: bytes, *, op: str = "sha3_256",
               timeout_s: Optional[float] = None) -> Request:
        """Queue one payload; returns a ``Request`` future.

        Raises ``Overloaded`` when the bounded queue is full (load
        shedding — the caller should back off) and ``ValueError`` for
        unsupported ops.
        """
        if op not in _SUPPORTED_OPS:
            raise ValueError(f"unsupported op {op!r}; supported: "
                             f"{_SUPPORTED_OPS}")
        if op == "gcm_seal":
            session = _aead_header(payload)[1]
            if session >= len(self.sessions):
                raise ValueError(f"record names session {session}; the "
                                 f"engine has {len(self.sessions)}")
        if timeout_s is None:
            timeout_s = self.opt.default_timeout_s
        deadline = (None if timeout_s is None
                    else time.monotonic() + timeout_s)
        req = Request(bytes(payload), op, deadline)
        with self._work:
            if len(self._queue) >= self.opt.max_queue:
                telemetry.incr("serve_shed")
                raise Overloaded(
                    f"admission queue full ({self.opt.max_queue} pending); "
                    "request shed")
            self._queue.append(req)
            telemetry.incr("serve_admitted")
            self._work.notify()
        return req

    def map(self, payloads: Sequence[bytes], *, op: str = "sha3_256",
            timeout_s: Optional[float] = None) -> list:
        """Submit-and-wait convenience: digests in input order."""
        reqs = [self.submit(p, op=op, timeout_s=timeout_s)
                for p in payloads]
        return [r.result() for r in reqs]

    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    # -- dispatch -----------------------------------------------------------

    def _take_batch_locked(self) -> tuple:
        """Pop one bucket-aligned batch; finish expired/cancelled inline.

        The oldest live request defines the bucket; up to ``max_batch``
        live requests sharing it are taken in FIFO order.  Returns
        ``(batch, rejected)`` counts of requests removed.
        """
        now = time.monotonic()
        batch: list = []
        rejected = 0
        bucket = None
        keep: list = []
        while self._queue:
            req = self._queue.popleft()
            if req.done():          # cancelled while queued
                rejected += 1
                continue
            if req.deadline is not None and now >= req.deadline:
                req._finish(exc=TimeoutFault(
                    f"deadline expired after {now - (req.deadline):.3f}s "
                    "in queue"))
                telemetry.incr("serve_timeouts")
                rejected += 1
                continue
            if bucket is None:
                bucket = req.bucket
            if req.bucket == bucket and len(batch) < self.opt.max_batch:
                batch.append(req)
            else:
                keep.append(req)
        self._queue.extend(keep)
        if batch and _obs.enabled():
            # Queue wait is only knowable retroactively: it spans the
            # admission thread's submit and THIS thread's take.
            t_take = time.perf_counter()
            for req in batch:
                _obs.span_at("queue_wait", req.t_submit, t_take,
                             trace_id=req.trace_id, op=req.op)
        return batch, rejected

    # -- mesh membership ----------------------------------------------------

    def report_device_fault(self, device: int) -> bool:
        """Feed one device-attributed fault into the per-device breaker
        (external signal: XLA device error, host watchdog, chaos test).
        Returns True when this fault trips the device out of the mesh —
        subsequent batches rebuild onto the survivor mesh."""
        if self.device_health is None:
            raise ValueError("report_device_fault: engine has no mesh")
        tripped = self.device_health.record_failure(device)
        if tripped:
            telemetry.incr("serve_mesh_device_drops")
        return tripped

    def _active_mesh(self):
        """The mesh batches should run on right now: the full mesh, a
        survivor mesh excluding tripped devices, or None (single-device
        fallback when too few survivors remain)."""
        if self.opt.mesh is None or self.device_health is None:
            return None
        lost = self.device_health.lost()
        if not lost:
            return self.opt.mesh
        healthy = tuple(self.device_health.healthy())
        cached = self._survivor_cache.get(healthy)
        if cached is not None:
            return cached
        try:
            # survivor_mesh_shape shrinks by name; serving meshes are
            # 1-axis, so compute under "data" and relabel to our axis.
            shape = survivor_mesh_shape({"data": len(self._mesh_devices)},
                                        len(lost))
        except (ValueError, RuntimeError):
            telemetry.incr("serve_mesh_collapsed")
            self._survivor_cache[healthy] = None
            return None
        s = shape["data"]
        devs = [self._mesh_devices[d] for d in healthy[:s]]
        mesh = jax.sharding.Mesh(np.asarray(devs).reshape(s),
                                 (self.opt.mesh_axis,))
        telemetry.incr("serve_mesh_rebuilds")
        self._survivor_cache[healthy] = mesh
        return mesh

    def _mesh_lane_floor(self) -> int:
        """Lane padding must cover the FULL mesh so any pow2 survivor
        mesh still divides it."""
        return max(1, len(self._mesh_devices))

    # -- dispatch -----------------------------------------------------------

    def _prepare(self, batch: list) -> tuple:
        """Host half of a bucket execution: pow2 lane padding + payload
        packing.  Runs on the prep thread when double-buffered."""
        bucket = batch[0].bucket
        op, geom = bucket[0], bucket[1:]
        # Pad the lane count to the next power of two so bucket shapes
        # come from a fixed set: (b_pad, *geom) IS the geometry the
        # fixed-latency contract and the circuit breaker key on.  On a
        # mesh the floor is the device count so every shard gets lanes.
        b_pad = self._mesh_lane_floor()
        while b_pad < len(batch):
            b_pad *= 2
        payloads = [r.payload for r in batch]
        if op == "gcm_seal":
            pt_len, aad_len = geom
            filler = encode_aead_record(b"\x00" * gcm.IV_BYTES,
                                        b"\x00" * pt_len,
                                        b"\x00" * aad_len)
            payloads += [filler] * (b_pad - len(batch))
            telemetry.incr("serve_padded_lanes", b_pad - len(batch))
            # Records stay as wire bytes: the seal path owns its own
            # bit packing (gcm._pack_records) per backend.
            return op, geom, b_pad, payloads
        (n_blocks,) = geom
        payloads += [_dummy_payload(n_blocks)] * (b_pad - len(batch))
        telemetry.incr("serve_padded_lanes", b_pad - len(batch))
        with _obs.span("bucket_pack", trace_id=batch[0].trace_id, op=op,
                       n_blocks=n_blocks, lanes=len(batch), b_pad=b_pad):
            return op, geom, b_pad, _pack_blocks(payloads)

    def _execute_batch(self, batch: list, prepared: tuple) -> None:
        op, geom, b_pad, data = prepared
        shape = (b_pad,) + geom
        mesh = self._active_mesh()
        mesh_shape = None if mesh is None else dict(mesh.shape)
        if (self.opt.partial_results and mesh is not None
                and op != "gcm_seal"
                and int(np.prod(list(mesh_shape.values()))) > 1):
            # Per-shard journaled execution: a device fault mid-batch
            # loses one lane window, not the batch.  (gcm_seal keeps
            # the single-launch fused path — it never shards.)
            return self._execute_batch_partial(batch, op, geom, b_pad,
                                               data, mesh)

        phase = _Phases(trace_id=batch[0].trace_id, op=op,
                        lanes=len(batch))
        if op == "gcm_seal":
            def work(backend: str) -> list:
                return _bucket_seal(data, backend, self.sessions,
                                    len(batch),
                                    fixed_latency=self.opt.fixed_latency,
                                    interpret=self.interpret, phase=phase)
            registry_keys = _gcm_registry_keys(*geom)
        else:
            def work(backend: str) -> list:
                return _absorb_digests(data, backend,
                                       fixed_latency=self.opt.fixed_latency,
                                       interpret=self.interpret,
                                       mesh=mesh,
                                       mesh_axis=self.opt.mesh_axis,
                                       phase=phase)
            registry_keys = _keccak_registry_keys

        def run(backend: str) -> list:
            try:
                return work(backend)
            finally:
                phase()

        chain = self.tuning.rank_chain(op, shape, self.chain,
                                       mesh_shape=mesh_shape)
        # The span IS the batch stopwatch: straggler tracking and the
        # tuning EWMA both read its duration (works with tracing off —
        # a disabled span still times itself).
        sp = _obs.span("device_absorb", trace_id=batch[0].trace_id, op=op,
                       b_pad=b_pad, geom=str(geom), lanes=len(batch),
                       mesh=bool(mesh is not None))
        try:
            with sp:
                res = self.executor.execute(
                    op, shape, run, chain=chain,
                    registry_keys=registry_keys)
                sp.set(backend=res.backend)
        except Fault as e:
            telemetry.incr("serve_failed", len(batch))
            for req in batch:
                req._finish(exc=e)
            return
        finally:
            self.straggler.observe(sp.duration_s)
            telemetry.incr("serve_batches")
        self.tuning.record_span(sp, op, shape, res.backend,
                                mesh_shape=mesh_shape)
        if mesh is not None:
            telemetry.incr("serve_mesh_batches")
            # A successful mesh batch is a health signal for every
            # participating device (half-open probes rejoin here).
            active = set(np.asarray(mesh.devices).reshape(-1).tolist())
            for d, dev in enumerate(self._mesh_devices):
                if dev in active:
                    self.device_health.record_success(d)
        self.batch_log.append((op, shape, res.backend, len(batch)))
        telemetry.incr("serve_completed", len(batch))
        for req, digest in zip(batch, res.value):
            req._finish(value=digest, backend=res.backend)

    # -- partial-batch recovery --------------------------------------------

    def _force_trip(self, device_index: int) -> None:
        """Take a device out of the mesh NOW: a device-attributed fault
        mid-batch is definitive, not a strike toward a threshold."""
        while self.device_health.is_healthy(device_index):
            self.device_health.record_failure(device_index)
        telemetry.incr("serve_mesh_device_drops")

    def _run_shard(self, op: str, geom: tuple, window: np.ndarray,
                   shard_index: int, device_index: int):
        """Execute one shard's lane window on one device through the
        resilient chain.  Returns the ResilientResult."""
        device = self._mesh_devices[device_index]

        def run(backend: str) -> list:
            _shard_probe(shard_index, device_index)
            return _absorb_digests(window, backend,
                                   fixed_latency=self.opt.fixed_latency,
                                   interpret=self.interpret,
                                   device=device)

        chain = self.tuning.rank_chain(
            op, (window.shape[0],) + geom, self.chain)
        telemetry.incr("serve_shard_launches")
        return self.executor.execute(op, (window.shape[0],) + geom, run,
                                     chain=chain,
                                     registry_keys=_keccak_registry_keys)

    @staticmethod
    def _device_of_fault(exc: BaseException) -> Optional[int]:
        """Walk the cause chain for a device-attributed failure."""
        seen = 0
        while exc is not None and seen < 16:
            device = getattr(exc, "device", None)
            if isinstance(device, int):
                return device
            exc = exc.__cause__ or exc.__context__
            seen += 1
        return None

    def _execute_batch_partial(self, batch: list, op: str, geom: tuple,
                               b_pad: int, data: np.ndarray, mesh) -> None:
        """Mesh execution with per-shard journaling and lost-lane replay.

        Each shard of the padded batch axis runs as its own resilient
        execution pinned to its device.  A completed shard's real lanes
        finish (and journal) immediately — they are salvaged no matter
        what later shards do.  A faulted shard force-trips its device
        and queues ONLY its window for replay on a surviving device:
        idempotent (journaled lanes are skipped), deadline-aware (lanes
        that cannot make their deadline on the survivors shed with
        ``Overloaded``), and geometry-stable (the replay window keeps
        the per-shard shape, so no new compilation is triggered).
        """
        devices = list(np.asarray(mesh.devices).reshape(-1))
        bounds = mx.shard_bounds(b_pad, len(devices))
        by_lane: list = list(batch) + [None] * (b_pad - len(batch))
        telemetry.incr("serve_partial_batches")
        sp = _obs.span("partial_batch", trace_id=batch[0].trace_id, op=op,
                       b_pad=b_pad, shards=len(devices), lanes=len(batch))
        backend_used = None
        lost: list = []
        last_fault: Optional[Fault] = None

        def finish_window(lo: int, hi: int, values: list,
                          backend: str) -> None:
            for lane in range(lo, hi):
                req = by_lane[lane]
                if req is None:
                    continue
                self.journal.record(req.rid, values[lane - lo])
                if req._finish(value=values[lane - lo], backend=backend):
                    telemetry.incr("serve_completed")

        with sp:
            for s, (lo, hi) in enumerate(bounds):
                device_index = self._mesh_devices.index(devices[s])
                try:
                    res = self._run_shard(op, geom, data[lo:hi], s,
                                          device_index)
                except Fault as e:
                    at_fault = self._device_of_fault(e)
                    self._force_trip(at_fault if at_fault is not None
                                     else device_index)
                    sp.event("shard_lost", shard=s, device=device_index)
                    lost.append((s, lo, hi))
                    last_fault = e
                    continue
                backend_used = backend_used or res.backend
                self.device_health.record_success(device_index)
                finish_window(lo, hi, res.value, res.backend)
            if lost:
                telemetry.incr("serve_shards_salvaged",
                               len(bounds) - len(lost))
                self._replay_lost(op, geom, data, by_lane, lost,
                                  last_fault, sp)
        # Span closed: its duration is the whole batch (salvage + any
        # replay), which is what the straggler EWMA should see.
        self.straggler.observe(sp.duration_s)
        telemetry.incr("serve_batches")
        telemetry.incr("serve_mesh_batches")
        self.batch_log.append((op, (b_pad,) + geom,
                               backend_used or "replay", len(batch)))

    def _replay_lost(self, op: str, geom: tuple, data: np.ndarray,
                     by_lane: list, lost: list,
                     last_fault: Optional[Fault], sp) -> None:
        """Replay only the lost shards' lane windows on the survivors."""
        survivors = [d for d in range(len(self._mesh_devices))
                     if self.device_health.is_healthy(d)]
        if not survivors:
            telemetry.incr("serve_mesh_collapsed")
            for s, lo, hi in lost:
                for lane in range(lo, hi):
                    req = by_lane[lane]
                    if req is not None:
                        telemetry.incr("serve_failed")
                        req._finish(exc=last_fault)
            return
        # Deadline-aware resubmission: the straggler EWMA (scaled by
        # its deadline factor) estimates one replay window's wall time;
        # lanes that cannot make their deadline shed NOW with
        # Overloaded instead of wasting survivor capacity.
        est_s = self.straggler.deadline
        now = time.monotonic()
        for s, lo, hi in lost:
            for lane in range(lo, hi):
                req = by_lane[lane]
                if req is None or req.deadline is None:
                    continue
                if now >= req.deadline or (math.isfinite(est_s)
                                           and now + est_s > req.deadline):
                    if req._finish(exc=Overloaded(
                            "survivor mesh cannot absorb the replay "
                            "before this request's deadline")):
                        telemetry.incr("serve_shed")
                        by_lane[lane] = None
        rr = itertools.cycle(survivors)
        for s, lo, hi in lost:
            live = [lane for lane in range(lo, hi)
                    if by_lane[lane] is not None
                    and not by_lane[lane].done()]
            # Idempotent replay: a window whose live lanes all have
            # journaled results (an earlier replay got them) re-serves
            # from the journal without re-executing.
            pending = [lane for lane in live
                       if self.journal.get(by_lane[lane].rid) is None]
            if live and not pending:
                for lane in live:
                    req = by_lane[lane]
                    if req._finish(value=self.journal.get(req.rid),
                                   backend="journal"):
                        telemetry.incr("serve_completed")
                continue
            if not live:
                continue  # nothing real in this window survived
            device_index = next(rr)
            try:
                res = self._run_shard(op, geom, data[lo:hi], s,
                                      device_index)
            except Fault as e:
                at_fault = self._device_of_fault(e)
                self._force_trip(at_fault if at_fault is not None
                                 else device_index)
                sp.event("replay_lost", shard=s, device=device_index)
                for lane in live:
                    telemetry.incr("serve_failed")
                    by_lane[lane]._finish(exc=e)
                continue
            telemetry.incr("lanes_replayed", len(live))
            sp.event("replayed", shard=s, lanes=len(live),
                     device=device_index)
            self.device_health.record_success(device_index)
            for lane in live:
                req = by_lane[lane]
                self.journal.record(req.rid, res.value[lane - lo])
                if req._finish(value=res.value[lane - lo],
                               backend=res.backend):
                    telemetry.incr("serve_completed")

    def run_once(self) -> int:
        """Process one batch synchronously (deterministic test hook).

        Returns the number of requests removed from the queue (completed,
        timed out, or skipped-as-cancelled); 0 means the queue was empty.
        """
        with self._lock:
            batch, rejected = self._take_batch_locked()
        if batch:
            self._feed(*self._stage(batch))
        return len(batch) + rejected

    def _stage(self, batch: list) -> tuple:
        """``(batch, prepared, t_ready)``: the bucket prepared, and when
        it became ready to feed (stamped only while spans record)."""
        prepared = self._prepare(batch)
        return (batch, prepared,
                time.perf_counter() if _obs.enabled() else None)

    def _feed(self, batch: list, prepared: tuple,
              t_ready: Optional[float]) -> None:
        """The feed thread's whole handling of one bucket, from its
        pick-up until every request of it is finished.  The time the
        bucket stood ready before that is its ``bucket_wait``."""
        head = batch[0]
        if t_ready is not None:
            _obs.span_at("bucket_wait", t_ready, time.perf_counter(),
                         trace_id=head.trace_id, op=head.op,
                         lanes=len(batch))
        with _obs.span("bucket_feed", trace_id=head.trace_id, op=head.op,
                       lanes=len(batch)):
            self._execute_batch(batch, prepared)

    def _prep_loop(self) -> None:
        """Double-buffer producer: pack/pad the next bucket while the
        feed thread's current absorb still owns the device.  The bounded
        staging queue (depth 2) provides the backpressure."""
        while True:
            with self._work:
                while self._running and not self._queue:
                    self._work.wait(self.opt.poll_interval_s)
                if not self._running and not self._queue:
                    break
                batch, _ = self._take_batch_locked()
            if batch:
                try:
                    _staging_put(self._staging, self._stage(batch))
                except Exception:  # noqa: BLE001 — staging drop/chaos
                    # A dropped staging put must not lose requests: the
                    # batch goes back to the FRONT of the admission
                    # queue (it still holds the oldest requests) and is
                    # re-taken — and re-prepared — on the next pass.
                    telemetry.incr("serve_staging_drops")
                    with self._work:
                        self._queue.extendleft(reversed(batch))
                        self._work.notify()
        self._staging.put(None)  # sentinel: feed thread drains then exits

    def _next_staged(self) -> Optional[tuple]:
        """The next bucket the prep thread staged; None once it stops."""
        while True:
            try:
                return self._staging.get(timeout=self.opt.poll_interval_s)
            except queue_mod.Empty:
                self.heartbeats.beat(0)

    def _next_taken(self) -> Optional[list]:
        """Single-buffered: the next batch taken from the admission
        queue on this thread; None once the engine stops."""
        while True:
            with self._work:
                while self._running and not self._queue:
                    self._work.wait(self.opt.poll_interval_s)
                if not self._running and not self._queue:
                    return None
                batch, _ = self._take_batch_locked()
            if batch:
                return batch
            self.heartbeats.beat(0)

    def _worker_loop(self) -> None:
        staged = self.opt.double_buffer
        while True:
            with _obs.span("feed_wait") as wait:
                item = self._next_staged() if staged else self._next_taken()
                if item is not None:
                    batch = item[0] if staged else item
                    wait.set(trace_id=batch[0].trace_id, op=batch[0].op,
                             lanes=len(batch))
            if item is None:
                return
            self.heartbeats.beat(0)
            self._feed(*(item if staged else self._stage(item)))

    # -- supervision --------------------------------------------------------

    def check_workers(self) -> list:
        """Watchdog tick: hosts at/over the miss threshold (the worker
        beats once per dispatched batch/poll).  Call periodically from a
        supervisor; a returned ``[0]`` means the device feed is wedged."""
        missed = self.heartbeats.tick()
        if missed:
            telemetry.incr("serve_watchdog_misses")
        return missed

    def stats(self) -> dict:
        """Queue/telemetry/breaker snapshot for dashboards and tests."""
        snap = telemetry.snapshot()
        out = {k: v for k, v in snap.items()
               if k.startswith(("serve_", "resilience_"))}
        out["queue_depth"] = self.queue_depth()
        out["breaker_open"] = [
            list(map(str, k)) for k in self.executor.breaker.open_keys()]
        out["straggler_deadline_s"] = self.straggler.deadline
        out["tuning_entries"] = len(self.tuning)
        out["journal_depth"] = self.journal.depth()
        if self.device_health is not None:
            mesh = self._active_mesh()
            out["mesh_devices"] = len(self._mesh_devices)
            out["mesh_active"] = (0 if mesh is None
                                  else int(np.prod(list(
                                      dict(mesh.shape).values()))))
            out["mesh_lost"] = self.device_health.lost()
        return out
