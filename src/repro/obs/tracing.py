"""Thread-safe spans for the permutation engine (`repro.obs`).

``core.telemetry`` counts *how many* passes/launches happened; this
module records *when* and *how long*.  A span is one timed host-side
unit of work — a crossbar pass, a megakernel launch, a collective
apply, a serving request's queue wait — with a name, free-form
attributes, a thread, and a trace ID that groups every span belonging
to one logical request even when its stages execute on different
threads (the serving engine's admission / prep / device-feed split).

Design constraints, in order:

* **No-op when disabled.**  Tracing is off by default (enable with
  ``REPRO_OBS=1`` or ``obs.enable()``); a disabled ``span()`` returns a
  two-slot timer object and touches no locks, no ids, and no shared
  state.  The timer still measures its own duration — callers like the
  serving engine feed ``core.tuning``'s EWMA from span timings, and
  that feed must work whether or not anything is being *recorded* —
  but two ``perf_counter`` calls is the entire disabled cost.
* **Thread-safe when enabled.**  Finished spans land in a bounded ring
  buffer under one lock; span/trace IDs come from an atomic counter.
  The serving engine's three threads (admission, host-prep,
  device-feed) record concurrently.
* **Stdlib only.**  This module is imported from the bottom of the
  engine (``core.crossbar``) and must not import anything from
  ``repro`` — metrics feeding happens via a registered sink callback
  (``repro.obs.metrics`` installs itself on import).
* **One clock with the device profile.**  A recording span named in
  ``PROFILER_SPANS`` or ``SESSION_PROFILER_SPANS`` also enters
  ``jax.profiler.TraceAnnotation(name)`` (the name alone, no
  attributes), so the host phases that leave the device idle show up
  in a ``jax.profiler`` trace on ``/host:CPU``, in the recording
  thread's line, beside the device's operations.
  Only leaf phases are mirrored: an enclosing span (``bucket_feed``,
  ``device_absorb``, ``resilient_execute``, ``registry_observe``,
  ``gcm_seal``, ``request``) would overlap every gap between device
  operations and hide the phase that fills it.  Spans recorded after
  the fact with ``span_at`` (``queue_wait``, ``bucket_wait``,
  ``request``) are waits between two threads, not work on one, and
  never reach the profiler.  ``jax.profiler`` is looked up in
  ``sys.modules`` when such a span opens, never imported here: a
  process that has not imported JAX has no device to line up with.
* **Garbage collection is a phase too.**  While recording is on, a
  ``gc.callbacks`` hook times each collection of the oldest generation
  as a mirrored ``gc_pause`` span on the collecting thread (every other
  thread is held up meanwhile).  The hook records nothing itself — a
  collection can start inside ``_record`` with its lock held — and
  leaves the finished span for the next ``_record`` or
  ``finished_spans()`` to file.  ``disable()`` removes the hook.

The buffer is exported two ways: ``finished_spans()`` (raw records,
consumed by the metrics histograms and tests) and
``repro.obs.timeline`` (Chrome/Perfetto trace-event JSON).
"""

from __future__ import annotations

import collections
import gc
import itertools
import os
import sys
import threading
import time
from typing import Callable, Optional

# The trace epoch: every span timestamp is perf_counter() relative to
# this, so exported timelines start near zero and remain monotonic
# across threads (perf_counter is a global clock on CPython >= 3.3).
_EPOCH = time.perf_counter()

_IDS = itertools.count(1)  # span + trace ids (atomic under the GIL)

# Ring buffer of finished _Span objects.  Bounded: a 10^6-request mesh
# run must not hold 10^6 span dicts alive — the default keeps the most
# recent window, and exporters say how much was dropped.
DEFAULT_BUFFER_CAP = 200_000

_LOCK = threading.Lock()
_SPANS: "collections.deque" = collections.deque(maxlen=DEFAULT_BUFFER_CAP)
_DROPPED = 0          # spans evicted from the ring since last clear()
_DISABLED_CALLS = 0   # span() calls taken on the disabled fast path

# Sinks: callables fired on every finished recorded span (the metrics
# module registers its histogram feed here; tests can register probes).
_SINKS: "list[Callable]" = []

# Per-thread span stack: parent ids + trace-id inheritance.
_TLS = threading.local()


def _truthy_env(name: str, default: str = "0") -> bool:
    return os.environ.get(name, default).strip().lower() not in (
        "", "0", "false", "no", "off")


# Spans that enter a jax.profiler.TraceAnnotation of their name while
# they record: the leaf host phases of serving any bucket, the
# megakernel launch, and garbage collection.
PROFILER_SPANS = frozenset({
    "feed_wait", "bucket_pack", "bucket_launch", "bucket_sync",
    "bucket_unpack", "program_launch", "gc_pause"})
# The leaf phase that only AEAD buckets have, mirrored alike: the
# lookup of each lane's session key material.
SESSION_PROFILER_SPANS = frozenset({"key_lookup"})


def _annotation(name: str):
    """An entered ``TraceAnnotation(name)``, or None where JAX's
    profiler has not been imported."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None
    ann = profiler.TraceAnnotation(name)
    ann.__enter__()
    return ann


# The collection of the oldest generation in progress ([t0, annotation])
# and the finished gc_pause spans waiting to be filed.
_GC_OPEN: list = []
_GC_DONE: "collections.deque" = collections.deque()


def _gc_hook(phase: str, info: dict) -> None:
    if info["generation"] != 2:
        return
    if phase == "start":
        _GC_OPEN[:] = [time.perf_counter(), _annotation("gc_pause")]
        return
    if not _GC_OPEN:
        return
    t1 = time.perf_counter()
    t0, ann = _GC_OPEN
    _GC_OPEN.clear()
    if ann is not None:
        ann.__exit__(None, None, None)
    sp = Span("gc_pause", {"collected": info["collected"]})
    sp.trace_id = next(_IDS)
    sp.t0, sp.t1 = t0, t1
    _GC_DONE.append(sp)


def _set_gc_hook(on: bool) -> None:
    if on and _gc_hook not in gc.callbacks:
        gc.callbacks.append(_gc_hook)
    elif not on and _gc_hook in gc.callbacks:
        gc.callbacks.remove(_gc_hook)
        _GC_OPEN.clear()


_ENABLED = _truthy_env("REPRO_OBS")


def enabled() -> bool:
    """Is span recording on?  (Module-global; default off.)"""
    return _ENABLED


def enable() -> None:
    global _ENABLED
    _ENABLED = True
    _set_gc_hook(True)


def disable() -> None:
    global _ENABLED
    _ENABLED = False
    _set_gc_hook(False)


def new_trace_id() -> int:
    """A fresh trace ID (request-scoped grouping key for spans)."""
    return next(_IDS)


def current_trace_id() -> Optional[int]:
    """The trace ID of the innermost open span on this thread, if any."""
    stack = getattr(_TLS, "stack", None)
    if stack:
        return stack[-1].trace_id
    return getattr(_TLS, "trace_id", None)


class _NullSpan:
    """The disabled fast path: a timer and nothing else.

    Still context-managed and still measures its own wall time (the
    tuning-table feed reads ``duration_s`` regardless of recording),
    but records nothing, allocates no ids, and takes no locks.
    """

    __slots__ = ("t0", "t1")
    recording = False
    trace_id = None
    span_id = None

    def __enter__(self) -> "_NullSpan":
        global _DISABLED_CALLS
        _DISABLED_CALLS += 1
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0

    def set(self, **attrs) -> "_NullSpan":
        return self

    def event(self, name: str, **attrs) -> None:
        pass


class Span:
    """One recorded timed region.

    ``trace_id`` groups spans across threads: pass it explicitly to
    adopt a request's trace (the serving engine stamps each request at
    admission and hands the id to the prep and device-feed threads), or
    leave it None to inherit from the enclosing span on this thread
    (falling back to a fresh id for a root span).
    """

    __slots__ = ("name", "attrs", "trace_id", "span_id", "parent_id",
                 "thread_id", "thread_name", "t0", "t1", "events",
                 "annotation")

    recording = True

    def __init__(self, name: str, attrs: dict,
                 trace_id: Optional[int] = None):
        self.name = name
        self.attrs = attrs
        self.trace_id = trace_id
        self.span_id = next(_IDS)
        self.parent_id: Optional[int] = None
        t = threading.current_thread()
        self.thread_id = t.ident or 0
        self.thread_name = t.name
        self.t0 = 0.0
        self.t1 = 0.0
        self.events: "list[tuple]" = []
        self.annotation = None

    def __enter__(self) -> "Span":
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        if stack:
            self.parent_id = stack[-1].span_id
            if self.trace_id is None:
                self.trace_id = stack[-1].trace_id
        if self.trace_id is None:
            self.trace_id = getattr(_TLS, "trace_id", None) or next(_IDS)
        stack.append(self)
        if self.name in PROFILER_SPANS or self.name in SESSION_PROFILER_SPANS:
            self.annotation = _annotation(self.name)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.t1 = time.perf_counter()
        if self.annotation is not None:
            self.annotation.__exit__(None, None, None)
        stack = getattr(_TLS, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        elif stack and self in stack:   # mis-nested exit: still unwind
            stack.remove(self)
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        _record(self)

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0

    def set(self, *, trace_id: Optional[int] = None, **attrs) -> "Span":
        """Attach attributes discovered mid-span (resolved backend,
        batch size after padding, ...); ``trace_id`` adopts a trace
        learnt mid-span (a wait that finds out what it waited for)."""
        if trace_id is not None:
            self.trace_id = trace_id
        self.attrs.update(attrs)
        return self

    def event(self, name: str, **attrs) -> None:
        """A zero-duration mark inside this span (retry, fallback,
        breaker trip) — exported as an instant event on the timeline."""
        self.events.append((name, time.perf_counter(), attrs))


def span(name: str, *, trace_id: Optional[int] = None, **attrs):
    """Open a span.  The ONE instrumentation entry point.

    Usage::

        with obs.span("apply_plan", backend="einsum") as sp:
            ...
            sp.set(n_out=plan.n_out)

    Disabled (the default): returns a ``_NullSpan`` — a bare timer, no
    recording, no locks.  Enabled: returns a ``Span`` that lands in the
    ring buffer on exit and feeds every registered sink.
    """
    if not _ENABLED:
        return _NullSpan()
    return Span(name, attrs, trace_id)


def span_at(name: str, t0: float, t1: float, *,
            trace_id: Optional[int] = None, thread_name: Optional[str] = None,
            **attrs) -> None:
    """Record a span retroactively from two ``perf_counter`` readings.

    For phases whose boundaries are only known after the fact — a
    serving request's queue wait is (submit time, batch-take time),
    measured on two different threads.  No-op when disabled.
    """
    if not _ENABLED:
        return
    sp = Span(name, attrs, trace_id)
    if sp.trace_id is None:
        sp.trace_id = next(_IDS)
    sp.t0, sp.t1 = t0, t1
    if thread_name is not None:
        sp.thread_name = thread_name
    _record(sp)


def event(name: str, *, trace_id: Optional[int] = None, **attrs) -> None:
    """A free-standing instant event (zero-duration span)."""
    if not _ENABLED:
        return
    t = time.perf_counter()
    span_at(name, t, t, trace_id=trace_id, **attrs)


def _record(sp: Span) -> None:
    _file(sp)
    _file_gc_pauses()


def _file_gc_pauses() -> None:
    while _GC_DONE:
        try:
            sp = _GC_DONE.popleft()
        except IndexError:  # another thread filed the last one
            return
        _file(sp)


def _file(sp: Span) -> None:
    global _DROPPED
    with _LOCK:
        if len(_SPANS) == _SPANS.maxlen:
            _DROPPED += 1
        _SPANS.append(sp)
        sinks = tuple(_SINKS)
    for sink in sinks:
        try:
            sink(sp)
        except Exception:  # noqa: BLE001 — a broken sink must not
            pass           # take down the instrumented hot path


def add_sink(fn: Callable) -> None:
    """Register a callable fired with every finished recorded span."""
    with _LOCK:
        if fn not in _SINKS:
            _SINKS.append(fn)


def finished_spans() -> list:
    """A consistent copy of the ring buffer (oldest first)."""
    _file_gc_pauses()
    with _LOCK:
        return list(_SPANS)


def dropped_count() -> int:
    with _LOCK:
        return _DROPPED


def disabled_call_count() -> int:
    """How many ``span()`` calls took the disabled fast path — the
    numerator of the instrumentation-overhead bound checked in CI."""
    return _DISABLED_CALLS


def clear() -> None:
    """Drop recorded spans and reset drop/disabled counters (test
    isolation; sinks and the enabled flag are preserved)."""
    global _DROPPED, _DISABLED_CALLS
    with _LOCK:
        _SPANS.clear()
        _DROPPED = 0
    _GC_DONE.clear()
    _DISABLED_CALLS = 0


def set_buffer_capacity(cap: int) -> None:
    """Resize the ring buffer (keeps the newest spans)."""
    global _SPANS
    if cap < 1:
        raise ValueError(f"span buffer capacity must be >= 1, got {cap}")
    with _LOCK:
        _SPANS = collections.deque(_SPANS, maxlen=cap)


_set_gc_hook(_ENABLED)
