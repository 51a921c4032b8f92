"""Drive an engine's ``submit`` for a window: an open loop on a schedule,
or a closed loop of clients that each send again when answered.

Every request becomes a ``Record``.  Its latency runs from the time it
was due to the engine's completion stamp (``Request.t_done``, on the
same ``perf_counter`` clock), so a stall that delays later sends counts
against them.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Optional

from repro.serve.batching import Overloaded

# How long past the window's close an answer may still come.
DRAIN_S = 60.0


@dataclasses.dataclass
class Record:
    index: int
    size: int
    due: float
    sent: float = 0.0
    req: object = None
    t_done: Optional[float] = None
    value: Optional[bytes] = None
    error: Optional[str] = None     # exception name, or "NoAnswer"
    correct: Optional[bool] = None  # set by the check


def _submit(engine, op: str, payload: bytes, rec: Record) -> Record:
    rec.sent = time.perf_counter()
    try:
        rec.req = engine.submit(payload, op=op)
    except Overloaded:
        rec.error = "Overloaded"
        rec.t_done = rec.sent
    return rec


def _wait(rec: Record, timeout: float) -> bool:
    """Wait for ``rec``'s answer up to ``timeout``; True once it has one."""
    if rec.req is None:
        return True
    if not rec.req.done():
        if timeout <= 0:
            return False
        try:
            rec.req.result(timeout=max(0.0, timeout))
        except Exception:  # noqa: BLE001 - any answer counts; read below
            pass
        if not rec.req.done():
            return False
    try:
        rec.value = rec.req.result(timeout=0)
    except Exception as e:  # noqa: BLE001 - a failed request is recorded
        rec.error = type(e).__name__
    rec.t_done = rec.req.t_done
    return True


def drain(records, deadline: float) -> None:
    """Collect every answer, waiting until ``deadline`` at the latest;
    a request still unanswered then is ``NoAnswer``."""
    for rec in records:
        if rec.t_done is None and not _wait(
                rec, deadline - time.perf_counter()):
            rec.error = "NoAnswer"


def open_loop(engine, op: str, payloads, schedule, t0: float) -> list:
    """Send ``payloads[i]`` at ``t0 + schedule[i][0]``; returns records
    of every request due in the window, answered or not."""
    records = []
    for i, ((offset, size), payload) in enumerate(zip(schedule, payloads)):
        due = t0 + offset
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        records.append(_submit(engine, op, payload,
                               Record(index=i, size=size, due=due)))
    return records


def closed_loop(engine, op: str, make_payload, sizes, clients: int,
                t0: float, t1: float) -> list:
    """``clients`` outstanding requests from ``t0``; each answered one is
    replaced until ``t1``.  ``make_payload(index, size)`` builds one."""
    records: list = []

    def send() -> Record:
        i, size = len(records), next(sizes)
        rec = Record(index=i, size=size, due=time.perf_counter())
        records.append(rec)
        return _submit(engine, op, make_payload(i, size), rec)

    while time.perf_counter() < t0:
        time.sleep(t0 - time.perf_counter())
    outstanding = collections.deque(send() for _ in range(clients))
    while True:
        now = time.perf_counter()
        if now >= t1:
            break
        # The engine serves the oldest request's bucket first, so the
        # head is always in the next batch to finish.
        _wait(outstanding[0], t1 - now)
        kept = collections.deque()
        for rec in outstanding:
            if rec.t_done is not None or _wait(rec, 0):
                if time.perf_counter() < t1:
                    kept.append(send())
            else:
                kept.append(rec)
        outstanding = kept
    return records
