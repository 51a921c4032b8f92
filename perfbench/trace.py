"""Reduce a profiler trace (``.xplane.pb``) to what the readers need.

* busy time: the union of the intervals in which an operation ran on a
  device, averaged over the devices traced;
* device operations by name, with their count and summed duration, so
  a reader can take one kernel's events;
* the longest idle gaps between device operations, each named by the
  host event that overlaps it most (ties go to the shorter event):
  what the host was doing while the device waited.

Only ``jax.profiler.ProfileData`` is used to read the file.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
TOP = 10
# An XLA op event is named by its HLO text: "%name = shape opcode(...)".
_HLO = re.compile(r"^(%\S+) = .*? ([\w-]+)\(")


def short_name(op: str) -> str:
    """``"%name opcode"`` of an HLO op's text; other names unchanged."""
    m = _HLO.match(op)
    return f"{m.group(1)} {m.group(2)}" if m else op


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    n_devices: int
    ops: dict          # name -> [count, seconds], over every device
    gaps: list         # [[host event name, seconds]], longest first

    def kernel(self, name_part: str) -> tuple:
        """(count, seconds) of the device operations whose name holds
        ``name_part``."""
        count, seconds = 0, 0.0
        for name, (c, s) in self.ops.items():
            if name_part in name:
                count += c
                seconds += s
        return count, seconds

    def breakdown(self) -> dict:
        by_short: dict = {}
        for name, (_, s) in self.ops.items():
            key = short_name(name)
            by_short[key] = by_short.get(key, 0.0) + s
        top = sorted(by_short.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[name, s] for name, s in top],
                "idle_gaps": [list(g) for g in self.gaps[:TOP]]}


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals) -> list:
    """Merge ``[(start, end)]`` into disjoint sorted intervals."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def _name_gap(start: float, end: float, host: list) -> str:
    best, best_key = "no host event", (0.0, 0.0)
    for name, h0, h1 in host:
        overlap = min(end, h1) - max(start, h0)
        if overlap > 0:
            key = (overlap, -(h1 - h0))
            if key > best_key:
                best, best_key = name, key
    return best


def summarize(planes, *, window_s: float) -> Summary:
    """``planes``: ``[(plane name, [(line name, [(event name, start_ns,
    duration_ns)])])]``, the shape ``ProfileData`` has."""
    ops: dict = {}
    host: list = []
    busy_ns, n_devices, gaps = 0.0, 0, []
    for plane, lines in planes:
        if plane.startswith(DEVICE_PREFIX):
            intervals = []
            for line, events in lines:
                if line != OPS_LINE:
                    continue
                for name, start, dur in events:
                    intervals.append((start, start + dur))
                    entry = ops.setdefault(name, [0, 0.0])
                    entry[0] += 1
                    entry[1] += dur * 1e-9
            if not intervals:
                continue
            n_devices += 1
            merged = union(intervals)
            busy_ns += sum(e - s for s, e in merged)
            gaps += [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
        elif plane == HOST_PLANE:
            host += [(name, start, start + dur)
                     for _, events in lines for name, start, dur in events
                     if dur > 0]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [[_name_gap(s, e, host), (e - s) * 1e-9] for s, e in gaps[:TOP]]
    return Summary(window_s=window_s,
                   busy_s=busy_ns * 1e-9 / max(1, n_devices),
                   n_devices=n_devices, ops=ops, gaps=named)


def read(path: str):
    """The planes of one ``.xplane.pb`` in ``summarize``'s shape."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    return [(p.name, [(ln.name, [(e.name, e.start_ns, e.duration_ns)
                                 for e in ln.events])
                      for ln in p.lines])
            for p in data.planes]


def reduce(log_dir: str, *, window_s: float) -> Summary:
    return summarize(read(find_xplane(log_dir)), window_s=window_s)
