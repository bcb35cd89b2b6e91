"""From a profiler trace to numbers — part of the yardstick.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX. On a TPU the
plane ``/device:TPU:<n>`` has a line ``XLA Modules`` (one event per run
of an executable) and a line ``XLA Ops`` (one event per HLO operation,
its name the operation's HLO text, so a Mosaic kernel reads
``... custom-call(...), custom_call_target="tpu_custom_call"`` and a
collective ``all-reduce(`` without any name in the program). Host
threads are lines of ``/host:CPU``; ``jax.profiler.TraceAnnotation``
spans land there on the same clock.

Everything below the reader works on plain intervals, so it is tested
on hand-built ones.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]          # (start, end) in seconds

MOSAIC_MARK = 'custom_call_target="tpu_custom_call"'
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "all-to-all", "collective-permute", "collective-broadcast")
# operations that only hold other operations: their time is their
# children's, which the line lists too
CONTAINER_OPS = ("while", "conditional", "call")
# ``%name = SHAPE opcode(operands``: a shape ends in ``]``, ``}`` or ``)``
_OPCODE = re.compile(r"[\]\})] ([a-z][a-z0-9\-]*)\(")


class TraceError(Exception):
    """The trace could not be written, found or read: a traced run
    fails, it never reports zeros."""


@dataclass
class Event:
    name: str
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclass
class DeviceTrace:
    device: str
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[DeviceTrace]
    host: Dict[str, List[Event]]       # thread line -> events


def opcode(hlo_text: str) -> str:
    """``fusion``, ``custom-call``, ``all-reduce`` ... of an XLA Ops
    event name; '' where the name is not HLO text."""
    m = _OPCODE.search(hlo_text) if " = " in hlo_text else None
    return m.group(1) if m else ""


def short_name(hlo_text: str, limit: int = 96) -> str:
    """Enough of an operation to recognise it in a ledger line: its
    name, what it is and the shape it yields."""
    m = _OPCODE.search(hlo_text) if " = " in hlo_text else None
    if m is None:
        return hlo_text[:limit]
    name, shape = hlo_text[:m.start() + 1].split(" = ", 1)
    mark = " [mosaic]" if MOSAIC_MARK in hlo_text else ""
    return ("%s %s%s %s" % (name, m.group(1), mark, shape))[:limit]


def is_mosaic(name: str) -> bool:
    return MOSAIC_MARK in name and opcode(name) == "custom-call"


def is_collective(name: str) -> bool:
    op = opcode(name)
    return any(op == c or op == c + "-start" or op == c + "-done"
               for c in COLLECTIVE_OPS)


def is_container(name: str) -> bool:
    return opcode(name) in CONTAINER_OPS


# -- interval arithmetic ----------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the intervals."""
    out: List[List[float]] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def total(intervals: Sequence[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of ``a`` (disjoint, sorted) that ``b`` (same) leaves."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def busy_and_window(ops: Sequence[Event]) -> Tuple[float, float]:
    """(seconds in which some operation ran, seconds from the first
    operation's start to the last one's end)."""
    if not ops:
        raise TraceError("no operation ran on the device in the trace")
    cover = union((e.start, e.end) for e in ops)
    return total(cover), cover[-1][1] - cover[0][0]


def idle_gaps(ops: Sequence[Event]) -> List[Interval]:
    cover = union((e.start, e.end) for e in ops)
    return [(a[1], b[0]) for a, b in zip(cover, cover[1:])]


def exposed_collective_seconds(ops: Sequence[Event]) -> float:
    """Seconds in which a collective runs and no other operation does."""
    coll = union((e.start, e.end) for e in ops if is_collective(e.name))
    rest = union((e.start, e.end) for e in ops
                 if not is_collective(e.name) and not is_container(e.name))
    return total(subtract(coll, rest))


def time_by_name(ops: Sequence[Event], top: int = 10
                 ) -> List[Tuple[str, float]]:
    """The operations that took most device time, containers left out
    (their children are listed); same-named events add up."""
    acc: Dict[str, float] = {}
    for e in ops:
        if is_container(e.name):
            continue
        key = short_name(e.name)
        acc[key] = acc.get(key, 0.0) + e.seconds
    return sorted(acc.items(), key=lambda kv: -kv[1])[:top]


def attribute_gaps(gaps: Sequence[Interval], host: Dict[str, List[Event]],
                   top: int = 10, prefer: Sequence[str] = ()
                   ) -> List[Tuple[str, float]]:
    """What the host was doing in each idle gap: the span in ``prefer``
    (the benchmark's own annotations, innermost last) that covers the
    gap's middle, else the shortest host event that does. Gaps add up
    by that name; the longest totals are returned."""
    spans = sorted((e for evs in host.values() for e in evs),
                   key=lambda e: e.start)
    starts = [e.start for e in spans]
    acc: Dict[str, float] = {}
    longest = max((e.seconds for e in spans), default=0.0)
    for lo, hi in gaps:
        mid = (lo + hi) / 2.0
        right = bisect.bisect_right(starts, mid)
        left = bisect.bisect_left(starts, mid - longest)
        covering = [e for e in spans[left:right] if e.end >= mid]
        name = "no host span"
        if covering:
            mine = [e for e in covering if e.name in prefer]
            pick = max(mine, key=lambda e: prefer.index(e.name)) if mine \
                else min(covering, key=lambda e: e.seconds)
            name = pick.name
        acc[name] = acc.get(name, 0.0) + (hi - lo)
    return sorted(acc.items(), key=lambda kv: -kv[1])[:top]


# -- reading the file -------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise TraceError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


def read(path: str) -> Trace:
    try:
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
    except Exception as e:          # the reader's own errors are untyped
        raise TraceError("cannot read %s: %s" % (path, e))
    devices: List[DeviceTrace] = []
    host: Dict[str, List[Event]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = DeviceTrace(plane.name)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = _events(line)
                elif line.name == "XLA Modules":
                    dev.modules = _events(line)
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                events = _events(line)
                if events:
                    host.setdefault(line.name, []).extend(events)
    if not devices:
        raise TraceError("no /device:TPU plane in %s" % path)
    return Trace(devices, host)


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        start = e.start_ns * 1e-9
        out.append(Event(e.name, start, start + e.duration_ns * 1e-9))
    return out


def summarize(trace: Trace, prefer: Sequence[str] = ()) -> Dict:
    """The reduction every traced run makes: busy and window averaged
    over the chips, the breakdown from the first chip, and per-chip
    Mosaic, collective and step-module figures for the readers."""
    busy, window = [], []
    for dev in trace.devices:
        b, w = busy_and_window(dev.ops)
        busy.append(b)
        window.append(w)
    first = trace.devices[0]
    by_module: Dict[str, List[float]] = {}
    for m in first.modules:
        by_module.setdefault(m.name, []).append(m.seconds)
    step_module = max(by_module, key=lambda k: sum(by_module[k])) \
        if by_module else ""
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": sum(window) / len(window),
        "device_ops": [[n, s] for n, s in time_by_name(first.ops)],
        "idle_gaps": [[n, s] for n, s in attribute_gaps(
            idle_gaps(first.ops), trace.host, prefer=prefer)],
        "mosaic_seconds": sum(e.seconds for e in first.ops
                              if is_mosaic(e.name)),
        "mosaic_calls": sum(1 for e in first.ops if is_mosaic(e.name)),
        "collective_exposed_s": exposed_collective_seconds(first.ops),
        "collective_s": total(union((e.start, e.end) for e in first.ops
                                    if is_collective(e.name))),
        "modules": {k: {"runs": len(v), "seconds": sum(v)}
                    for k, v in by_module.items()},
        "step_module": step_module,
    }
