"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

A TPU plane (``/device:TPU:<n>``) holds a line of XLA program executions
(``XLA Modules``) and a line of the operations inside them (``XLA Ops``);
host planes hold the annotations the benchmark and the scheduler's
``TickTracer`` open while the profiler runs, on the same clock. All
times here are nanoseconds on that clock.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


@dataclass(frozen=True)
class Ev:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    modules: Dict[str, List[Ev]] = field(default_factory=dict)  # per device
    ops: Dict[str, List[Ev]] = field(default_factory=dict)      # per device
    host: Dict[str, List[Ev]] = field(default_factory=dict)  # per thread

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)


def _events(line) -> List[Ev]:
    out = [Ev(e.name, float(e.start_ns), float(e.duration_ns))
           for e in line.events]
    out.sort(key=lambda e: e.start)
    return out


def from_profile(pd) -> Trace:
    """A ``jax.profiler.ProfileData`` reduced to the lines used here."""
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    tr.modules[plane.name] = _events(line)
                elif line.name == OPS_LINE:
                    tr.ops[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host[f"{plane.name}/{line.name}"] = _events(line)
    return tr


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def named(events: Iterable[Ev], pattern: str) -> List[Ev]:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.name)]


def within(events: Iterable[Ev], lo: float, hi: float) -> List[Ev]:
    """Events that start inside [lo, hi)."""
    return [e for e in events if lo <= e.start < hi]


def union(events: Iterable[Ev], lo: float, hi: float) -> float:
    """Length of the union of the events' intervals, clipped to [lo, hi]."""
    total, cur_lo, cur_hi = 0.0, None, None
    for e in sorted(events, key=lambda e: e.start):
        a, b = max(e.start, lo), min(e.end, hi)
        if b <= a:
            continue
        if cur_hi is None or a > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = a, b
        else:
            cur_hi = max(cur_hi, b)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def gaps(events: Iterable[Ev], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The idle intervals between the events' union, inside [lo, hi]."""
    out, t = [], lo
    for e in sorted(events, key=lambda e: e.start):
        if e.end <= t:
            continue
        if e.start > t:
            out.append((t, min(e.start, hi)))
        t = max(t, e.end)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def host_thread(tr: Trace, span_name: str) -> List[Ev]:
    """The events of the host thread that opened ``span_name`` (the
    thread that drives the scheduler)."""
    for evs in tr.host.values():
        if any(e.name == span_name for e in evs):
            return evs
    return []


def window(host: List[Ev], span_name: str) -> Optional[Tuple[float, float]]:
    """The traced slice: the host annotation ``span_name``."""
    spans = [e for e in host if e.name == span_name]
    if not spans:
        return None
    return spans[0].start, spans[0].end


def short_name(name: str) -> str:
    """An "XLA Ops" event is named by its whole HLO instruction; keep the
    instruction's name and, for an array result, its shape."""
    head, _, rest = name.partition(" = ")
    if not rest or rest.startswith("("):
        return head
    return f"{head} = {rest.split('{', 1)[0].split(' ', 1)[0]}"


def self_times(events: List[Ev]) -> List[Tuple[Ev, float]]:
    """Each event with its own time: its duration less that of the
    events nested directly inside it (a while loop holds its body's
    ops on the same line)."""
    order = sorted(events, key=lambda e: (e.start, -e.dur))
    own = [e.dur for e in order]
    stack: List[int] = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            own[stack[-1]] -= e.dur
        stack.append(i)
    return list(zip(order, own))


def top_ops(tr: Trace, lo: float, hi: float, n: int = 10):
    """[[op, seconds]] of the device ops that took most time by their own
    time (nested ops not counted twice), summed by instruction and
    averaged over the devices."""
    tot: Dict[str, float] = {}
    for evs in tr.ops.values():
        for e, own in self_times(within(evs, lo, hi)):
            key = short_name(e.name)
            tot[key] = tot.get(key, 0.0) + own
    k = max(1, len(tr.ops))
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k * 1e-9] for name, ns in best]


def _innermost(host: List[Ev], starts: List[float], t: float) -> str:
    """The latest-starting span still open at ``t`` (spans nest, so that
    is the innermost)."""
    i = bisect.bisect_right(starts, t)
    for e in reversed(host[max(0, i - 256):i]):
        if e.end >= t:
            return e.name
    return "(no host span)"


def idle_by_host(tr: Trace, thread: List[Ev], lo: float, hi: float,
                 ignore: Tuple[str, ...], n: int = 10):
    """[[host span, seconds]]: the device's idle time inside [lo, hi],
    summed by the innermost host span open at each gap's midpoint
    (``ignore`` names spans too wide to say anything, such as the window
    itself; the Python tracer's per-call events, named ``$...``, are
    skipped too), averaged over the devices."""
    host = [e for e in thread
            if e.name not in ignore and not e.name.startswith("$")]
    starts = [e.start for e in host]
    tot: Dict[str, float] = {}
    for evs in tr.ops.values():
        for a, b in gaps(evs, lo, hi):
            name = _innermost(host, starts, (a + b) / 2)
            tot[name] = tot.get(name, 0.0) + (b - a)
    k = max(1, len(tr.ops))
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k * 1e-9] for name, ns in best]
