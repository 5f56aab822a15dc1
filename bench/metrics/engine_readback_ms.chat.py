"""Engine readback per tick: the mean over the traced slice's ticks of
the part of the tick's ``engine.outputs`` spans (the watchdog's launch
and the reads of tokens and finite flags back to the host) that lies
after the last device op to start inside the tick has ended. The device
sits idle through that part while the results come back. Spans are the
engine's own ``TickTracer`` spans, as they enter the profiler's trace;
ops are the first device's."""
import bisect

from bench import trace as T

LAYER = "engine step"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "itl_p95_ms"


def read(r):
    if not r.trace.ops:
        return None
    ops = r.trace.ops[r.trace.devices[0]]
    starts = [e.start for e in ops]
    evs = T.within(r.thread, r.lo, r.hi)
    ticks = [e for e in evs if e.name == "tick" and e.end <= r.hi]
    outs = [e for e in evs if e.name == "engine.outputs"]
    if not ticks or not outs:
        return None
    per_tick = []
    for t in ticks:
        mine = ops[bisect.bisect_left(starts, t.start):
                   bisect.bisect_left(starts, t.end)]
        last = max((e.end for e in mine), default=t.start)
        per_tick.append(sum(max(0.0, o.end - max(o.start, last))
                            for o in T.within(outs, t.start, t.end)))
    return sum(per_tick) / len(per_tick) * 1e-6
