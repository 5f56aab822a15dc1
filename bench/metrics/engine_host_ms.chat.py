"""Engine host time before the step, per tick: the mean over the traced
slice's ticks of the time in the tick's ``engine.inputs`` spans (the
packed batch, block tables, task ids and sampling vectors sent to the
device) and ``engine.launch`` spans (the jitted step's call until it
returns). The device waits on this work before each step starts; with
``sched_host_ms.chat`` (host work outside ``dispatch``) and
``engine_readback_ms.chat`` it splits the host-caused idle time of a
tick. Spans are the engine's own ``TickTracer`` spans, as they enter the
profiler's trace. A trace with no device plane has no device idle time
to split, and reads nothing."""
from bench import trace as T

LAYER = "engine step"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "itl_p95_ms"
SPANS = ("engine.inputs", "engine.launch")


def read(r):
    if not r.trace.ops:
        return None
    evs = T.within(r.thread, r.lo, r.hi)
    ticks = [e for e in evs if e.name == "tick" and e.end <= r.hi]
    host = [e for e in evs if e.name in SPANS]
    if not ticks or not host:
        return None
    per_tick = [sum(e.dur for e in T.within(host, t.start, t.end))
                for t in ticks]
    return sum(per_tick) / len(per_tick) * 1e-6
