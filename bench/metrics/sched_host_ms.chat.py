"""Scheduler host time per tick: the mean over the traced slice's ticks
of the ``tick`` span minus the ``dispatch`` spans inside it (admission,
page assurance, packing, postprocessing: the host work that sits between
device steps). Spans are the scheduler's own ``TickTracer`` spans, as
they enter the profiler's trace."""
from bench import trace as T

LAYER = "scheduler"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "itl_p95_ms"


def read(r):
    ticks = [e for e in T.within(r.thread, r.lo, r.hi) if e.name == "tick"
             and e.end <= r.hi]
    disp = [e for e in T.within(r.thread, r.lo, r.hi) if e.name == "dispatch"]
    if not ticks:
        return None
    host = [t.dur - sum(d.dur for d in T.within(disp, t.start, t.end))
            for t in ticks]
    return sum(host) / len(host) * 1e-6
