"""Mean device time of the serve_step program on decode-only ticks
(packed width = slots), from the profiler trace."""
LAYER = "engine step"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(r):
    ts = [t.step_ns for t in r.ticks if t.width == r.widths()[0]
          and t.step_ns > 0]
    return sum(ts) / len(ts) * 1e-6 if ts else None
