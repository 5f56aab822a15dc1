"""Mean device time of the serve_step program on chunk ticks (packed
width = slots - 1 + prefill budget), from the profiler trace."""
LAYER = "engine step"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(r):
    ts = [t.step_ns for t in r.ticks if t.width == r.widths()[1]
          and t.step_ns > 0]
    return sum(ts) / len(ts) * 1e-6 if ts else None
