"""The ragged paged attention kernel's share of its roofline: the least
time the chip needs for the kernel's work (FLOPs over peak FLOP/s, or
bytes over peak HBM bandwidth, whichever is larger; bench/costs.py counts
each slot's live K/V pages once per layer plus the live queries and
outputs) over the kernel's device time in the trace."""
from bench import readings

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(r):
    return readings.ragged_roofline(r)
