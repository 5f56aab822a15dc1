"""Whole serve_step's share of the chip's peak bf16 FLOP/s: model FLOPs
the traced ticks' live tokens need (dead padding excluded, attention over
each token's live context included; bench/costs.py) over the summed
device time of the serve_step program times the peak."""
from bench import readings

LAYER = "model step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(r):
    return readings.step_mfu(r)
