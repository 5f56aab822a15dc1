"""Share of the traced slice in which no operation ran on the device:
1 - (union of device-op intervals) / (slice length)."""
from bench import readings

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(r):
    return readings.device_idle(r)
