"""What a traced run hands the per-layer metric readers.

The traced slice of the window is the host annotation ``bench.window``;
each serve_step dispatch inside it is a ``bench.serve_step`` annotation,
matched in order to the dispatches the driver recorded (packed width,
greedy or sampled, token routing). The device's executions of the
serve_step program that start inside a dispatch's annotation are that
tick's step, and the ragged attention kernel's ops inside them are its
kernel time. Names, as the trace prints them on a TPU v5e with this
JAX, are the patterns below (read by hand from a chip trace; PERF.md).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from bench import costs
from bench import trace as T
from bench.costs import Dims
from bench.driver import STEP_SPAN, WINDOW_SPAN

# XLA module of the jitted ServeEngine._serve_step_impl, greedy or sampled:
# jit of a functools.partial, which the trace names jit__unknown(<id>)
STEP_PROGRAM = r"^jit__unknown\(|serve_step"
# the Mosaic ragged paged attention kernel: an "XLA Ops" event whose HLO
# text starts "%ragged_paged_attention.<n> = ... custom-call(...)"
RAGGED_KERNEL = r"^%ragged_paged_attention\b"


@dataclass
class Tick:
    width: int
    sampled: bool
    token_rows: np.ndarray
    token_pos: np.ndarray
    logit_idx: np.ndarray
    step_ns: float = 0.0        # device time of the serve_step program
    kernel_ns: float = 0.0      # device time of the ragged kernel's ops


@dataclass
class Readings:
    dims: Dims
    peaks: dict
    slots: int
    chunk: int
    trace: T.Trace
    thread: List[T.Ev]          # the scheduler thread's host events
    lo: float                   # the traced slice, ns
    hi: float
    ticks: List[Tick] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self) -> Optional[float]:
        """Union of device-op intervals in the slice, averaged over the
        devices; None where the trace holds no device ops."""
        if not self.trace.ops:
            return None
        tot = [T.union(evs, self.lo, self.hi)
               for evs in self.trace.ops.values()]
        return float(np.mean(tot)) * 1e-9

    def widths(self):
        return self.slots, self.slots - 1 + self.chunk


def build(tr: T.Trace, dispatches, dims: Dims, peaks: dict, slots: int,
          chunk: int) -> Optional[Readings]:
    """Match the profiled dispatches to the trace; None where the trace
    has no traced slice."""
    thread = T.host_thread(tr, WINDOW_SPAN)
    win = T.window(thread, WINDOW_SPAN)
    if win is None:
        return None
    r = Readings(dims, peaks, slots, chunk, tr, thread, win[0], win[1])
    spans = [e for e in thread if e.name == STEP_SPAN
             and r.lo <= e.start < r.hi]
    profiled = [d for d in dispatches if d.profiled]
    dev = tr.devices[0] if tr.devices else None
    modules = T.named(tr.modules.get(dev, []), STEP_PROGRAM)
    kernels = T.named(tr.ops.get(dev, []), RAGGED_KERNEL)
    for span, d in zip(spans, profiled):
        tick = Tick(d.width, d.sampled, d.token_rows, d.token_pos,
                    d.logit_idx)
        for m in T.within(modules, span.start, span.end):
            tick.step_ns += m.dur
            tick.kernel_ns += sum(k.dur for k in T.within(kernels, m.start,
                                                          m.end))
        r.ticks.append(tick)
    return r


def step_mfu(r: Readings) -> Optional[float]:
    """Model FLOPs the traced ticks' live tokens need, over the serve_step
    program's device time times the peak bf16 FLOP/s (%)."""
    ticks = [t for t in r.ticks if t.step_ns > 0]
    if not ticks:
        return None
    flops = sum(costs.step_flops(r.dims, t.token_rows, t.token_pos,
                                 t.logit_idx) for t in ticks)
    secs = sum(t.step_ns for t in ticks) * 1e-9
    return 100.0 * flops / (secs * r.peaks["bf16_flops_per_s"])


def ragged_bound(r: Readings):
    """(roofline seconds, kernel seconds, bound) over the traced ticks
    whose kernel ops were found. Each tick's roofline is the larger of
    its FLOPs over peak FLOP/s and its bytes over peak bandwidth; the
    bound names the side that holds most of the summed roofline."""
    ticks = [t for t in r.ticks if t.kernel_ns > 0]
    if not ticks:
        return None
    need = {"compute": 0.0, "memory": 0.0}
    for t in ticks:
        f, b = costs.ragged_attention_need(r.dims, t.token_rows, t.token_pos)
        tf = f / r.peaks["bf16_flops_per_s"]
        tb = b / r.peaks["hbm_bytes_per_s"]
        need["memory" if tb >= tf else "compute"] += max(tf, tb)
    bound = max(need, key=need.get)
    return sum(need.values()), sum(t.kernel_ns for t in ticks) * 1e-9, bound


def ragged_roofline(r: Readings) -> Optional[float]:
    got = ragged_bound(r)
    return None if got is None else 100.0 * got[0] / got[1]


def device_idle(r: Readings) -> Optional[float]:
    busy = r.busy_s()
    if busy is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy / r.window_s)
