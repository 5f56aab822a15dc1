"""Tick tracing: Chrome-trace-event spans for every scheduler tick.

Each scheduler tick decomposes into host phases — admission, append-page
assurance, packing (the budget split across concurrent prefills), the one
device dispatch, and postprocessing (emit/finish/install) — and the
tracer records each as a complete ("X") event with microsecond
timestamps, plus instant events for request lifecycle transitions
(finish, preempt, prefill abort, fork). The output of :meth:`write` is a
standard Chrome trace-event JSON object (``{"traceEvents": [...]}``)
loadable directly in ``chrome://tracing`` or https://ui.perfetto.dev —
no custom viewer.

The tracer is deliberately host-only and allocation-light: a disabled
tracer's :meth:`span` returns one shared reusable null context and its
event methods are no-ops, so tracing can stay compiled into the
scheduler's hot loop. Like the metrics registry it never reaches inside
jitted code. An enabled tracer also opens every span as a
``jax.profiler.TraceAnnotation`` carrying the span's arguments, so any
profiler session that is collecting (the opt-in bracket of :meth:`start`
/ :meth:`stop`, ``jax.profiler.trace``, a profiler server) records the
span, with its arguments as event stats, on the host plane of the same
clock as the device's programs; with no session collecting the
annotation costs about a microsecond.
"""
from __future__ import annotations

import json
import time
from contextlib import contextmanager
from typing import List, Optional

from jax.profiler import TraceAnnotation, start_trace, stop_trace


class _NullContext:
    """Reusable no-op context (``contextlib.nullcontext`` allocates one
    object per ``with``; this one is shared)."""

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullContext()


class TickTracer:
    """Span/instant/counter event recorder in Chrome trace-event format."""

    def __init__(self, enabled: bool = True,
                 jax_profile_dir: Optional[str] = None):
        self.enabled = enabled
        self.events: List[dict] = []
        self.jax_profile_dir = jax_profile_dir
        self._profiling = False
        self._t0 = time.perf_counter()
        if enabled:
            # process metadata so trace viewers label the track
            self.events.append({"ph": "M", "pid": 0, "tid": 0,
                                "name": "process_name",
                                "args": {"name": "serve scheduler"}})

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    # ------------------------------------------------------------------
    # events
    # ------------------------------------------------------------------
    def span(self, name: str, **args):
        """Context manager recording a complete event around its body."""
        if not self.enabled:
            return _NULL_CTX
        return self._span(name, args)

    @contextmanager
    def _span(self, name: str, args: dict):
        with TraceAnnotation(name, **args):
            t0 = self._now_us()
            try:
                yield None
            finally:
                ev = {"ph": "X", "pid": 0, "tid": 0, "name": name,
                      "ts": t0, "dur": self._now_us() - t0}
                if args:
                    ev["args"] = args
                self.events.append(ev)

    def instant(self, name: str, **args) -> None:
        """Zero-duration lifecycle marker (finish / preempt / fork)."""
        if not self.enabled:
            return
        ev = {"ph": "i", "pid": 0, "tid": 0, "name": name,
              "ts": self._now_us(), "s": "t"}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def counter(self, name: str, **values) -> None:
        """Counter-track sample: per-tick levels (pages in use, queue
        depth) render as stacked area charts in the trace viewer."""
        if not self.enabled:
            return
        self.events.append({"ph": "C", "pid": 0, "tid": 0, "name": name,
                            "ts": self._now_us(), "args": values})

    # ------------------------------------------------------------------
    # optional jax.profiler bracket
    # ------------------------------------------------------------------
    def start(self) -> None:
        """Open the opt-in device-profiler bracket (no-op without a
        ``jax_profile_dir``)."""
        if self.enabled and self.jax_profile_dir and not self._profiling:
            start_trace(self.jax_profile_dir)
            self._profiling = True

    def stop(self) -> None:
        if self._profiling:
            stop_trace()
            self._profiling = False

    # ------------------------------------------------------------------
    # export
    # ------------------------------------------------------------------
    def trace_object(self) -> dict:
        return {"traceEvents": self.events, "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        """Write the Perfetto/chrome://tracing-loadable trace JSON."""
        with open(path, "w") as f:
            json.dump(self.trace_object(), f)
            f.write("\n")


NULL_TRACER = TickTracer(enabled=False)
