"""Drive the scheduler through one measured window on the host clock.

The window opens once set-up is done. Poisson traffic is submitted when
it falls due (between ticks: a request due during a tick is submitted
after it, and its time to first token still runs from its due time);
backlog traffic keeps the queue topped up. After the window closes, the
run keeps serving, with no new arrivals, until every request due in the
window has its first token and ``check_requests`` greedy requests have
finished (the sample the reference compares), or ``drain_seconds`` have
passed. Nothing after the close enters a metric but the first tokens of
requests due in the window.

Per tick the driver keeps the few numbers the metrics need: the tick's
end, its live and decode tokens. With ``profile`` set it also keeps each
dispatch's packed routing (for the cost functions), names each dispatch
``bench.serve_step`` and the traced slice ``bench.window`` in the
profiler's trace, and opens the profiler for that slice only.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

STEP_SPAN = "bench.serve_step"
WINDOW_SPAN = "bench.window"


@dataclass
class Dispatch:
    """One serve_step call."""
    t_end: float
    live: int                   # tokens with a position (not dead padding)
    decode: int                 # decode rows among them
    width: int = 0
    sampled: bool = False
    profiled: bool = False
    token_rows: Optional[np.ndarray] = None
    token_pos: Optional[np.ndarray] = None
    logit_idx: Optional[np.ndarray] = None


@dataclass
class Served:
    """What one request saw, on the host clock."""
    spec: object
    t_due: float = 0.0
    t_submit: float = 0.0
    times: List[float] = field(default_factory=list)    # per output token
    req: object = None


@dataclass
class Window:
    t0: float = 0.0
    t_end: float = 0.0          # the close: end of the last tick it started
    served: Dict[int, Served] = field(default_factory=dict)
    dispatches: List[Dispatch] = field(default_factory=list)
    trace_t0: float = 0.0
    trace_t1: float = 0.0
    compiles: int = 0           # compile events inside the window

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0

    def lateness(self) -> np.ndarray:
        """How late each due request was submitted (seconds)."""
        return np.array([s.t_submit - s.t_due for s in self.served.values()
                         if s.spec.due is not None])


def _instrument(engine, sched, win: Window, state: dict) -> None:
    """Wrap ``engine.serve_step`` (on the instance) to keep the tick's
    numbers; with the profiler open, name the call in its trace."""
    inner = engine.serve_step

    def serve_step(tokens, token_rows, token_pos, logit_idx, *rest):
        prof = state.get("annotation") is not None
        if prof:
            import jax
            with jax.profiler.TraceAnnotation(STEP_SPAN):
                out = inner(tokens, token_rows, token_pos, logit_idx, *rest)
        else:
            out = inner(tokens, token_rows, token_pos, logit_idx, *rest)
        d = Dispatch(t_end=time.perf_counter(),
                     live=int(np.count_nonzero(token_pos >= 0)),
                     decode=len(sched.running))
        if state.get("keep_routing"):
            d.width = len(token_pos)
            d.sampled = bool(np.any(rest[-1][0] > 0.0))
            d.profiled = prof
            d.token_rows = np.array(token_rows)
            d.token_pos = np.array(token_pos)
            d.logit_idx = np.array(logit_idx)
        win.dispatches.append(d)
        return out
    engine.serve_step = serve_step


def _request(spec, on_token):
    from repro.serve.sampling import SamplingParams
    from repro.serve.scheduler import Request
    sampling = (SamplingParams(temperature=spec.temperature, top_p=spec.top_p,
                               seed=spec.sample_seed)
                if spec.temperature > 0 else None)
    return Request(rid=spec.rid, prompt=spec.prompt, task_id=spec.task,
                   max_new_tokens=spec.max_new, on_token=on_token,
                   sampling=sampling)


def drive(system, sched, mix: dict, seconds: float, *, plan=None, source=None,
          profile: Optional[dict] = None) -> Window:
    """Serve one window. ``plan``: Poisson specs in due order; ``source``:
    a backlog iterator. ``profile``: ``{"start", "stop", "tracer"}``
    opens the device profiler (through the scheduler's ``TickTracer``,
    whose spans then enter the trace too) from ``start`` to ``stop``
    seconds into the window."""
    import jax
    win = Window()
    state = {"keep_routing": profile is not None}
    compiles = [0]

    def on_compile(name, *_a, **_k):
        if name.startswith("/jax/core/compile/"):
            compiles[0] += 1
    _instrument(system.engine, sched, win, state)
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        _serve(sched, mix, seconds, plan, source, profile, win, state,
               compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        del system.engine.serve_step        # back to the class's method
        if state.get("annotation") is not None:
            state["annotation"].__exit__(None, None, None)
            profile["tracer"].stop()
    return win


def _serve(sched, mix, seconds, plan, source, profile, win, state,
           compiles) -> None:
    import jax
    clock = time.perf_counter
    served = win.served

    def on_token(req, tok):
        served[req.rid].times.append(clock())

    def submit(spec, now):
        s = Served(spec=spec, t_submit=now,
                   t_due=win.t0 + (spec.due if spec.due is not None else 0.0))
        s.req = _request(spec, on_token)
        served[spec.rid] = s
        sched.submit(s.req)

    def profiler(now_rel):
        if profile is None:
            return
        ann = state.get("annotation")
        if ann is None and profile["start"] <= now_rel < profile["stop"]:
            profile["tracer"].start()       # opens jax.profiler
            ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            ann.__enter__()
            state["annotation"] = ann
            win.trace_t0 = clock()
        elif ann is not None and now_rel >= profile["stop"]:
            win.trace_t1 = clock()
            ann.__exit__(None, None, None)
            state["annotation"] = None
            profile["tracer"].stop()

    depth = int(mix.get("backlog", 0))
    i = 0
    win.t0 = clock()
    compiles[0] = 0
    while True:
        now = clock()
        rel = now - win.t0
        if rel >= seconds:
            break
        profiler(rel)
        if plan is not None:
            while i < len(plan) and plan[i].due <= rel:
                submit(plan[i], now)
                i += 1
        else:
            while len(sched.queue) < depth:
                submit(next(source), now)
        if sched.busy():
            sched.step()
        elif plan is not None:
            nxt = plan[i].due if i < len(plan) else seconds
            time.sleep(max(0.0, min(nxt, seconds) - rel))
    win.t_end = clock()
    win.compiles = compiles[0]
    profiler(float("inf"))
    if plan is not None:
        # requests due in the window but not yet submitted go in late
        now = clock()
        for spec in plan[i:]:
            submit(spec, now)
    greedy = [s for s in served.values() if s.spec.temperature == 0.0]
    need = min(int(mix["check_requests"]), len(greedy))

    def settled():
        return (all(s.times for s in served.values()
                    if s.spec.due is not None)
                and sum(s.req.state == "finished" for s in greedy) >= need)
    deadline = clock() + float(mix["drain_seconds"])
    while not settled() and sched.busy() and clock() < deadline:
        sched.step()
