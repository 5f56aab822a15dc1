"""Deterministic fault injection for the serving path.

A :class:`FaultPlan` is a *seeded, precomputed* schedule of faults — which
tick gets which fault is fixed at construction, so a chaos run is exactly
reproducible from ``(seed, horizon, rates)`` and a failing soak seed can be
replayed in a debugger. Each ``(tick, kind)`` pair draws from its own
``np.random.default_rng([seed, tick, salt])`` stream (salt = the kind's
index in :data:`FAULT_KINDS`), so adding a new fault kind — or zeroing a
rate — never reshuffles the schedule of the kinds that were already there.
Seven fault kinds, each exercising real overload/recovery machinery rather
than mocks:

  * ``exhaust`` — :meth:`PagedKVPool.seize_pages` pulls pages off the free
    list for a few ticks, so admission backpressure, decode preemption,
    prefill aborts, and (at total exhaustion) the last row's self-preempt
    all fire exactly as they would under genuine memory pressure.
  * ``straggler`` — a host-side stall (``time.sleep``) before the tick:
    wall-clock series degrade, tick series and tokens must not.
  * ``disconnect`` — a mid-stream client abort of a live request picked by
    the plan's own seeded uniform draw, through the public
    :meth:`ContinuousScheduler.abort` (queued / mid-prefill / mid-decode /
    forked — whatever state the victim happens to be in).
  * ``malformed`` — a garbage submission (empty prompt, ``n=0``,
    ``max_tokens=0``, unknown task id, NaN temperature) that MUST be
    rejected with :class:`InvalidRequest` and leave no state behind.
  * ``nan`` — poisons one running slot's logits row after the next
    dispatch (:meth:`ServeEngine.inject_fault`); the scheduler's watchdog
    must quarantine exactly that request and retry the tick, leaving every
    other stream bitwise untouched.
  * ``alloc_failure`` — the next dispatch raises :class:`DispatchFault`
    before launching; the self-healing tick loop must absorb it within
    ``tick_retries`` with zero observable effect on any stream.
  * ``crash`` — simulated process death: :func:`run_chaos` (when given a
    ``sched_factory``) abandons the scheduler mid-stream, replays its
    journal, restores a fresh scheduler, and keeps serving. Recovery rides
    the preempt-and-recompute path, so surviving streams stay bitwise
    identical.

The chaos invariants (test-enforced in tests/test_robustness.py): the
scheduler always drains, ``leak_report()`` comes back empty, and every
SURVIVING request's token stream is bitwise identical to a fault-free run
of the same arrivals — preempt-and-recompute is exact and every sample's
RNG stream is counter-based, so no amount of eviction, stalling, or
neighbor churn may change anyone's tokens.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.serve.sampling import SamplingParams

# Order is load-bearing: a kind's index is the RNG salt for its per-tick
# streams. Append new kinds at the END — reordering (or inserting) would
# silently reshuffle every existing chaos soak schedule.
FAULT_KINDS = ("exhaust", "straggler", "disconnect", "malformed",
               "nan", "alloc_failure", "crash")


@dataclass(frozen=True)
class FaultEvent:
    """One scheduled fault. ``u`` is the event's own seeded uniform draw,
    used where the fault needs a choice (disconnect victim, malformed
    variant) so the schedule stays a pure function of the plan."""
    tick: int
    kind: str                           # one of FAULT_KINDS
    u: float = 0.0
    pages: int = 0                      # exhaust: pages to seize
    dur: int = 0                        # exhaust: ticks until restore


@dataclass
class FaultPlan:
    """Seeded fault schedule over ``horizon`` ticks. Each ``(tick, kind)``
    pair draws ``(fire, u)`` from its own generator seeded
    ``[seed, tick, FAULT_KINDS.index(kind)]``, so the full schedule —
    including every victim choice — is a pure function of the constructor
    arguments, and kinds never perturb each other's streams."""
    seed: int = 0
    horizon: int = 128
    p_exhaust: float = 0.05
    exhaust_pages: int = 6
    exhaust_ticks: int = 4
    p_straggler: float = 0.04
    straggler_ms: float = 1.0
    p_disconnect: float = 0.03
    p_malformed: float = 0.04
    p_nan: float = 0.0
    p_alloc_failure: float = 0.0
    p_crash: float = 0.0
    protect_rids: Tuple[int, ...] = ()  # rids disconnect/nan must not take
    _events: Optional[List[FaultEvent]] = field(default=None, repr=False)

    def events(self) -> List[FaultEvent]:
        if self._events is None:
            rates = (self.p_exhaust, self.p_straggler, self.p_disconnect,
                     self.p_malformed, self.p_nan, self.p_alloc_failure,
                     self.p_crash)
            evs: List[FaultEvent] = []
            for t in range(self.horizon):
                for salt, (kind, p) in enumerate(zip(FAULT_KINDS, rates)):
                    if p <= 0.0:
                        continue
                    fire, u = np.random.default_rng(
                        [self.seed, t, salt]).random(2)
                    if fire >= p:
                        continue
                    if kind == "exhaust":
                        evs.append(FaultEvent(t, kind, u=u,
                                              pages=self.exhaust_pages,
                                              dur=self.exhaust_ticks))
                    else:
                        evs.append(FaultEvent(t, kind, u=u))
            self._events = evs
        return self._events


def _malformed_request(rid: int, variant: int):
    """A submission that must bounce off validation. Imported lazily to
    dodge the scheduler<->faults import cycle."""
    from repro.serve.scheduler import Request
    prompt = np.asarray([1, 2, 3], np.int32)
    if variant == 0:
        return Request(rid=rid, prompt=np.asarray([], np.int32))
    if variant == 1:
        return Request(rid=rid, prompt=prompt, max_new_tokens=0)
    if variant == 2:
        return Request(rid=rid, prompt=prompt, task_id=10 ** 6)
    if variant == 3:
        return Request(rid=rid, prompt=prompt,
                       sampling=SamplingParams(temperature=float("nan")))
    return Request(rid=rid, prompt=prompt, sampling=SamplingParams(n=0))


class FaultInjector:
    """Applies a :class:`FaultPlan` to a scheduler at tick boundaries.

    Call :meth:`before_tick` right before each ``sched.step()`` and
    :meth:`finish` after the drain (it restores any pages a trailing
    exhaustion still holds — a forgotten restore is a leak-report finding
    by design). ``applied`` counts events that actually fired, so a soak
    test can assert each fault kind was exercised, not just scheduled.

    The injector keeps its OWN tick counter (one increment per
    :meth:`before_tick`): after a crash-restart the restored scheduler's
    ``ticks`` resets to zero, and counting locally keeps the plan marching
    forward instead of replaying the early schedule onto the survivor."""

    def __init__(self, sched, plan: FaultPlan):
        self.sched = sched
        self.plan = plan
        self.t = 0                                     # injector-local tick
        self._by_tick: Dict[int, List[FaultEvent]] = {}
        for ev in plan.events():
            self._by_tick.setdefault(ev.tick, []).append(ev)
        self._held: List[Tuple[int, List[int]]] = []   # (release_tick, pages)
        self.applied: Dict[str, int] = {k: 0 for k in FAULT_KINDS}
        self.disconnected: List[int] = []
        self.malformed_ok = True
        self._bad_rid = -1                             # rids for garbage
                                                       # submissions, disjoint
                                                       # from real traffic

    # ------------------------------------------------------------------
    def rebind(self, sched) -> None:
        """Point the injector at a freshly restored scheduler after a
        simulated crash. Applied counts and the local tick counter carry
        over (the plan keeps marching); seized-page holds do NOT — the
        pages died with the old pool's process."""
        self.sched = sched
        self._held = []

    def crash_now(self) -> bool:
        """True iff a crash event is scheduled for the CURRENT tick;
        consumes the event. The driver (not :meth:`before_tick`) performs
        the kill/replay/restore dance, so this is a peek-and-pop."""
        evs = self._by_tick.get(self.t, ())
        hit = [ev for ev in evs if ev.kind == "crash"]
        if not hit:
            return False
        self._by_tick[self.t] = [ev for ev in evs if ev.kind != "crash"]
        self.applied["crash"] += len(hit)
        return True

    def before_tick(self) -> None:
        from repro.serve.scheduler import InvalidRequest
        sched = self.sched
        t = self.t
        self.t += 1
        still: List[Tuple[int, List[int]]] = []
        for release, pages in self._held:
            if t >= release:
                sched.pool.restore_pages(pages)
            else:
                still.append((release, pages))
        self._held = still
        for ev in self._by_tick.get(t, ()):
            if ev.kind == "exhaust":
                pages = sched.pool.seize_pages(ev.pages)
                if pages:
                    self._held.append((t + ev.dur, pages))
                    self.applied["exhaust"] += 1
            elif ev.kind == "straggler":
                time.sleep(self.plan.straggler_ms / 1e3)
                self.applied["straggler"] += 1
            elif ev.kind == "disconnect":
                rid = self._pick_victim(ev.u)
                if rid is not None:
                    sched.abort(rid, reason="disconnect")
                    self.disconnected.append(rid)
                    self.applied["disconnect"] += 1
            elif ev.kind == "malformed":
                req = _malformed_request(self._bad_rid, int(ev.u * 5) % 5)
                self._bad_rid -= 1
                try:
                    sched.submit(req)
                    self.malformed_ok = False          # validation hole!
                except InvalidRequest:
                    self.applied["malformed"] += 1
            elif ev.kind == "nan":
                slot = self._pick_slot(ev.u)
                if slot is not None and hasattr(sched.engine,
                                                "inject_fault"):
                    sched.engine.inject_fault("nan", slot)
                    self.applied["nan"] += 1
            elif ev.kind == "alloc_failure":
                if hasattr(sched.engine, "inject_fault"):
                    sched.engine.inject_fault("alloc_failure")
                    self.applied["alloc_failure"] += 1

    def _pick_victim(self, u: float) -> Optional[int]:
        sched = self.sched
        live = sorted(({r.rid for r in sched.queue}
                       | {pf.req.rid for pf in sched._prefills}
                       | {r.rid for r in sched.running.values()})
                      - set(self.plan.protect_rids))
        if not live:
            return None
        return live[int(u * len(live)) % len(live)]

    def _pick_slot(self, u: float) -> Optional[int]:
        """A decode slot whose request NaN-poisoning is allowed to take —
        running slots only, so the victim is live at the next dispatch."""
        slots = sorted(s for s, r in self.sched.running.items()
                       if r.rid not in self.plan.protect_rids)
        if not slots:
            return None
        return slots[int(u * len(slots)) % len(slots)]

    def finish(self) -> None:
        for _, pages in self._held:
            self.sched.pool.restore_pages(pages)
        self._held = []
        # disarm any one-shot engine fault that never met a dispatch
        if hasattr(self.sched.engine, "_pending_fault"):
            self.sched.engine._pending_fault = None


def run_chaos(sched, arrivals, plan: FaultPlan, sched_factory=None) -> dict:
    """Serve a timed arrival stream under a fault plan — the chaos-soak
    driver. Mirrors :meth:`ContinuousScheduler.run_stream` tick for tick
    (same arrival clock, same idle fast-forward) with
    :meth:`FaultInjector.before_tick` applied at every tick boundary.

    ``crash`` events need a ``sched_factory`` — a zero-arg callable
    returning a FRESH scheduler journaling to the SAME path as the one it
    replaces. At each consumed crash event the current scheduler is
    abandoned where it stands (no shutdown, no page frees — that is the
    point), its journal is replayed into a snapshot, and the factory's
    replacement is restored from it and keeps serving the remaining
    arrivals. Without a factory, crash events are scheduled but inert.

    Returns ``{"finished", "injector", "shed_rids", "leak_findings",
    "quarantined", "crashes", "sched"}`` — ``finished`` spans every
    incarnation (terminal state survives restore), ``sched`` is the LAST
    incarnation (the one drain/leak invariants were checked on)."""
    from repro.serve.recovery import replay_journal
    from repro.serve.scheduler import ShedError
    inj = FaultInjector(sched, plan)
    shed_rids: List[int] = []
    crashes = 0
    order = sorted(range(len(arrivals)), key=lambda i: arrivals[i][0])
    i = 0
    while i < len(order) or sched.busy():
        if (not sched.busy() and i < len(order)
                and arrivals[order[i]][0] > sched.clock):
            sched.clock = arrivals[order[i]][0]
        while i < len(order) and arrivals[order[i]][0] <= sched.clock:
            try:
                sched.submit(arrivals[order[i]][1])
            except ShedError:
                shed_rids.append(arrivals[order[i]][1].rid)
            i += 1
        if (sched_factory is not None and sched.journal.enabled
                and inj.crash_now()):
            path = sched.journal.path
            sched.journal.close()      # the dying process's buffers flush
            snap = replay_journal(path)
            sched = sched_factory()
            sched.restore(snap)
            inj.rebind(sched)
            crashes += 1
        inj.before_tick()
        sched.step()
    inj.finish()
    findings = sched.drain_check()
    return {"finished": sched.finished, "injector": inj,
            "shed_rids": shed_rids, "leak_findings": findings,
            "quarantined": dict(sched.quarantined),
            "crashes": crashes, "sched": sched}
