"""Continuous-batching scheduler: queue → admit → prefill → decode → finish.

Static batching forces every request to arrive together, share one prompt
length, and finish together. This scheduler serves realistic traffic: each
request carries its own ``task_id``, prompt, and ``max_new_tokens``; new
requests are admitted *between* decode steps, and every decode step is ONE
jitted mixed pass over all occupied slots with per-slot positions and the
multitask AoT gather routed by the slot task-id vector.

Two KV layouts share the same request lifecycle:

  * ``kv_layout="paged"`` (default): a :class:`PagedKVPool` — KV pages are
    claimed block-by-block as requests deepen, so HBM is bounded by tokens
    in flight and ``num_slots`` can far exceed what ``num_slots * max_len``
    contiguous regions would cost. A tick is ONE jitted
    ``ServeEngine.serve_step`` call over a RAGGED, PACKED token list:
    every decode row contributes its one fed-back token, each of up to
    ``max_prefills`` in-flight prefills its next prompt chunk (each token
    tagged with its owning slot and absolute position), free slots
    nothing — chunk KV scatters straight into pool pages, so there is no
    per-request temp cache and no install copy, and padding never exceeds
    the static packed width. The per-tick chunk budget
    (``prefill_chunk`` tokens) is split across the in-flight prefills
    shortest-remaining-first — short prompts clear the queue fast
    instead of waiting behind a long one — with the oldest prefill
    guaranteed a ``budget / max_prefills`` slice so a stream of short
    prompts can never starve it. When the pool runs out of pages
    mid-decode the newest request is preempted (freed + requeued) and
    later *recomputed* — greedy decode makes the recompute
    token-for-token identical.
  * ``kv_layout="slots"``: the contiguous :class:`SlotKVPool` — one
    ``max_len`` region per slot, whole-prompt bucket prefills plus a
    separate mixed decode call (kept for comparison benchmarks).

Whole-prompt prefill is bucket-padded (one compilation per bucket). With
``prefill_chunk > 0`` (paged only) prompts instead stream through the
unified step in chunks drawn from a fixed per-tick token budget shared
by up to ``max_prefills`` concurrent prefills — decode rows advance in
the SAME device call, so a long prompt neither stalls running requests
(head-of-line blocking) nor delays *queued* prompts behind it, and no
batch composition ever costs a second dispatch.

Because the AoT bias is a per-(task, token) gather from the fused tables
(paper Eq. 1), the mixed-task batch costs exactly what a single-task batch
costs — no extra KV length (P-Tuning v2), no per-task matmuls (unfused
LoRA/Adapters). That zero-cost property is what makes continuous batching
across tasks free, not just across lengths.

Greedy decode here is token-for-token identical to per-request static
``ServeEngine.generate``: bucket padding is inert under causal attention,
per-slot decode writes/reads the same cache rows a dedicated cache would
(pages are just a scattered layout of those rows), and masked (invalid)
rows never contribute (see tests/test_serve_scheduler).

Stochastic decode (``Request.sampling``) keeps every one of those
contracts. Each sample owns a counter-based RNG stream —
``fold_in(fold_in(PRNGKey(seed), sample_idx), token_index)`` — so a draw
depends only on request constants, never on batch composition or slot
assignment; preempt-and-recompute replays the identical stream instead of
relying on argmax determinism. ``n > 1`` parallel samples prefill ONCE and
fork the request's KV pages copy-on-write (:meth:`PagedKVPool.fork`), so
extra samples cost only their divergent decode pages.
"""
from __future__ import annotations

import math
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.obs import NULL_OBS, ServeObservability
from repro.serve.engine import DispatchFault, ServeEngine
from repro.serve.kv_pool import PagedKVPool, SlotKVPool
from repro.serve.recovery import NULL_JOURNAL, RequestJournal
from repro.serve.sampling import SamplingParams, request_base_key

QUEUED, RUNNING, FINISHED = "queued", "running", "finished"
ABORTED, SHED = "aborted", "shed"
# terminal state for poisoned requests (NaN/inf logits): pages go to the
# pool's quarantine hold instead of the free list, the rest of the batch
# retries the tick — see ContinuousScheduler.quarantine
QUARANTINED = "quarantined"

# Every state a request can end in. Append-only (pinned by the repro-lint
# enum manifest): dispatch sites keyed on terminal state must either use
# this tuple or enumerate every member (rule state-exhaustive), so adding
# a fifth terminal state — beam-search pruning is ROADMAP item 2 — turns
# each missed site into a lint error instead of a silent page leak.
TERMINAL_STATES = (FINISHED, SHED, ABORTED, QUARANTINED)

# Priority classes, best first. Admission is strict-priority across classes
# (FIFO within a class), the per-tick prefill budget guarantees the oldest
# prefill of EACH class a slice (the PR 5 no-starvation guarantee, per
# class), and page-pressure victims are chosen worst-class-first so a
# latency request reclaims pages from best-effort decode rows before it
# ever touches a peer.
LATENCY, STANDARD, BEST_EFFORT = "latency", "standard", "best_effort"
PRIORITIES = (LATENCY, STANDARD, BEST_EFFORT)
PRIORITY_RANK = {c: i for i, c in enumerate(PRIORITIES)}


class InvalidRequest(ValueError):
    """A malformed submission, rejected at ``submit()`` before it can claim
    a slot, pages, or a place in the queue — never deep inside a tick.
    Subclasses ValueError so pre-existing callers' handlers keep working."""


class InvalidConfig(ValueError):
    """A malformed :class:`SchedulerConfig` knob or scheduler-API argument
    (negative, NaN, or non-integral where a count is required), rejected
    at construction / call time — never as a mid-drain surprise. The
    config analog of :class:`InvalidRequest`."""


def _check_count(name: str, v, minimum: int) -> int:
    """Validate an integral, finite, bounded count knob -> plain int."""
    if isinstance(v, bool) or not isinstance(
            v, (int, float, np.integer, np.floating)):
        raise InvalidConfig(f"{name} must be an integer (got {v!r})")
    f = float(v)
    if not math.isfinite(f) or f != int(f):
        raise InvalidConfig(f"{name} must be a finite integer (got {v!r})")
    if int(f) < minimum:
        raise InvalidConfig(f"{name} must be >= {minimum} (got {v!r})")
    return int(f)


class ShedError(RuntimeError):
    """The scheduler refused an admissible request: the bounded queue is
    full (``reason="queue_full"``), a higher class displaced it
    (``"displaced"``), or the scheduler is draining (``"shutting_down"``).
    Explicit rejection is the overload contract — clients retry with
    backoff instead of the queue growing without bound."""

    def __init__(self, rid: int, reason: str):
        super().__init__(f"request {rid} shed: {reason}")
        self.rid = rid
        self.reason = reason


class _ClassQueues:
    """Admission queue partitioned by priority class: strict priority
    across classes, FIFO within one. Mirrors the deque surface the
    scheduler already leans on (``len``, ``[0]``, ``append``,
    ``appendleft``, ``popleft``, iteration) so every existing call site
    reads unchanged — ``appendleft`` fronts the request's OWN class, which
    is how preempted/recomputing requests keep their place without jumping
    a class they don't belong to."""

    def __init__(self):
        self._q: Dict[str, deque] = {c: deque() for c in PRIORITIES}

    def __len__(self) -> int:
        return sum(len(q) for q in self._q.values())

    def __bool__(self) -> bool:
        return any(self._q.values())

    def __iter__(self):
        for c in PRIORITIES:
            yield from self._q[c]

    def __getitem__(self, i: int) -> "Request":
        if i != 0:
            raise IndexError("class queue exposes only the head")
        for c in PRIORITIES:
            if self._q[c]:
                return self._q[c][0]
        raise IndexError("empty queue")

    def append(self, req: "Request") -> None:
        self._q[req.priority].append(req)

    def appendleft(self, req: "Request") -> None:
        self._q[req.priority].appendleft(req)

    def popleft(self) -> "Request":
        for c in PRIORITIES:
            if self._q[c]:
                return self._q[c].popleft()
        raise IndexError("empty queue")

    def remove(self, req: "Request") -> None:
        # identity scan: Request's dataclass __eq__ would compare numpy
        # prompt arrays (ambiguous truth value), so deque.remove is out
        q = self._q[req.priority]
        for i, r in enumerate(q):
            if r is req:
                del q[i]
                return
        raise ValueError(f"request {req.rid} is not queued")

    def worst(self) -> Optional["Request"]:
        """Displacement victim: the NEWEST request of the worst non-empty
        class (mirrors preemption's newest-first ordering)."""
        for c in reversed(PRIORITIES):
            if self._q[c]:
                return self._q[c][-1]
        return None


@dataclass
class Request:
    """One serving request. ``on_token`` streams tokens as they decode.

    ``sampling`` (None = greedy) controls temperature/top-k/top-p, the RNG
    seed, stop tokens, and ``n`` parallel samples. For ``n > 1`` the
    finished request's ``samples`` holds every sample's tokens (and ``out``
    aliases sample 0); the scheduler internally runs each sample as a child
    request (``parent``/``sample_idx`` set) sharing one prefill via COW
    page forking — ``on_token`` callbacks receive those children."""
    rid: int
    prompt: np.ndarray                  # (s,) int32
    task_id: int = 0
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    on_token: Optional[Callable[["Request", int], None]] = None
    sampling: Optional[SamplingParams] = None
    priority: str = STANDARD            # latency | standard | best_effort
    deadline_ticks: Optional[int] = None  # abort if not finished within this
                                          # many ticks of submission
    # filled in by the scheduler
    out: List[int] = field(default_factory=list)
    state: str = QUEUED
    slot: int = -1
    finish_reason: str = ""             # "" (completed) | deadline | client |
                                        # disconnect | shutdown | shed reason
    submit_tick: int = 0                # scheduler tick at submit (deadlines)
    t_submit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    # n>1 bookkeeping: parent aggregates its per-sample children
    samples: Optional[List[Optional[List[int]]]] = None
    parent: Optional["Request"] = None
    sample_idx: int = 0


@dataclass(frozen=True)
class SchedulerConfig:
    num_slots: int = 8                  # batch width (mixed-step rows)
    bucket_min: int = 16                # smallest prefill bucket (doubles up)
    admit_per_step: int = 0             # max prefills between decode steps
                                        # (0 = fill every free slot)
    kv_layout: str = "paged"            # "paged" | "slots"
    block_size: int = 16                # KV page size in tokens (paged)
    num_blocks: int = 0                 # physical pages incl. scratch page 0
                                        # (0 = capacity parity with slots)
    prefill_chunk: int = 0              # per-tick prefill TOKEN BUDGET:
                                        # prompts stream through the unified
                                        # ragged serve step in chunks, the
                                        # budget split across in-flight
                                        # prefills shortest-remaining-first
                                        # (paged only; 0 = whole-prompt)
    max_prefills: int = 4               # cap on concurrently chunking
                                        # prefills sharing that budget
    prefix_cache_pages: int = 0         # cross-request shared-prefix page
                                        # cache capacity: finished requests'
                                        # full prompt pages are retained
                                        # (LRU) and matched into new
                                        # admissions of the SAME task, so
                                        # chunked prefill starts at the
                                        # first uncached token (paged +
                                        # prefill_chunk only; 0 = off)
    max_queue: int = 0                  # bounded admission queue: submits
                                        # beyond this many waiters are SHED
                                        # (ShedError) unless they outrank
                                        # and displace a queued request
                                        # (0 = unbounded, the old behavior)
    check_leaks: bool = False           # debug: sweep the KV pool's
                                        # alloc/refcount invariants when the
                                        # scheduler drains; findings land in
                                        # the obs metrics snapshot and raise
    tick_retries: int = 2               # self-healing dispatch loop: how many
                                        # times one tick may repack + retry
                                        # after a faulted dispatch or a
                                        # NaN-quarantine before the fault is
                                        # re-raised to the caller


@dataclass
class _Prefill:
    """A chunked prefill in flight: the request holds its slot (and pages)
    while its prompt streams through the unified serve step chunk-by-chunk
    — each chunk is just a ragged span of the tick's single device call,
    scattering its KV straight into the slot's mapped pool pages. Several
    prefills chunk concurrently, splitting the tick's token budget
    shortest-remaining-first."""
    req: Request
    slot: int
    toks: np.ndarray                    # (s,) the tokens to prefill
    length: int                         # == len(toks): prompt [+ recompute]
    done: int = 0                       # tokens processed so far

    @property
    def remaining(self) -> int:
        return self.length - self.done


@dataclass
class DrainReport:
    """What :meth:`ContinuousScheduler.shutdown` did with in-flight work."""
    finished: int                       # requests completed overall
    shed_rids: List[int]                # rids aborted when grace expired
    grace_ticks_used: int               # ticks spent draining
    leak_findings: List[str]            # pool invariant sweep (empty = clean)
    cache_pages_released: int = 0       # prefix-cache pages flushed back to
                                        # the free list at shutdown
    quarantined_pages_released: int = 0  # forensic quarantine hold released
                                         # back to the free list at shutdown

    @property
    def clean(self) -> bool:
        return not self.leak_findings


class ContinuousScheduler:
    """Drives a ServeEngine + KV pool over an online request stream."""

    def __init__(self, engine: ServeEngine, cfg: Optional[SchedulerConfig] = None,
                 obs: Optional[ServeObservability] = None,
                 journal: Optional[RequestJournal] = None):
        # default constructed here, not in the signature: a shared default
        # instance would alias across schedulers (mutable-default footgun)
        cfg = cfg if cfg is not None else SchedulerConfig()
        # reject malformed count knobs (negative / NaN / non-integral) at
        # construction — never as a mid-drain surprise (InvalidConfig)
        for knob, lo in (("num_slots", 1), ("bucket_min", 1),
                         ("admit_per_step", 0), ("block_size", 1),
                         ("num_blocks", 0), ("prefill_chunk", 0),
                         ("max_prefills", 1), ("prefix_cache_pages", 0),
                         ("max_queue", 0), ("tick_retries", 0)):
            _check_count(f"SchedulerConfig.{knob}", getattr(cfg, knob), lo)
        mcfg = engine.model.cfg
        assert mcfg.causal, (
            "continuous batching pads prompts to buckets; that is only "
            "inert under causal attention")
        assert not mcfg.prefix_lm_len, (
            f"{mcfg.name}: a bidirectional prefix ({mcfg.prefix_lm_len} "
            "tokens) attends to bucket padding; continuous batching needs "
            "fully-causal attention")
        kinds = {k for plan in engine.model.plan for k in plan.kinds}
        assert kinds <= {"attn"}, (
            f"{mcfg.name}: recurrent blocks ({kinds - {'attn'}}) fold bucket "
            "padding into their state; continuous batching needs "
            "attention-only stacks (or exact-length prefill) for now")
        assert mcfg.frontend != "audio_frames", "token requests only"
        method = engine.peft["method"] if engine.peft else "none"
        assert method not in ("ptv1", "ptv2"), (
            f"{method}: prompt/prefix tuning changes cache layout per "
            "request; serve it with static batches")
        assert cfg.kv_layout in ("paged", "slots"), cfg.kv_layout
        assert not (cfg.kv_layout == "paged" and mcfg.attn_kind == "swa"
                    and mcfg.sliding_window), (
            f"{mcfg.name}: paged decode has no sliding-window masking yet; "
            "serve SWA models with kv_layout='slots'")
        assert not (cfg.prefill_chunk > 0 and cfg.kv_layout == "slots"), (
            "chunked prefill rides the unified paged serve step; "
            "kv_layout='slots' serves whole-prompt prefills only")
        self.engine = engine
        self.cfg = cfg
        self.max_len = engine.cfg.max_len
        if cfg.kv_layout == "paged":
            self.pool = PagedKVPool(
                engine.model, cfg.num_slots, self.max_len,
                block_size=cfg.block_size,
                num_blocks=cfg.num_blocks or None)
        else:
            self.pool = SlotKVPool(engine.model, cfg.num_slots, self.max_len)
        if cfg.prefix_cache_pages > 0:
            assert cfg.kv_layout == "paged" and cfg.prefill_chunk > 0, (
                "the prefix cache maps cached pages into block tables and "
                "starts prefill at the first uncached token — that needs "
                "kv_layout='paged' with chunked prefill (prefill_chunk > 0)")
            self.pool.enable_prefix_cache(cfg.prefix_cache_pages)
        self.queue = _ClassQueues()
        self.running: Dict[int, Request] = {}        # slot -> request
        self.finished: Dict[int, Request] = {}       # rid -> request
        self.aborted: Dict[int, Request] = {}        # rid -> request (client
                                                     # abort / deadline /
                                                     # disconnect / shutdown)
        self.shed: Dict[int, Request] = {}           # rid -> request refused
                                                     # or displaced from the
                                                     # bounded queue
        self.quarantined: Dict[int, Request] = {}    # rid -> poisoned request
                                                     # (NaN/inf logits; pages
                                                     # in the pool's hold)
        self.deadline_misses = 0
        self.dispatch_faults = 0        # serve_step calls that raised
        self.tick_retries_used = 0      # repack+retry passes actually taken
        # append-only lifecycle journal (crash recovery); NULL by default —
        # every hook is then a no-op attribute call
        self.journal = journal if journal is not None else NULL_JOURNAL
        self._draining = False
        self.slot_tokens = np.zeros((cfg.num_slots, 1), np.int32)
        # per-slot sampling vectors, threaded into the jitted decode step
        self.slot_temps = np.zeros(cfg.num_slots, np.float32)
        self.slot_topk = np.zeros(cfg.num_slots, np.int32)
        self.slot_topp = np.ones(cfg.num_slots, np.float32)
        self.slot_keys = np.zeros((cfg.num_slots, 2), np.uint32)
        self.slot_steps = np.zeros(cfg.num_slots, np.int32)
        self.clock = 0                               # arrival-stream clock
                                                     # (fast-forwards when idle)
        self.ticks = 0                               # real step() calls
        self.steps_decoded = 0
        self.tokens_emitted = 0
        self.preemptions = 0
        self.prefill_chunks_run = 0
        self.peak_running = 0
        self.peak_prefills = 0
        # chunked prefills in flight, admission order (newest last — the
        # abort victim ordering); several share the per-tick token budget
        self._prefills: List[_Prefill] = []
        self._admit_seq: Dict[int, int] = {}         # slot -> admission order
        self._seq = 0
        # static per-tick prefill token budget of the unified serve step's
        # packed token list: ticks compile to exactly two shapes
        # (decode-only, and decode + up to _qw chunk tokens shared by every
        # in-flight prefill, dead-token padded)
        self._qw = max(1, cfg.prefill_chunk)
        # ---- observability (repro.obs) -------------------------------
        # NULL_OBS hands out no-op instruments, so every hook below stays
        # branch-free and costs one attribute lookup when disabled; real
        # instruments only ever read host scalars this scheduler already
        # computes per tick, never anything inside jitted code — which is
        # why metrics-on vs metrics-off token streams are bitwise equal
        # (test-enforced, tests/test_obs.py)
        self.obs = obs if obs is not None else NULL_OBS
        if self.obs.metrics.enabled:
            self.pool.attach_metrics(self.obs.metrics)
            engine.attach_metrics(self.obs.metrics)
        m = self.obs.metrics
        self._m_ticks = m.counter(
            "sched_ticks_total", "real step() calls (no idle fast-forward)")
        self._m_tokens = m.counter(
            "sched_tokens_emitted_total", "generated tokens streamed out")
        self._m_submitted = m.counter(
            "sched_requests_submitted_total", "requests entering the queue")
        self._m_admitted = m.counter(
            "sched_admissions_total", "queue departures (slot+pages claimed; "
            "recomputes re-admit)")
        self._m_finished = m.counter(
            "sched_requests_finished_total", "requests (or sample children) "
            "completed")
        self._m_preempt = m.counter(
            "sched_preemptions_total", "decode rows preempted for pages")
        self._m_aborts = m.counter(
            "sched_prefill_aborts_total", "in-flight prefills aborted for "
            "pages")
        self._m_chunks = m.counter(
            "sched_prefill_chunks_total", "prefill chunks advanced")
        self._m_queue = m.gauge("sched_queue_depth", "requests waiting")
        self._m_running = m.gauge("sched_running", "decode rows in flight")
        self._m_inflight_pf = m.gauge(
            "sched_prefills_inflight", "prompts mid-chunking")
        self._m_peak_running = m.gauge(
            "sched_peak_running", "high-water decode concurrency")
        self._m_peak_pf = m.gauge(
            "sched_peak_prefills", "high-water concurrent prefills")
        self._m_tick_tokens = m.histogram(
            "sched_tick_packed_tokens", [1, 2, 4, 8, 16, 32, 64, 128, 256],
            "real (non-dead) tokens advanced per tick")
        self._m_tick_ms = m.histogram(
            "sched_tick_wall_ms", [1, 2, 5, 10, 20, 50, 100, 200, 500, 1000],
            "wall ms per tick (includes jit compiles on first shapes)")
        self._m_leaks = m.gauge(
            "kv_leak_findings", "drain-time pool invariant violations "
            "(0 = clean; see ContinuousScheduler.drain_check)")
        self._m_shed = m.counter(
            "sched_shed_total", "submissions refused or displaced from the "
            "bounded queue (see sched_shed_<reason>_total)")
        self._m_client_aborts = m.counter(
            "sched_aborts_total", "requests cancelled via abort() — client "
            "aborts, disconnects, deadline misses, shutdown sheds")
        self._m_deadline = m.counter(
            "sched_deadline_misses_total", "requests aborted past their "
            "deadline_ticks budget")
        self._m_invalid = m.counter(
            "sched_invalid_requests_total", "submissions rejected by "
            "validation (InvalidRequest)")
        self._m_draining = m.gauge(
            "sched_draining", "1 while shutdown() drains (submits shed)")
        self._m_quarantined = m.counter(
            "sched_quarantined_total", "requests quarantined by the NaN/inf "
            "logits watchdog (terminal; pages held for forensics)")
        self._m_tick_retries = m.counter(
            "sched_tick_retries_total", "tick repack+retry passes taken by "
            "the self-healing dispatch loop")
        self._m_dispatch_faults = m.counter(
            "sched_dispatch_faults_total", "serve_step dispatches that "
            "raised (retried up to tick_retries, then re-raised)")

    @property
    def paged(self) -> bool:
        return isinstance(self.pool, PagedKVPool)

    # ------------------------------------------------------------------
    def _max_new(self, req: Request) -> int:
        sp = req.sampling
        return sp.max_tokens if (sp is not None and sp.max_tokens) \
            else req.max_new_tokens

    def _base_key(self, req: Request) -> np.ndarray:
        if req.sampling is None:
            return np.zeros(2, np.uint32)
        return request_base_key(req.sampling.seed, req.sample_idx)

    def _validate(self, req: Request) -> None:
        """Reject malformed submissions up front (InvalidRequest) instead
        of letting them fail slots-deep inside a jitted tick."""
        prompt = np.asarray(req.prompt)
        if prompt.ndim != 1 or len(prompt) < 1:
            raise InvalidRequest(f"request {req.rid}: empty prompt")
        if req.priority not in PRIORITY_RANK:
            raise InvalidRequest(
                f"request {req.rid}: unknown priority {req.priority!r} "
                f"(one of {PRIORITIES})")
        if req.deadline_ticks is not None and req.deadline_ticks < 1:
            raise InvalidRequest(
                f"request {req.rid}: deadline_ticks must be >= 1 "
                f"(got {req.deadline_ticks})")
        num_tasks = getattr(self.engine, "num_tasks", None)
        if num_tasks is not None and not 0 <= req.task_id < num_tasks:
            raise InvalidRequest(
                f"request {req.rid}: unknown task id {req.task_id} "
                f"(engine fuses {num_tasks} tasks)")
        sp = req.sampling
        if sp is not None:
            try:
                sp.validate()
            except ValueError as e:
                raise InvalidRequest(f"request {req.rid}: {e}") from e
            if sp.n > 1 and not self.paged:
                raise InvalidRequest(
                    f"request {req.rid}: n={sp.n} parallel samples need "
                    "kv_layout='paged' (COW page forking)")
        max_new = self._max_new(req)
        if max_new < 1:
            raise InvalidRequest(
                f"request {req.rid}: max_new_tokens must be >= 1 "
                f"(got {max_new})")
        # the last generated token is emitted without being fed back, so the
        # deepest KV row written is prompt + max_new - 2
        s = len(prompt)
        if s + max_new - 1 > self.max_len:
            raise InvalidRequest(
                f"request {req.rid}: prompt {s} + {max_new} new "
                f"tokens does not fit max_len {self.max_len}")

    def _shed(self, req: Request, reason: str) -> None:
        req.state = SHED
        req.finish_reason = reason
        self.shed[req.rid] = req
        self._m_shed.inc()
        self.obs.metrics.counter(
            f"sched_shed_{reason}_total",
            f"submissions shed with reason={reason}").inc()
        self.obs.slo.on_shed(req, self.ticks, reason)
        self.obs.tracer.instant("shed", rid=req.rid, reason=reason,
                                priority=req.priority)
        self.journal.shed(req.rid, reason)

    def submit(self, req: Request) -> None:
        """Validate and enqueue. Raises :class:`InvalidRequest` on a
        malformed request and :class:`ShedError` when the bounded queue
        refuses it (queue full and nothing worse to displace, or the
        scheduler is draining). A shed request is recorded in
        ``self.shed`` with its reason; a higher-class submission instead
        DISPLACES the newest worst-class waiter (that victim lands in
        ``self.shed`` with reason ``"displaced"`` for the client's
        retry policy to pick up)."""
        try:
            self._validate(req)
        except InvalidRequest:
            self._m_invalid.inc()
            raise
        if self._draining:
            self._shed(req, "shutting_down")
            raise ShedError(req.rid, "shutting_down")
        if self.cfg.max_queue and len(self.queue) >= self.cfg.max_queue:
            victim = self.queue.worst()
            if victim is not None and (PRIORITY_RANK[req.priority]
                                       < PRIORITY_RANK[victim.priority]):
                self.queue.remove(victim)
                self._shed(victim, "displaced")
            else:
                self._shed(req, "queue_full")
                raise ShedError(req.rid, "queue_full")
        req.state = QUEUED
        req.finish_reason = ""
        req.submit_tick = self.ticks
        req.t_submit = time.perf_counter()
        self.shed.pop(req.rid, None)    # resubmit after a shed: back in play
        self.queue.append(req)
        self._m_submitted.inc()
        self._m_queue.set(len(self.queue))
        self.obs.slo.on_submit(req, self.ticks)
        self.journal.submit(req, self.ticks)

    def _bucket(self, length: int) -> int:
        b = self.cfg.bucket_min
        while b < length:
            b *= 2
        return min(b, self.max_len)

    def _emit(self, req: Request, tok: int) -> bool:
        """Record one generated token; returns True when the request is done."""
        if not req.out:
            req.t_first = time.perf_counter()
            if req.parent is not None and req.parent.t_first == 0.0:
                req.parent.t_first = req.t_first
            self.obs.slo.on_first_token(req, self.ticks)
        req.out.append(tok)
        self.tokens_emitted += 1
        self._m_tokens.inc()
        self.journal.emit(req, tok)
        if req.on_token is not None:
            req.on_token(req, tok)
        sp = req.sampling
        done = len(req.out) >= self._max_new(req) or (
            req.eos_id is not None and tok == req.eos_id) or (
            sp is not None and tok in sp.stop)
        return done

    def _retain_prefix(self, req: Request) -> None:
        """Retain a finishing request's full prompt pages in the prefix
        cache (before the slot frees them). Generated tokens are never
        cached — only the prompt is input, and only full pages carry a
        complete block's KV. Forked sample children retain too: their
        leading pages are the shared prompt pages, and an already-cached
        chain just gets an LRU touch."""
        cache = getattr(self.pool, "prefix_cache", None)
        if cache is not None and req.slot >= 0:
            cache.retain(req.task_id, req.prompt, req.slot)

    def _finish(self, req: Request) -> None:
        self.running.pop(req.slot, None)
        self._retain_prefix(req)
        self.pool.free(req.slot)
        self.slot_temps[req.slot] = 0.0     # freed rows ride along as greedy
        req.state = FINISHED
        req.t_done = time.perf_counter()
        self._m_finished.inc()
        self.obs.slo.on_finish(req, self.ticks)
        self.obs.tracer.instant("finish", rid=req.rid,
                                sample=req.sample_idx, tokens=len(req.out))
        self.journal.finish(req)
        if req.parent is not None:
            self._finish_sample(req)
        else:
            self.finished[req.rid] = req

    def _finish_sample(self, child: Request) -> None:
        """A per-sample child finished; complete the parent when the last
        sibling lands."""
        parent = child.parent
        parent.samples[child.sample_idx] = child.out
        if all(s is not None for s in parent.samples):
            parent.out = list(parent.samples[0])
            parent.state = FINISHED
            parent.t_done = child.t_done
            self.finished[parent.rid] = parent

    # ------------------------------------------------------------------
    # admission (bucketed prefill; optionally chunked across ticks)
    # ------------------------------------------------------------------
    def _prefill_tokens(self, req: Request) -> np.ndarray:
        """The token sequence whose KV must be resident before decode.

        A fresh request prefills its prompt. A preempted request recomputes
        prompt + all-but-the-last generated token (the last one is the
        pending decode input, not yet in any cache)."""
        if req.out:
            return np.concatenate([req.prompt,
                                   np.asarray(req.out[:-1], np.int32)])
        return req.prompt

    def _alloc_slot(self, req: Request, length: int) -> Optional[int]:
        if self.paged:
            return self.pool.alloc(req.task_id, self.pool.pages_needed(length))
        return self.pool.alloc(req.task_id)

    def _can_admit(self, req: Request) -> bool:
        if not self.pool.has_free():
            return False
        if self.paged:
            need = self.pool.pages_needed(len(self._prefill_tokens(req)))
            return self.pool.free_blocks() >= need
        return True

    def _match_prefix(self, req: Request) -> List[bytes]:
        """Cache keys for the request's longest cached full-page prefix
        ([] without a cache or on a miss). Recomputes after preemption
        match too: their prefill stream begins with the prompt, and the
        chain walk simply stops where the cache's knowledge ends."""
        cache = getattr(self.pool, "prefix_cache", None)
        if cache is None:
            return []
        return cache.match(req.task_id, self._prefill_tokens(req))

    def _can_admit_chunked(self, req: Request) -> bool:
        """Chunked admission claims the prompt's pages for several ticks
        before the request emits anything, so it must leave headroom: one
        append page per running decode row stays reserved. Without the
        guard, an aborted prefill requeued at the head is re-admitted on
        the very next tick, re-burns its pages, and is aborted again as
        soon as a decode append runs dry — thrash that can starve decode
        progress entirely.

        A prefix-cache hit shrinks the claim to the UNCACHED pages; the
        matched entries are passed to ``can_claim`` as excluded so their
        pages are never double-counted as evictable headroom (pinning
        them is what admission is about to do)."""
        if not self.pool.has_free():
            return False
        keys = self._match_prefix(req)
        need = self.pool.pages_needed(
            len(self._prefill_tokens(req))) - len(keys)
        return self.pool.can_claim(need, reserve=len(self.running),
                                   exclude_keys=keys)

    def _first_sample_spec(self, req: Request):
        """Sampling spec for the first-token draw from the prefill logits.

        None (exact argmax) for greedy singles and for recompute installs —
        a recomputed request's pending token was already emitted, so its
        prefill logits are never sampled. A fresh stochastic request draws
        token 0 under ``fold_in(base_key, 0)``; a fresh n>1 parent draws n
        first tokens, one per sample stream, from the SAME prefill row."""
        sp = req.sampling
        if sp is None or req.out:
            return None
        fresh_parent = req.parent is None and sp.n > 1
        if sp.greedy and not fresh_parent:
            return None
        idxs = list(range(sp.n)) if fresh_parent else [req.sample_idx]
        n = len(idxs)
        return (np.full(n, sp.temperature, np.float32),
                np.full(n, sp.top_k, np.int32),
                np.full(n, sp.top_p, np.float32),
                np.stack([request_base_key(sp.seed, i) for i in idxs]),
                np.zeros(n, np.int32))

    def _make_child(self, parent: Request, i: int) -> Request:
        child = Request(
            rid=parent.rid, prompt=parent.prompt, task_id=parent.task_id,
            max_new_tokens=parent.max_new_tokens, eos_id=parent.eos_id,
            on_token=parent.on_token, sampling=parent.sampling,
            priority=parent.priority, deadline_ticks=parent.deadline_ticks,
            parent=parent, sample_idx=i)
        child.t_submit = parent.t_submit
        child.submit_tick = parent.submit_tick
        return child

    def _install_single(self, req: Request, slot: int, tok: int) -> None:
        """Start one sample decoding from its freshly-populated slot."""
        req.state, req.slot = RUNNING, slot
        self._seq += 1
        self._admit_seq[slot] = self._seq
        self.running[slot] = req
        sp = req.sampling
        self.slot_temps[slot] = sp.temperature if sp is not None else 0.0
        self.slot_topk[slot] = sp.top_k if sp is not None else 0
        self.slot_topp[slot] = sp.top_p if sp is not None else 1.0
        self.slot_keys[slot] = self._base_key(req)
        if req.out:
            # recompute after preemption: the pending input token was already
            # emitted; feed it back and let the counter-based stream resume
            # at fold_in(base_key, len(out)) — no determinism assumption
            self.slot_tokens[slot, 0] = req.out[-1]
        else:
            self.slot_tokens[slot, 0] = tok
            if self._emit(req, tok):
                self._finish(req)

    def _install(self, req: Request, slot: int, length: int,
                 prefill_toks: List[int], cache=None) -> None:
        """Publish the prefilled slot and start decoding.

        ``cache`` carries a whole-prompt prefill's contiguous cache to
        scatter into the pool; ``None`` means the unified serve step
        already wrote the KV straight into the slot's pages (the chunked
        path) and only the depth needs committing.

        A fresh ``n > 1`` request expands here: the prefilled slot becomes
        sample 0, and every other sample forks it copy-on-write (sharing
        the prompt's pages). When the pool has no slot left to fork into, a
        sample is requeued as an independent request instead — its
        counter-based stream makes the tokens identical either way, only
        the prefill sharing is lost."""
        if cache is not None:
            self.pool.write_prefill(slot, cache, length)
        else:
            self.pool.commit_prefill(slot, length)
        sp = req.sampling
        if req.out or req.parent is not None or sp is None or sp.n == 1:
            self._install_single(req, slot, prefill_toks[0])
            return
        req.samples = [None] * sp.n
        req.state = RUNNING
        children = [self._make_child(req, i) for i in range(sp.n)]
        slots = {0: slot}
        pending: List[Request] = []
        for i in range(1, sp.n):        # fork before any child can finish
            forked = self.pool.fork(slot)
            if forked is None:
                pending.append(children[i])
            else:
                slots[i] = forked
                self.obs.tracer.instant("fork", rid=req.rid, sample=i,
                                        slot=forked)
        for i, child in enumerate(children):
            if i in slots:
                if i > 0:       # sample 0 inherits the parent's admission
                    self.obs.slo.on_admit(child, self.ticks)
                self._install_single(child, slots[i], prefill_toks[i])
        for child in reversed(pending):
            self.queue.appendleft(child)

    def _admit_whole(self, req: Request) -> None:
        """Whole-prompt path: the entire (bucket-padded) prompt in one
        prefill call, scattered into the pool at install."""
        toks_full = self._prefill_tokens(req)
        s = len(toks_full)
        slot = self._alloc_slot(req, s)
        assert slot is not None
        self._m_admitted.inc()
        self.obs.slo.on_admit(req, self.ticks)
        self.journal.admit(req, self.ticks)
        bucket = self._bucket(s)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :s] = toks_full
        first, cache = self.engine.prefill_request(
            toks, s, req.task_id, sample=self._first_sample_spec(req))
        self._install(req, slot, s, first, cache=cache)

    def _start_chunked(self, req: Request) -> None:
        """Claim a slot + prompt pages; the chunks themselves ride the
        unified serve step as ragged spans of each tick's packed list — no
        device call here, no temp cache, no bucket padding (the static
        budget width is the only prefill compilation).

        On a prefix-cache hit the slot's leading pages alias the cached
        prefix (refcount bump, entries pinned until the slot frees) and
        the prefill starts ``done`` tokens in — the ragged kernel reads
        the cached KV through the block table at the same absolute
        positions a cold prefill would have written, so the tokens that
        come out are bitwise identical (test-enforced)."""
        toks = self._prefill_tokens(req)
        cache = getattr(self.pool, "prefix_cache", None)
        keys = self._match_prefix(req)
        if keys:
            slot = self.pool.alloc_cached(
                req.task_id, keys, self.pool.pages_needed(len(toks)))
        else:
            slot = self._alloc_slot(req, len(toks))
        assert slot is not None
        cached = len(keys) * self.cfg.block_size
        if cache is not None:
            cache.record_lookup(cached)
            if cached:
                self.obs.slo.on_prefix_hit(req, self.ticks, cached)
                self.obs.tracer.instant("prefix_hit", rid=req.rid,
                                        tokens=cached)
        self._m_admitted.inc()
        self.obs.slo.on_admit(req, self.ticks)
        self.journal.admit(req, self.ticks)
        self.slot_temps[slot] = 0.0     # draws armed on the final chunk only
        self._prefills.append(_Prefill(req=req, slot=slot,
                                       toks=np.asarray(toks, np.int32),
                                       length=len(toks), done=cached))
        self.peak_prefills = max(self.peak_prefills, len(self._prefills))

    def _arm_first_draw(self, req: Request, slot: int) -> None:
        """Point the slot's sampling vectors at the request's token-0 draw
        so the final prefill chunk's logits are sampled inside the same
        serve_step call (fresh stochastic singles). Arming is per slot, on
        each prefill's OWN final chunk — several prompts finishing in one
        tick each draw their own first token there. Recomputes and greedy
        requests stay on the exact-argmax path."""
        sp = req.sampling
        if sp is not None and not req.out and not sp.greedy:
            self.slot_temps[slot] = sp.temperature
            self.slot_topk[slot] = sp.top_k
            self.slot_topp[slot] = sp.top_p
        else:
            self.slot_temps[slot] = 0.0
        self.slot_keys[slot] = self._base_key(req)
        self.slot_steps[slot] = 0

    def _preempt_for_admission(self, head: Request) -> bool:
        """A blocked queue head may reclaim pages from a STRICTLY worse
        class's decode row (worst class, newest admission first) — this is
        how a latency request gets pages off best-effort rows instead of
        waiting out their decode. The oldest admitted row of every class
        is protected, so admission pressure can delay but never starve an
        already-admitted request: per class, someone always finishes.
        Returns True if a row was preempted (admission should re-check)."""
        if not self.paged:
            return False
        rank = PRIORITY_RANK[head.priority]
        protected = self._protected_slots()
        victims = [s for s, req in self.running.items()
                   if PRIORITY_RANK[req.priority] > rank
                   and s not in protected]
        if not victims:
            return False
        self._preempt(max(victims, key=self._victim_key))
        return True

    def _try_compact(self) -> bool:
        """On-device paged-KV defrag as an admission rescue: when the pool
        cannot cover a claim plus its reserve headroom, fold duplicate
        full prompt pages across committed decode rows
        (:meth:`PagedKVPool.compact`) before reaching for
        preempt-and-recompute — dedup costs zero recompute and zero
        dispatches (block tables remap host-side), preemption costs a full
        prompt replay. Only running rows are offered: in-flight prefills'
        pages are still being scattered into by the ragged kernel.
        Returns True when compaction freed at least one page."""
        if not self.paged or not self.running:
            return False
        freed = self.pool.compact(
            {slot: req.prompt for slot, req in self.running.items()})
        if freed:
            self.obs.tracer.instant("compact", pages_freed=freed)
        return freed > 0

    def _admission_tick(self) -> None:
        if self.cfg.prefill_chunk > 0:
            # starting a chunked prefill is pure host bookkeeping; up to
            # max_prefills prompts then chunk concurrently through the
            # single serve_step call each tick, so long prompts never
            # stall running requests, never serialize queued prompts
            # behind them, and never cost a dispatch
            while len(self._prefills) < self.cfg.max_prefills and self.queue:
                head = self.queue[0]
                if self._can_admit_chunked(head):
                    self._start_chunked(self.queue.popleft())
                elif self._try_compact() and self._can_admit_chunked(head):
                    # defrag rescued the admission: duplicate prompt pages
                    # folded together instead of preempting a decode row
                    self._start_chunked(self.queue.popleft())
                elif not self._preempt_for_admission(head):
                    break
            return
        lim = self.cfg.admit_per_step or self.cfg.num_slots
        admitted = 0
        while self.queue and admitted < lim:
            head = self.queue[0]
            if self._can_admit(head):
                self._admit_whole(self.queue.popleft())
                admitted += 1
            elif not (self.paged and self._preempt_for_admission(head)):
                break

    # ------------------------------------------------------------------
    # page backpressure (paged layout only)
    # ------------------------------------------------------------------
    def _preempt(self, slot: int) -> None:
        """Free a running request's slot and pages; requeue it at the front
        for recompute (greedy decode makes the recompute exact)."""
        req = self.running.pop(slot)
        self._admit_seq.pop(slot, None)
        self.pool.free(slot)
        self.slot_temps[slot] = 0.0
        req.state, req.slot = QUEUED, -1
        self.queue.appendleft(req)
        self.preemptions += 1
        self._m_preempt.inc()
        self.obs.slo.on_preempt(req, self.ticks)
        self.obs.tracer.instant("preempt", rid=req.rid, slot=slot)

    def _abort_prefill(self) -> None:
        """Abort an in-flight prefill for pages — the NEWEST of the WORST
        class present (the victim ordering mirrors preemption: better
        classes and older admissions keep their pages and make progress),
        freeing its pages and requeueing it at its class queue's head."""
        k = max(range(len(self._prefills)),
                key=lambda i: (PRIORITY_RANK[self._prefills[i].req.priority],
                               i))
        pf = self._prefills[k]
        self._prefills = self._prefills[:k] + self._prefills[k + 1:]
        self.pool.free(pf.slot)
        self.slot_temps[pf.slot] = 0.0
        pf.req.state, pf.req.slot = QUEUED, -1
        self.queue.appendleft(pf.req)
        self.preemptions += 1
        self._m_aborts.inc()
        self.obs.slo.on_preempt(pf.req, self.ticks)
        self.obs.tracer.instant("abort_prefill", rid=pf.req.rid,
                                done=pf.done, length=pf.length)

    def _victim_key(self, slot: int):
        """Page-pressure victim ordering over running rows: worst priority
        class first, newest admission within a class — latency rows
        reclaim pages from best-effort decode before touching a peer, and
        the oldest row of each class outlives every younger classmate."""
        return (PRIORITY_RANK[self.running[slot].priority],
                self._admit_seq[slot])

    def _protected_slots(self) -> set:
        """The oldest admitted row of EVERY priority class. These are the
        last rows eligible for preemption: strict priority admission means
        a preempted best-effort row may requeue behind a sustained latency
        stream forever, so the only way the per-class no-starvation
        guarantee holds is if the oldest admitted row of each class keeps
        its pages and finishes."""
        oldest: Dict[str, int] = {}
        for s, req in self.running.items():
            c = req.priority
            if c not in oldest or self._admit_seq[s] < self._admit_seq[oldest[c]]:
                oldest[c] = s
        return set(oldest.values())

    def _ensure_pages(self) -> None:
        """Every running row appends one KV row this step; map each row's
        next page, preempting worst-class newest-admitted requests when
        the pool runs dry (better classes and older requests keep their
        pages and make progress). The oldest admitted row of each class is
        preempted only when no other victim is left — see
        :meth:`_protected_slots`."""
        for slot in sorted(self.running, key=self._victim_key):
            if slot not in self.running:
                continue
            while not self.pool.ensure_append_page(slot):
                protected = self._protected_slots()
                victims = [s for s in self.running
                           if s != slot and s not in protected]
                if victims:
                    self._preempt(max(victims, key=self._victim_key))
                elif self._prefills:
                    # a pending prefill (no tokens emitted yet) is a cheaper
                    # victim than any decode row
                    self._abort_prefill()
                elif slot not in protected:
                    # every OTHER row is its class's oldest: the needer
                    # yields rather than evict a protected row. Protected
                    # rows keep appending, so they finish and the yielder
                    # is readmitted — no livelock.
                    self._preempt(slot)
                    break
                elif len(self.running) > 1:
                    # all rows protected (one per class) and the pool is
                    # still dry: the worst class's row is the last resort
                    worst = max(self.running, key=self._victim_key)
                    self._preempt(worst)
                    if worst == slot:
                        break
                elif self.pool.num_seized():
                    # transient external exhaustion (fault injection seized
                    # the free list): even the last row can't append, so it
                    # waits out the fault as a queued recompute instead of
                    # crashing the scheduler
                    self._preempt(slot)
                    break
                else:
                    raise RuntimeError(
                        "paged KV pool cannot hold a single request; raise "
                        "num_blocks (needs >= max_len/block_size + 1)")

    def _decode_sample_spec(self):
        """Per-slot sampling vectors for this decode step, or None when
        every running request is greedy (the pure-argmax fast path). Step
        counters are refreshed from each request's emitted-token count, so
        the draw for token j is always keyed fold_in(base, j) no matter
        how the request got here (fresh, forked, or recomputed)."""
        stochastic = False
        for slot, req in self.running.items():
            self.slot_steps[slot] = len(req.out)
            sp = req.sampling
            if sp is not None and sp.temperature > 0.0:
                stochastic = True
        if not stochastic:
            return None
        return (self.slot_temps, self.slot_topk, self.slot_topp,
                self.slot_keys, self.slot_steps)

    # ------------------------------------------------------------------
    # client aborts, deadlines, graceful drain
    # ------------------------------------------------------------------
    def abort(self, rid: int, reason: str = "client") -> bool:
        """Cancel request ``rid`` in WHATEVER lifecycle state it is in —
        queued (fresh, preempted, or a pending fork child), mid-chunked-
        prefill, mid-decode, or spread across COW-forked children — freeing
        every slot and page it holds. Safe to call between ticks and from
        ``on_token`` callbacks mid-tick (the postprocess loops re-check row
        ownership). Aborting a forked request takes the whole sample group:
        parent and every live child. Returns True if anything was
        cancelled; False if ``rid`` holds nothing live (already finished,
        shed, or unknown)."""
        found: List[Request] = []
        for r in [r for r in self.queue if r.rid == rid]:
            self.queue.remove(r)
            found.append(r)
        live_pfs = [pf for pf in self._prefills if pf.req.rid == rid]
        if live_pfs:
            # rebuild rather than mutate: a mid-tick abort must not disturb
            # the tick's own iteration over the captured prefill list
            self._prefills = [pf for pf in self._prefills
                              if pf.req.rid != rid]
            for pf in live_pfs:
                self.pool.free(pf.slot)
                self.slot_temps[pf.slot] = 0.0
                found.append(pf.req)
        for slot, r in list(self.running.items()):
            if r.rid == rid:
                self.running.pop(slot)
                self._admit_seq.pop(slot, None)
                self.pool.free(slot)
                self.slot_temps[slot] = 0.0
                found.append(r)
        if not found:
            return False
        root = next((r.parent for r in found if r.parent is not None),
                    None) or found[0]
        t_done = time.perf_counter()
        for r in found:
            r.state, r.slot, r.finish_reason = ABORTED, -1, reason
            r.t_done = t_done
        root.state, root.finish_reason = ABORTED, reason
        root.t_done = t_done
        self.aborted[rid] = root
        self._m_client_aborts.inc()
        self.obs.metrics.counter(
            f"sched_aborts_{reason}_total",
            f"requests aborted with reason={reason}").inc()
        self.obs.slo.on_abort(root, self.ticks, reason)
        self.obs.tracer.instant("abort", rid=rid, reason=reason,
                                cancelled=len(found))
        self.journal.abort(rid, reason)
        return True

    def _quarantine_slot(self, slot: int) -> None:
        """Tear down one slot of a poisoned group: the slot frees, its
        exclusively-owned pages go to the pool's quarantine hold."""
        if self.paged:
            self.pool.quarantine_slot(slot)
        else:
            self.pool.free(slot)
        self.slot_temps[slot] = 0.0

    def quarantine(self, rid: int, reason: str = "nan_logits") -> bool:
        """Terminally remove a poisoned request — the watchdog's response
        to NaN/inf logits. Mirrors :meth:`abort` (whole fork group, any
        lifecycle state) with two deliberate differences: the request's
        pages go to the pool's quarantine hold instead of the free list
        (the KV that produced the bad logits stays dumpable until
        ``shutdown`` or ``pool.release_quarantined()``), and the terminal
        record lands in ``self.quarantined`` under the QUARANTINED state
        with its own metric/SLO accounting. Partial output stays on the
        request. Returns True if anything live was quarantined."""
        found: List[Request] = []
        for r in [r for r in self.queue if r.rid == rid]:
            self.queue.remove(r)
            found.append(r)
        live_pfs = [pf for pf in self._prefills if pf.req.rid == rid]
        if live_pfs:
            self._prefills = [pf for pf in self._prefills
                              if pf.req.rid != rid]
            for pf in live_pfs:
                self._quarantine_slot(pf.slot)
                found.append(pf.req)
        for slot, r in list(self.running.items()):
            if r.rid == rid:
                self.running.pop(slot)
                self._admit_seq.pop(slot, None)
                self._quarantine_slot(slot)
                found.append(r)
        if not found:
            return False
        root = next((r.parent for r in found if r.parent is not None),
                    None) or found[0]
        t_done = time.perf_counter()
        for r in found:
            r.state, r.slot, r.finish_reason = QUARANTINED, -1, reason
            r.t_done = t_done
        root.state, root.finish_reason = QUARANTINED, reason
        root.t_done = t_done
        self.quarantined[rid] = root
        self._m_quarantined.inc()
        self.obs.metrics.counter(
            f"sched_quarantined_{reason}_total",
            f"requests quarantined with reason={reason}").inc()
        self.obs.slo.on_quarantine(root, self.ticks, reason)
        self.obs.tracer.instant("quarantine", rid=rid, reason=reason,
                                cancelled=len(found))
        self.journal.quarantine(rid, reason)
        return True

    def _expire_deadlines(self) -> None:
        """Abort every live request whose ``deadline_ticks`` budget ran out
        (it had that many full ticks since submission); pages freed through
        the ordinary abort path, so a deadline storm leaves the pool
        leak-report clean."""
        t = self.ticks
        expired = set()
        for r in self.queue:
            if (r.deadline_ticks is not None
                    and t - r.submit_tick >= r.deadline_ticks):
                expired.add(r.rid)
        for pf in self._prefills:
            r = pf.req
            if (r.deadline_ticks is not None
                    and t - r.submit_tick >= r.deadline_ticks):
                expired.add(r.rid)
        for r in self.running.values():
            if (r.deadline_ticks is not None
                    and t - r.submit_tick >= r.deadline_ticks):
                expired.add(r.rid)
        for rid in sorted(expired):
            if self.abort(rid, reason="deadline"):
                self.deadline_misses += 1
                self._m_deadline.inc()

    def shutdown(self, grace_ticks: int = 0) -> DrainReport:
        """Graceful drain: stop admitting NEW submissions (submits shed
        with reason ``"shutting_down"``), keep ticking up to
        ``grace_ticks`` so in-flight and queued work can finish, then
        abort whatever remains (reason ``"shutdown"``, partial output kept
        on the request) and sweep the pool for leaks. Returns a
        :class:`DrainReport`; call sites that must fail loudly check
        ``report.clean`` and the shed list.

        ``grace_ticks`` is validated up front (:class:`InvalidConfig` on
        negative/NaN/non-integral) — a bad drain budget must fail before
        the scheduler stops admitting, not midway through the drain."""
        grace_ticks = _check_count("grace_ticks", grace_ticks, 0)
        self._draining = True
        self._m_draining.set(1)
        start = self.ticks
        while self.busy() and self.ticks - start < grace_ticks:
            self.step()
        shed_rids = sorted({r.rid for r in self.queue}
                           | {pf.req.rid for pf in self._prefills}
                           | {r.rid for r in self.running.values()})
        for rid in shed_rids:
            self.abort(rid, reason="shutdown")
        # a shut-down server returns every page: flush the prefix cache
        # (all requests are gone, so nothing is pinned and the flush
        # releases every retained page) before the invariant sweep
        cache_released = (self.pool.flush_prefix_cache()
                          if self.paged else 0)
        # the forensic quarantine hold does not outlive the process: a
        # shut-down server returns every page (the hold exists to keep
        # poisoned KV dumpable while the server is LIVE)
        quarantine_released = (self.pool.release_quarantined()
                               if self.paged else 0)
        findings = self.drain_check()
        if (self.cfg.check_leaks or self.obs.check_leaks) and findings:
            raise RuntimeError(
                "KV pool leaked at shutdown: " + "; ".join(findings))
        report = DrainReport(
            finished=len(self.finished), shed_rids=shed_rids,
            grace_ticks_used=self.ticks - start, leak_findings=findings,
            cache_pages_released=cache_released,
            quarantined_pages_released=quarantine_released)
        self.obs.tracer.instant(
            "shutdown", grace=report.grace_ticks_used,
            shed=len(shed_rids), finished=report.finished)
        return report

    # ------------------------------------------------------------------
    # crash recovery (serve.recovery)
    # ------------------------------------------------------------------
    def snapshot(self) -> dict:
        """Capture host-side request state (queues, prefill progress,
        per-slot emitted tokens, terminal records) as a JSON-serializable
        snapshot. KV pages are deliberately NOT serialized — restore
        recomputes them through the preempt-and-recompute path. See
        :func:`repro.serve.recovery.scheduler_snapshot`."""
        from repro.serve.recovery import scheduler_snapshot
        return scheduler_snapshot(self)

    def restore(self, snap: dict, on_token=None) -> Dict[str, int]:
        """Re-admit a snapshot's surviving requests into this (fresh, idle)
        scheduler; recovered streams resume bitwise-identically to an
        uninterrupted run. See
        :func:`repro.serve.recovery.scheduler_restore`."""
        from repro.serve.recovery import scheduler_restore
        return scheduler_restore(self, snap, on_token=on_token)

    # ------------------------------------------------------------------
    def step(self) -> None:
        """One scheduler tick. Paged: ONE jitted serve_step call over the
        packed ragged batch of decode tokens + every in-flight prefill's
        chunk. Slots: whole-prompt admission then a separate mixed decode
        call (the comparison layout)."""
        t0 = time.perf_counter()
        self._expire_deadlines()
        with self.obs.tracer.span("tick", tick=self.ticks):
            if self.paged:
                self._paged_tick()
            else:
                self._slots_tick()
        self.clock += 1
        self.ticks += 1
        self._m_ticks.inc()
        self._m_tick_ms.observe((time.perf_counter() - t0) * 1e3)
        self._m_queue.set(len(self.queue))
        self._m_running.set(len(self.running))
        self._m_inflight_pf.set(len(self._prefills))
        self._m_peak_running.set_max(self.peak_running)
        self._m_peak_pf.set_max(self.peak_prefills)

    def _split_budget(self) -> List[int]:
        """Split the tick's ``_qw``-token chunk budget across the in-flight
        prefills, shortest-remaining-first: the prefill closest to its last
        prompt token takes as much of the budget as it can use, then the
        next-shortest, and so on — short prompts reach their first token in
        as few ticks as possible instead of waiting out a long prompt.

        Anti-starvation, per priority class: the OLDEST prefill of EACH
        class present is first guaranteed a ``budget / max_prefills``
        slice (better classes reserve theirs first when the budget is
        tiny) before the greedy pass spends the rest class-major,
        shortest-remaining-first within a class. Pure shortest-first
        would let a sustained stream of short prompts zero out a long
        prompt's share every tick — the long request would hold its
        claimed pages forever while its TTFT grew without bound; making
        the guarantee per class extends that to mixed-criticality load:
        sustained latency-class traffic cannot zero out an admitted
        best-effort prefill's slice. With a single class in flight this
        reduces exactly to the PR 5 split. Returns per-prefill token
        counts aligned with ``self._prefills`` (admission order; ties
        broken oldest-first)."""
        pfs = self._prefills
        shares = [0] * len(pfs)
        budget = self._qw
        guaranteed: List[int] = []      # oldest prefill per class, best first
        for cls in PRIORITIES:
            idx = [i for i in range(len(pfs))
                   if pfs[i].req.priority == cls]
            if idx:
                guaranteed.append(idx[0])
        for i in guaranteed:
            if budget <= 0:
                break
            shares[i] = min(pfs[i].remaining,
                            max(1, self._qw // self.cfg.max_prefills),
                            budget)
            budget -= shares[i]
        order = sorted(range(len(pfs)),
                       key=lambda i: (PRIORITY_RANK[pfs[i].req.priority],
                                      pfs[i].remaining, i))
        for i in order:
            if budget <= 0:
                break
            take = min(pfs[i].remaining - shares[i], budget)
            shares[i] += take
            budget -= take
        return shares

    def _paged_tick(self) -> None:
        """The unified single-dispatch tick: pack the batch's real tokens
        into one flat list (decode rows, then every in-flight prefill's
        chunk) — padding never exceeds the static packed width, so a tick
        costs the tokens it actually advances, not ``num_slots × budget``."""
        tr = self.obs.tracer
        with tr.span("admission", queued=len(self.queue)):
            self._admission_tick()
        if self.running:
            with tr.span("ensure_pages", rows=len(self.running)):
                self._ensure_pages()    # may preempt rows / abort prefills
        if not self.running and not self._prefills:
            return
        ns, qw = self.cfg.num_slots, self._qw
        # ---- the self-healing dispatch loop --------------------------
        # Pack + dispatch run inside a retry loop. A dispatch that raises
        # DispatchFault (the transient fault this loop exists for) mutated
        # no host state — pool.cache is only replaced on success — so the
        # tick simply repacks and retries, up to cfg.tick_retries, then
        # re-raises. Any other exception (a compile error, device memory
        # exhausted) would fail the same way again, after recompiling the
        # step: it propagates on the first attempt.
        # A dispatch that returns NON-FINITE logits for a live row (the
        # watchdog check: real NaN/inf or an injected poison) quarantines
        # that row's whole request group and retries with the survivors —
        # their retry tokens are bitwise identical to a never-poisoned
        # tick because the inputs (pool cache, fed-back tokens, RNG
        # counters) are all unchanged. Quarantine shrinks the batch every
        # pass, so the NaN path terminates without a retry budget.
        faults = 0
        while True:
            pfs = self._prefills
            if not self.running and not pfs:
                return              # everything quarantined away mid-tick
            # two static packed widths (decode-only ticks cost exactly the
            # old decode call; chunk ticks add qw - 1 — the qw-token shared
            # budget, split across however many prefills are in flight,
            # minus the one slot a prefill always occupies instead of a
            # decode row) x serve_step's greedy/sampled traces = at most
            # four compilations over a scheduler's lifetime
            T = ns - 1 + qw if pfs else ns
            tokens = np.zeros((T, 1), np.int32)
            token_rows = np.zeros(T, np.int32)
            token_pos = np.full(T, -1, np.int32)     # -1 = dead padding
            logit_idx = np.zeros(ns, np.int32)
            finishing: List[_Prefill] = []  # final chunk lands this tick
            with tr.span("pack_budget_split", decode_rows=len(self.running),
                         prefills=len(pfs), width=T):
                t = 0
                for slot, req in self.running.items():
                    tokens[t, 0] = self.slot_tokens[slot, 0]
                    token_rows[t] = slot
                    token_pos[t] = self.pool.cur_len[slot]
                    logit_idx[slot] = t
                    self.slot_steps[slot] = len(req.out)
                    t += 1
                shares = self._split_budget()
                for pf, n in zip(pfs, shares):
                    if n == 0:      # budget spent by shorter prefills
                        continue
                    lo = pf.done
                    tokens[t:t + n, 0] = pf.toks[lo:lo + n]
                    token_rows[t:t + n] = pf.slot
                    token_pos[t:t + n] = np.arange(lo, lo + n)
                    if lo + n >= pf.length:
                        logit_idx[pf.slot] = t + n - 1  # prompt's last token
                        self._arm_first_draw(pf.req, pf.slot)
                        finishing.append(pf)
                    t += n
            sample = (self.slot_temps, self.slot_topk, self.slot_topp,
                      self.slot_keys, self.slot_steps)
            try:
                with tr.span("dispatch", tokens=int(t), width=T):
                    toks, logits, cache, finite = self.engine.serve_step(
                        tokens, token_rows, token_pos, logit_idx,
                        self.pool.cache, self.pool.block_tables,
                        self.pool.task_id[token_rows], sample)
            except DispatchFault as e:
                self.dispatch_faults += 1
                self._m_dispatch_faults.inc()
                tr.instant("dispatch_fault", error=type(e).__name__)
                faults += 1
                if faults > self.cfg.tick_retries:
                    raise
                self.tick_retries_used += 1
                self._m_tick_retries.inc()
                continue
            # watchdog: only rows whose logits this tick actually reports
            # are consulted — active decode rows, and prefills completing
            # their final chunk (other slots' logit_idx defaults to 0 and
            # would alias row 0's logits)
            bad = {req.rid for slot, req in self.running.items()
                   if not finite[slot]}
            bad |= {pf.req.rid for pf in finishing if not finite[pf.slot]}
            if not bad:
                break
            for rid in sorted(bad):
                self.quarantine(rid, reason="nan_logits")
            self.tick_retries_used += 1
            self._m_tick_retries.inc()
            # the poisoned dispatch's outputs (cache included) are dropped
        self._m_tick_tokens.observe(t)      # real tokens; T - t are dead
        self.pool.cache = cache
        with tr.span("postprocess"):
            active = list(self.running.items())
            if active:
                self.pool.advance([s for s, _ in active])
                self.steps_decoded += 1
                for slot, req in active:
                    if self.running.get(slot) is not req:
                        continue    # aborted mid-postprocess (on_token)
                    tok = int(toks[slot])
                    self.slot_tokens[slot, 0] = tok
                    done = self._emit(req, tok)
                    if self.running.get(slot) is not req:
                        continue    # on_token aborted this very request
                    if done:
                        self._finish(req)
            still: List[_Prefill] = []
            for pf, n in zip(pfs, shares):
                if pf.req.state in TERMINAL_STATES:
                    continue        # torn down mid-tick; pages already gone
                if n == 0:
                    still.append(pf)
                    continue
                pf.done += n
                self.prefill_chunks_run += 1
                self._m_chunks.inc()
                if pf.done < pf.length:
                    still.append(pf)
                    continue
                spec = self._first_sample_spec(pf.req)
                if spec is not None and len(spec[0]) > 1:
                    # fresh n>1 parent: every sample's token 0 comes from
                    # this one prefill row, each under its own stream (the
                    # only second dispatch, and only on n>1 installs)
                    first = self.engine.sample_first(logits[pf.slot], spec)
                else:
                    # singles drew (or argmax'd) inside serve_step itself
                    first = [int(toks[pf.slot])]
                self._install(pf.req, pf.slot, pf.length, first)
            # an on_token abort during an install above rebuilt
            # self._prefills; don't resurrect an aborted entry from `still`
            self._prefills = [pf for pf in still
                              if pf.req.state not in TERMINAL_STATES]
        self.peak_running = max(self.peak_running, len(self.running))
        if tr.enabled and self.paged:
            tr.counter("pages", used=self.pool.blocks_in_use(),
                       free=self.pool.free_blocks())
            tr.counter("requests", running=len(self.running),
                       queued=len(self.queue), prefills=len(self._prefills))

    def _slots_tick(self) -> None:
        """The contiguous-layout tick: bucketed whole-prompt admission,
        then one mixed decode call over all occupied slots."""
        tr = self.obs.tracer
        with tr.span("admission", queued=len(self.queue)):
            self._admission_tick()
        if self.running:
            sample = self._decode_sample_spec()
            self._m_tick_tokens.observe(len(self.running))
            with tr.span("dispatch", tokens=len(self.running)):
                toks, cache = self.engine.decode_mixed(
                    self.slot_tokens, self.pool.cur_len, self.pool.cache,
                    self.pool.task_id, sample=sample)
            self.pool.cache = cache
            with tr.span("postprocess"):
                active = list(self.running.items())
                self.peak_running = max(self.peak_running, len(active))
                self.pool.advance([s for s, _ in active])
                self.steps_decoded += 1
                for slot, req in active:
                    if self.running.get(slot) is not req:
                        continue    # aborted mid-postprocess (on_token)
                    tok = int(toks[slot])
                    self.slot_tokens[slot, 0] = tok
                    done = self._emit(req, tok)
                    if self.running.get(slot) is not req:
                        continue    # on_token aborted this very request
                    if done:
                        self._finish(req)

    def busy(self) -> bool:
        """Anything left to do: queued, decoding, or mid-prefill."""
        return bool(self.queue or self.running or self._prefills)

    def drain_check(self) -> List[str]:
        """Sweep the KV pool's alloc/refcount invariants (a drained pool
        must have every page free and every refcount zero) and publish the
        finding count through the metrics snapshot as ``kv_leak_findings``.
        Returns the findings; callers behind the ``check_leaks`` debug
        flag raise on a non-empty report so leaks in live runs fail
        loudly instead of silently shrinking the pool."""
        report = self.pool.leak_report()
        self._m_leaks.set(len(report))
        for msg in report:
            self.obs.tracer.instant("kv_leak", finding=msg)
        return report

    def _maybe_check_leaks(self) -> None:
        if not (self.cfg.check_leaks or self.obs.check_leaks):
            return
        report = self.drain_check()
        if report:
            raise RuntimeError(
                "KV pool leaked at drain: " + "; ".join(report))

    def run(self) -> Dict[int, Request]:
        """Drain everything currently submitted."""
        while self.busy():
            self.step()
        self._maybe_check_leaks()
        return self.finished

    def run_stream(self, arrivals: List[Tuple[int, Request]]) -> Dict[int, Request]:
        """Serve a timed stream: ``(arrival_step, request)`` pairs, arrival
        measured on the scheduler's decode-step clock. Requests join the
        running batch as their arrival step passes; idle gaps fast-forward."""
        order = sorted(range(len(arrivals)), key=lambda i: arrivals[i][0])
        i = 0
        while i < len(order) or self.busy():
            if (not self.busy() and i < len(order)
                    and arrivals[order[i]][0] > self.clock):
                self.clock = arrivals[order[i]][0]       # idle: fast-forward
            while i < len(order) and arrivals[order[i]][0] <= self.clock:
                self.submit(arrivals[order[i]][1])
                i += 1
            self.step()
        self._maybe_check_leaks()
        return self.finished
