"""Multi-task serving launcher.

Loads (or fabricates, with --demo) fused AoT task tables and serves a
continuous stream of mixed-task requests from a single frozen backbone —
the paper's deployment story as a runnable process. Requests arrive as a
Poisson process (or --arrivals bursty / --arrival-trace FILE), carry a
priority class drawn from --priority-mix, pick a task at random, and
stream their tokens through a callback as they decode; a static batched
mode (--static) keeps the old all-arrive-together behavior for
comparison. Overload knobs: --max-queue bounds admission (shed requests
are retried with exponential backoff up to --max-retries), latency-class
requests can carry --deadline-ticks, and --grace-ticks hands the drain to
Scheduler.shutdown. The process exits non-zero if the pool leaks.

    # fabricated tables, continuous stream
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
        --demo --tasks 3 --requests 12 --rate 0.5

    # real tables exported by examples/fuse_and_export.py
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
        --load results/fused_artifacts
"""
from __future__ import annotations

import argparse
import dataclasses
import heapq
import sys
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import aot as aot_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model, ModelOptions
from repro.obs import ServeObservability
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.recovery import RequestJournal, replay_journal
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import (PRIORITIES, ContinuousScheduler, Request,
                                   SchedulerConfig, ShedError, STANDARD)


def demo_tasks(cfg, params, n_tasks: int, dtype=jnp.float32):
    """Fabricate plausibly-scaled fused tables (no training), stacked
    {'table': (L, T, V, d)} in ``dtype``. Each layer of each task is
    written into the stack as soon as it is made, so building holds the
    stack plus one layer, never a list of whole tables beside it."""
    embed = params["embed"]["tok"]
    stack = jnp.zeros((cfg.num_layers, n_tasks, cfg.vocab_size, cfg.d_model),
                      dtype)
    for t in range(n_tasks):
        pp, opt = aot_mod.random_fc_params(cfg, seed=t, scale=0.03)
        for layer, rows in enumerate(aot_mod.fused_layers(
                pp, cfg, opt, embed=embed, vocab_chunk=4096)):
            stack = aot_mod.write_block(stack, rows[None], layer, t)
    return {"table": stack}


def load_tasks(cfg, directory: str):
    """Load fused task tables written by examples/fuse_and_export.py
    (one checkpoint step per task)."""
    from repro.checkpoint.manager import CheckpointManager
    mgr = CheckpointManager(directory, async_save=False)
    steps = mgr.all_steps()
    if not steps:
        raise FileNotFoundError(
            f"no fused-table checkpoints under {directory!r}; run "
            "examples/fuse_and_export.py first (or pass --demo)")
    like = {"table": np.zeros(
        (cfg.num_layers, cfg.vocab_size, cfg.d_model), np.float32)}
    tasks = []
    for s in steps:
        tree, extra = mgr.restore(like, step=s)
        print(f"  step {s}: fused {extra.get('mode', '?')} tables "
              f"({extra.get('arch', '?')})")
        tasks.append(tree)
    return tasks


def build_engine(cfg, opts: ModelOptions, max_len: int, *,
                 demo: int = 0, load: Optional[str] = None,
                 seed: int = 0) -> ServeEngine:
    """Model, seeded params (in ``opts.param_dtype``), the stacked fused
    tables — ``demo`` fabricated tasks, or the ones exported under
    ``load`` — and the engine that serves them. The engine holds the only
    device copy of the tables: nothing here outlives the call."""
    model = Model(cfg, opts)
    params = model.init(jax.random.PRNGKey(seed))
    tables = (aot_mod.stack_tasks(load_tasks(cfg, load), opts.param_dtype)
              if load else demo_tasks(cfg, params, demo, opts.param_dtype))
    return ServeEngine(model, params, ServeConfig(max_len=max_len),
                       fused_tasks=tables)


def parse_priority_mix(spec: str):
    """``latency=0.2,standard=0.5,best_effort=0.3`` -> normalized weights
    over the scheduler's priority classes (missing classes get 0)."""
    weights = {c: 0.0 for c in PRIORITIES}
    for part in spec.split(","):
        if not part.strip():
            continue
        name, _, val = part.partition("=")
        name = name.strip()
        if name not in weights:
            raise ValueError(f"unknown priority class {name!r} "
                             f"(choose from {', '.join(PRIORITIES)})")
        weights[name] = float(val)
    total = sum(weights.values())
    if total <= 0:
        raise ValueError(f"priority mix {spec!r} has no positive weight")
    return {c: w / total for c, w in weights.items()}


def bursty_ticks(rng, n: int, burst: int, gap: int):
    """On/off arrival process: bursts of near-simultaneous arrivals
    separated by quiet gaps — the adversarial pattern a Poisson stream
    (independent increments) essentially never produces, and the one that
    actually exercises shedding, displacement, and class-aware admission."""
    ticks, t = [], 0
    while len(ticks) < n:
        k = min(burst, n - len(ticks))
        ticks.extend(t + int(rng.integers(0, 2)) for _ in range(k))
        t += max(gap, 1)
    return sorted(ticks[:n])


def load_arrival_trace(path: str, n: int):
    """Trace-driven arrivals: one line per request, ``tick[,priority]``.
    Extra lines are ignored; if the trace is shorter than --requests the
    run is truncated to the trace (the trace IS the workload)."""
    ticks, prios = [], []
    with open(path) as fh:
        for line in fh:
            line = line.split("#", 1)[0].strip()
            if not line:
                continue
            parts = [p.strip() for p in line.split(",")]
            ticks.append(int(parts[0]))
            prios.append(parts[1] if len(parts) > 1 and parts[1] else None)
            if len(ticks) == n:
                break
    if not ticks:
        raise ValueError(f"arrival trace {path!r} is empty")
    return ticks, prios


def run_with_retries(sched, arrivals, grace_ticks: int,
                     max_retries: int, backoff: int,
                     crash_at_tick: int = 0, make_sched=None,
                     on_token=None):
    """Client loop: submit on each request's arrival tick; a shed request
    is re-enqueued with exponential backoff (``backoff ** attempt`` ticks)
    up to ``max_retries`` resubmissions. Two shed paths reach the client:
    a ShedError raised at submit (queue full), and DISPLACEMENT — a
    queued request evicted later by a higher-class arrival, which raises
    nothing at the victim's own submit, so the loop scans
    ``sched.shed`` after every tick for victims to resubmit. When the
    stream ends, ``grace_ticks >= 0`` hands off to ``Scheduler.shutdown``
    (graceful drain with a deadline); ``-1`` drains fully.

    ``crash_at_tick > 0`` (with ``make_sched``, a zero-arg factory for a
    fresh scheduler journaling to the SAME path) simulates process death
    once, at that global tick: the live scheduler is abandoned where it
    stands, its journal is replayed, and the factory's replacement is
    restored and keeps serving. Returns
    ``(gave_up_rids, retries, drain_report_or_None, sched)`` — ``sched``
    is the scheduler that finished the run (the replacement, after a
    crash)."""
    heap = [(t, i, req) for i, (t, req) in enumerate(arrivals)]
    heapq.heapify(heap)
    seq = len(heap)
    attempts = {}                        # rid -> submissions so far
    pending = {req.rid for _, _, req in heap}   # queued for (re)submit
    gave_up, retries = [], 0
    gt = 0                               # global tick, survives the crash

    def requeue(req):
        nonlocal seq, retries
        if attempts[req.rid] > max_retries:
            if req.rid not in gave_up:
                gave_up.append(req.rid)
            return
        retries += 1
        heapq.heappush(heap, (sched.clock + backoff ** (attempts[req.rid] - 1),
                              seq, req))
        seq += 1
        pending.add(req.rid)

    while heap:
        if not sched.busy() and heap[0][0] > sched.clock:
            sched.clock = heap[0][0]     # idle fast-forward, like run_stream
        while heap and heap[0][0] <= sched.clock:
            _, _, req = heapq.heappop(heap)
            pending.discard(req.rid)
            attempts[req.rid] = attempts.get(req.rid, 0) + 1
            try:
                sched.submit(req)
            except ShedError:
                requeue(req)
        sched.step()
        gt += 1
        if crash_at_tick and gt == crash_at_tick and make_sched is not None:
            path = sched.journal.path
            sched.journal.close()
            snap = replay_journal(path)
            live = sum(1 for r in snap["requests"]
                       if r.get("status") == "live")
            sched = make_sched()
            sched.restore(snap, on_token=on_token)
            print(f"simulated crash at tick {gt}: replayed journal "
                  f"{path} ({len(snap['requests'])} requests, {live} live "
                  "re-admitted through chunked prefill replay)")
        for rid in [r for r in sched.shed if r not in pending]:
            requeue(sched.shed[rid])     # displaced victim: client resubmits
    if grace_ticks >= 0:
        report = sched.shutdown(grace_ticks)
        return gave_up, retries, report, sched
    while sched.busy():
        sched.step()
    sched._maybe_check_leaks()
    return gave_up, retries, None, sched


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    src = ap.add_argument_group("task tables (one of)")
    src.add_argument("--demo", action="store_true",
                     help="fabricate random task tables instead of loading")
    src.add_argument("--load", metavar="DIR",
                     help="load fused tables exported by examples/"
                          "fuse_and_export.py (one checkpoint step per task)")
    ap.add_argument("--tasks", type=int, default=3,
                    help="number of fabricated tasks (--demo only)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="mean arrivals per decode step (Poisson stream)")
    ovl = ap.add_argument_group("overload / robustness")
    ovl.add_argument("--arrivals", choices=("poisson", "bursty"),
                     default="poisson",
                     help="arrival process: poisson (independent "
                          "increments) or bursty (on/off bursts — the "
                          "pattern that actually exercises shedding)")
    ovl.add_argument("--burst", type=int, default=6,
                     help="arrivals per burst (--arrivals bursty)")
    ovl.add_argument("--burst-gap", type=int, default=0,
                     help="quiet ticks between bursts (0 = derive from "
                          "--rate so the mean rate matches poisson)")
    ovl.add_argument("--arrival-trace", metavar="FILE",
                     help="trace-driven arrivals: one 'tick[,priority]' "
                          "line per request (overrides --arrivals/--rate)")
    ovl.add_argument("--priority-mix", metavar="SPEC",
                     default="standard=1",
                     help="request class weights, e.g. "
                          "'latency=0.2,standard=0.5,best_effort=0.3'")
    ovl.add_argument("--deadline-ticks", type=int, default=0,
                     help="deadline for latency-class requests in real "
                          "ticks; past-deadline requests are aborted and "
                          "their pages freed (0 = no deadlines)")
    ovl.add_argument("--max-queue", type=int, default=0,
                     help="bounded admission queue: beyond this depth "
                          "submissions are shed with a reason (0 = "
                          "unbounded, never sheds)")
    ovl.add_argument("--max-retries", type=int, default=4,
                     help="client retries for a shed submission "
                          "(exponential backoff, --backoff ** attempt)")
    ovl.add_argument("--backoff", type=int, default=2,
                     help="backoff base in ticks for shed retries")
    ovl.add_argument("--grace-ticks", type=int, default=-1,
                     help="graceful-drain budget handed to "
                          "Scheduler.shutdown once the stream ends: "
                          "in-flight work gets this many ticks, the rest "
                          "is shed and reported (-1 = drain fully)")
    rec = ap.add_argument_group("crash recovery (repro.serve.recovery)")
    rec.add_argument("--journal", metavar="FILE",
                     help="append every request lifecycle transition "
                          "(submit/admit/emit/finish/shed/abort/"
                          "quarantine) to this JSONL file — enough to "
                          "replay the run after a crash")
    rec.add_argument("--restore-from", metavar="FILE",
                     help="before serving, replay this journal and "
                          "re-admit its surviving requests through "
                          "chunked prefill replay (recovered streams are "
                          "bitwise-identical to an uninterrupted run)")
    rec.add_argument("--crash-at-tick", type=int, default=0,
                     help="demo: simulate process death at this global "
                          "tick — abandon the scheduler mid-stream, "
                          "replay --journal, restore a fresh scheduler, "
                          "keep serving (0 = off; requires --journal)")
    ap.add_argument("--slots", type=int, default=4,
                    help="KV-pool slots (continuous batch width)")
    ap.add_argument("--layout", choices=("paged", "slots"), default="paged",
                    help="KV pool layout: block-table pages (default) or "
                         "one contiguous max-len region per slot")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV page size in tokens (--layout paged)")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="physical KV pages incl. the scratch page "
                         "(0 = capacity parity with --layout slots)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="per-tick prefill token budget: prompts stream "
                         "through the unified serve step in chunks drawn "
                         "from it (0 = whole-prompt prefill)")
    ap.add_argument("--max-prefills", type=int, default=4,
                    help="prompts allowed to chunk concurrently, splitting "
                         "the per-tick budget shortest-remaining-first "
                         "(1 = serial prefill admission)")
    pc = ap.add_argument_group("prefix caching (cross-request page reuse)")
    pc.add_argument("--prefix-cache-pages", type=int, default=0,
                    help="cross-request shared-prefix page cache capacity: "
                         "finished requests' full prompt pages are retained "
                         "(LRU) and matched into later same-task "
                         "admissions, so their prefill starts at the first "
                         "uncached token (needs --layout paged and "
                         "--prefill-chunk > 0; 0 = off)")
    pc.add_argument("--system-prompt", type=int, default=0,
                    help="repeated-system-prompt workload: prepend a fixed "
                         "per-task system prefix of this many tokens to "
                         "every request's prompt — the many-users-per-task "
                         "traffic shape the prefix cache exists for "
                         "(0 = fully random prompts)")
    samp = ap.add_argument_group("sampling (default: greedy)")
    samp.add_argument("--temperature", type=float, default=0.0,
                      help="0 = greedy argmax; > 0 samples from the scaled "
                           "distribution with per-request seeded streams")
    samp.add_argument("--top-k", type=int, default=0,
                      help="keep only the k highest logits (0 = off)")
    samp.add_argument("--top-p", type=float, default=1.0,
                      help="nucleus sampling mass (1.0 = off)")
    samp.add_argument("--samples", type=int, default=1,
                      help="parallel samples per request (n > 1 shares the "
                           "prefill KV pages copy-on-write; --layout paged)")
    samp.add_argument("--seed", type=int, default=0,
                      help="base RNG seed (request i uses seed + i)")
    ap.add_argument("--prompt", type=int, default=16,
                    help="max prompt length (sampled 4..this)")
    ap.add_argument("--steps", type=int, default=8,
                    help="max new tokens per request (sampled 2..this)")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--static", action="store_true",
                    help="old behavior: one static batch, uniform lengths")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress per-token streaming output")
    obs_g = ap.add_argument_group("observability (repro.obs)")
    obs_g.add_argument("--metrics", action="store_true",
                       help="collect serve-path metrics + request "
                            "lifecycles; prints the snapshot and the "
                            "TTFT/TPOT/e2e SLO summary at drain")
    obs_g.add_argument("--metrics-out", metavar="FILE",
                       help="append the final metrics snapshot as one "
                            "JSONL line (implies --metrics)")
    obs_g.add_argument("--trace-out", metavar="FILE",
                       help="write a Chrome-trace-event JSON of every "
                            "scheduler tick (admission / budget split / "
                            "dispatch / postprocess spans; open in "
                            "chrome://tracing or ui.perfetto.dev)")
    obs_g.add_argument("--jax-profile", metavar="DIR",
                       help="bracket the run with jax.profiler so device "
                            "traces line up with the scheduler spans")
    obs_g.add_argument("--check-leaks", action="store_true",
                       help="debug: sweep KV-pool alloc/refcount "
                            "invariants at drain; findings go into the "
                            "metrics snapshot and fail the run")
    obs_g.add_argument("--slo-ttft-ticks", type=float, default=8.0,
                       help="TTFT SLO target in real scheduler ticks "
                            "(attainment reported with --metrics)")
    args = ap.parse_args()
    enable_compile_cache()

    if not args.demo and not args.load:
        ap.error("pass --demo (fabricated tables) or --load DIR "
                 "(fused tables from examples/fuse_and_export.py)")
    if args.system_prompt + args.prompt + args.steps - 1 > args.max_len:
        ap.error(f"--system-prompt {args.system_prompt} + --prompt "
                 f"{args.prompt} + --steps {args.steps} cannot fit "
                 f"--max-len {args.max_len}; raise --max-len or shrink the "
                 "requests")
    if args.prefix_cache_pages > 0 and (args.layout != "paged"
                                        or args.prefill_chunk <= 0):
        ap.error(f"--prefix-cache-pages {args.prefix_cache_pages} needs "
                 "--layout paged with --prefill-chunk > 0 (cached pages "
                 "are mapped through block tables and prefill resumes at "
                 "the first uncached token)")
    if args.samples > 1 and args.layout != "paged":
        ap.error(f"--samples {args.samples} needs --layout paged "
                 "(parallel samples share prefill KV via COW page forking)")
    if args.crash_at_tick > 0 and not args.journal:
        ap.error("--crash-at-tick needs --journal (recovery replays the "
                 "journal; without one there is nothing to restore from)")

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg, repeats=2)
    eng = build_engine(cfg, ModelOptions(chunk_q=64, chunk_kv=args.max_len),
                       args.max_len, demo=args.tasks if args.demo else 0,
                       load=args.load)
    n_tasks = eng.num_tasks
    print(f"serving {n_tasks} tasks; fused tables "
          f"{aot_mod.table_bytes(cfg, n_tasks, 2) / 1e6:.1f} MB total")

    rng = np.random.default_rng(0)

    if args.static:
        if args.temperature > 0 or args.samples > 1 or args.top_k > 0 \
                or args.top_p < 1.0:
            print("warning: --static is greedy single-sample only; ignoring "
                  "--temperature/--top-k/--top-p/--samples/--seed")
        prompts = rng.integers(0, cfg.vocab_size,
                               (args.requests, args.prompt)).astype(np.int32)
        task_ids = rng.integers(0, n_tasks, args.requests).astype(np.int32)
        out = eng.generate(prompts, args.steps, task_ids)
        for i in range(args.requests):
            print(f"req {i} task={task_ids[i]}: {out[i].tolist()}")
        return

    # ---- continuous stream: Poisson arrivals, mixed tasks, streaming ----
    def on_token(req, tok):
        if not args.quiet:
            print(f"  [stream] req {req.rid} task={req.task_id} "
                  f"tok#{len(req.out)}: {tok}")

    sampling = None
    if args.temperature > 0 or args.samples > 1:
        sampling = SamplingParams(
            temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
            n=args.samples)
        print(f"sampling: temp={args.temperature} top_k={args.top_k} "
              f"top_p={args.top_p} n={args.samples} (seeded per request)")
    if args.temperature <= 0 and (args.top_k > 0 or args.top_p < 1.0):
        print("warning: --top-k/--top-p have no effect at --temperature 0 "
              "(greedy argmax)")
    if args.temperature <= 0 and args.samples > 1:
        print(f"warning: --samples {args.samples} at --temperature 0 forks "
              f"{args.samples} identical greedy continuations")

    try:
        mix = parse_priority_mix(args.priority_mix)
    except ValueError as e:
        ap.error(str(e))
    trace_prios = [None] * args.requests
    if args.arrival_trace:
        ticks, trace_prios = load_arrival_trace(args.arrival_trace,
                                                args.requests)
        if len(ticks) < args.requests:
            print(f"arrival trace has {len(ticks)} entries; truncating "
                  f"--requests {args.requests} to match")
            args.requests = len(ticks)
        print(f"trace-driven arrivals from {args.arrival_trace} "
              f"({len(ticks)} requests)")
    elif args.arrivals == "bursty":
        gap = args.burst_gap or max(int(args.burst / max(args.rate, 1e-6)), 1)
        ticks = bursty_ticks(rng, args.requests, args.burst, gap)
        print(f"bursty arrivals: bursts of {args.burst} every {gap} ticks")
    else:
        ticks, t = [], 0.0
        for _ in range(args.requests):
            t += rng.exponential(1.0 / max(args.rate, 1e-6))
            ticks.append(int(t))
    classes = list(mix)
    weights = [mix[c] for c in classes]
    # repeated-system-prompt workload: every request for task t opens with
    # the SAME seeded prefix — across requests those prefixes are identical
    # KV, which is exactly what --prefix-cache-pages deduplicates
    sys_prompts = {}
    if args.system_prompt > 0:
        sys_prompts = {t: rng.integers(0, cfg.vocab_size, args.system_prompt)
                       .astype(np.int32) for t in range(n_tasks)}
        print(f"repeated-system-prompt workload: {args.system_prompt} shared "
              f"tokens per task + 4..{args.prompt} unique tokens per request")
    arrivals = []
    for i in range(args.requests):
        plen = int(rng.integers(4, args.prompt + 1))
        prio = trace_prios[i] or str(rng.choice(classes, p=weights))
        if prio not in PRIORITIES:
            ap.error(f"arrival trace priority {prio!r} is not one of "
                     f"{', '.join(PRIORITIES)}")
        task = int(rng.integers(0, n_tasks))
        prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
        if sys_prompts:
            prompt = np.concatenate([sys_prompts[task], prompt])
        req = Request(
            rid=i, prompt=prompt,
            task_id=task,
            max_new_tokens=int(rng.integers(2, args.steps + 1)),
            priority=prio,
            deadline_ticks=(args.deadline_ticks
                            if args.deadline_ticks > 0 and prio == "latency"
                            else None),
            on_token=on_token,
            sampling=None if sampling is None
            else dataclasses.replace(sampling, seed=args.seed + i))
        arrivals.append((ticks[i], req))

    if args.prefill_chunk > 0 and args.layout != "paged":
        print("warning: chunked prefill rides the unified paged serve step; "
              "--layout slots falls back to whole-prompt prefills")
        args.prefill_chunk = 0
    want_obs = (args.metrics or args.metrics_out or args.trace_out
                or args.jax_profile or args.check_leaks)
    obs = None
    if want_obs:
        obs = ServeObservability(
            metrics=bool(args.metrics or args.metrics_out),
            trace=bool(args.trace_out), jax_profile_dir=args.jax_profile,
            check_leaks=args.check_leaks)
    sched_cfg = SchedulerConfig(
        num_slots=args.slots, kv_layout=args.layout,
        block_size=args.block_size, num_blocks=args.num_blocks,
        prefill_chunk=args.prefill_chunk, max_prefills=args.max_prefills,
        prefix_cache_pages=args.prefix_cache_pages,
        max_queue=args.max_queue)

    def make_sched():
        journal = RequestJournal(args.journal) if args.journal else None
        return ContinuousScheduler(eng, sched_cfg, obs=obs, journal=journal)

    sched = make_sched()
    if args.restore_from:
        snap = replay_journal(args.restore_from)
        sched.restore(snap, on_token=on_token)
        live = sum(1 for r in snap["requests"] if r.get("status") == "live")
        print(f"restored from journal {args.restore_from}: "
              f"{len(snap['requests'])} requests replayed, {live} live "
              "re-admitted through chunked prefill replay")
    if obs is not None:
        obs.tracer.start()          # no-op without --jax-profile
    try:
        shed_rids, retries, drain_report, sched = run_with_retries(
            sched, arrivals, grace_ticks=args.grace_ticks,
            max_retries=args.max_retries, backoff=args.backoff,
            crash_at_tick=args.crash_at_tick,
            make_sched=make_sched if args.journal else None,
            on_token=on_token)
        finished = sched.finished
    finally:
        if obs is not None:
            obs.tracer.stop()
            if args.trace_out:
                obs.tracer.write(args.trace_out)
                print(f"tick trace -> {args.trace_out} "
                      f"({len(obs.tracer.events)} events; load in "
                      "chrome://tracing or ui.perfetto.dev)")
    # a tick is not "one decode step plus maybe one prefill chunk" anymore:
    # the paged path folds chunk + decode rows into ONE device call, so
    # report realized dispatches per tick instead of assuming the split.
    # sched.ticks counts REAL step() calls only; sched.clock additionally
    # fast-forwards across idle gaps in the arrival stream, so the
    # difference is exactly the idle air that must never leak into
    # per-tick aggregates (it used to skew the old combined report)
    idle_gap = sched.clock - sched.ticks
    per_tick = eng.dispatches / max(sched.ticks, 1)
    print(f"\nserved {len(finished)} requests in {sched.ticks} real ticks "
          f"(+{idle_gap} idle fast-forwarded arrival steps, excluded from "
          "every per-tick stat): "
          f"{sched.steps_decoded} decode steps, {sched.prefill_chunks_run} "
          f"prefill chunks, {sched.tokens_emitted} tokens, "
          f"{eng.dispatches} device dispatches ({per_tick:.2f}/tick, "
          f"{args.slots} slots, layout={args.layout})")
    if sched.paged:
        pool = sched.pool
        print(f"paged pool: {pool.num_blocks - 1} usable pages x "
              f"{pool.block_size} tokens, peak pages {pool.peak_pages}, "
              f"peak concurrency {sched.peak_running}, "
              f"peak concurrent prefills {sched.peak_prefills}, "
              f"{sched.preemptions} preemptions, "
              f"{pool.forks} forks, {pool.cow_copies} COW page copies")
        cache = pool.prefix_cache
        if cache is not None:
            total = cache.hits + cache.misses
            rate = cache.hits / max(total, 1)
            print(f"prefix cache ({cache.capacity} pages): {cache.hits}/"
                  f"{total} admissions hit ({rate:.0%}), "
                  f"{cache.hit_tokens} prefill tokens skipped, "
                  f"{cache.retained_pages} pages retained, "
                  f"{cache.evicted_pages} evicted, {len(cache)} resident "
                  "at exit")
    if retries or shed_rids or sched.shed or sched.aborted:
        print(f"overload: {retries} shed retries (backoff base "
              f"{args.backoff}), {len(shed_rids)} requests gave up after "
              f"{args.max_retries} retries "
              f"{sorted(shed_rids) if shed_rids else ''}".rstrip())
        if sched.deadline_misses:
            print(f"  {sched.deadline_misses} deadline misses "
                  f"(--deadline-ticks {args.deadline_ticks}); pages freed "
                  "at abort")
        if sched.aborted:
            reasons = {}
            for r in sched.aborted.values():
                reasons[r.finish_reason] = reasons.get(r.finish_reason, 0) + 1
            print(f"  aborted in flight: {dict(sorted(reasons.items()))}")
    if drain_report is not None:
        print(f"shutdown(grace={args.grace_ticks}): finished "
              f"{drain_report.finished}, used {drain_report.grace_ticks_used}"
              f" grace ticks, released {drain_report.cache_pages_released} "
              f"cached prefix pages, shed {len(drain_report.shed_rids)} "
              f"in-flight "
              f"{drain_report.shed_rids if drain_report.shed_rids else ''}"
              .rstrip())
    if sched.quarantined:
        print(f"quarantined {len(sched.quarantined)} poisoned requests "
              f"{sorted(sched.quarantined)}; their pages were held for "
              "forensics and released at shutdown")
    if args.journal:
        j = sched.journal
        print(f"journal {args.journal}: {j.events_written} events, "
              f"{j.bytes_written} bytes this run")
        j.close()
    if obs is not None and obs.metrics.enabled:
        summary = obs.slo.summary(
            targets={"ttft_ticks": args.slo_ttft_ticks})
        # tick series and wall series are separate on purpose: ticks are
        # load-invariant and idle-proof (one tick == one dispatch's worth
        # of scheduler work); wall ms swings with machine load and eats
        # every jit compile — never mix the two
        tick = {k: v for k, v in summary.items() if k.endswith("_ticks")}
        wall = {k: v for k, v in summary.items() if k.endswith("_ms")}
        print("\nSLO summary (real-tick series, load-invariant):")
        for k, v in tick.items():
            print(f"  {k:>18}: p50={v['p50']:g} p95={v['p95']:g} "
                  f"p99={v['p99']:g}")
        print("SLO summary (wall-clock series; includes jit compiles, "
              "swings with machine load):")
        for k, v in wall.items():
            print(f"  {k:>18}: p50={v['p50']:g} p95={v['p95']:g} "
                  f"p99={v['p99']:g}")
        for name, frac in summary.get("slo_attainment", {}).items():
            print(f"  attainment {name}: {frac:.1%}")
        by_class = summary.get("by_class", {})
        if by_class:
            print("per-class SLO (real-tick series):")
            for cls, s in by_class.items():
                parts = [f"requests={s.get('requests', 0)}"]
                for key in ("ttft_ticks", "tpot_ticks"):
                    if key in s:
                        parts.append(f"{key} p50={s[key]['p50']:g} "
                                     f"p95={s[key]['p95']:g}")
                if s.get("shed"):
                    parts.append(f"shed={s['shed']}")
                if s.get("aborted"):
                    parts.append(f"aborted={s['aborted']}")
                print(f"  {cls:>12}: " + " ".join(parts))
                for name, frac in s.get("slo_attainment", {}).items():
                    print(f"  {'':>12}  attainment {name}: {frac:.1%}")
        if summary.get("sheds"):
            print(f"  sheds: {summary['sheds']} "
                  f"(by class: {summary.get('sheds_by_class', {})})")
        pcs = summary.get("prefix_cache")
        if pcs:
            print("prefix-cache TTFT (real-tick series): "
                  f"warm p50={pcs['warm_ttft_ticks']['p50']:g} "
                  f"({pcs['warm_requests']} requests) vs "
                  f"cold p50={pcs['cold_ttft_ticks']['p50']:g} "
                  f"({pcs['cold_requests']} requests); "
                  f"{pcs['cached_tokens']} prompt tokens served from cache")
        if args.metrics_out:
            obs.metrics.write_jsonl(args.metrics_out,
                                    extra={"slo": summary,
                                           "ticks": sched.ticks,
                                           "idle_fast_forward": idle_gap})
            print(f"metrics JSONL -> {args.metrics_out}")
        if args.metrics:
            print("\nmetrics snapshot (prometheus text):")
            print(obs.metrics.prometheus_text())
    # hard-fail on pool-accounting findings regardless of --metrics /
    # --check-leaks: a leak at drain is never OK in a launcher run, and a
    # zero exit code must mean "drained clean"
    findings = sched.drain_check()
    if drain_report is not None:
        findings = sorted(set(findings) | set(drain_report.leak_findings))
    if findings:
        print("DRAIN FAILED: KV pool leak findings at exit:",
              file=sys.stderr)
        for f in findings:
            print(f"  {f}", file=sys.stderr)
        sys.exit(1)

    for rid in sorted(finished):
        req = finished[rid]
        ms = (req.t_done - req.t_submit) * 1e3
        if req.samples is not None:
            print(f"req {rid} task={req.task_id} plen={len(req.prompt)} "
                  f"latency={ms:.0f}ms ({len(req.samples)} samples):")
            for i, s in enumerate(req.samples):
                print(f"    sample {i}: {s}")
        else:
            print(f"req {rid} task={req.task_id} plen={len(req.prompt)} "
                  f"latency={ms:.0f}ms: {req.out}")


if __name__ == "__main__":
    main()
