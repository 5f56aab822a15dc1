"""Paper §3.1 / Table 1: multi-task inference with one backbone.

Comparisons:

  (a) one batched multi-task pass over mixed task ids vs sequential
      per-task batches — the resource-allocation win the paper argues for;
  (b) continuous batching (KV pool, requests admitted between decode
      steps) vs static batching at EQUAL batch capacity, over a workload
      with heterogeneous output lengths — tokens/s;
  (c) request latency (p50/p99) under a Poisson arrival stream at varying
      offered load and task counts;
  (e) stochastic sampling overhead — the same workload decoded greedy vs
      temperature/top-p sampled (the fused sample-in-decode-step path);
  (f) n=4 parallel samples via COW page forking vs n=4 independent
      decodes — peak KV pages (prompt pages shared, only divergent decode
      tails cost HBM);
  (g) the unified ragged mixed step (``--mixed-step`` reruns just this) —
      a short-prompt workload on a page budget of two max_len regions
      through the one-call-per-tick scheduler, recording tok/s and device
      dispatches per tick;
  (h) multi-prefill packing (``--multi-prefill`` reruns just this) — a
      Poisson stream mixing long and short prompts, served with
      ``max_prefills=1`` (serial chunking, the old scheduler) vs several
      prefills sharing the per-tick budget; records queued-request
      time-to-first-token percentiles in *scheduler ticks* (p50/p99,
      load-invariant) alongside wall-clock ms and tok/s;
  (j) the cross-request prefix cache (``--prefix-cache`` reruns just
      this) — a repeated-system-prompt workload served cold (cache off)
      vs warm (per-task prefixes cached): queued TTFT tick percentiles,
      prefill tokens saved, hit rate, bitwise-equal token streams;
  (k) crash recovery (``--recovery`` reruns just this) — a journaled
      stream killed mid-flight and restored: bitwise-equal recovered
      streams, journal bytes/events per request, recovery ticks.

Besides tok/s — which swings ±20% with CPU machine load — every serving
section records load-invariant structure: device dispatches per tick and
tokens advanced per dispatch. Those are the stable cross-PR claims; the
wall-clock numbers are context. Also reports the fused-table residency
cost (paper §3.3 RAM trade-off), and writes every serving number to
``BENCH_serve.json`` at the repo root so the perf trajectory is
machine-trackable across PRs.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import bench_model, emit, random_aot_fused, time_fn
from repro.core import aot as A
from repro.kernels.decode_attention import round_kv_len
from repro.obs import ServeObservability
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import (BEST_EFFORT, ContinuousScheduler, LATENCY,
                                   Request, SchedulerConfig, ShedError,
                                   STANDARD)

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")
RESULTS: dict = {"schema": 1, "bench": "multitask_serving"}


def _requests(rng, cfg, n, n_tasks, prompt, max_new_lo, max_new_hi):
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, prompt).astype(np.int32),
                    task_id=int(rng.integers(0, n_tasks)),
                    max_new_tokens=int(rng.integers(max_new_lo, max_new_hi + 1)))
            for i in range(n)]


def _static_serve(eng, reqs, slots):
    """Static batching at capacity ``slots``: FIFO batches; each batch
    decodes until its LONGEST request finishes (the head-of-line blocking
    continuous batching removes). Returns useful (non-wasted) token count."""
    useful = 0
    for lo in range(0, len(reqs), slots):
        batch = reqs[lo:lo + slots]
        prompts = np.stack([r.prompt for r in batch])
        tids = np.asarray([r.task_id for r in batch], np.int32)
        steps = max(r.max_new_tokens for r in batch)
        eng.generate(prompts, steps, tids)
        useful += sum(r.max_new_tokens for r in batch)
    return useful


def run_continuous_vs_static(n_tasks=4, slots=4, n_requests=16, prompt=16,
                             max_new=(4, 24), rates=(0.25, 1.0)):
    cfg, model, params = bench_model(d_model=128, layers=4, vocab=512, heads=4,
                                     kv=2)
    rng = np.random.default_rng(0)
    tasks = [random_aot_fused(cfg, params, seed=t) for t in range(n_tasks)]
    max_len = prompt + max_new[1] + 4

    # ---- (b) throughput at equal capacity, everyone queued at t=0 ----
    reqs = _requests(rng, cfg, n_requests, n_tasks, prompt, *max_new)
    eng = ServeEngine(model, params, ServeConfig(max_len=max_len),
                      fused_tasks=tasks)

    # warm both paths' compilations out of the measurement
    sched = ContinuousScheduler(eng, SchedulerConfig(num_slots=slots))
    for r in _requests(rng, cfg, slots, n_tasks, prompt, *max_new):
        sched.submit(r)
    sched.run()
    _static_serve(eng, reqs[:slots], slots)

    t0 = time.perf_counter()
    sched = ContinuousScheduler(eng, SchedulerConfig(num_slots=slots))
    for r in reqs:
        sched.submit(r)
    sched.run()
    us_cont = (time.perf_counter() - t0) * 1e6
    tput_cont = sched.tokens_emitted / (us_cont / 1e6)
    emit("multitask/continuous", us_cont,
         f"tok_per_s={tput_cont:.0f} slots={slots} requests={n_requests}")

    reqs2 = [Request(rid=r.rid, prompt=r.prompt, task_id=r.task_id,
                     max_new_tokens=r.max_new_tokens) for r in reqs]
    t0 = time.perf_counter()
    useful = _static_serve(eng, reqs2, slots)
    us_stat = (time.perf_counter() - t0) * 1e6
    tput_stat = useful / (us_stat / 1e6)
    emit("multitask/static_batched", us_stat,
         f"tok_per_s={tput_stat:.0f} slots={slots} requests={n_requests}")
    emit("multitask/continuous_speedup", 0.0,
         f"x={us_stat / us_cont:.2f}")
    RESULTS["continuous_vs_static"] = {
        "slots": slots, "requests": n_requests,
        "continuous_tok_per_s": round(tput_cont, 1),
        "static_tok_per_s": round(tput_stat, 1),
        "speedup": round(us_stat / us_cont, 3)}

    # ---- (c) latency under Poisson offered load ----
    # reuses ``eng`` so its jit caches stay warm: latency percentiles must
    # measure serving, not the first request's compilation
    RESULTS["latency"] = []
    for rate in rates:
        for nt in sorted({1, n_tasks}):
            arrivals, t = [], 0.0
            rr = _requests(rng, cfg, n_requests, nt, prompt, *max_new)
            for r in rr:
                t += rng.exponential(1.0 / rate)
                arrivals.append((int(t), r))
            sched = ContinuousScheduler(eng, SchedulerConfig(num_slots=slots))
            fin = sched.run_stream(arrivals)
            lat = np.asarray(sorted((f.t_done - f.t_submit) * 1e3
                                    for f in fin.values()))
            p50 = float(np.percentile(lat, 50))
            p99 = float(np.percentile(lat, 99))
            emit(f"multitask/latency_rate{rate}_tasks{nt}", 0.0,
                 f"p50_ms={p50:.1f} p99_ms={p99:.1f} "
                 f"steps={sched.steps_decoded}")
            RESULTS["latency"].append({
                "rate": rate, "tasks": nt, "p50_ms": round(p50, 2),
                "p99_ms": round(p99, 2), "steps": sched.steps_decoded})


def _drain_tracking_peak(sched):
    """Run a scheduler to empty, tracking peak concurrency and peak pages."""
    peak_pages = 0
    while sched.busy():
        sched.step()
        peak_pages = max(peak_pages, sched.pool.blocks_in_use())
    return sched.peak_running, peak_pages


def run_mixed_step(n_tasks=2, contig_slots=2, max_len=256, prompt=8,
                   max_new=8, n_requests=24, block_size=16):
    """(g) the unified single-call tick: a short-prompt workload on a page
    budget of ``contig_slots`` max_len regions, served by the ragged mixed
    step (one jitted serve_step per tick, prefill chunks scattered
    straight into pool pages). Records tok/s and the realized device
    dispatches per scheduler tick."""
    cfg, model, params = bench_model(d_model=128, layers=4, vocab=512, heads=4,
                                     kv=2)
    rng = np.random.default_rng(0)
    tasks = [random_aot_fused(cfg, params, seed=t) for t in range(n_tasks)]
    eng = ServeEngine(model, params, ServeConfig(max_len=max_len),
                      fused_tasks=tasks)
    budget_tokens = contig_slots * round_kv_len(max_len)
    num_blocks = budget_tokens // block_size + 1
    paged_slots = min(n_requests, budget_tokens // block_size)

    def serve():
        # metrics on for the measured run too: the no-Heisenberg test
        # guarantees tokens are unchanged, and the registry feeds the
        # page/SLO fields below straight into BENCH_serve.json
        obs = ServeObservability(metrics=True)
        sched = ContinuousScheduler(eng, SchedulerConfig(
            num_slots=paged_slots, block_size=block_size,
            num_blocks=num_blocks, prefill_chunk=block_size), obs=obs)
        reqs = _requests(rng, cfg, n_requests, n_tasks, prompt,
                         max_new, max_new)
        for r in reqs:
            sched.submit(r)
        d0 = eng.dispatches
        t0 = time.perf_counter()
        sched.run()
        dt = time.perf_counter() - t0
        dispatches = eng.dispatches - d0
        per_tick = dispatches / max(sched.ticks, 1)
        prompt_toks = sum(len(r.prompt) for r in reqs)
        tpd = (sched.tokens_emitted + prompt_toks) / max(dispatches, 1)
        return sched, obs, sched.tokens_emitted / dt, per_tick, tpd

    serve()                                  # warm the serve_step trace
    sched, obs, tput, per_tick, tpd = serve()
    slo = obs.slo.summary()
    emit("multitask/mixed_step", 0.0,
         f"tok_per_s={tput:.0f} dispatches_per_tick={per_tick:.2f} "
         f"tokens_per_dispatch={tpd:.1f} ticks={sched.ticks}")
    RESULTS["mixed_step"] = {
        "workload": {"requests": n_requests, "prompt": prompt,
                     "max_new": max_new, "max_len": max_len,
                     "block_size": block_size, "slots": paged_slots,
                     "prefill_chunk": block_size},
        "tok_per_s": round(tput, 1),
        "dispatches_per_tick": round(per_tick, 3),
        # advanced tokens (prompt + emitted) per device dispatch: the
        # load-invariant work-per-call measure that, unlike tok/s, does
        # not swing with CPU machine load
        "tokens_per_dispatch": round(tpd, 2),
        "ticks": sched.ticks,
        "prefill_chunks": sched.prefill_chunks_run,
        # load-invariant lifecycle percentiles (scheduler ticks, from the
        # observability layer's SLO tracker)
        "peak_pages": sched.pool.peak_pages,
        "ttft_p50_ticks": slo["ttft_ticks"]["p50"],
        "ttft_p99_ticks": slo["ttft_ticks"]["p99"],
        "tpot_p50_ticks": slo["tpot_ticks"]["p50"],
        # dispatches_per_tick and tokens_per_dispatch are the stable
        # structural claims; tok/s is CPU timing context
        "note": "CPU tok/s swings with machine load, dispatches_per_tick "
                "and tokens_per_dispatch are load-invariant"}


def run_multi_prefill(n_tasks=2, slots=8, max_len=256, block_size=16,
                      budget=32, n_requests=24, rate=1.0, seed=4):
    """(h) prefill head-of-line blocking: a Poisson stream mixing long
    prompts (several chunking ticks each) with short interactive prompts.
    ``max_prefills=1`` serializes every queued prompt behind whichever is
    chunking; packing several prefills into the tick's budget
    (shortest-remaining-first) lets short prompts overtake. Reported TTFT
    percentiles are measured in scheduler TICKS (queued-request
    first-token tick minus submission tick) — load-invariant, unlike the
    wall-clock ms also recorded."""
    cfg, model, params = bench_model(d_model=128, layers=4, vocab=512, heads=4,
                                     kv=2)
    tasks = [random_aot_fused(cfg, params, seed=t) for t in range(n_tasks)]
    eng = ServeEngine(model, params, ServeConfig(max_len=max_len),
                      fused_tasks=tasks)

    def arrivals():
        rr = np.random.default_rng(seed)
        out, t = [], 0.0
        for i in range(n_requests):
            t += rr.exponential(1.0 / rate)
            long = rr.random() < 0.4
            plen = int(rr.integers(96, 161)) if long \
                else int(rr.integers(8, 17))
            out.append((int(t), Request(
                rid=i, prompt=rr.integers(0, cfg.vocab_size, plen)
                .astype(np.int32),
                task_id=int(rr.integers(0, n_tasks)),
                max_new_tokens=int(rr.integers(4, 13)))))
        return out

    def serve(max_prefills):
        stream = arrivals()
        # the SLO tracker stamps submit/first-token on sched.ticks at the
        # same transitions this loop used to hand-roll via on_token
        # callbacks, so the reported TTFT tick values are unchanged
        obs = ServeObservability(metrics=True, check_leaks=True)
        sched = ContinuousScheduler(eng, SchedulerConfig(
            num_slots=slots, block_size=block_size,
            prefill_chunk=budget, max_prefills=max_prefills), obs=obs)
        d0 = eng.dispatches
        t0 = time.perf_counter()
        i, idle_ticks = 0, 0
        while i < len(stream) or sched.busy():
            if not sched.busy() and i < len(stream):
                # idle: jump the tick clock to the next arrival so TTFT
                # measures queueing + prefill, not idle air (idle ticks
                # carry no dispatch and are excluded from the per-tick
                # dispatch ratio below)
                while sched.ticks < stream[i][0]:
                    sched.ticks += 1
                    sched.clock += 1
                    idle_ticks += 1
            while i < len(stream) and stream[i][0] <= sched.ticks:
                sched.submit(stream[i][1])
                i += 1
            sched.step()
        dt = time.perf_counter() - t0
        assert sched.drain_check() == []
        fin = sched.finished
        assert len(fin) == n_requests
        slo = obs.slo.summary()
        ttft_ms = np.asarray(sorted((r.t_first - r.t_submit) * 1e3
                                    for r in fin.values()))
        dispatches = eng.dispatches - d0
        busy_ticks = sched.ticks - idle_ticks
        prompt_toks = sum(len(r.prompt) for r in fin.values())
        return {
            "ttft_p50_ticks": slo["ttft_ticks"]["p50"],
            "ttft_p99_ticks": slo["ttft_ticks"]["p99"],
            "ttft_p50_ms": round(float(np.percentile(ttft_ms, 50)), 2),
            "ttft_p99_ms": round(float(np.percentile(ttft_ms, 99)), 2),
            "tok_per_s": round(sched.tokens_emitted / dt, 1),
            "dispatches_per_tick": round(
                dispatches / max(busy_ticks, 1), 3),
            "tokens_per_dispatch": round(
                (sched.tokens_emitted + prompt_toks) / max(dispatches, 1), 2),
            "peak_prefills": sched.peak_prefills,
            "preemptions": sched.preemptions,
            "queue_wait_p50_ticks": slo["queue_wait_ticks"]["p50"],
        }

    serve(1), serve(4)                       # warm both compilations
    single, multi = serve(1), serve(4)
    emit("multitask/multi_prefill_ttft", 0.0,
         f"p50_ticks {single['ttft_p50_ticks']:.0f}->"
         f"{multi['ttft_p50_ticks']:.0f} "
         f"p99_ticks {single['ttft_p99_ticks']:.0f}->"
         f"{multi['ttft_p99_ticks']:.0f} "
         f"peak_prefills={multi['peak_prefills']}")
    RESULTS["multi_prefill"] = {
        "workload": {"requests": n_requests, "rate": rate, "slots": slots,
                     "long_prompt": [96, 160], "short_prompt": [8, 16],
                     "long_fraction": 0.4, "max_new": [4, 12],
                     "block_size": block_size, "prefill_budget": budget},
        "single_prefill": single,
        "multi_prefill": multi,
        "p50_ttft_ticks_speedup": round(
            single["ttft_p50_ticks"] / max(multi["ttft_p50_ticks"], 1e-9), 3),
        "note": "TTFT tick percentiles are load-invariant (CPU wall-clock "
                "ms swings with machine load); multi packs up to 4 "
                "prefills into the per-tick chunk budget, "
                "shortest-remaining-first"}


def run_sampling_and_forking(n_tasks=2, slots=6, n_requests=12, prompt=16,
                             max_new=(4, 16), block_size=16, temp=0.8,
                             top_p=0.9, fork_prompt=100, fork_new=8,
                             fork_n=4):
    """(e) sampled-vs-greedy decode throughput and (f) the COW forking
    HBM claim: n parallel samples share the prompt's KV pages, so the
    forked run's peak pages stay well under n independent decodes (the
    acceptance bar is < 1.5x a single-sample run for n=4)."""
    cfg, model, params = bench_model(d_model=128, layers=4, vocab=512, heads=4,
                                     kv=2)
    rng = np.random.default_rng(0)
    tasks = [random_aot_fused(cfg, params, seed=t) for t in range(n_tasks)]
    max_len = max(prompt + max_new[1] + 4, fork_prompt + fork_new + 4)
    eng = ServeEngine(model, params, ServeConfig(max_len=max_len),
                      fused_tasks=tasks)

    # ---- (e) same workload, greedy vs stochastic decode ----
    def serve(sampler):
        rr = np.random.default_rng(1)
        reqs = [Request(
            rid=i,
            prompt=rr.integers(0, cfg.vocab_size, prompt).astype(np.int32),
            task_id=int(rr.integers(0, n_tasks)),
            max_new_tokens=int(rr.integers(*max_new)),
            sampling=sampler(i)) for i in range(n_requests)]
        sched = ContinuousScheduler(eng, SchedulerConfig(
            num_slots=slots, block_size=block_size))
        for r in reqs:
            sched.submit(r)
        t0 = time.perf_counter()
        sched.run()
        return sched.tokens_emitted / (time.perf_counter() - t0)

    greedy = lambda i: None
    stoch = lambda i: SamplingParams(temperature=temp, top_p=top_p, seed=i)
    serve(greedy), serve(stoch)             # warm both decode compilations
    tput_g, tput_s = serve(greedy), serve(stoch)
    emit("multitask/decode_greedy", 0.0, f"tok_per_s={tput_g:.0f}")
    emit("multitask/decode_sampled", 0.0,
         f"tok_per_s={tput_s:.0f} temp={temp} top_p={top_p}")
    RESULTS["sampling"] = {
        "workload": {"requests": n_requests, "prompt": prompt,
                     "max_new": list(max_new), "slots": slots},
        "greedy_tok_per_s": round(tput_g, 1),
        "sampled_tok_per_s": round(tput_s, 1),
        "sampled_over_greedy": round(tput_s / max(tput_g, 1e-9), 3)}

    # ---- (f) n parallel samples: COW fork vs independent decodes ----
    fprompt = rng.integers(0, cfg.vocab_size, fork_prompt).astype(np.int32)

    def peak_pages(n, slots_n):
        req = Request(rid=0, prompt=fprompt, task_id=0,
                      max_new_tokens=fork_new,
                      sampling=SamplingParams(temperature=temp, top_p=top_p,
                                              seed=7, n=n))
        sched = ContinuousScheduler(eng, SchedulerConfig(
            num_slots=slots_n, block_size=block_size))
        sched.submit(req)
        _, pages = _drain_tracking_peak(sched)
        return pages, sched.pool.forks, sched.pool.cow_copies

    pages_1, _, _ = peak_pages(1, slots)
    pages_n, forks, cows = peak_pages(fork_n, slots)
    pages_indep, _, _ = peak_pages(fork_n, 1)   # 1 slot: forks impossible
    ratio = pages_n / max(pages_1, 1)
    emit("multitask/fork_cow_pages", 0.0,
         f"n={fork_n} forked={pages_n} single={pages_1} "
         f"independent_serial={pages_indep} ratio={ratio:.2f}x "
         f"forks={forks} cow_copies={cows}")
    RESULTS["fork_cow"] = {
        "n": fork_n, "prompt": fork_prompt, "max_new": fork_new,
        "block_size": block_size,
        "peak_pages_single": pages_1,
        "peak_pages_forked": pages_n,
        "peak_pages_independent_serial": pages_indep,
        "forks": forks, "cow_copies": cows,
        "forked_over_single": round(ratio, 3)}


def run_prefix_cache(n_tasks=2, slots=4, n_requests=16, sys_prompt=64,
                     tail=(4, 12), max_new=8, block_size=16, chunk=32,
                     cache_pages=8, max_len=96, num_blocks=33):
    """(j) cross-request shared-prefix page cache (``--prefix-cache``
    reruns just this): every request of a task opens with the task's
    64-token system prompt — 4 full pages at ``block_size=16`` — followed
    by a short unique tail. The COLD pass serves the stream with the
    cache off; the WARM pass pre-warms the cache with one short request
    per task and serves the SAME stream, so every admission maps the
    4-page prefix straight out of the cache and chunked prefill starts
    at the first uncached token. The headline numbers are load-invariant:
    queued-request TTFT tick percentiles warm vs cold, prefill tokens
    skipped, hit rate, and one-dispatch-per-tick preserved — plus the
    correctness bar asserted in-process: the two passes' token streams
    are bitwise identical (the cache is a pure optimization)."""
    cfg, model, params = bench_model(d_model=128, layers=4, vocab=512, heads=4,
                                     kv=2)
    tasks = [random_aot_fused(cfg, params, seed=t) for t in range(n_tasks)]
    eng = ServeEngine(model, params, ServeConfig(max_len=max_len),
                      fused_tasks=tasks)
    rng = np.random.default_rng(11)
    sys_p = {t: rng.integers(0, cfg.vocab_size, sys_prompt).astype(np.int32)
             for t in range(n_tasks)}

    def reqs():
        rr = np.random.default_rng(12)
        out = []
        for i in range(n_requests):
            t = int(rr.integers(0, n_tasks))
            tl = rr.integers(0, cfg.vocab_size,
                             int(rr.integers(tail[0], tail[1] + 1)))
            out.append(Request(
                rid=i, prompt=np.concatenate([sys_p[t], tl.astype(np.int32)]),
                task_id=t, max_new_tokens=max_new))
        return out

    def serve(cached):
        obs = ServeObservability(metrics=True, check_leaks=True)
        sched = ContinuousScheduler(eng, SchedulerConfig(
            num_slots=slots, block_size=block_size,
            num_blocks=num_blocks, prefill_chunk=chunk,
            prefix_cache_pages=cache_pages if cached else 0), obs=obs)
        if cached:      # pre-warm: one short request per task retains the
            for t in range(n_tasks):         # system prompt's full pages
                sched.submit(Request(
                    rid=1000 + t,
                    prompt=np.concatenate([sys_p[t],
                                           np.asarray([7], np.int32)]),
                    task_id=t, max_new_tokens=2))
            sched.run()
        cache = sched.pool.prefix_cache
        pre_hits = cache.hits if cached else 0
        pre_tokens = cache.hit_tokens if cached else 0
        d0, ticks0 = eng.dispatches, sched.ticks
        t0 = time.perf_counter()
        for r in reqs():
            sched.submit(r)
        fin = sched.run()
        dt = time.perf_counter() - t0
        dispatches = eng.dispatches - d0
        slo = sched.obs.slo.summary()
        if cached:      # measured-stream TTFT = the hit (warm) requests
            ttft = slo["prefix_cache"]["warm_ttft_ticks"]
            assert slo["prefix_cache"]["warm_requests"] == n_requests
        else:
            ttft = slo["ttft_ticks"]
        return {
            "ttft_p50_ticks": ttft["p50"],
            "ttft_p99_ticks": ttft["p99"],
            "tok_per_s": round(sched.tokens_emitted / dt, 1),
            "dispatches_per_tick": round(
                dispatches / max(sched.ticks - ticks0, 1), 3),
            "hit_rate": round((cache.hits - pre_hits) / n_requests, 3)
            if cached else 0.0,
            "prefill_tokens_saved": (cache.hit_tokens - pre_tokens)
            if cached else 0,
            "cached_pages": len(cache) if cached else 0,
            "outs": {rid: list(r.out) for rid, r in fin.items()
                     if rid < 1000},
        }

    serve(False), serve(True)               # warm both passes' compilations
    cold, warm = serve(False), serve(True)
    assert warm["outs"] == cold["outs"], \
        "cache-hit decode diverged from cold decode (must be bitwise equal)"
    speedup = cold["ttft_p50_ticks"] / max(warm["ttft_p50_ticks"], 1e-9)
    emit("multitask/prefix_cache", 0.0,
         f"ttft_p50_ticks {cold['ttft_p50_ticks']:.0f}->"
         f"{warm['ttft_p50_ticks']:.0f} ({speedup:.1f}x) "
         f"hit_rate={warm['hit_rate']:.2f} "
         f"tokens_saved={warm['prefill_tokens_saved']}")
    for d in (cold, warm):
        d.pop("outs")
    RESULTS["prefix_cache"] = {
        "workload": {"requests": n_requests, "tasks": n_tasks,
                     "system_prompt": sys_prompt, "tail": list(tail),
                     "max_new": max_new, "slots": slots,
                     "block_size": block_size, "prefill_chunk": chunk,
                     "cache_pages": cache_pages, "num_blocks": num_blocks},
        "cold": cold,
        "warm": warm,
        "ttft_p50_ticks_speedup": round(speedup, 3),
        "bitwise_equal": 1,
        "note": "warm pre-caches each task's 64-token system prompt (4 "
                "full pages) then serves the identical stream; TTFT tick "
                "percentiles are load-invariant, tok/s is CPU context; "
                "bitwise_equal=1 records the in-process assertion that "
                "warm and cold token streams matched exactly"}


def run_overload(n_tasks=2, slots=4, max_len=64, block_size=8, num_blocks=13,
                 n_requests=40, burst=8, gap=6, max_queue=14,
                 deadline_ticks=24, ttft_slo=10.0, seed=7):
    """(i) overload: a bursty arrival stream (``burst`` simultaneous
    arrivals every ``gap`` ticks — offered load far above the pool's
    capacity) with a 1:2:1 latency/standard/best_effort class mix, a
    bounded admission queue, and deadlines on the latency class. The
    numbers that matter are structural, not tok/s: per-class TTFT/TPOT
    tick percentiles, shed rate, deadline-miss rate, and the class
    attainment gap (latency must meet its TTFT SLO at least as often as
    best-effort — that is the entire point of the classes). The stream is
    burst overload followed by a recovery trickle: during the bursts the
    bounded queue sheds and displaces best-effort (by design); during
    recovery admitted best-effort work completes (the no-starvation
    guarantee covers ADMITTED rows, not an infinitely refilling queue).
    Gated by check_bench via the ``overload.*`` baseline rules."""
    cfg, model, params = bench_model(d_model=128, layers=4, vocab=512, heads=4,
                                     kv=2)
    tasks = [random_aot_fused(cfg, params, seed=t) for t in range(n_tasks)]
    eng = ServeEngine(model, params, ServeConfig(max_len=max_len),
                      fused_tasks=tasks)
    cycle = (LATENCY, STANDARD, STANDARD, BEST_EFFORT)

    n_burst_reqs = 3 * burst                   # overload phase: 3 bursts
    trickle_start = 4 * gap                    # then recovery: 1 per 2 ticks

    def arrivals():
        rr = np.random.default_rng(seed)
        out = []
        for i in range(n_requests):
            prio = cycle[i % len(cycle)]
            plen = int(rr.integers(8, 17))
            t = ((i // burst) * gap if i < n_burst_reqs
                 else trickle_start + (i - n_burst_reqs) * 2)
            out.append((t, Request(
                rid=i,
                prompt=rr.integers(0, cfg.vocab_size, plen).astype(np.int32),
                task_id=int(rr.integers(0, n_tasks)),
                max_new_tokens=int(rr.integers(4, 11)),
                priority=prio,
                deadline_ticks=deadline_ticks if prio == LATENCY else None)))
        return out

    def serve():
        obs = ServeObservability(metrics=True, check_leaks=True)
        sched = ContinuousScheduler(eng, SchedulerConfig(
            num_slots=slots, block_size=block_size,
            num_blocks=num_blocks, prefill_chunk=block_size,
            max_queue=max_queue), obs=obs)
        stream = arrivals()
        shed, i = [], 0
        d0 = eng.dispatches
        t0 = time.perf_counter()
        while i < len(stream) or sched.busy():
            if (not sched.busy() and i < len(stream)
                    and stream[i][0] > sched.clock):
                sched.clock = stream[i][0]
            while i < len(stream) and stream[i][0] <= sched.clock:
                try:
                    sched.submit(stream[i][1])
                except ShedError:
                    shed.append(stream[i][1].rid)
                i += 1
            sched.step()
        dt = time.perf_counter() - t0
        assert sched.drain_check() == []
        return sched, obs, shed, eng.dispatches - d0, dt

    serve()                                    # warm the serve_step traces
    sched, obs, shed, dispatches, dt = serve()

    summary = obs.slo.summary(targets={"ttft_ticks": ttft_slo})
    n_latency = sum(1 for i in range(n_requests) if cycle[i % 4] == LATENCY)
    by_class = {}
    for cls, s in summary.get("by_class", {}).items():
        att = s.get("slo_attainment", {})
        by_class[cls] = {
            "finished": s["requests"],
            "shed": s.get("shed", 0),
            "aborted": s.get("aborted", 0),
            "ttft_p50_ticks": s["ttft_ticks"]["p50"],
            "ttft_p95_ticks": s["ttft_ticks"]["p95"],
            "tpot_p50_ticks": s["tpot_ticks"]["p50"],
            "queue_wait_p50_ticks": s["queue_wait_ticks"]["p50"],
            "ttft_attainment": next(iter(att.values()), 0.0),
        }
    lat_att = by_class.get(LATENCY, {}).get("ttft_attainment", 0.0)
    be_att = by_class.get(BEST_EFFORT, {}).get("ttft_attainment", 0.0)
    shed_rate = len(shed) / n_requests
    miss_rate = sched.deadline_misses / max(n_latency, 1)
    per_tick = dispatches / max(sched.ticks, 1)
    emit("multitask/overload", 0.0,
         f"shed_rate={shed_rate:.2f} deadline_miss_rate={miss_rate:.2f} "
         f"lat_attain={lat_att:.2f} be_attain={be_att:.2f} "
         f"preempts={sched.preemptions} ticks={sched.ticks}")
    RESULTS["overload"] = {
        "workload": {"requests": n_requests, "burst": burst, "gap": gap,
                     "mix": "latency:standard:best_effort = 1:2:1",
                     "slots": slots, "block_size": block_size,
                     "num_blocks": num_blocks, "max_queue": max_queue,
                     "deadline_ticks": deadline_ticks,
                     "ttft_slo_ticks": ttft_slo},
        "shed_rate": round(shed_rate, 4),
        "deadline_miss_rate": round(miss_rate, 4),
        "dispatches_per_tick": round(per_tick, 3),
        "ticks": sched.ticks,
        "preemptions": sched.preemptions,
        "tok_per_s": round(sched.tokens_emitted / dt, 1),
        "by_class": by_class,
        # the headline class guarantee, precomputed so the baseline gate
        # is a single dotted path: latency meets its TTFT SLO at least as
        # often as best-effort under the same overload
        "latency_minus_best_effort_attainment": round(lat_att - be_att, 4),
        "note": "tok/s is CPU context; shed/miss rates, per-class tick "
                "percentiles, and the attainment gap are the structural "
                "claims (deterministic workload, seeded)"}


def run_recovery(n_tasks=2, slots=4, max_len=64, block_size=8, num_blocks=20,
                 n_requests=24, kill_tick=20, seed=11):
    """(k) crash recovery (``--recovery`` reruns just this): a journaled
    stream killed mid-flight at a fixed tick, restored from the journal,
    and drained to completion. The structural claims: every recovered
    stream is bitwise identical to an uninterrupted run (preempt-and-
    recompute replay is exact), the journal overhead is a bounded number
    of bytes/events per request, and recovery cost is the deterministic
    number of ticks the restored scheduler needs to drain the survivors.
    Gated by check_bench via the ``recovery.*`` baseline rules."""
    import tempfile

    from repro.serve.recovery import RequestJournal, replay_journal

    cfg, model, params = bench_model(d_model=128, layers=4, vocab=512, heads=4,
                                     kv=2)
    tasks = [random_aot_fused(cfg, params, seed=t) for t in range(n_tasks)]
    eng = ServeEngine(model, params, ServeConfig(max_len=max_len),
                      fused_tasks=tasks)

    def arrivals():
        rr = np.random.default_rng(seed)
        out = []
        for i in range(n_requests):
            plen = int(rr.integers(8, 17))
            sp = (SamplingParams(temperature=0.8, top_k=20, seed=100 + i,
                                 n=2 if i % 8 == 0 else 1)
                  if i % 4 == 0 else None)
            out.append((i // 3, Request(
                rid=i,
                prompt=rr.integers(0, cfg.vocab_size, plen).astype(np.int32),
                task_id=int(rr.integers(0, n_tasks)),
                max_new_tokens=int(rr.integers(4, 11)), sampling=sp)))
        return out

    def make_sched(journal=None):
        return ContinuousScheduler(eng, SchedulerConfig(
            num_slots=slots, block_size=block_size,
            num_blocks=num_blocks, prefill_chunk=block_size),
            journal=journal)

    baseline = make_sched().run_stream(arrivals())

    fd, path = tempfile.mkstemp(suffix=".jsonl")
    os.close(fd)
    os.remove(path)                    # journal opens its own append handle
    try:
        sched = make_sched(RequestJournal(path))
        stream = arrivals()
        i = 0
        while i < len(stream) or sched.busy():
            if (not sched.busy() and i < len(stream)
                    and stream[i][0] > sched.clock):
                sched.clock = stream[i][0]
            while i < len(stream) and stream[i][0] <= sched.clock:
                sched.submit(stream[i][1])
                i += 1
            sched.step()
            if sched.ticks >= kill_tick and sched.busy():
                break                  # simulated SIGKILL: no shutdown
        journal_events = sched.journal.events_written
        journal_bytes = sched.journal.bytes_written
        sched.journal.close()

        t0 = time.perf_counter()
        snap = replay_journal(path)
        sched2 = make_sched(RequestJournal(path))
        counts = sched2.restore(snap)
        restore_ms = (time.perf_counter() - t0) * 1e3
        recompute_tokens = sum(
            len(r["prompt"]) + sum(len(v) for v in r["out"].values())
            for r in snap["requests"] if r["status"] == "live")
        for j in range(i, len(stream)):
            sched2.submit(stream[j][1])
        fin = sched2.run()
        recovery_ticks = sched2.ticks
        assert sched2.drain_check() == []
    finally:
        if os.path.exists(path):
            os.remove(path)

    def _same(a, b):
        if not np.array_equal(np.asarray(a.out), np.asarray(b.out)):
            return False
        if (b.samples is None) != (a.samples is None):
            return False
        return b.samples is None or all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(a.samples, b.samples))

    bitwise = (set(fin) == set(baseline)
               and all(_same(fin[r], baseline[r]) for r in baseline))
    emit("multitask/recovery", 0.0,
         f"bitwise={int(bitwise)} live_restored={counts['live']} "
         f"recovery_ticks={recovery_ticks} "
         f"journal_bytes_per_req={journal_bytes / n_requests:.0f}")
    RESULTS["recovery"] = {
        "workload": {"requests": n_requests, "slots": slots,
                     "block_size": block_size, "num_blocks": num_blocks,
                     "kill_tick": kill_tick},
        "bitwise_equal": float(bitwise),
        "live_restored": counts["live"],
        "finished_restored": counts["finished"],
        "recompute_tokens": recompute_tokens,
        "recovery_ticks": recovery_ticks,
        "journal_events": journal_events,
        "journal_bytes": journal_bytes,
        "journal_bytes_per_request": round(journal_bytes / n_requests, 1),
        "restore_ms": round(restore_ms, 2),
        "note": "restore_ms is CPU context; bitwise_equal, restored "
                "counts, recovery ticks, and journal overhead are the "
                "structural claims (deterministic workload, fixed kill "
                "tick)"}


def write_bench_json():
    with open(BENCH_JSON, "w") as f:
        json.dump(RESULTS, f, indent=2, sort_keys=True)
        f.write("\n")
    emit("multitask/bench_json", 0.0, f"path={os.path.abspath(BENCH_JSON)}")


def run(n_tasks=4, batch=8, prompt=32, steps=16):
    cfg, model, params = bench_model()
    rng = np.random.default_rng(0)
    tasks = [random_aot_fused(cfg, params, seed=t) for t in range(n_tasks)]

    eng_mt = ServeEngine(model, params, ServeConfig(max_len=prompt + steps + 4),
                         fused_tasks=tasks)
    prompts = rng.integers(0, cfg.vocab_size, (batch, prompt)).astype(np.int32)
    task_ids = rng.integers(0, n_tasks, batch).astype(np.int32)

    us_mt = time_fn(lambda: eng_mt.generate(prompts, steps, task_ids), iters=3)
    tput_mt = batch * steps / (us_mt / 1e6)
    emit("multitask/batched", us_mt, f"tok_per_s={tput_mt:.0f}")

    # sequential per-task serving (what you do without multi-task inference)
    def sequential():
        outs = []
        for t in range(n_tasks):
            idx = np.where(task_ids == t)[0]
            if len(idx) == 0:
                continue
            eng1 = ServeEngine(model, params,
                               ServeConfig(max_len=prompt + steps + 4),
                               fused_tasks=[tasks[t]])
            outs.append(eng1.generate(prompts[idx], steps,
                                      np.zeros(len(idx), np.int32)))
        return outs
    us_seq = time_fn(sequential, warmup=1, iters=2)
    tput_seq = batch * steps / (us_seq / 1e6)
    emit("multitask/sequential", us_seq, f"tok_per_s={tput_seq:.0f}")
    emit("multitask/speedup", 0.0, f"x={us_seq / us_mt:.2f}")

    gb = A.table_bytes(cfg, n_tasks=n_tasks, bytes_per_el=2) / 1e9
    emit("multitask/fused_tables_gb", 0.0, f"gb={gb:.3f} tasks={n_tasks}")
    RESULTS["fused_tables_gb"] = round(gb, 4)

    run_continuous_vs_static()
    run_mixed_step()
    run_multi_prefill()
    run_sampling_and_forking()
    run_overload()
    run_prefix_cache()
    run_recovery()
    write_bench_json()
    # asserted AFTER the write so a regression still records the evidence
    ratio = RESULTS["fork_cow"]["forked_over_single"]
    assert ratio < 1.5, (
        f"n={RESULTS['fork_cow']['n']} forked sampling used {ratio:.2f}x "
        "the pages of a single-sample run (acceptance bar: < 1.5x)")
    mp = RESULTS["multi_prefill"]
    assert (mp["multi_prefill"]["ttft_p50_ticks"]
            < mp["single_prefill"]["ttft_p50_ticks"]), (
        "multi-prefill packing did not improve queued-request p50 TTFT "
        f"({mp['multi_prefill']['ttft_p50_ticks']} vs "
        f"{mp['single_prefill']['ttft_p50_ticks']} ticks)")
    pc = RESULTS["prefix_cache"]
    assert (pc["warm"]["ttft_p50_ticks"] < pc["cold"]["ttft_p50_ticks"]), (
        "warm cache-hit p50 TTFT is not below cold "
        f"({pc['warm']['ttft_p50_ticks']} vs "
        f"{pc['cold']['ttft_p50_ticks']} ticks)")


def _rerun_section(fn):
    """Rerun one section and merge it into the existing BENCH_serve.json."""
    if os.path.exists(BENCH_JSON):         # keep the other sections' numbers
        with open(BENCH_JSON) as f:
            RESULTS.update(json.load(f))
    fn()
    write_bench_json()


def main():
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mixed-step", action="store_true",
                    help="rerun only the unified mixed-step measurement and "
                         "merge it into the existing BENCH_serve.json")
    ap.add_argument("--multi-prefill", action="store_true",
                    help="rerun only the multi-prefill TTFT measurement and "
                         "merge it into the existing BENCH_serve.json")
    ap.add_argument("--overload", action="store_true",
                    help="rerun only the overload (priority classes / "
                         "shedding / deadlines) measurement and merge it "
                         "into the existing BENCH_serve.json")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="rerun only the warm-vs-cold prefix-cache "
                         "measurement and merge it into the existing "
                         "BENCH_serve.json")
    ap.add_argument("--recovery", action="store_true",
                    help="rerun only the kill-and-restore crash-recovery "
                         "measurement and merge it into the existing "
                         "BENCH_serve.json")
    args = ap.parse_args()
    if args.mixed_step:
        _rerun_section(run_mixed_step)
    elif args.multi_prefill:
        _rerun_section(run_multi_prefill)
    elif args.overload:
        _rerun_section(run_overload)
    elif args.prefix_cache:
        _rerun_section(run_prefix_cache)
    elif args.recovery:
        _rerun_section(run_recovery)
    else:
        run()


if __name__ == "__main__":
    main()
