#!/usr/bin/env python3
"""Smoke run of the paged multi-task serve path on one TPU chip.

Serves smollm-360m at its published widths (32 layers, d 960, 15/5 heads,
vocab 49,152) with random weights from a seed, in bfloat16 (weights, KV
pages and fused tables), with two fabricated AoT tasks, through the same
objects the launcher builds: ``build_engine`` (model, params, stacked
tables, ``ServeEngine``), then ``ContinuousScheduler``, then
``run_with_retries``. Attention runs the Pallas ragged paged kernel.

    python chip_smoke.py

It runs in one process and needs a TPU: with no TPU (for example under
``JAX_PLATFORMS=cpu``), or outside a checkout of this repository, it exits
non-zero before any work. It fails unless every request finishes with its
token count, the pool drains clean with nothing quarantined, the compiled
greedy serve step holds the Mosaic kernel (``tpu_custom_call``), the paged
bfloat16 path's first-token logits agree with a float32 ``Model.prefill``
of the same prompt and task, and one prompt served under task 0 and under
task 1 gives logits nearest each task's own reference. Its last line of output is then
``{"ok": true, "device": {...}}``, and only then.
"""
from __future__ import annotations

import json
import sys
import time
import traceback
from pathlib import Path

SEED = 0
MAX_LEN = 2048
SLOTS = 8
BLOCK_SIZE = 16
PREFILL_CHUNK = 256
N_TASKS = 2
N_REQUESTS = 16
PROMPT_LEN = (64, 1024)              # shortest, longest prompt
NEW_TOKENS = (16, 64)
SAMPLED = (5, 9, 13)                 # request ids drawn at temperature 0.8
CHECKED = (0, 1, 2)                  # first-token logits vs the f32 reference
# Relative L2 error of the bf16 path's logits against the f32 reference.
# bf16 keeps 8 significant bits (unit roundoff 2^-9 ~ 0.002); weights, KV
# pages, tables and every activation between matmuls are rounded to it,
# about 200 roundings along the 32-layer residual stream, which add up
# like a random walk to a few percent. fp8 (e4m3, roundoff 2^-4) would put
# the same walk far above 0.1, so 0.1 admits bf16 and refuses anything
# coarser.
REL_TOL = 0.1


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"check ok: {what}", flush=True)


def rel_l2(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def make_requests(vocab: int, on_token):
    """16 requests over both tasks: prompts of 64-1024 tokens, 16-64 new
    tokens, mostly greedy. Requests 0 and 1 are the same prompt under task
    0 and task 1; three are sampled at temperature 0.8 so the sampled
    traces compile too. Arrivals spread over the first ticks so that ticks
    with and without a prefill in flight both occur, greedy and sampled."""
    import numpy as np
    from repro.serve.sampling import SamplingParams
    from repro.serve.scheduler import Request
    rng = np.random.default_rng(SEED)
    lo, hi = PROMPT_LEN
    shared = rng.integers(0, vocab, hi).astype(np.int32)
    arrivals = []
    for i in range(N_REQUESTS):
        if i < 2:
            prompt, task = shared, i
        else:
            prompt = rng.integers(0, vocab, int(rng.integers(lo, hi + 1)))
            prompt, task = prompt.astype(np.int32), i % N_TASKS
        sampling = (SamplingParams(temperature=0.8, seed=SEED + i)
                    if i in SAMPLED else None)
        req = Request(rid=i, prompt=prompt, task_id=task,
                      max_new_tokens=int(rng.integers(NEW_TOKENS[0],
                                                      NEW_TOKENS[1] + 1)),
                      on_token=on_token, sampling=sampling)
        arrivals.append((6 * i, req))
    return arrivals


def smoke(jax, dev, cfg) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.launch.serve import build_engine, run_with_retries
    from repro.models.model import Model, ModelOptions
    from repro.serve.scheduler import ContinuousScheduler, SchedulerConfig

    check(not ops._interpret(), "Pallas kernels compile through Mosaic "
          "(interpret mode is off on the TPU)")
    bf16 = jnp.bfloat16
    opts = ModelOptions(compute_dtype=bf16, param_dtype=bf16,
                        attn_impl="pallas", chunk_q=64, chunk_kv=MAX_LEN)

    t0 = time.perf_counter()
    eng = build_engine(cfg, opts, MAX_LEN, demo=N_TASKS, seed=SEED)
    table = eng._pp["aot"]["table"]
    jax.block_until_ready((eng.params, table))
    build_s = time.perf_counter() - t0
    print(f"build: {build_s:.2f} s (params + {N_TASKS} tasks' tables "
          f"{table.shape} {table.dtype}, {table.nbytes / 1e9:.2f} GB; "
          f"table layout {table.format})", flush=True)

    sched_cfg = SchedulerConfig(num_slots=SLOTS, block_size=BLOCK_SIZE,
                                prefill_chunk=PREFILL_CHUNK)
    sched = ContinuousScheduler(eng, sched_cfg)
    pool = sched.pool
    widths = (SLOTS, SLOTS - 1 + PREFILL_CHUNK)   # decode-only, chunk tick
    t0 = time.perf_counter()
    greedy_text = ""
    for fn in (eng._serve_greedy, eng._serve_sampled):
        for w in widths:
            shapes = eng.serve_step_shapes(pool.cache, SLOTS, pool.max_pages,
                                           w)
            compiled = fn.lower(*shapes).compile()
            if fn is eng._serve_greedy:
                greedy_text += compiled.as_text()
                mem = compiled.memory_analysis()
                print(f"greedy serve_step width {w}: arguments "
                      f"{mem.argument_size_in_bytes}, outputs "
                      f"{mem.output_size_in_bytes}, temporaries "
                      f"{mem.temp_size_in_bytes} bytes; table input "
                      f"{compiled.input_formats[0][1]['aot']['table']}",
                      flush=True)
    compile_s = time.perf_counter() - t0
    print(f"compile: {compile_s:.2f} s (serve_step: greedy and sampled x "
          f"packed widths {widths})", flush=True)
    check("tpu_custom_call" in greedy_text,
          "compiled greedy serve_step holds the Mosaic ragged kernel "
          "(tpu_custom_call)")

    # every tick reports per-slot logits; keep the last ones so a request's
    # first token can be paired with the logits it was drawn from
    last = {}
    serve_step = eng.serve_step

    def recording_serve_step(*args, **kwargs):
        out = serve_step(*args, **kwargs)
        last["logits"] = out[1]
        return out
    eng.serve_step = recording_serve_step
    first_logits = {}

    def on_token(req, tok):
        if len(req.out) == 1 and req.rid in CHECKED:
            first_logits[req.rid] = np.asarray(last["logits"][req.slot],
                                               np.float32)

    arrivals = make_requests(cfg.vocab_size, on_token)
    want = {req.rid: req.max_new_tokens for _, req in arrivals}
    prompts = {req.rid: (req.prompt, req.task_id) for _, req in arrivals}
    t0 = time.perf_counter()
    gave_up, retries, _, sched = run_with_retries(
        sched, arrivals, grace_ticks=-1, max_retries=0, backoff=2)
    serve_s = time.perf_counter() - t0
    tokens = sched.tokens_emitted
    print(f"serve: {len(sched.finished)} requests, {sched.ticks} ticks, "
          f"{tokens} tokens, {sched.prefill_chunks_run} prefill chunks, "
          f"{eng.dispatches / max(sched.ticks, 1):.2f} dispatches/tick, "
          f"{serve_s:.2f} s wall (includes the jit's own first calls)",
          flush=True)
    traces = (eng._serve_greedy._cache_size(),
              eng._serve_sampled._cache_size())
    print(f"serve_step traces run: greedy {traces[0]}, sampled {traces[1]}")
    check(not gave_up and not retries and len(sched.finished) == N_REQUESTS
          and all(len(sched.finished[r].out) == n for r, n in want.items()),
          f"all {N_REQUESTS} requests finished with their token counts")
    findings = sched.drain_check()
    check(not findings and not sched.quarantined,
          f"drain clean, nothing quarantined ({findings}, "
          f"{sorted(sched.quarantined)})")
    check(traces == (2, 2), "all four serve_step traces ran")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')} "
          f"(bytes_limit {stats.get('bytes_limit')})", flush=True)

    # drop the KV pool before the reference runs beside the tables
    del sched, pool
    eng.serve_step = serve_step
    ref = Model(cfg, ModelOptions(attn_impl="ref"))        # f32 compute

    @jax.jit
    def reference(params, pp, tokens, task):
        logits, _, _ = ref.prefill(params, {"tokens": tokens},
                                   eng._peft_for(pp, task),
                                   max_len=tokens.shape[1])
        return logits[0, -1]

    ref_logits = {}
    for rid in CHECKED:
        prompt, task = prompts[rid]
        with jax.default_matmul_precision("highest"):
            ref_logits[rid] = np.asarray(reference(
                eng.params, eng._pp, jnp.asarray(prompt[None]),
                jnp.asarray([task], jnp.int32)), np.float32)
        got = first_logits[rid]
        err = rel_l2(got, ref_logits[rid])
        print(f"request {rid} (task {task}, {len(prompt)} prompt tokens): "
              f"bf16 paged vs f32 prefill rel L2 {err:.5f}, max abs "
              f"{np.abs(got - ref_logits[rid]).max():.5f} (|ref| max "
              f"{np.abs(ref_logits[rid]).max():.5f})", flush=True)
        check(np.isfinite(got).all() and err <= REL_TOL,
              f"request {rid}: bf16 logits within rel L2 {REL_TOL} of f32")
    # requests 0 and 1: one prompt under task 0 and task 1
    print(f"same prompt, task 0 vs task 1: rel L2 "
          f"{rel_l2(first_logits[0], first_logits[1]):.5f} (bf16 paged), "
          f"{rel_l2(ref_logits[0], ref_logits[1]):.5f} (f32 reference)",
          flush=True)
    check(all(rel_l2(first_logits[r], ref_logits[r])
              < rel_l2(first_logits[r], ref_logits[1 - r]) for r in (0, 1)),
          "task 0 and task 1 give different logits, each nearest its own "
          "task's reference (the AoT bias is applied)")


def main() -> int:
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: not inside a checkout of this repository ({e})",
              file=sys.stderr)
        return 2
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no devices: {e}", file=sys.stderr)
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} x {len(devices)} ({dev.platform}); "
          f"compile cache {enable_compile_cache()}", flush=True)
    try:
        from repro import configs
        smoke(jax, dev, configs.get("smollm-360m"))
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
