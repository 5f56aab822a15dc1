"""Continuous-batching scheduler + paged KV pool.

The contract under test: serving a mixed-task request stream continuously
(staggered arrivals, heterogeneous prompt/output lengths, slot churn) is
token-for-token identical to decoding each request alone with the static
engine — the paper's zero-cost multi-task property under realistic traffic.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aot as A
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.kv_pool import PagedKVPool
from repro.serve.scheduler import (ContinuousScheduler, Request,
                                   SchedulerConfig)


@pytest.fixture(scope="module")
def mt_engine(tiny_lm):
    cfg, model, params = tiny_lm
    tasks = [A.random_fused(cfg, params["embed"]["tok"], seed=s)
             for s in range(3)]
    return cfg, ServeEngine(model, params, ServeConfig(max_len=48),
                            fused_tasks=tasks)


# per-tick prefill budgets: under a page (3), not a divisor of the page
# (5), one page (8), and at least max_len (48: every prompt runs as one
# chunk)
@pytest.mark.parametrize("prefill_chunk", [3, 5, 8, 48])
def test_continuous_matches_static(rng, mt_engine, prefill_chunk):
    """Mixed-task stream through the continuous scheduler == per-request
    static greedy decode, token for token, at every prefill budget.
    Staggered arrivals, ragged prompt lengths, ragged output lengths,
    fewer slots than requests."""
    cfg, eng = mt_engine
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=3, block_size=8, prefill_chunk=prefill_chunk))
    reqs, arrivals = [], []
    for i in range(8):
        plen = int(rng.integers(3, 17))
        req = Request(rid=i,
                      prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
                      task_id=int(rng.integers(0, 3)),
                      max_new_tokens=int(rng.integers(1, 9)))
        reqs.append(req)
        arrivals.append((int(rng.integers(0, 12)), req))
    finished = sched.run_stream(arrivals)
    sched.pool.check_no_leaks()
    assert len(finished) == len(reqs)
    for req in reqs:
        ref = eng.generate(req.prompt[None], req.max_new_tokens,
                           np.asarray([req.task_id], np.int32))[0]
        np.testing.assert_array_equal(
            np.asarray(finished[req.rid].out), ref,
            err_msg=f"req {req.rid} (task {req.task_id}) diverged "
                    f"(prefill_chunk {prefill_chunk})")


def test_paged_preemption_recompute_exact(rng, mt_engine):
    """A pool too small for the offered load preempts (newest victim,
    recompute on re-admission) and still matches static decode exactly."""
    cfg, eng = mt_engine
    # 48-token max_len -> 6 pages of 8; 11 usable pages forces churn
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=4, block_size=8,
        num_blocks=12, prefill_chunk=8))
    reqs = []
    for i in range(8):
        plen = int(rng.integers(3, 17))
        req = Request(rid=i,
                      prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
                      task_id=int(rng.integers(0, 3)),
                      max_new_tokens=int(rng.integers(4, 12)))
        reqs.append(req)
        sched.submit(req)
    finished = sched.run()
    sched.pool.check_no_leaks()
    assert sched.preemptions > 0, "pool was sized to force preemption"
    assert len(finished) == len(reqs)
    for req in reqs:
        ref = eng.generate(req.prompt[None], req.max_new_tokens,
                           np.asarray([req.task_id], np.int32))[0]
        np.testing.assert_array_equal(
            np.asarray(finished[req.rid].out), ref,
            err_msg=f"req {req.rid} diverged after preemption churn")


def test_paged_admission_backpressure(rng, mt_engine):
    """Out-of-blocks admission: queued requests wait for pages instead of
    overdrawing the pool, and everything still drains."""
    cfg, eng = mt_engine
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=6, block_size=8,
        num_blocks=8, prefill_chunk=16))  # 7 usable pages << 6 slots x 3
    for i in range(6):
        sched.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, 16).astype(np.int32),
            task_id=i % 3, max_new_tokens=4))
    # first step can admit at most 3 requests (2 pages each, 7 free)
    sched.step()
    assert len(sched.running) <= 3
    assert sched.pool.free_blocks() <= 1
    assert len(sched.queue) >= 3, "admission must wait for pages"
    finished = sched.run()
    sched.pool.check_no_leaks()
    assert len(finished) == 6 and sched.pool.free_blocks() == 7


def test_page_starved_pool_decodes_without_thrash(rng, mt_engine):
    """REGRESSION (prefill-abort thrash): chunked admission must leave an
    append-page reserve for running decode rows. Without the guard, a
    queued prompt is admitted into a page-starved pool, aborted the moment
    a decode append runs dry, requeued at the head, and re-admitted next
    tick — re-burning its pages in a loop while decode stalls. With the
    guard the prompt waits and decode makes progress."""
    cfg, eng = mt_engine
    # 7 usable pages of 8. A (8-token prompt, 1 page) decodes while B's
    # 36-token prompt wants 5 pages: admitting B without reserve leaves
    # free = 1 and A's very next page-crossing starts the abort cycle.
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=4, block_size=8,
        num_blocks=8, prefill_chunk=4))
    a = Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, 8)
                .astype(np.int32), task_id=0, max_new_tokens=6)
    b = Request(rid=1, prompt=rng.integers(0, cfg.vocab_size, 36)
                .astype(np.int32), task_id=1, max_new_tokens=4)
    sched.submit(a)
    sched.step()                    # A prefilling (chunked)
    sched.submit(b)
    a_done_tick = None
    for _ in range(200):
        sched.step()
        if a.state == "finished" and a_done_tick is None:
            a_done_tick = sched.ticks
        if not sched.busy():
            break
    assert not sched.busy(), "page-starved pool livelocked"
    assert a_done_tick is not None, "decode never made progress"
    assert sched.preemptions == 0, (
        f"{sched.preemptions} aborts: admission guard failed to hold the "
        "queued prompt back from a page-starved pool")
    sched.pool.check_no_leaks()
    for req in (a, b):
        ref = eng.generate(req.prompt[None], req.max_new_tokens,
                           np.asarray([req.task_id], np.int32))[0]
        np.testing.assert_array_equal(np.asarray(req.out), ref)


def test_mid_prefill_abort_recovers_and_recomputes(rng, mt_engine):
    """A decode page-crossing with zero free pages aborts the newest
    in-flight prefill MID-PROMPT: its pages free, it requeues at the head,
    and its eventual re-admission recomputes from token 0 — no leaked
    pages, token streams exact."""
    cfg, eng = mt_engine
    # 7 usable pages. A: 8-token prompt (1 page) + 14 new tokens — crosses
    # into page 2 on its first append and page 3 at depth 16. B: 40-token
    # prompt (5 pages) chunked 4/tick (10 ticks). B passes the admission
    # guard (free 6 >= 5 + 1), then A's depth-16 crossing at ~tick 9 finds
    # the pool dry and aborts B one chunk short of done.
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=4, block_size=8,
        num_blocks=8, prefill_chunk=4))
    a = Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, 8)
                .astype(np.int32), task_id=0, max_new_tokens=14)
    b = Request(rid=1, prompt=rng.integers(0, cfg.vocab_size, 40)
                .astype(np.int32), task_id=1, max_new_tokens=3)
    sched.submit(a)
    sched.step()                    # A starts chunking (2 ticks of 4)
    sched.step()
    sched.submit(b)
    finished = sched.run()
    sched.pool.check_no_leaks()
    assert sched.preemptions >= 1, (
        "setup failed: B was never aborted mid-prefill")
    assert len(finished) == 2
    for req in (a, b):
        ref = eng.generate(req.prompt[None], req.max_new_tokens,
                           np.asarray([req.task_id], np.int32))[0]
        np.testing.assert_array_equal(
            np.asarray(req.out), ref,
            err_msg=f"req {req.rid} diverged across the mid-prefill abort")


def test_finish_exactly_on_final_chunk_frees_pages(rng, mt_engine):
    """max_new_tokens=1: the one token comes out of the final prefill
    chunk's logits and the request finishes INSIDE the install — its slot
    and pages must free in that same tick (no decode step ever runs)."""
    cfg, eng = mt_engine
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=3, block_size=8,
        prefill_chunk=8))
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 4 + 7 * i)
                    .astype(np.int32), task_id=i % 3, max_new_tokens=1)
            for i in range(3)]
    for r in reqs:
        sched.submit(r)
    finished = sched.run()
    sched.pool.check_no_leaks()
    assert sched.pool.num_free() == 3 and sched.pool.free_blocks() == \
        sched.pool.num_blocks - 1
    assert len(finished) == 3 and sched.steps_decoded == 0, (
        "a 1-token request must never enter the decode batch")
    for req in reqs:
        ref = eng.generate(req.prompt[None], 1,
                           np.asarray([req.task_id], np.int32))[0]
        np.testing.assert_array_equal(np.asarray(req.out), ref)


def test_fork_then_preempt_lineage_no_leaks(rng, mt_engine):
    """An n>1 parent forks its prompt pages COW, then pool pressure
    preempts forked children mid-decode; recompute re-prefills them as
    independents. Refcounts and the free lists must reconcile at drain,
    and the counter-based streams keep every sample's tokens identical to
    a roomy-pool run."""
    cfg, eng = mt_engine
    from repro.serve.sampling import SamplingParams
    prompt = rng.integers(0, cfg.vocab_size, 18).astype(np.int32)

    def serve(num_blocks):
        req = Request(rid=0, prompt=prompt, task_id=1, max_new_tokens=10,
                      sampling=SamplingParams(temperature=0.9, top_p=0.9,
                                              seed=13, n=3))
        sched = ContinuousScheduler(eng, SchedulerConfig(
            num_slots=4, block_size=8,
            num_blocks=num_blocks, prefill_chunk=8))
        sched.submit(req)
        sched.run()
        sched.pool.check_no_leaks()
        return req, sched

    roomy, _ = serve(num_blocks=0)          # max_len per slot: no pressure
    tight, sched = serve(num_blocks=7)      # 6 usable pages: forces churn
    assert sched.pool.forks > 0, "setup failed: parent never forked"
    assert sched.preemptions > 0, "setup failed: no child was preempted"
    assert sched.pool.free_blocks() == 6 and sched.pool.num_free() == 4
    assert tight.samples == roomy.samples, (
        "fork-then-preempt lineage changed a sample's tokens")


def test_streaming_and_latency_bookkeeping(rng, mt_engine):
    cfg, eng = mt_engine
    sched = ContinuousScheduler(eng, SchedulerConfig(num_slots=2,
                                                     prefill_chunk=8))
    seen = []
    req = Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
                  task_id=1, max_new_tokens=4,
                  on_token=lambda r, t: seen.append((r.rid, t)))
    sched.submit(req)
    sched.run()
    assert [t for _, t in seen] == req.out and len(req.out) == 4
    assert req.t_done >= req.t_first >= req.t_submit > 0


def test_request_too_long_rejected(rng, mt_engine):
    cfg, eng = mt_engine
    sched = ContinuousScheduler(eng, SchedulerConfig(num_slots=2))
    long_prompt = rng.integers(0, cfg.vocab_size, 40).astype(np.int32)
    with pytest.raises(ValueError, match="does not fit"):
        sched.submit(Request(rid=0, prompt=long_prompt, max_new_tokens=32))
    with pytest.raises(ValueError, match="max_new_tokens"):
        sched.submit(Request(rid=1, prompt=long_prompt[:4], max_new_tokens=0))


def test_sliding_window_model_refused_and_served_statically(rng):
    """The paged serve path has no window masking yet: building a
    scheduler for a sliding-window model fails with that reason, and the
    static engine still serves the model — its greedy tokens are the
    windowed full forward's argmax, with the prompt longer than the
    window so the ring-buffered cache wraps."""
    import jax
    from repro import configs
    from repro.models.model import Model, ModelOptions
    cfg = configs.reduced(configs.get("h2o-danube-1.8b"))
    assert cfg.attn_kind == "swa" and cfg.sliding_window == 16
    model = Model(cfg, ModelOptions(chunk_q=8, chunk_kv=8))
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, ServeConfig(max_len=48))
    with pytest.raises(AssertionError, match="no sliding-window masking"):
        ContinuousScheduler(eng, SchedulerConfig(num_slots=2,
                                                 prefill_chunk=8))
    prompt = rng.integers(0, cfg.vocab_size, (2, 20)).astype(np.int32)
    out = eng.generate(prompt, 10)
    logits, _ = model.logits(params, {"tokens": jnp.asarray(
        np.concatenate([prompt, out[:, :-1]], axis=1))})
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(logits[:, 19:], axis=-1)), out)


def test_paged_pool_churn(rng, tiny_lm):
    """Block allocator edge cases: out-of-blocks alloc returns None
    (admission backpressure), freed pages are reused, and slot/page
    bookkeeping never leaks or double-maps under churn."""
    cfg, model, params = tiny_lm
    pool = PagedKVPool(model, num_slots=4, max_len=32, block_size=8,
                       num_blocks=9)            # 8 usable pages
    assert pool.free_blocks() == 8 and pool.max_pages == 4
    live = []
    ever_freed, reused = set(), False
    for i in range(400):
        if live and (len(live) == 4 or rng.random() < 0.45):
            slot = live.pop(int(rng.integers(0, len(live))))
            ever_freed.update(pool._pages[slot])
            pool.free(slot)
        else:
            npages = int(rng.integers(1, 4))
            slot = pool.alloc(task_id=int(rng.integers(0, 3)), npages=npages)
            if slot is None:      # backpressure: slots or pages exhausted
                assert (not pool.has_free()
                        or pool.free_blocks() < npages)
                continue
            assert slot not in live
            reused |= bool(set(pool._pages[slot]) & ever_freed)
            pool.cur_len[slot] = int(rng.integers(1, npages * 8 + 1))
            # grow into fresh pages as decode would
            while (rng.random() < 0.3
                   and pool.cur_len[slot] < 32
                   and pool.ensure_append_page(slot)):
                pool.cur_len[slot] = (pool.cur_len[slot] // 8 + 1) * 8
            live.append(slot)
        pool.check_no_leaks()
    assert reused, "churn never recycled a freed page"
    # hard out-of-blocks: drain everything, then exhaust the pool exactly
    for s in list(live):
        pool.free(s)
    pool.check_no_leaks()
    assert pool.free_blocks() == 8
    s1 = pool.alloc(npages=3)
    s2 = pool.alloc(npages=4)
    s3 = pool.alloc(npages=1)
    assert None not in (s1, s2, s3) and pool.free_blocks() == 0
    assert pool.alloc(npages=1) is None, "overdrawing the pool must fail"
    pool.cur_len[s1] = 24       # next append needs a 4th page: none left
    assert not pool.ensure_append_page(s1)
    pool.free(s3)               # decode backpressure clears as pages free up
    assert pool.ensure_append_page(s1) and pool.free_blocks() == 0
    pool.cur_len[s1] = 0
    pool.free(s1)
    assert pool.alloc(npages=4) is not None, "freed pages must be reusable"
    pool.check_no_leaks()
    unallocated = (set(range(4)) - pool._used_slots).pop()
    with pytest.raises(ValueError):
        pool.free(unallocated)


def test_scheduler_drains_under_churn(rng, mt_engine):
    """Many more requests than slots: every request finishes, slots all
    return to the free list, totals add up."""
    cfg, eng = mt_engine
    sched = ContinuousScheduler(eng, SchedulerConfig(num_slots=2,
                                                     prefill_chunk=8,
                                                     max_prefills=1))
    n = 11
    for i in range(n):
        sched.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, 5).astype(np.int32),
            task_id=i % 3, max_new_tokens=1 + i % 4))
    finished = sched.run()
    sched.pool.check_no_leaks()
    assert len(finished) == n and sched.pool.num_free() == 2
    assert sched.tokens_emitted == sum(1 + i % 4 for i in range(n))
    assert all(len(finished[i].out) == 1 + i % 4 for i in range(n))


def test_multitask_pallas_gather_matches_rows_fused(rng):
    """The serve-path Pallas (task, token) gather == core.aot's
    rows_fused_multitask (interpret mode)."""
    from repro.kernels.aot_bias import aot_gather_add_multitask_kernel
    b, s, V, d, nt = 3, 6, 40, 16, 4
    tables = jnp.asarray(rng.normal(size=(nt, V, d)), jnp.float32)
    h = jnp.asarray(rng.normal(size=(b, s, d)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, V, (b, s)), jnp.int32)
    tids = jnp.asarray(rng.integers(0, nt, (b,)), jnp.int32)
    # reference path used inside the model's scan (table layer-major slice)
    ref = h + A.rows_fused_multitask(tables, tids, ids)
    out = aot_gather_add_multitask_kernel(
        h.reshape(b * s, d), tables,
        jnp.broadcast_to(tids[:, None], (b, s)).reshape(b * s),
        ids.reshape(b * s), interpret=True).reshape(b, s, d)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_mixed_step_pallas_decode_parity(rng, tiny_lm):
    """The per-slot flash-decode path (attn_impl='pallas', interpret on CPU)
    matches the jnp decode on a mixed-depth pool step."""
    from repro.models.model import Model, ModelOptions
    cfg, model, params = tiny_lm
    pmodel = Model(cfg, ModelOptions(chunk_q=8, chunk_kv=8, attn_impl="pallas"))
    b, s = 3, 8
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    _, cache, _ = model.prefill(params, {"tokens": toks}, max_len=16)
    pos = jnp.asarray([8, 5, 2], jnp.int32)
    step_tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, 1)), jnp.int32)
    lg_ref, _ = model.decode_step(params, step_tok, pos, cache)
    lg_pal, _ = pmodel.decode_step(params, step_tok, pos, cache)
    np.testing.assert_allclose(np.asarray(lg_ref), np.asarray(lg_pal),
                               atol=2e-5, rtol=2e-5)


def test_paged_mixed_step_decode_parity(rng, tiny_lm):
    """A paged cache built from a contiguous prefill (rows scattered into
    scrambled pages) decodes through the paged mixed step identically to
    the contiguous decode step — through both the XLA gather path and the
    Pallas ragged kernel."""
    from repro.models.model import Model, ModelOptions
    cfg, model, params = tiny_lm
    pmodel = Model(cfg, ModelOptions(chunk_q=8, chunk_kv=8, attn_impl="pallas"))
    b, s, bs_page, nblocks = 3, 8, 4, 14
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    _, cache, _ = model.prefill(params, {"tokens": toks}, max_len=16)
    depths = np.asarray([8, 5, 2], np.int32)
    npages = 16 // bs_page
    bt = np.zeros((b, npages), np.int32)
    avail = list(rng.permutation(np.arange(1, nblocks)))
    paged = model.init_paged_cache(nblocks, bs_page)
    for i in range(b):
        for j in range(-(-int(depths[i]) // bs_page)):
            bt[i, j] = avail.pop()
    for gi in range(len(paged)):
        for u in paged[gi]:
            for nm in ("k", "v"):
                pool = np.array(paged[gi][u][nm])
                src = np.asarray(cache[gi][u][nm])
                for i in range(b):
                    for j in range(-(-int(depths[i]) // bs_page)):
                        lo = j * bs_page
                        hi = min(lo + bs_page, int(depths[i]))
                        pool[:, bt[i, j], :hi - lo] = src[:, i, lo:hi]
                paged[gi][u][nm] = jnp.asarray(pool)
    step_tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, 1)), jnp.int32)
    pos = jnp.asarray(depths)
    btj = jnp.asarray(bt)
    rows = jnp.arange(b, dtype=jnp.int32)
    lg_ref, _ = model.decode_step(params, step_tok, pos, cache)
    lg_paged, _ = model.mixed_step(params, step_tok, rows, pos, paged,
                                   block_tables=btj)
    lg_pal, _ = pmodel.mixed_step(params, step_tok, rows, pos, paged,
                                  block_tables=btj)
    np.testing.assert_allclose(np.asarray(lg_ref[:, 0]), np.asarray(lg_paged),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lg_ref[:, 0]), np.asarray(lg_pal),
                               atol=2e-5, rtol=2e-5)
