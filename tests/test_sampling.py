"""Stochastic sampling engine + copy-on-write paged-KV forking.

Contracts under test:

* top-k / top-p masking matches a straightforward numpy oracle;
* temperature 0 is bitwise argmax (so greedy parity contracts survive);
* a sample's tokens are a pure function of (seed, sample_idx, token index)
  — identical across batch compositions and across preempt-and-recompute;
* ``PagedKVPool.fork`` shares pages by refcount, COWs the first divergent
  append, never leaks, and forked samples match independently-decoded ones.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aot as A
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.kv_pool import PagedKVPool
from repro.serve.sampling import (SamplingParams, masked_logits,
                                  request_base_key, sample_tokens, step_keys)
from repro.serve.scheduler import (ContinuousScheduler, Request,
                                   SchedulerConfig)


# ---------------------------------------------------------------------------
# sample_tokens / masked_logits unit contracts
# ---------------------------------------------------------------------------

def _np_masked_oracle(logits, temp, top_k, top_p):
    """Reference warper: scale, keep k best, keep the smallest descending
    prefix whose mass reaches p (first token always kept)."""
    x = (logits / max(temp, 1e-6)).astype(np.float64)
    V = x.shape[-1]
    order = np.argsort(-x, kind="stable")
    keep_sorted = np.ones(V, bool)
    k = V if top_k <= 0 else min(top_k, V)
    keep_sorted[k:] = False
    xs = x[order]
    probs = np.exp(xs - xs.max())
    probs /= probs.sum()
    mass_before = np.cumsum(probs) - probs
    keep_sorted &= mass_before < top_p
    keep_sorted[0] = True
    keep = np.zeros(V, bool)
    keep[order] = keep_sorted
    return keep


@pytest.mark.parametrize("top_k,top_p", [(0, 1.0), (5, 1.0), (0, 0.7),
                                         (12, 0.5), (3, 0.9), (1, 0.2)])
def test_masking_matches_numpy_oracle(rng, top_k, top_p):
    b, V = 6, 64
    logits = rng.normal(size=(b, V)).astype(np.float32) * 3.0
    temp = 0.8
    out = np.asarray(masked_logits(
        jnp.asarray(logits), jnp.full(b, temp, jnp.float32),
        jnp.full(b, top_k, jnp.int32), jnp.full(b, top_p, jnp.float32)))
    neg = np.finfo(np.float32).min
    for i in range(b):
        keep = _np_masked_oracle(logits[i], temp, top_k, top_p)
        np.testing.assert_array_equal(
            out[i] > neg / 2, keep,
            err_msg=f"row {i}: kept-token set diverged (k={top_k}, p={top_p})")
        np.testing.assert_allclose(out[i][keep], logits[i][keep] / temp,
                                   rtol=1e-6)


def test_masking_topk_ties_break_deterministically(rng):
    """REGRESSION: logits duplicated at the k-th value must keep exactly k
    survivors (stable index order), not every token tied at the cutoff.
    The old single-value-cutoff masking admitted all ties (> k kept)."""
    V = 32
    # row 0: all-equal logits; row 1: the top value duplicated 8 times;
    # row 2: ties exactly at the k-th rank; row 3: no ties (control)
    logits = np.zeros((4, V), np.float32)
    logits[1, 4:12] = 5.0
    logits[2, :3] = 3.0
    logits[2, 3:10] = 1.0               # k=5 cuts through this tied run
    logits[3] = np.linspace(3.0, -3.0, V)
    ks = np.asarray([4, 3, 5, 6], np.int32)
    out = np.asarray(masked_logits(
        jnp.asarray(logits), jnp.full(4, 0.7, jnp.float32),
        jnp.asarray(ks), jnp.ones(4, jnp.float32)))
    neg = np.finfo(np.float32).min
    for i in range(4):
        keep = out[i] > neg / 2
        assert keep.sum() == ks[i], (
            f"row {i}: {keep.sum()} survivors, want exactly k={ks[i]}")
        oracle = _np_masked_oracle(logits[i], 0.7, int(ks[i]), 1.0)
        np.testing.assert_array_equal(
            keep, oracle, err_msg=f"row {i}: tie-break diverged from the "
                                  "stable-argsort oracle")
    # top-p through a tied run must also respect the prefix length
    logits_p = np.zeros((1, V), np.float32)
    out_p = np.asarray(masked_logits(
        jnp.asarray(logits_p), jnp.ones(1, jnp.float32),
        jnp.zeros(1, jnp.int32), jnp.full(1, 0.5, jnp.float32)))
    kept_p = (out_p[0] > neg / 2)
    oracle_p = _np_masked_oracle(logits_p[0], 1.0, 0, 0.5)
    np.testing.assert_array_equal(kept_p, oracle_p)
    assert kept_p.sum() == oracle_p.sum() < V


def test_masking_heterogeneous_rows_independent(rng):
    """Per-row params in one batched call == one call per row."""
    b, V = 5, 32
    logits = jnp.asarray(rng.normal(size=(b, V)), jnp.float32)
    temps = jnp.asarray([0.5, 1.0, 0.7, 2.0, 0.1])
    ks = jnp.asarray([0, 3, 10, 1, 7], jnp.int32)
    ps = jnp.asarray([1.0, 0.6, 0.9, 1.0, 0.3])
    batched = np.asarray(masked_logits(logits, temps, ks, ps))
    for i in range(b):
        solo = np.asarray(masked_logits(logits[i:i + 1], temps[i:i + 1],
                                        ks[i:i + 1], ps[i:i + 1]))[0]
        np.testing.assert_array_equal(batched[i], solo)


def test_temperature_zero_is_exact_argmax(rng):
    b, V = 8, 100
    logits = jnp.asarray(rng.normal(size=(b, V)), jnp.float32)
    keys = np.stack([request_base_key(s) for s in range(b)])
    toks = sample_tokens(logits, jnp.zeros(b), jnp.zeros(b, jnp.int32),
                         jnp.ones(b), jnp.asarray(keys),
                         jnp.arange(b, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.argmax(np.asarray(logits), -1))


def test_draws_deterministic_and_step_keyed(rng):
    """Same (key, step) -> same token; different steps -> a different
    stream (statistically: not all draws equal across 16 steps)."""
    V = 50
    logits = jnp.asarray(np.tile(rng.normal(size=(1, V)), (16, 1)), jnp.float32)
    base = np.tile(request_base_key(seed=3), (16, 1))
    temps, ks, ps = jnp.full(16, 1.0), jnp.zeros(16, jnp.int32), jnp.ones(16)
    steps = jnp.arange(16, dtype=jnp.int32)
    t1 = np.asarray(sample_tokens(logits, temps, ks, ps, jnp.asarray(base), steps))
    t2 = np.asarray(sample_tokens(logits, temps, ks, ps, jnp.asarray(base), steps))
    np.testing.assert_array_equal(t1, t2)
    assert len(set(t1.tolist())) > 1, "fold_in(step) produced one constant"
    # and the draws respect masking: top_k=1 must equal argmax even at temp 1
    t3 = np.asarray(sample_tokens(logits, temps, jnp.ones(16, jnp.int32), ps,
                                  jnp.asarray(base), steps))
    np.testing.assert_array_equal(t3, np.argmax(np.asarray(logits), -1))


def test_step_keys_pure_function():
    base = np.stack([request_base_key(9, 0), request_base_key(9, 1)])
    k1 = np.asarray(step_keys(jnp.asarray(base), jnp.asarray([4, 4], jnp.int32)))
    k2 = np.asarray(step_keys(jnp.asarray(base), jnp.asarray([4, 4], jnp.int32)))
    np.testing.assert_array_equal(k1, k2)
    assert not np.array_equal(k1[0], k1[1]), "sample streams must differ"


def test_sampling_params_validation():
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-0.1).validate()
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0).validate()
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(top_k=-1).validate()
    with pytest.raises(ValueError, match="n must"):
        SamplingParams(n=0).validate()
    SamplingParams(temperature=1.0, top_k=5, top_p=0.9, n=4).validate()


# ---------------------------------------------------------------------------
# PagedKVPool fork / COW
# ---------------------------------------------------------------------------

def test_fork_refcounts_cow_and_no_leaks(rng, tiny_lm):
    cfg, model, params = tiny_lm
    pool = PagedKVPool(model, num_slots=4, max_len=32, block_size=8,
                       num_blocks=12)
    slot = pool.alloc(task_id=1, npages=2)
    pool.cur_len[slot] = 12                     # tail page half full
    f1 = pool.fork(slot)
    f2 = pool.fork(slot)
    assert f1 is not None and f2 is not None
    assert pool._pages[f1] == pool._pages[slot]
    np.testing.assert_array_equal(pool.block_tables[f1],
                                  pool.block_tables[slot])
    assert pool.cur_len[f1] == 12 and pool.task_id[f1] == 1
    assert all(pool._refs[p] == 3 for p in pool._pages[slot])
    assert pool.blocks_in_use() == 2            # sharing costs nothing
    pool.check_no_leaks()

    # first divergent append: sharers COW the tail page, last one in place
    tail = pool._pages[slot][1]
    assert pool.ensure_append_page(slot) and pool._pages[slot][1] != tail
    assert pool.cow_copies == 1 and pool._refs[tail] == 2
    assert pool.ensure_append_page(f1) and pool._pages[f1][1] != tail
    assert pool.cow_copies == 2 and pool._refs[tail] == 1
    assert pool.ensure_append_page(f2) and pool._pages[f2][1] == tail, (
        "sole remaining sharer must write in place, not copy")
    assert pool.cow_copies == 2
    assert pool.blocks_in_use() == 4            # 1 shared full + 3 tails
    pool.check_no_leaks()

    # frees decrement; shared pages only return to the pool at refcount 0
    shared = pool._pages[slot][0]
    pool.free(slot)
    assert pool._refs[shared] == 2 and shared not in pool._free_blocks
    pool.free(f1)
    pool.free(f2)
    assert pool._refs[shared] == 0 and shared in pool._free_blocks
    pool.check_no_leaks()
    assert pool.free_blocks() == 11


def test_fork_cow_preserves_shared_content(rng, tiny_lm):
    """COW must copy the shared tail rows: after the copy, the forked
    slot's pages hold the same KV values the source slot wrote."""
    cfg, model, params = tiny_lm
    pool = PagedKVPool(model, num_slots=2, max_len=16, block_size=4,
                       num_blocks=10)
    slot = pool.alloc(npages=2)
    # fill the slot's two pages with recognizable KV and publish 6 real
    # tokens (tail page half full), as a finished prefill leaves them
    pages = jnp.asarray(pool._pages[slot])
    pool.cache = jax.tree.map(
        lambda leaf: leaf.at[:, pages].set(jnp.asarray(
            rng.normal(size=leaf[:, pages].shape), leaf.dtype)),
        pool.cache)
    pool.commit_prefill(slot, 6)
    fork = pool.fork(slot)
    assert pool.ensure_append_page(fork)        # COW the shared tail
    assert pool.cow_copies == 1
    src_pages, dst_pages = pool._pages[slot], pool._pages[fork]
    assert src_pages[0] == dst_pages[0] and src_pages[1] != dst_pages[1]
    for gi in range(len(pool.cache)):
        for u in pool.cache[gi]:
            for nm in ("k", "v"):
                leaf = np.asarray(pool.cache[gi][u][nm])
                np.testing.assert_array_equal(
                    leaf[:, dst_pages[1]], leaf[:, src_pages[1]],
                    err_msg="COW page content diverged from source")
    pool.check_no_leaks()


def test_fork_out_of_slots_returns_none(tiny_lm):
    cfg, model, params = tiny_lm
    pool = PagedKVPool(model, num_slots=2, max_len=16, block_size=8,
                       num_blocks=6)
    slot = pool.alloc(npages=1)
    assert pool.fork(slot) is not None
    assert pool.fork(slot) is None, "no slot left: fork must refuse"
    with pytest.raises(ValueError):
        pool.fork(7)
    pool.check_no_leaks()


def test_cow_backpressure_when_out_of_pages(tiny_lm):
    """A shared tail append with zero free pages fails (False) — the
    scheduler preempts someone; once the sharer frees, the survivor owns
    the page and appends in place."""
    cfg, model, params = tiny_lm
    pool = PagedKVPool(model, num_slots=3, max_len=16, block_size=8,
                       num_blocks=3)            # 2 usable pages
    slot = pool.alloc(npages=2)
    pool.cur_len[slot] = 12
    fork = pool.fork(slot)
    assert not pool.ensure_append_page(slot), "COW without pages must fail"
    pool.free(fork)
    assert pool.ensure_append_page(slot), "sole owner appends in place"
    assert pool.cow_copies == 0
    pool.check_no_leaks()


# ---------------------------------------------------------------------------
# scheduler-level determinism contracts
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mt_engine(tiny_lm):
    cfg, model, params = tiny_lm
    tasks = [A.random_fused(cfg, params["embed"]["tok"], seed=s)
             for s in range(3)]
    return cfg, ServeEngine(model, params, ServeConfig(max_len=48),
                            fused_tasks=tasks)


def _stoch_requests(rng, cfg, n=8):
    return [Request(
        rid=i,
        prompt=rng.integers(0, cfg.vocab_size,
                            int(rng.integers(3, 17))).astype(np.int32),
        task_id=int(rng.integers(0, 3)),
        max_new_tokens=int(rng.integers(6, 12)),
        sampling=SamplingParams(temperature=0.9, top_k=20, top_p=0.95,
                                seed=100 + i))
        for i in range(n)]


def _run_all(eng, reqs, **cfg_kw):
    cfg_kw.setdefault("prefill_chunk", 8)
    sched = ContinuousScheduler(eng, SchedulerConfig(**cfg_kw))
    for r in reqs:
        sched.submit(r)
    sched.run()
    sched.pool.check_no_leaks()
    return sched


def test_sampled_stream_batch_invariant(rng, mt_engine):
    """Sampled tokens depend only on (seed, sample_idx, step): the same
    requests produce identical tokens at different batch widths and
    prefill budgets (48 >= max_len: every prompt in one chunk)."""
    cfg, eng = mt_engine
    outs = []
    for kw in (dict(num_slots=3, block_size=8, prefill_chunk=48),
               dict(num_slots=5, block_size=8,
                    prefill_chunk=8)):
        rng_r = np.random.default_rng(7)
        reqs = _stoch_requests(rng_r, cfg)
        _run_all(eng, reqs, **kw)
        outs.append([r.out for r in reqs])
    assert outs[0] == outs[1], "sampling depends on composition"


def test_sampled_preempt_recompute_exact(rng, mt_engine):
    """ACCEPTANCE: with a fixed seed, preempting and recomputing a sampled
    request reproduces the identical token sequence."""
    cfg, eng = mt_engine
    rng_r = np.random.default_rng(2)
    reqs_free = _stoch_requests(rng_r, cfg)
    _run_all(eng, reqs_free, num_slots=3, block_size=8)

    rng_r = np.random.default_rng(2)
    reqs_tight = _stoch_requests(rng_r, cfg)
    sched = _run_all(eng, reqs_tight, num_slots=4,
                     block_size=8, num_blocks=9)
    assert sched.preemptions > 0, "pool was sized to force preemption"
    for a, b in zip(reqs_free, reqs_tight):
        assert a.out == b.out, (
            f"req {a.rid}: preempt/recompute changed the sampled stream")


def test_greedy_sampling_params_match_plain_greedy(rng, mt_engine):
    """SamplingParams(temperature=0) is bitwise the greedy path."""
    cfg, eng = mt_engine
    rng_r = np.random.default_rng(5)
    plain = [Request(rid=i, prompt=p.copy(), task_id=t, max_new_tokens=m)
             for i, (p, t, m) in enumerate(
                 (r.prompt, r.task_id, r.max_new_tokens)
                 for r in _stoch_requests(rng_r, cfg, 6))]
    wrapped = [Request(rid=r.rid, prompt=r.prompt, task_id=r.task_id,
                       max_new_tokens=r.max_new_tokens,
                       sampling=SamplingParams(temperature=0.0, seed=r.rid))
               for r in plain]
    _run_all(eng, plain, num_slots=3)
    _run_all(eng, wrapped, num_slots=3)
    for a, b in zip(plain, wrapped):
        assert a.out == b.out


def test_fork_divergence_parity_vs_independent_slots(rng, mt_engine):
    """ACCEPTANCE: an n=4 forked request's samples are identical to the
    same request decoded without forking (num_slots=1 forces each sample
    through its own independent prefill) — COW divergence is invisible."""
    cfg, eng = mt_engine
    prompt = rng.integers(0, cfg.vocab_size, 10).astype(np.int32)

    def nreq():
        return Request(rid=0, prompt=prompt, task_id=1, max_new_tokens=6,
                       sampling=SamplingParams(temperature=0.8, top_p=0.9,
                                               seed=21, n=4))
    forked = nreq()
    s1 = _run_all(eng, [forked], num_slots=6, block_size=8)
    assert s1.pool.forks == 3 and s1.pool.cow_copies > 0
    indep = nreq()
    s2 = _run_all(eng, [indep], num_slots=1, block_size=8)
    assert s2.pool.forks == 0
    assert forked.samples == indep.samples, (
        "forked COW samples diverged from independent decodes")
    assert forked.out == forked.samples[0]
    assert len({tuple(s) for s in forked.samples}) > 1, (
        "temperature 0.8 samples all collapsed — sampling is suspect")


def test_fork_shares_prompt_pages(rng, mt_engine):
    """ACCEPTANCE: n=4 forked sampling uses < 1.5x the peak KV pages of a
    single-sample run (prompt pages shared, only decode tails diverge)."""
    cfg, eng = mt_engine
    # 38-token prompt over 4-token pages: 10 prompt pages, and 3 new tokens
    # stay inside the shared tail page, so n=4 costs 10 + 3 COW tails = 13
    # pages vs 10 single (1.3x) — the prefill KV is genuinely shared
    prompt = rng.integers(0, cfg.vocab_size, 38).astype(np.int32)

    def peak_pages(n):
        req = Request(rid=0, prompt=prompt, task_id=0, max_new_tokens=3,
                      sampling=SamplingParams(temperature=0.7, seed=3, n=n))
        sched = ContinuousScheduler(eng, SchedulerConfig(
            num_slots=6, block_size=4, prefill_chunk=8))
        sched.submit(req)
        peak = 0
        while sched.busy():
            sched.step()
            peak = max(peak, sched.pool.blocks_in_use())
        sched.pool.check_no_leaks()
        return peak

    p1, p4 = peak_pages(1), peak_pages(4)
    assert p4 < 1.5 * p1, (
        f"n=4 used {p4} pages vs {p1} single — forking is not sharing")


def test_stop_tokens_and_max_tokens_override(rng, mt_engine):
    cfg, eng = mt_engine
    prompt = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    probe = Request(rid=0, prompt=prompt, max_new_tokens=8,
                    sampling=SamplingParams(temperature=0.9, seed=2))
    _run_all(eng, [probe], num_slots=2)
    assert len(probe.out) == 8
    # max_tokens overrides Request.max_new_tokens
    r2 = Request(rid=0, prompt=prompt, max_new_tokens=8,
                 sampling=SamplingParams(temperature=0.9, seed=2, max_tokens=3))
    _run_all(eng, [r2], num_slots=2)
    assert r2.out == probe.out[:3]
    # a stop token ends the stream at its first occurrence
    stop = probe.out[4]
    r3 = Request(rid=0, prompt=prompt, max_new_tokens=8,
                 sampling=SamplingParams(temperature=0.9, seed=2,
                                         stop=(stop,)))
    _run_all(eng, [r3], num_slots=2)
    first = probe.out.index(stop)
    assert r3.out == probe.out[:first + 1]


def test_mixed_greedy_and_stochastic_batch(rng, mt_engine):
    """Greedy requests sharing a decode batch with stochastic ones still
    match dedicated static greedy decode bitwise."""
    cfg, eng = mt_engine
    greedy = Request(rid=0,
                     prompt=rng.integers(0, cfg.vocab_size, 7).astype(np.int32),
                     task_id=2, max_new_tokens=6)
    stoch = [Request(rid=i,
                     prompt=rng.integers(0, cfg.vocab_size, 5).astype(np.int32),
                     task_id=i % 3, max_new_tokens=6,
                     sampling=SamplingParams(temperature=1.1, seed=i))
             for i in range(1, 4)]
    _run_all(eng, [greedy] + stoch, num_slots=4)
    ref = eng.generate(greedy.prompt[None], 6, np.asarray([2], np.int32))[0]
    np.testing.assert_array_equal(np.asarray(greedy.out), ref,
                                  err_msg="greedy row perturbed by sampled "
                                          "batchmates")
