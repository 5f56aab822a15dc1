"""Cross-request shared-prefix page cache (PR 8).

AoT serving is many requests per task hammering the same per-task system
prompt, and the per-(task, token) bias is position-independent — so two
requests for the SAME task with the same prompt prefix produce bitwise
identical KV pages. The :class:`PrefixCache` retains finished requests'
full prompt pages (refcounted, LRU, capacity-bounded) and admission maps
a new request's longest matching prefix run straight into its block
table, starting chunked prefill at the first uncached token.

The contracts under test:

  * cache-hit decode is BITWISE identical to cold decode — greedy and
    stochastic (the cached pages hold exactly the KV a cold prefill
    would have written, and the ragged kernel reads them through the
    block table at the same absolute positions);
  * the cache key includes the task id: the same token prefix under a
    different task MUST miss (a different task bias means different KV);
  * refcount/leak invariants hold across hit→preempt→recompute and
    hit→abort lineages (pins released by ``pool.free`` on every path);
  * LRU eviction under page pressure never evicts pinned entries;
  * ``leak_report()`` treats cache-retained pages as a distinct
    category — a warm cache at drain is clean, a genuine leak still
    fires (the ``--check-leaks`` false-positive regression);
  * ``shutdown(grace_ticks)`` with a warm cache flushes it: the
    DrainReport shows every cached page released and zero findings;
  * a seeded property/oracle sweep and a chaos-soak where fault-injected
    page seizure races cache retention (both ``-m soak`` in CI).
"""
import numpy as np
import pytest

from repro.core import aot as A
from repro.obs import ServeObservability
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.faults import FaultPlan, run_chaos
from repro.serve.kv_pool import PagedKVPool
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import (ContinuousScheduler, Request,
                                   SchedulerConfig)

BS = 8          # page size used throughout: small enough that a short
                # system prompt spans several full pages


@pytest.fixture(scope="module")
def mt_engine(tiny_lm):
    cfg, model, params = tiny_lm
    tasks = [A.random_fused(cfg, params["embed"]["tok"], seed=s)
             for s in range(3)]
    return cfg, ServeEngine(model, params, ServeConfig(max_len=48),
                            fused_tasks=tasks)


def _sched(eng, **kw):
    base = dict(num_slots=4, block_size=BS,
                prefill_chunk=16, prefix_cache_pages=16)
    base.update(kw)
    return ContinuousScheduler(eng, SchedulerConfig(**base))


def _preq(rid, prompt, task=0, max_new=6, sampling=None):
    return Request(rid=rid, prompt=np.asarray(prompt, np.int32),
                   task_id=task, max_new_tokens=max_new, sampling=sampling)


def _ref(eng, req):
    """Static per-request generate: the cold greedy reference."""
    return eng.generate(req.prompt[None], req.max_new_tokens,
                        np.asarray([req.task_id], np.int32))[0]


def _tokens(rng, n):
    # in-vocab ids only (the reduced test vocab is 128): out-of-range ids
    # embed to garbage and the whole logits row goes NaN — which the
    # dispatch watchdog now (correctly) quarantines. In-vocab tokens also
    # make the bitwise-parity assertions non-vacuous: argmax over real
    # logits instead of argmax over NaN (= 0) on both sides.
    return rng.integers(0, 128, n).astype(np.int32)


# ---------------------------------------------------------------------------
# tentpole: bitwise parity of cache-hit vs cold decode
# ---------------------------------------------------------------------------

def test_cache_hit_bitwise_parity_greedy(rng, mt_engine):
    """Three requests sharing a 24-token (3 full pages) system prefix:
    the first misses and retains, the second and third hit — and every
    one of them decodes bitwise identical to a cold static generate."""
    cfg, eng = mt_engine
    sched = _sched(eng)
    cache = sched.pool.prefix_cache
    sys_p = _tokens(rng, 3 * BS)
    reqs = [_preq(i, np.concatenate([sys_p, _tokens(rng, 3 + 2 * i)]),
                  task=1, max_new=6) for i in range(3)]
    for i, r in enumerate(reqs):
        sched.submit(r)
        fin = sched.run()
        np.testing.assert_array_equal(
            np.asarray(fin[r.rid].out), _ref(eng, r),
            err_msg=f"request {i} diverged from the cold reference")
    assert cache.misses == 1 and cache.hits == 2, (cache.hits, cache.misses)
    assert cache.hit_tokens == 2 * 3 * BS, "each hit skips the 3 full pages"
    sched.pool.check_no_leaks()


def test_full_prompt_hit_still_recomputes_last_token(rng, mt_engine):
    """An exact-duplicate prompt matches at most (len-1)//bs pages: the
    last prefill token always recomputes, because its LOGITS (not just
    its KV) seed the first decode step. Tokens stay bitwise exact."""
    cfg, eng = mt_engine
    sched = _sched(eng)
    cache = sched.pool.prefix_cache
    prompt = _tokens(rng, 4 * BS)           # 4 exactly-full pages
    r1, r2 = _preq(0, prompt, task=2), _preq(1, prompt, task=2)
    sched.submit(r1)
    sched.run()
    sched.submit(r2)
    fin = sched.run()
    # retain kept all 4 full pages, but the duplicate may only map 3:
    # the page holding the last prompt token is recomputed
    assert len(cache) == 4 and cache.hit_tokens == 3 * BS
    np.testing.assert_array_equal(np.asarray(fin[1].out), _ref(eng, r2))
    np.testing.assert_array_equal(np.asarray(fin[1].out),
                                  np.asarray(fin[0].out))
    sched.pool.check_no_leaks()


def test_cache_hit_bitwise_parity_stochastic(rng, mt_engine):
    """Warm (cache-hit) stochastic decode vs a cold scheduler with the
    cache disabled: counter-based RNG streams + identical KV pages mean
    the sampled tokens must be bitwise identical too."""
    cfg, eng = mt_engine
    sys_p = _tokens(rng, 3 * BS)
    tails = [_tokens(rng, 3 + i) for i in range(4)]

    def reqs():
        return [_preq(i, np.concatenate([sys_p, tails[i]]), task=0,
                      max_new=8,
                      sampling=SamplingParams(temperature=0.8, top_k=20,
                                              top_p=0.9, seed=100 + i))
                for i in range(4)]

    cold = _sched(eng, prefix_cache_pages=0)
    for r in reqs():
        cold.submit(r)
    cold_fin = cold.run()
    cold.pool.check_no_leaks()

    warm = _sched(eng)
    for r in reqs():                        # sequential: each later request
        warm.submit(r)                      # hits the earlier ones' prefix
        warm.run()
    warm_fin = warm.finished
    assert warm.pool.prefix_cache.hits >= 3
    for i in range(4):
        np.testing.assert_array_equal(
            np.asarray(warm_fin[i].out), np.asarray(cold_fin[i].out),
            err_msg=f"stochastic request {i} diverged on a cache hit")
    warm.pool.check_no_leaks()


def test_same_tokens_different_task_misses(rng, mt_engine):
    """The cache key chains from the task id: identical token prefixes
    under different tasks are different prefixes (different bias →
    different KV) and must NOT share pages."""
    cfg, eng = mt_engine
    sched = _sched(eng)
    cache = sched.pool.prefix_cache
    prompt = np.concatenate([_tokens(rng, 3 * BS), _tokens(rng, 5)])
    r0 = _preq(0, prompt, task=0)
    r1 = _preq(1, prompt, task=1)           # same tokens, different task
    r2 = _preq(2, prompt, task=0)           # same tokens, SAME task
    for r in (r0, r1, r2):
        sched.submit(r)
        sched.run()
    assert cache.misses == 2, "task 1 must miss task 0's identical tokens"
    assert cache.hits == 1, "task 0's duplicate must hit"
    for r in (r0, r1, r2):
        np.testing.assert_array_equal(
            np.asarray(sched.finished[r.rid].out), _ref(eng, r),
            err_msg=f"rid {r.rid} (task {r.task_id}) diverged")
    sched.pool.check_no_leaks()


# ---------------------------------------------------------------------------
# tentpole: refcount/pin invariants across preempt and abort lineages
# ---------------------------------------------------------------------------

def test_hit_preempt_recompute_parity(rng, mt_engine):
    """hit → preempt → recompute: page seizure forces the hitting request
    out mid-decode; its pins release with the slot, the recompute
    re-matches the cached prefix, and the tokens stay bitwise exact."""
    cfg, eng = mt_engine
    sched = _sched(eng, num_slots=3, num_blocks=20, prefill_chunk=8)
    cache = sched.pool.prefix_cache
    sys_p = _tokens(rng, 3 * BS)
    warmer = _preq(0, np.concatenate([sys_p, _tokens(rng, 4)]), max_new=4)
    sched.submit(warmer)
    sched.run()
    assert len(cache) == 3

    victim = _preq(1, np.concatenate([sys_p, _tokens(rng, 6)]), max_new=12)
    sched.submit(victim)
    for _ in range(4):
        sched.step()
    assert victim.state == "running" and cache.pinned_entries() == 3
    pages = sched.pool.seize_pages(sched.pool.free_blocks())
    for _ in range(8):                      # decode crosses a page boundary:
        sched.step()                        # the sole row self-preempts
    assert sched.preemptions >= 1, "seizure should have forced a preempt"
    assert cache.pinned_entries() == 0, "preempt must release the pins"
    sched.pool.restore_pages(pages)
    fin = sched.run()
    sched.pool.check_no_leaks()
    assert cache.hits >= 2, "the recompute admission re-matches the prefix"
    np.testing.assert_array_equal(
        np.asarray(fin[1].out), _ref(eng, victim),
        err_msg="hit→preempt→recompute diverged from the cold reference")


def test_hit_abort_releases_pins_keeps_entries(rng, mt_engine):
    """hit → abort mid-decode: the pins go with the slot, the entries
    stay warm, the pool is leak-free, and the next same-prefix request
    still hits and still matches the cold reference."""
    cfg, eng = mt_engine
    sched = _sched(eng)
    cache = sched.pool.prefix_cache
    sys_p = _tokens(rng, 3 * BS)
    sched.submit(_preq(0, np.concatenate([sys_p, _tokens(rng, 4)])))
    sched.run()
    n_entries = len(cache)

    doomed = _preq(1, np.concatenate([sys_p, _tokens(rng, 5)]), max_new=10)
    sched.submit(doomed)
    for _ in range(3):
        sched.step()
    assert doomed.state == "running" and cache.pinned_entries() > 0
    assert sched.abort(1, reason="disconnect")
    assert cache.pinned_entries() == 0, "abort must release the pins"
    assert len(cache) == n_entries, "abort must not drop warm entries"
    sched.pool.check_no_leaks()

    again = _preq(2, np.concatenate([sys_p, _tokens(rng, 7)]))
    sched.submit(again)
    fin = sched.run()
    assert cache.hits >= 2
    np.testing.assert_array_equal(np.asarray(fin[2].out), _ref(eng, again))
    sched.pool.check_no_leaks()


def test_lru_eviction_never_evicts_pinned(rng, mt_engine):
    """Capacity and reclaim pressure evict cold unpinned leaves — never
    an entry pinned by a live slot, and never a chain interior under a
    surviving child (host-side pool surgery, no device work)."""
    cfg, eng = mt_engine
    sched = _sched(eng, prefix_cache_pages=4)
    pool, cache = sched.pool, sched.pool.prefix_cache
    pA, pB, pC = (_tokens(rng, 17) for _ in range(3))   # 2 full pages each

    for prompt in (pA, pB):
        slot = pool.alloc(0, 3)
        cache.retain(0, prompt, slot)
        pool.free(slot)
    assert len(cache) == 4                  # capacity reached

    keys_a = cache.match(0, pA)             # LRU-touches A's chain
    assert len(keys_a) == 2
    slot_a = pool.alloc_cached(0, keys_a, 3)    # pins A
    assert slot_a is not None and cache.pinned_entries() == 2

    slot_c = pool.alloc(0, 3)
    cache.retain(0, pC, slot_c)             # over capacity: evicts B (LRU,
    pool.free(slot_c)                       # unpinned), never pinned A
    assert cache.evicted_pages == 2 and len(cache) == 4
    assert all(k in cache._entries for k in keys_a), \
        "LRU eviction took a pinned entry"
    assert cache.match(0, pB) == [], "B should have been evicted"

    # reclaim pressure: only C's 2 unpinned pages are up for grabs
    assert cache.evictable_free() == 2
    assert not pool._reclaim(pool.free_blocks() + 3)
    assert len(cache) == 2 and cache.pinned_entries() == 2
    assert all(k in cache._entries for k in keys_a), \
        "reclaim pressure took a pinned entry"

    pool.free(slot_a)                       # pins release with the slot
    assert cache.pinned_entries() == 0
    assert pool.flush_prefix_cache() == 2 and len(cache) == 0
    pool.check_no_leaks()


# ---------------------------------------------------------------------------
# satellite: leak_report's cache-retained category (false-positive fix)
# ---------------------------------------------------------------------------

def test_leak_report_warm_cache_is_clean(rng, mt_engine):
    """A warm cache at drain is by design: retained pages are accounted
    as their own category (neither leaked nor free), so a check_leaks
    drain stays clean — while a genuine leak still fires."""
    cfg, eng = mt_engine
    # check_leaks on: run() sweeps at drain and would raise on the old
    # false positive (cache-retained pages counted as leaked)
    sched = _sched(eng, check_leaks=True)
    pool, cache = sched.pool, sched.pool.prefix_cache
    sched.submit(_preq(0, _tokens(rng, 3 * BS + 4)))
    sched.run()
    assert len(cache) == 3, "drain must leave the cache warm"
    assert pool.leak_report() == []

    # genuine leaks are still findings: a page that vanishes off the free
    # list (neither free, mapped, seized, nor cached) ...
    page = pool._free_blocks.pop()
    assert any("leaked pages" in f for f in pool.leak_report())
    pool._free_blocks.append(page)
    # ... and a cache refcount that drifts out of sync
    ent = next(iter(cache._entries.values()))
    pool._refs[ent.page] += 1
    assert any("refcounts out of sync" in f for f in pool.leak_report())
    pool._refs[ent.page] -= 1
    pool.check_no_leaks()


# ---------------------------------------------------------------------------
# satellite: shutdown with a warm cache
# ---------------------------------------------------------------------------

def test_shutdown_flushes_warm_cache(rng, mt_engine):
    """shutdown() with a warm cache (and a hitting request still in
    flight) must release every cached page in the DrainReport and sweep
    clean: abort releases the pins, then the flush empties the cache."""
    cfg, eng = mt_engine
    sched = _sched(eng)
    cache = sched.pool.prefix_cache
    sys_p = _tokens(rng, 3 * BS)
    sched.submit(_preq(0, np.concatenate([sys_p, _tokens(rng, 4)])))
    sched.run()
    n_cached = len(cache)
    assert n_cached == 3

    # leave a cache-hitting request mid-flight so shutdown's abort path
    # has pins to release before the flush
    sched.submit(_preq(1, np.concatenate([sys_p, _tokens(rng, 6)]),
                       max_new=12))
    for _ in range(3):
        sched.step()
    assert cache.pinned_entries() > 0
    report = sched.shutdown(grace_ticks=0)
    assert report.clean, f"shutdown leaked: {report.leak_findings}"
    assert report.shed_rids == [1]
    assert report.cache_pages_released == n_cached
    assert len(cache) == 0 and cache.pinned_entries() == 0
    sched.pool.check_no_leaks()
    assert sched.pool.blocks_in_use() == 0, "every page back on the free list"


def test_shutdown_graceful_drain_with_cache(rng, mt_engine):
    """A graceful shutdown (enough grace to finish) still reports the
    cache pages it flushed, with zero findings."""
    cfg, eng = mt_engine
    sched = _sched(eng)
    sys_p = _tokens(rng, 2 * BS)
    for i in range(3):
        sched.submit(_preq(i, np.concatenate([sys_p, _tokens(rng, 3 + i)]),
                           task=i % 2))
    report = sched.shutdown(grace_ticks=100)
    assert report.clean and not report.shed_rids and report.finished == 3
    # tasks 0 and 1 each retained the 2-page system prefix
    assert report.cache_pages_released == 4
    sched.pool.check_no_leaks()


# ---------------------------------------------------------------------------
# satellite: SLO tracker splits warm vs cold TTFT
# ---------------------------------------------------------------------------

def test_slo_summary_warm_vs_cold(rng, mt_engine):
    cfg, eng = mt_engine
    obs = ServeObservability(metrics=True)
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=4, block_size=BS, prefill_chunk=16,
        prefix_cache_pages=16), obs=obs)
    sys_p = _tokens(rng, 3 * BS)
    for i in range(3):
        sched.submit(_preq(i, np.concatenate([sys_p, _tokens(rng, 4 + i)])))
        sched.run()
    s = obs.slo.summary()["prefix_cache"]
    assert s["cold_requests"] == 1 and s["warm_requests"] == 2
    assert s["cached_tokens"] == 2 * 3 * BS
    # a warm request skips whole prefill chunks: its TTFT cannot exceed
    # the cold request's on this idle-free workload
    assert s["warm_ttft_ticks"]["p50"] <= s["cold_ttft_ticks"]["p50"]
    snap = obs.metrics.snapshot()
    assert snap["prefix_cache_hits_total"]["value"] == 2
    assert snap["prefix_cache_hit_tokens_total"]["value"] == 2 * 3 * BS
    sched.pool.check_no_leaks()


# ---------------------------------------------------------------------------
# satellite: property-style allocator sweep against a refcount oracle
# ---------------------------------------------------------------------------

def _allocator_property(eng, seed, n_ops):
    """Seeded random op-sequence over alloc / alloc_cached / fork /
    append(+COW) / retain / free / seize / restore / flush, with a plain
    Python dict oracle tracking every page's expected refcount. After
    every op: the oracle ledger must equal ``pool._refs`` exactly, the
    free list must hold precisely the unreferenced unseized pages, and
    ``leak_report()`` must be clean (modulo intentionally-seized pages)."""
    rng = np.random.default_rng(seed)
    pool = PagedKVPool(eng.model, num_slots=6, max_len=48, block_size=BS,
                       num_blocks=32)
    cache = pool.enable_prefix_cache(10)
    sys_p = {t: _tokens(rng, 2 * BS) for t in range(3)}
    live = {}                       # slot -> (task, prompt)
    refs = {}                       # page -> oracle refcount
    seized = []

    def snap():
        return {k: e.page for k, e in cache._entries.items()}

    def diff(pre):
        """Fold cache insert/evict deltas into the oracle ledger: the
        cache holds exactly one refcount per retained page."""
        post = snap()
        for k, p in pre.items():
            if k not in post:
                refs[p] -= 1
        for k, p in post.items():
            if k not in pre:
                refs[p] = refs.get(p, 0) + 1

    def check(op):
        got = {p: int(pool._refs[p]) for p in range(pool.num_blocks)
               if pool._refs[p]}
        want = {p: c for p, c in refs.items() if c}
        assert got == want, f"after {op}: refs {got} != oracle {want}"
        assert pool.free_blocks() == \
            pool.num_blocks - 1 - len(want) - len(seized), op
        rep = [f for f in pool.leak_report() if "still seized" not in f]
        assert not rep, f"after {op}: {rep}"

    def admit():
        t = int(rng.integers(0, 3))
        if rng.random() < 0.75:     # shared-prefix workload: matches happen
            prompt = np.concatenate(
                [sys_p[t], _tokens(rng, int(rng.integers(1, 16)))])
        else:
            prompt = _tokens(rng, int(rng.integers(3, 41)))
        npages = pool.pages_needed(len(prompt))
        keys = cache.match(t, prompt)
        if keys:
            shared = cache.pages(keys)
            slot = pool.alloc_cached(t, keys, npages)
        else:
            shared, slot = [], pool.alloc(t, npages)
        if slot is None:
            return
        for p in shared:
            refs[p] += 1
        for p in pool._pages[slot][len(shared):]:
            refs[p] = refs.get(p, 0) + 1
        pool.commit_prefill(slot, len(prompt))
        live[slot] = (t, prompt)

    def append():
        slot = int(rng.choice(list(live)))
        if pool.cur_len[slot] >= pool.max_len:
            return
        pre_pages = list(pool._pages[slot])
        if not pool.ensure_append_page(slot):
            return
        post_pages = pool._pages[slot]
        if len(post_pages) > len(pre_pages):
            refs[post_pages[-1]] = refs.get(post_pages[-1], 0) + 1
        else:                       # COW swapped a shared page
            for a, b in zip(pre_pages, post_pages):
                if a != b:
                    refs[a] -= 1
                    refs[b] = refs.get(b, 0) + 1
        pool.advance([slot])

    def fork():
        src = int(rng.choice(list(live)))
        new = pool.fork(src)
        if new is not None:
            for p in pool._pages[new]:
                refs[p] += 1
            live[new] = live[src]

    def release(retain):
        slot = int(rng.choice(list(live)))
        t, prompt = live.pop(slot)
        if retain:
            cache.retain(t, prompt, slot)
        pages = list(pool._pages[slot])
        pool.free(slot)
        for p in pages:
            refs[p] -= 1

    for i in range(n_ops):
        pre = snap()
        u = rng.random()
        if u < 0.32:
            op = "admit"
            admit()
        elif u < 0.55 and live:
            op = "append"
            append()
        elif u < 0.72 and live:
            op = "finish"
            release(retain=True)
        elif u < 0.80 and live:
            op = "abort"
            release(retain=False)
        elif u < 0.85 and live:
            op = "fork"
            fork()
        elif u < 0.90:
            op = "seize"
            seized.extend(pool.seize_pages(int(rng.integers(1, 5))))
        elif u < 0.95 and seized:
            op = "restore"
            pool.restore_pages(seized)
            seized = []
        else:
            op = "flush"
            pool.flush_prefix_cache()
        diff(pre)
        check(f"op {i} ({op}, seed {seed})")

    while live:                     # teardown must return every page
        pre = snap()
        release(retain=rng.random() < 0.5)
        diff(pre)
        check(f"teardown (seed {seed})")
    if seized:
        pool.restore_pages(seized)
        seized = []
    pre = snap()
    pool.flush_prefix_cache()
    diff(pre)
    check(f"final flush (seed {seed})")
    assert not any(refs.values()) and pool.blocks_in_use() == 0
    pool.check_no_leaks()


def test_allocator_oracle_quick(mt_engine):
    cfg, eng = mt_engine
    _allocator_property(eng, seed=0, n_ops=120)


@pytest.mark.soak
def test_allocator_oracle_soak(mt_engine):
    """Longer seeded sweeps (CI runs them under ``-m soak``)."""
    cfg, eng = mt_engine
    for seed in (1, 2, 3):
        _allocator_property(eng, seed=seed, n_ops=400)


# ---------------------------------------------------------------------------
# soak: fault-injected page seizure racing cache retention
# ---------------------------------------------------------------------------

def _prefix_workload(cfg, seed, n):
    """Deterministic shared-prefix arrivals: per-task 16-token system
    prompts + short unique tails, so cache hits, retention, and eviction
    all fire while the FaultPlan seizes pages."""
    rng = np.random.default_rng(seed)
    sys_p = {t: rng.integers(0, cfg.vocab_size, 2 * BS).astype(np.int32)
             for t in range(3)}
    arrivals = []
    for i in range(n):
        t = int(rng.integers(0, 3))
        tail = rng.integers(0, cfg.vocab_size,
                            int(rng.integers(1, 9))).astype(np.int32)
        arrivals.append((int(rng.integers(0, n)), Request(
            rid=i, prompt=np.concatenate([sys_p[t], tail]), task_id=t,
            max_new_tokens=int(rng.integers(3, 9)))))
    return arrivals


def _prefix_chaos_sched(eng, cached):
    return ContinuousScheduler(eng, SchedulerConfig(
        num_slots=3, block_size=BS,
        prefill_chunk=8, num_blocks=14,
        prefix_cache_pages=8 if cached else 0))


@pytest.mark.soak
def test_chaos_soak_seizure_races_retention(mt_engine):
    """FaultInjector page seizure races cache retention/eviction: the
    cached scheduler must still drain, stay leak-free, and every
    survivor's tokens must be bitwise identical to a fault-free run
    WITHOUT the cache — the strongest parity (cold + no faults)."""
    cfg, eng = mt_engine
    for plan_seed, wl_seed in [(11, 21), (12, 22), (13, 23)]:
        wl = _prefix_workload(cfg, wl_seed, n=14)
        baseline = _prefix_chaos_sched(eng, cached=False).run_stream(
            _prefix_workload(cfg, wl_seed, n=14))
        sched = _prefix_chaos_sched(eng, cached=True)
        plan = FaultPlan(seed=plan_seed, horizon=48,
                         p_exhaust=0.18, exhaust_pages=8, exhaust_ticks=3,
                         p_straggler=0.10, straggler_ms=0.2,
                         p_disconnect=0.08, p_malformed=0.10)
        res = run_chaos(sched, wl, plan)
        inj = res["injector"]
        assert not res["leak_findings"], res["leak_findings"]
        sched.pool.check_no_leaks()
        assert not sched.busy(), "cached chaos run must drain"
        assert inj.applied["exhaust"] > 0, \
            f"seizure never fired (applied: {inj.applied}) — retune seeds"
        cache = sched.pool.prefix_cache
        assert cache.hits > 0, "the shared-prefix workload must hit"
        survivors = set(res["finished"])
        assert survivors == set(baseline) - set(inj.disconnected)
        for rid in survivors:
            np.testing.assert_array_equal(
                np.asarray(res["finished"][rid].out),
                np.asarray(baseline[rid].out),
                err_msg=f"survivor {rid} diverged (seeds {plan_seed}/"
                        f"{wl_seed}): cache hit under faults is not "
                        "bitwise exact")
