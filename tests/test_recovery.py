"""Crash-safe serving: the request journal, scheduler snapshot/restore,
and the self-healing dispatch loop.

The contracts under test:

  * the append-only journal records every lifecycle transition with
    enough to replay: killing the process at ANY tick and restoring a
    fresh scheduler from the journal resumes every surviving stream
    bitwise-identically to an uninterrupted run — greedy and stochastic
    (including n>1 forks), with ``leak_report()`` clean and a clean
    ``DrainReport`` afterwards;
  * ``snapshot()`` / ``restore()`` capture host-side state only — KV
    pages are recomputed through the existing preempt-and-recompute
    path, which is what makes the bitwise guarantee hold;
  * the dispatch watchdog quarantines a request whose logits go NaN/inf
    (terminal QUARANTINED state, pages held for forensics) and retries
    the tick with the survivors, whose streams are bitwise unchanged;
  * a faulted dispatch (``DispatchFault``) is retried up to
    ``tick_retries`` times, then re-raised; any other exception from the
    dispatch (a compile error, device memory exhausted) propagates on the
    first attempt, unretried;
  * ``PagedKVPool.compact()`` deduplicates identical prompt pages as an
    admission rescue before preempt-and-recompute kicks in;
  * malformed SchedulerConfig knobs and ``shutdown(grace_ticks)`` bounce
    with a typed ``InvalidConfig`` at the call site, never mid-drain.
"""
import json

import numpy as np
import pytest

from repro.core import aot as A
from repro.obs import ServeObservability
from repro.serve.engine import DispatchFault, ServeConfig, ServeEngine
from repro.serve.faults import FaultInjector, FaultPlan, run_chaos
from repro.serve.recovery import (RequestJournal, read_snapshot,
                                  replay_journal, write_snapshot)
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import (ContinuousScheduler, InvalidConfig,
                                   QUARANTINED, Request, SchedulerConfig)


@pytest.fixture(scope="module")
def mt_engine(tiny_lm):
    cfg, model, params = tiny_lm
    tasks = [A.random_fused(cfg, params["embed"]["tok"], seed=s)
             for s in range(3)]
    return cfg, ServeEngine(model, params, ServeConfig(max_len=48),
                            fused_tasks=tasks)


def _sched(eng, journal=None, obs=None, **kw):
    base = dict(num_slots=3, bucket_min=8, kv_layout="paged", block_size=8,
                prefill_chunk=8, num_blocks=14)
    base.update(kw)
    return ContinuousScheduler(eng, SchedulerConfig(**base), obs=obs,
                               journal=journal)


def _req(cfg, rng, rid, plen=None, max_new=None, **kw):
    plen = plen if plen is not None else int(rng.integers(3, 17))
    max_new = max_new if max_new is not None else int(rng.integers(2, 9))
    return Request(
        rid=rid, prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
        task_id=int(rng.integers(0, 3)), max_new_tokens=max_new, **kw)


def _ref(eng, req):
    return eng.generate(req.prompt[None], req.max_new_tokens,
                        np.asarray([req.task_id], np.int32))[0]


def _wl(cfg, seed, n=8, stochastic=False):
    """Deterministic arrivals, reconstructible from the seed — the
    uninterrupted baseline and every killed/restored run regenerate the
    SAME workload so bitwise comparison is meaningful."""
    rng = np.random.default_rng(seed)
    arrivals = []
    for i in range(n):
        plen = int(rng.integers(3, 17))
        sp = None
        if stochastic and i % 3 == 0:
            sp = SamplingParams(temperature=0.8, top_k=20, seed=100 + i,
                                n=2 if i % 6 == 0 else 1)
        arrivals.append((int(rng.integers(0, n)), Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            task_id=int(rng.integers(0, 3)),
            max_new_tokens=int(rng.integers(3, 9)), sampling=sp)))
    return arrivals


def _assert_same_streams(fin, baseline, rids=None):
    rids = set(baseline) if rids is None else set(rids)
    assert set(fin) >= rids, f"missing rids: {rids - set(fin)}"
    for rid in sorted(rids):
        np.testing.assert_array_equal(
            np.asarray(fin[rid].out), np.asarray(baseline[rid].out),
            err_msg=f"request {rid} diverged after recovery")
        if baseline[rid].samples is not None:
            assert fin[rid].samples is not None
            for k, (a, b) in enumerate(zip(fin[rid].samples,
                                           baseline[rid].samples)):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=f"request {rid} sample {k} diverged")


# ---------------------------------------------------------------------------
# tentpole: the request journal
# ---------------------------------------------------------------------------

def test_journal_records_full_lifecycle(mt_engine, tmp_path):
    """Every transition lands in the journal; emit count matches the
    emitted tokens; replay marks the drained stream fully finished."""
    cfg, eng = mt_engine
    path = str(tmp_path / "journal.jsonl")
    sched = _sched(eng, journal=RequestJournal(path))
    fin = sched.run_stream(_wl(cfg, seed=40, n=4))
    sched.journal.close()
    events = [json.loads(l) for l in open(path)]
    kinds = {e["ev"] for e in events}
    assert {"submit", "admit", "emit", "finish"} <= kinds
    emitted = sum(1 for e in events if e["ev"] == "emit")
    assert emitted == sum(len(r.out) for r in fin.values())
    subs = [e for e in events if e["ev"] == "submit"]
    assert {e["rid"] for e in subs} == set(fin)
    for e in subs:       # enough to replay: prompt + sampling + identity
        assert e["prompt"] and "task_id" in e and "max_new_tokens" in e
    snap = replay_journal(path)
    assert all(r["status"] == "finished" for r in snap["requests"])


def test_journal_tolerates_torn_tail(mt_engine, tmp_path):
    """A crash mid-write tears the final line; replay must shrug it off.
    Corruption anywhere ELSE is real damage and raises."""
    cfg, eng = mt_engine
    path = str(tmp_path / "torn.jsonl")
    sched = _sched(eng, journal=RequestJournal(path))
    sched.run_stream(_wl(cfg, seed=41, n=3))
    sched.journal.close()
    with open(path, "a") as f:           # torn final record, no newline
        f.write('{"ev": "emit", "rid": 0, "i": 0, "t"')
    snap = replay_journal(path)
    assert all(r["status"] == "finished" for r in snap["requests"])

    lines = open(path).read().splitlines()
    lines[1] = "#### not json ####"
    bad = str(tmp_path / "corrupt.jsonl")
    with open(bad, "w") as f:
        f.write("\n".join(lines) + "\n")
    with pytest.raises(ValueError, match="corrupt"):
        replay_journal(bad)


# ---------------------------------------------------------------------------
# tentpole: kill-at-a-tick, restore from journal, bitwise parity
# ---------------------------------------------------------------------------

def _serve_killed(eng, cfg, arrivals, path, kill_tick):
    """Drive a journaled scheduler and abandon it mid-flight after
    ``kill_tick`` ticks — no shutdown, no page frees, exactly what a
    SIGKILL leaves behind. Recover a fresh scheduler from the journal,
    feed it the not-yet-arrived requests, and drain. Returns
    ``(finished, sched2)``, or None when the stream drained before the
    kill tick (nothing was interrupted)."""
    sched = _sched(eng, journal=RequestJournal(str(path)))
    order = sorted(range(len(arrivals)), key=lambda i: arrivals[i][0])
    i, killed = 0, False
    while i < len(order) or sched.busy():
        if (not sched.busy() and i < len(order)
                and arrivals[order[i]][0] > sched.clock):
            sched.clock = arrivals[order[i]][0]
        while i < len(order) and arrivals[order[i]][0] <= sched.clock:
            sched.submit(arrivals[order[i]][1])
            i += 1
        sched.step()
        if sched.ticks >= kill_tick and sched.busy():
            killed = True
            break
    sched.journal.close()
    if not killed:
        return None
    snap = replay_journal(str(path))
    sched2 = _sched(eng, journal=RequestJournal(str(path)))
    sched2.restore(snap)
    for j in order[i:]:                  # arrivals the old process never saw
        sched2.submit(arrivals[j][1])
    fin = sched2.run()
    return fin, sched2


@pytest.mark.parametrize("stochastic,wl_seed,kill_tick",
                         [(False, 50, 4), (True, 51, 5)])
def test_restore_midstream_parity(mt_engine, tmp_path, stochastic, wl_seed,
                                  kill_tick):
    cfg, eng = mt_engine
    baseline = _sched(eng).run_stream(_wl(cfg, wl_seed, stochastic=stochastic))
    got = _serve_killed(eng, cfg, _wl(cfg, wl_seed, stochastic=stochastic),
                        tmp_path / "kill.jsonl", kill_tick)
    assert got is not None, "stream drained before the kill tick — retune"
    fin, sched2 = got
    _assert_same_streams(fin, baseline)
    assert not sched2.pool.leak_report()
    report = sched2.shutdown(grace_ticks=4)
    assert report.clean


@pytest.mark.soak
def test_kill_at_every_tick_soak(mt_engine, tmp_path):
    """The tentpole acceptance soak: kill the serving process at EVERY
    tick of the stream, restore from the journal, and require every
    recovered stream bitwise-identical — greedy and stochastic (n>1
    forks included), leak-free, clean drain."""
    cfg, eng = mt_engine
    for stochastic, wl_seed in [(False, 60), (True, 61)]:
        baseline = _sched(eng).run_stream(
            _wl(cfg, wl_seed, stochastic=stochastic))
        k = 1
        while True:
            path = tmp_path / f"soak_{wl_seed}_{k}.jsonl"
            got = _serve_killed(eng, cfg,
                                _wl(cfg, wl_seed, stochastic=stochastic),
                                path, k)
            if got is None:              # stream outlived the kill tick
                break
            fin, sched2 = got
            _assert_same_streams(fin, baseline)
            assert not sched2.pool.leak_report(), f"leak at kill tick {k}"
            assert sched2.shutdown(grace_ticks=4).clean
            k += 1
        assert k > 3, "soak never killed mid-flight — workload too short"


def test_live_snapshot_restore_parity(mt_engine, tmp_path):
    """snapshot()/restore() midstream without a journal: host-side state
    round-trips through JSON on disk and the restored scheduler finishes
    bitwise-identically (KV pages recomputed, never serialized)."""
    cfg, eng = mt_engine
    baseline = _sched(eng).run_stream(_wl(cfg, 62, stochastic=True))
    sched = _sched(eng)
    arrivals = _wl(cfg, 62, stochastic=True)
    for _, req in arrivals:
        sched.submit(req)
    for _ in range(4):
        sched.step()
    assert sched.busy()
    path = str(tmp_path / "snap.json")
    write_snapshot(sched.snapshot(), path)
    snap = read_snapshot(path)
    assert "kv" not in snap and "cache" not in snap   # host-side only
    sched2 = _sched(eng)
    sched2.restore(snap)
    fin = sched2.run()
    _assert_same_streams(fin, baseline)
    sched2.pool.check_no_leaks()


def test_restore_requires_fresh_scheduler(mt_engine, rng):
    cfg, eng = mt_engine
    sched = _sched(eng)
    sched.submit(_req(cfg, rng, 0, plen=8, max_new=4))
    snap = sched.snapshot()
    sched.step()
    with pytest.raises(ValueError, match="fresh"):
        sched.restore(snap)
    sched.run()

    bad = dict(snap)
    bad["version"] = 999
    with pytest.raises(ValueError, match="version"):
        _sched(eng).restore(bad)


# ---------------------------------------------------------------------------
# tentpole: self-healing dispatch loop — NaN watchdog + quarantine
# ---------------------------------------------------------------------------

def test_nan_quarantines_poisoned_request_only(mt_engine, rng):
    """Poison one running slot's logits: the watchdog quarantines that
    request (pages held for forensics), survivors finish bitwise-exact,
    and shutdown releases the hold."""
    cfg, eng = mt_engine
    sched = _sched(eng, obs=ServeObservability())
    reqs = [_req(cfg, rng, rid, plen=9, max_new=6) for rid in range(3)]
    for r in reqs:
        sched.submit(r)
    while len(sched.running) < 3:
        sched.step()
    victim = sorted(sched.running)[1]
    victim_rid = sched.running[victim].rid
    eng.inject_fault("nan", victim)
    sched.step()
    assert victim_rid in sched.quarantined
    assert sched.quarantined[victim_rid].state == QUARANTINED
    assert sched.pool.num_quarantined() > 0
    assert sched.tick_retries_used >= 1
    fin = sched.run()
    for r in reqs:
        if r.rid == victim_rid:
            assert r.rid not in fin
            continue
        np.testing.assert_array_equal(np.asarray(fin[r.rid].out),
                                      _ref(eng, r))
    # quarantined pages are accounted (not a leak finding) until released
    assert not sched.pool.leak_report()
    report = sched.shutdown()
    assert report.quarantined_pages_released > 0 and report.clean
    assert sched.pool.num_quarantined() == 0
    sched.pool.check_no_leaks()
    m = sched.obs.metrics.snapshot()
    assert m["sched_quarantined_total"]["value"] == 1
    assert m["sched_quarantined_nan_logits_total"]["value"] == 1
    slo = sched.obs.slo.summary()
    assert slo["quarantines"] == {"nan_logits": 1}


def test_nan_chaos_plan_quarantines_and_survivors_hold(mt_engine):
    """Seeded NaN chaos through the FaultPlan path: at least one request
    quarantined, every survivor bitwise-identical to the fault-free twin,
    drain leak-free."""
    cfg, eng = mt_engine
    baseline = _sched(eng).run_stream(_wl(cfg, 63, n=10))
    plan = FaultPlan(seed=9, horizon=40, p_nan=0.22, p_exhaust=0.0,
                     p_straggler=0.0, p_disconnect=0.0, p_malformed=0.0)
    res = run_chaos(_sched(eng), _wl(cfg, 63, n=10), plan)
    inj = res["injector"]
    assert inj.applied["nan"] > 0, f"nan never fired: {inj.applied}"
    assert res["quarantined"], "no request was quarantined — retune seed"
    assert not res["leak_findings"], res["leak_findings"]
    survivors = set(res["finished"])
    assert survivors == set(baseline) - set(res["quarantined"])
    _assert_same_streams(res["finished"], baseline, rids=survivors)
    sched = res["sched"]
    assert sched.shutdown().quarantined_pages_released > 0
    sched.pool.check_no_leaks()


def test_alloc_failure_is_retried_transparently(mt_engine, rng):
    """A one-shot allocation fault raises inside dispatch; the tick loop
    retries and the stream is bitwise unaffected."""
    cfg, eng = mt_engine
    sched = _sched(eng, obs=ServeObservability())
    req = _req(cfg, rng, 0, plen=8, max_new=6)
    sched.submit(req)
    for _ in range(2):
        sched.step()
    eng.inject_fault("alloc_failure")
    fin = sched.run()
    assert sched.dispatch_faults == 1 and sched.tick_retries_used >= 1
    np.testing.assert_array_equal(np.asarray(fin[0].out), _ref(eng, req))
    sched.pool.check_no_leaks()
    m = sched.obs.metrics.snapshot()
    assert m["sched_dispatch_faults_total"]["value"] == 1
    assert m["sched_tick_retries_total"]["value"] >= 1


def test_dispatch_fault_exhausts_retries(mt_engine, rng, monkeypatch):
    """A dispatch that faults persistently is retried ``tick_retries``
    times, then re-raised to the caller."""
    cfg, eng = mt_engine
    sched = _sched(eng, tick_retries=1)
    sched.submit(_req(cfg, rng, 0, plen=8, max_new=4))
    calls = []

    def boom(*a, **kw):
        calls.append(1)
        raise DispatchFault("persistent device fault")

    monkeypatch.setattr(eng, "serve_step", boom)
    with pytest.raises(DispatchFault):
        sched.step()
    assert len(calls) == 2               # first attempt + tick_retries


@pytest.mark.parametrize("error", [RuntimeError, MemoryError, ValueError])
def test_non_dispatch_fault_propagates_unretried(mt_engine, rng, monkeypatch,
                                                 error):
    """Only DispatchFault is transient: a compile error or exhausted device
    memory would fail the same way again after recompiling the step, so it
    propagates on the first attempt and no retry is counted."""
    cfg, eng = mt_engine
    sched = _sched(eng, tick_retries=2)
    sched.submit(_req(cfg, rng, 0, plen=8, max_new=4))
    calls = []

    def boom(*a, **kw):
        calls.append(1)
        raise error("RESOURCE_EXHAUSTED: out of memory in hbm")

    monkeypatch.setattr(eng, "serve_step", boom)
    with pytest.raises(error):
        sched.step()
    assert len(calls) == 1
    assert sched.dispatch_faults == 0 and sched.tick_retries_used == 0


def test_compile_cache_dir(monkeypatch):
    """The entry points' cache helper leaves JAX_COMPILATION_CACHE_DIR to
    JAX where it is set, and otherwise sets one fixed directory inside the
    checkout — the same on every call, so a later process finds it."""
    import jax
    from pathlib import Path
    from repro.launch import compile_cache
    checkout = Path(__file__).resolve().parents[1]
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, "/elsewhere/jax-cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/jax-cache"
        assert jax.config.jax_compilation_cache_dir == was   # nothing set
        monkeypatch.delenv(compile_cache.ENV)
        first = compile_cache.enable_compile_cache()
        assert first == compile_cache.enable_compile_cache()
        assert Path(first) == checkout / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


# ---------------------------------------------------------------------------
# tentpole: crash faults through the chaos harness
# ---------------------------------------------------------------------------

def test_crash_restart_chaos_parity(mt_engine, tmp_path):
    """p_crash kills the scheduler mid-stream inside run_chaos; the
    factory's replacement restores from the shared journal and every
    stream still matches the crash-free twin bitwise."""
    cfg, eng = mt_engine
    for wl_seed, stochastic in [(64, False), (65, True)]:
        baseline = _sched(eng).run_stream(
            _wl(cfg, wl_seed, n=10, stochastic=stochastic))
        path = str(tmp_path / f"crash_{wl_seed}.jsonl")

        def factory():
            return _sched(eng, journal=RequestJournal(path))

        plan = FaultPlan(seed=21, horizon=40, p_crash=0.25, p_exhaust=0.0,
                         p_straggler=0.0, p_disconnect=0.0, p_malformed=0.0)
        res = run_chaos(factory(), _wl(cfg, wl_seed, n=10,
                                       stochastic=stochastic),
                        plan, sched_factory=factory)
        assert res["crashes"] >= 1, "crash never fired — retune seed"
        assert not res["leak_findings"], res["leak_findings"]
        _assert_same_streams(res["finished"], baseline)
        assert res["sched"].shutdown(grace_ticks=4).clean


def test_fault_streams_independent_per_kind(mt_engine):
    """Satellite: per-(tick, kind) RNG streams — enabling a NEW fault
    kind must not reshuffle the schedule of the kinds already enabled
    (chaos seeds stay reproducible across plan extensions)."""
    base = FaultPlan(seed=7, horizon=60, p_exhaust=0.15, p_straggler=0.2)
    ext = FaultPlan(seed=7, horizon=60, p_exhaust=0.15, p_straggler=0.2,
                    p_nan=0.3, p_alloc_failure=0.3, p_crash=0.3)

    def sched_of(plan):
        return [(e.tick, e.kind, e.u) for e in plan.events()
                if e.kind in ("exhaust", "straggler", "disconnect",
                              "malformed")]

    assert sched_of(base) == sched_of(ext), \
        "adding fault kinds reshuffled existing schedules"
    assert any(e.kind == "nan" for e in ext.events())
    assert any(e.kind == "crash" for e in ext.events())


# ---------------------------------------------------------------------------
# satellite: compact() — paged-KV defrag
# ---------------------------------------------------------------------------

def test_compact_dedupes_identical_prompts_bitwise(mt_engine, rng):
    """Two running slots with the SAME prompt share full prompt pages
    after compact(); decode proceeds through the COW append path and both
    streams stay bitwise-exact."""
    cfg, eng = mt_engine
    sched = _sched(eng)
    prompt = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    reqs = [Request(rid=i, prompt=prompt.copy(), task_id=1,
                    max_new_tokens=10) for i in range(2)]
    for r in reqs:
        sched.submit(r)
    while len(sched.running) < 2:
        sched.step()
    sched.step()                         # decode commits past the prompt
    freed = sched.pool.compact(
        {slot: r.prompt for slot, r in sched.running.items()})
    assert freed >= 1
    assert sched.pool.pages_deduped >= 1
    fin = sched.run()
    ref = _ref(eng, reqs[0])
    for r in reqs:
        np.testing.assert_array_equal(np.asarray(fin[r.rid].out), ref)
    sched.pool.check_no_leaks()


def test_compact_rescues_admission_before_preempt(mt_engine, rng):
    """A starved admission triggers compaction first: duplicate prompt
    pages come back, the new request admits, and nobody is preempted."""
    cfg, eng = mt_engine
    sched = _sched(eng, num_blocks=9,    # tight: forces the rescue path
                   obs=ServeObservability())
    prompt = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    dups = [Request(rid=i, prompt=prompt.copy(), task_id=0,
                    max_new_tokens=8) for i in range(2)]
    for r in dups:
        sched.submit(r)
    while len(sched.running) < 2:
        sched.step()
    late = _req(cfg, rng, 9, plen=10, max_new=4)
    sched.submit(late)
    fin = sched.run()
    assert sched.pool.compactions >= 1, "compaction rescue never fired"
    assert sched.preemptions == 0, "rescue should beat preempt-and-recompute"
    np.testing.assert_array_equal(np.asarray(fin[9].out), _ref(eng, late))
    ref = _ref(eng, dups[0])
    for r in dups:
        np.testing.assert_array_equal(np.asarray(fin[r.rid].out), ref)
    sched.pool.check_no_leaks()
    m = sched.obs.metrics.snapshot()
    assert m["kv_compactions_total"]["value"] >= 1
    assert m["kv_pages_deduped_total"]["value"] >= 1


# ---------------------------------------------------------------------------
# satellite: typed config validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("knob,value", [
    ("num_slots", 0), ("num_slots", -1), ("num_slots", 2.5),
    ("num_slots", float("nan")), ("bucket_min", 0), ("block_size", -8),
    ("tick_retries", -1), ("max_prefills", 0), ("prefill_chunk", -1),
    ("num_blocks", float("inf")), ("max_queue", -3),
])
def test_invalid_config_rejected_at_construction(mt_engine, knob, value):
    cfg, eng = mt_engine
    kw = dict(num_slots=2, kv_layout="paged", block_size=8, prefill_chunk=8)
    kw[knob] = value
    with pytest.raises(InvalidConfig, match=knob):
        ContinuousScheduler(eng, SchedulerConfig(**kw))


@pytest.mark.parametrize("grace", [-1, -7, float("nan"), 2.5])
def test_shutdown_grace_validated(mt_engine, grace):
    cfg, eng = mt_engine
    sched = _sched(eng)
    with pytest.raises(InvalidConfig, match="grace_ticks"):
        sched.shutdown(grace_ticks=grace)
    sched.pool.check_no_leaks()          # a rejected shutdown changed nothing


def test_invalid_config_is_value_error(mt_engine):
    cfg, eng = mt_engine
    with pytest.raises(ValueError):
        ContinuousScheduler(eng, SchedulerConfig(num_slots=-2))


# ---------------------------------------------------------------------------
# satellite: leak_report with every page category at once
# ---------------------------------------------------------------------------

def test_leak_report_seized_cached_quarantined_coexist(mt_engine, rng):
    """Seized, cache-retained, and quarantine-held pages at the same
    time: only SEIZED pages are a finding; the other two categories are
    accounted; releasing everything leaves the pool spotless."""
    cfg, eng = mt_engine
    sched = _sched(eng, prefix_cache_pages=4)
    done = _req(cfg, rng, 0, plen=16, max_new=3)
    sched.submit(done)
    sched.run()                          # finished → prompt pages cached
    assert len(sched.pool.prefix_cache.cached_pages()) > 0

    victim = _req(cfg, rng, 1, plen=9, max_new=8)
    sched.submit(victim)
    while not sched.running:
        sched.step()
    sched.quarantine(victim.rid, reason="test_poison")
    assert sched.pool.num_quarantined() > 0

    pages = sched.pool.seize_pages(2)
    report = sched.pool.leak_report()
    assert any("seized" in f for f in report)
    assert not any("quarantin" in f for f in report)
    assert not any("cache" in f for f in report)

    sched.pool.restore_pages(pages)
    assert not sched.pool.leak_report()
    report = sched.shutdown()            # releases quarantine, flushes cache
    assert report.clean and report.quarantined_pages_released > 0
    assert report.cache_pages_released > 0
    sched.pool.check_no_leaks()


def test_quarantine_terminal_in_journal_and_slo(mt_engine, rng, tmp_path):
    """A quarantine is a terminal transition: journaled (so replay keeps
    it out of re-admission) and visible in SLO accounting."""
    cfg, eng = mt_engine
    path = str(tmp_path / "q.jsonl")
    sched = _sched(eng, journal=RequestJournal(path))
    reqs = [_req(cfg, rng, rid, plen=8, max_new=5) for rid in range(2)]
    for r in reqs:
        sched.submit(r)
    while len(sched.running) < 2:
        sched.step()
    sched.quarantine(reqs[0].rid, reason="nan_logits")
    sched.run()
    sched.journal.close()
    events = [json.loads(l) for l in open(path)]
    assert any(e["ev"] == "quarantine" and e["rid"] == 0 for e in events)
    snap = replay_journal(path)
    by_rid = {r["rid"]: r for r in snap["requests"]}
    assert by_rid[0]["status"] == "quarantined"
    assert by_rid[1]["status"] == "finished"
    sched2 = _sched(eng)
    counts = sched2.restore(snap)
    assert counts["live"] == 0           # terminals are not re-admitted
    assert counts["quarantined"] == 1 and counts["finished"] == 1
    assert 0 in sched2.quarantined and 1 in sched2.finished
