"""Overload resilience: priority classes, deadlines, shedding, aborts,
graceful drain, and the deterministic chaos harness.

The contracts under test:

  * validation rejects malformed submissions at ``submit()`` with a typed
    ``InvalidRequest`` and leaves no scheduler/pool state behind;
  * ``abort(rid)`` cancels a request in ANY lifecycle state (queued,
    mid-chunked-prefill, mid-decode, COW-forked children) with a clean
    ``leak_report()`` and zero effect on unrelated in-flight requests
    (bitwise);
  * class-aware admission/preemption: latency preempts best-effort for
    pages, but the oldest admitted row of each class always finishes
    (the PR 5 no-starvation guarantee, per class);
  * past-deadline requests are aborted with every page freed;
  * the bounded queue sheds explicitly (reject-with-reason, displacement);
  * ``shutdown(grace_ticks)`` drains gracefully and reports what it shed;
  * under a seeded FaultPlan (page exhaustion + stragglers + disconnects +
    malformed submits) the scheduler always drains, never leaks, and every
    SURVIVOR's token stream is bitwise identical to a fault-free run —
    greedy and stochastic.
"""
import numpy as np
import pytest

from repro.core import aot as A
from repro.obs import ServeObservability
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.faults import FaultInjector, FaultPlan, run_chaos
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import (ABORTED, BEST_EFFORT, ContinuousScheduler,
                                   InvalidRequest, LATENCY, Request,
                                   SchedulerConfig, ShedError, STANDARD)


@pytest.fixture(scope="module")
def mt_engine(tiny_lm):
    cfg, model, params = tiny_lm
    tasks = [A.random_fused(cfg, params["embed"]["tok"], seed=s)
             for s in range(3)]
    return cfg, ServeEngine(model, params, ServeConfig(max_len=48),
                            fused_tasks=tasks)


def _req(cfg, rng, rid, plen=None, max_new=None, **kw):
    plen = plen if plen is not None else int(rng.integers(3, 17))
    max_new = max_new if max_new is not None else int(rng.integers(2, 9))
    return Request(
        rid=rid, prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
        task_id=int(rng.integers(0, 3)), max_new_tokens=max_new, **kw)


def _ref(eng, req):
    return eng.generate(req.prompt[None], req.max_new_tokens,
                        np.asarray([req.task_id], np.int32))[0]


# ---------------------------------------------------------------------------
# satellite: submit() validation
# ---------------------------------------------------------------------------

def _invalid_variants():
    p = np.asarray([1, 2, 3], np.int32)
    return {
        "empty_prompt": Request(rid=0, prompt=np.asarray([], np.int32)),
        "2d_prompt": Request(rid=0, prompt=np.zeros((2, 3), np.int32)),
        "zero_max_new": Request(rid=0, prompt=p, max_new_tokens=0),
        "zero_max_tokens": Request(rid=0, prompt=p,
                                   sampling=SamplingParams(max_tokens=0)),
        "n_zero": Request(rid=0, prompt=p, sampling=SamplingParams(n=0)),
        "unknown_task": Request(rid=0, prompt=p, task_id=99),
        "negative_task": Request(rid=0, prompt=p, task_id=-1),
        "nan_temperature": Request(
            rid=0, prompt=p,
            sampling=SamplingParams(temperature=float("nan"))),
        "nan_top_p": Request(
            rid=0, prompt=p,
            sampling=SamplingParams(temperature=0.7, top_p=float("nan"))),
        "bad_priority": Request(rid=0, prompt=p, priority="extreme"),
        "bad_deadline": Request(rid=0, prompt=p, deadline_ticks=0),
        "does_not_fit": Request(rid=0, prompt=p, max_new_tokens=1000),
    }


@pytest.mark.parametrize("variant", sorted(_invalid_variants()))
def test_invalid_request_rejected(mt_engine, variant):
    """Every malformed-submission class bounces with InvalidRequest and
    leaves the scheduler exactly as it was: nothing queued, pool clean."""
    cfg, eng = mt_engine
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=2, block_size=8, prefill_chunk=8))
    with pytest.raises(InvalidRequest):
        sched.submit(_invalid_variants()[variant])
    assert len(sched.queue) == 0 and not sched.running
    sched.pool.check_no_leaks()


def test_invalid_request_is_value_error(mt_engine):
    """Back-compat: InvalidRequest subclasses ValueError, so pre-existing
    handlers (and the old tests' pytest.raises) keep matching."""
    cfg, eng = mt_engine
    sched = ContinuousScheduler(eng, SchedulerConfig(num_slots=2))
    with pytest.raises(ValueError, match="does not fit"):
        sched.submit(Request(rid=1, prompt=np.asarray([1, 2], np.int32),
                             max_new_tokens=1000))


# ---------------------------------------------------------------------------
# satellite + tentpole: abort() in every lifecycle state
# ---------------------------------------------------------------------------

def _abort_sched(eng, **kw):
    base = dict(num_slots=3, block_size=8,
                prefill_chunk=8)
    base.update(kw)
    return ContinuousScheduler(eng, SchedulerConfig(**base))


def test_abort_queued(rng, mt_engine):
    cfg, eng = mt_engine
    # pool fits one request's pages at a time -> second request queues
    sched = _abort_sched(eng, num_slots=2, num_blocks=7)
    keeper = _req(cfg, rng, 0, plen=16, max_new=6)
    victim = _req(cfg, rng, 1, plen=33, max_new=6)   # 5 pages: can't co-fit
    sched.submit(keeper)
    sched.submit(victim)
    sched.step()
    assert victim.state == "queued" and len(sched.queue) == 1
    assert sched.abort(1, reason="client")
    assert victim.state == ABORTED and victim.finish_reason == "client"
    assert not sched.abort(1), "double abort must be a no-op"
    fin = sched.run()
    sched.pool.check_no_leaks()
    assert sorted(fin) == [0] and 1 in sched.aborted
    np.testing.assert_array_equal(np.asarray(fin[0].out), _ref(eng, keeper))


def test_abort_mid_prefill(rng, mt_engine):
    cfg, eng = mt_engine
    sched = _abort_sched(eng)
    keeper = _req(cfg, rng, 0, plen=6, max_new=6)
    victim = _req(cfg, rng, 1, plen=16, max_new=6)   # 2 chunk-ticks of prompt
    sched.submit(keeper)
    sched.submit(victim)
    sched.step()
    assert any(pf.req.rid == 1 for pf in sched._prefills), \
        "victim should be mid-chunked-prefill"
    assert sched.abort(1)
    assert not any(pf.req.rid == 1 for pf in sched._prefills)
    fin = sched.run()
    sched.pool.check_no_leaks()
    assert sorted(fin) == [0]
    np.testing.assert_array_equal(np.asarray(fin[0].out), _ref(eng, keeper))


def test_abort_mid_decode(rng, mt_engine):
    cfg, eng = mt_engine
    sched = _abort_sched(eng)
    keeper = _req(cfg, rng, 0, plen=8, max_new=8)
    victim = _req(cfg, rng, 1, plen=8, max_new=8)
    sched.submit(keeper)
    sched.submit(victim)
    for _ in range(3):
        sched.step()
    assert victim.state == "running" and victim.out, "victim mid-decode"
    assert sched.abort(1)
    assert 1 not in {r.rid for r in sched.running.values()}
    fin = sched.run()
    sched.pool.check_no_leaks()
    assert sorted(fin) == [0]
    np.testing.assert_array_equal(np.asarray(fin[0].out), _ref(eng, keeper))


def test_abort_forked_children(rng, mt_engine):
    """Aborting a forked rid takes the whole COW sample group — parent and
    every child — and the shared/diverged pages all come back."""
    cfg, eng = mt_engine
    sched = _abort_sched(eng, num_slots=4)
    keeper = _req(cfg, rng, 0, plen=8, max_new=8)
    victim = _req(cfg, rng, 1, plen=8, max_new=8,
                  sampling=SamplingParams(temperature=0.8, top_k=20, seed=7,
                                          n=3))
    sched.submit(keeper)
    sched.submit(victim)
    for _ in range(4):
        sched.step()
    live = [r for r in sched.running.values() if r.rid == 1]
    assert len(live) >= 2, "fork group should be decoding"
    assert sched.abort(1, reason="disconnect")
    assert not any(r.rid == 1 for r in sched.running.values())
    assert victim.state == ABORTED and victim.finish_reason == "disconnect"
    fin = sched.run()
    sched.pool.check_no_leaks()
    assert sorted(fin) == [0]
    np.testing.assert_array_equal(np.asarray(fin[0].out), _ref(eng, keeper))


def test_abort_unknown_rid(mt_engine):
    cfg, eng = mt_engine
    sched = _abort_sched(eng)
    assert not sched.abort(12345)


# ---------------------------------------------------------------------------
# tentpole: priority classes + deadlines
# ---------------------------------------------------------------------------

def test_priority_admission_order(rng, mt_engine):
    """Strict-priority admission: with every class queued at once, the
    latency request is admitted (and finishes) first, best-effort last —
    regardless of submission order."""
    cfg, eng = mt_engine
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=1, block_size=8, prefill_chunk=8))
    be = _req(cfg, rng, 0, plen=8, max_new=4, priority=BEST_EFFORT)
    st = _req(cfg, rng, 1, plen=8, max_new=4, priority=STANDARD)
    lat = _req(cfg, rng, 2, plen=8, max_new=4, priority=LATENCY)
    for r in (be, st, lat):        # submitted worst-first
        sched.submit(r)
    fin = sched.run()
    sched.pool.check_no_leaks()
    assert list(fin) == [2, 1, 0], "finish order must follow class rank"
    for r in (be, st, lat):
        np.testing.assert_array_equal(np.asarray(fin[r.rid].out),
                                      _ref(eng, r))


def test_latency_preempts_best_effort_for_pages(rng, mt_engine):
    """A latency arrival blocked on pages reclaims them from best-effort
    decode rows (newest first, oldest-of-class protected) — and the
    preempted row still finishes with exact tokens via recompute."""
    cfg, eng = mt_engine
    obs = ServeObservability(metrics=True, trace=False)
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=3, block_size=8,
        prefill_chunk=8, num_blocks=9), obs=obs)
    be = [_req(cfg, rng, i, plen=16, max_new=10, priority=BEST_EFFORT)
          for i in range(2)]
    for r in be:
        sched.submit(r)
    for _ in range(4):             # both BE rows decoding, pages mostly gone
        sched.step()
    lat = _req(cfg, rng, 9, plen=16, max_new=4, priority=LATENCY)
    sched.submit(lat)
    fin = sched.run()
    sched.pool.check_no_leaks()
    assert sched.preemptions >= 1, "latency admission should preempt"
    assert sorted(fin) == [0, 1, 9]
    # the oldest best-effort row kept its pages (no-starvation, per class)
    assert obs.slo.records[(0, 0)].preemptions == 0
    for r in be + [lat]:
        np.testing.assert_array_equal(
            np.asarray(fin[r.rid].out), _ref(eng, r),
            err_msg=f"rid {r.rid} diverged across class preemption")


def test_sustained_latency_cannot_starve_admitted_best_effort(rng, mt_engine):
    """The per-class no-starvation guarantee: one admitted best-effort
    request finishes even while latency-class arrivals land every tick
    and admission pressure wants its pages."""
    cfg, eng = mt_engine
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=3, block_size=8,
        prefill_chunk=8, num_blocks=10))
    be = _req(cfg, rng, 0, plen=16, max_new=12, priority=BEST_EFFORT)
    arrivals = [(0, be)]
    lats = [_req(cfg, rng, 1 + i, plen=8, max_new=4, priority=LATENCY)
            for i in range(12)]
    for i, r in enumerate(lats):
        arrivals.append((1 + i, r))    # one latency arrival per tick
    fin = sched.run_stream(arrivals)
    sched.pool.check_no_leaks()
    assert 0 in fin, "admitted best-effort request must finish"
    np.testing.assert_array_equal(np.asarray(fin[0].out), _ref(eng, be))
    for r in lats:
        np.testing.assert_array_equal(np.asarray(fin[r.rid].out),
                                      _ref(eng, r))


def test_deadline_abort_frees_pages(rng, mt_engine):
    """A queued request whose deadline passes is aborted with its state
    (and any pages) reclaimed; the survivor is unaffected bitwise."""
    cfg, eng = mt_engine
    sched = _abort_sched(eng, num_slots=2, num_blocks=7)
    keeper = _req(cfg, rng, 0, plen=16, max_new=10)
    doomed = _req(cfg, rng, 1, plen=16, max_new=6, deadline_ticks=3)
    sched.submit(keeper)
    sched.submit(doomed)          # queues behind keeper's pages
    fin = sched.run()
    sched.pool.check_no_leaks()
    assert sorted(fin) == [0]
    assert doomed.state == ABORTED and doomed.finish_reason == "deadline"
    assert sched.deadline_misses == 1 and 1 in sched.aborted
    np.testing.assert_array_equal(np.asarray(fin[0].out), _ref(eng, keeper))


def test_deadline_met_is_untouched(rng, mt_engine):
    cfg, eng = mt_engine
    sched = _abort_sched(eng)
    req = _req(cfg, rng, 0, plen=8, max_new=4, deadline_ticks=50)
    sched.submit(req)
    fin = sched.run()
    sched.pool.check_no_leaks()
    assert sched.deadline_misses == 0
    np.testing.assert_array_equal(np.asarray(fin[0].out), _ref(eng, req))


# ---------------------------------------------------------------------------
# tentpole: bounded queue, shedding, graceful drain
# ---------------------------------------------------------------------------

def test_queue_full_sheds_with_reason(rng, mt_engine):
    cfg, eng = mt_engine
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=1, block_size=8, prefill_chunk=8,
        max_queue=2))
    reqs = [_req(cfg, rng, i, plen=8, max_new=4) for i in range(4)]
    sched.submit(reqs[0])
    sched.step()                  # rid 0 occupies the only slot
    sched.submit(reqs[1])
    sched.submit(reqs[2])         # queue now at max_queue=2
    with pytest.raises(ShedError) as ei:
        sched.submit(reqs[3])
    assert ei.value.reason == "queue_full" and ei.value.rid == 3
    assert reqs[3].state == "shed" and 3 in sched.shed
    fin = sched.run()
    sched.pool.check_no_leaks()
    assert sorted(fin) == [0, 1, 2]


def test_higher_class_displaces_queued_best_effort(rng, mt_engine):
    """A latency submission into a full queue displaces the newest queued
    best-effort request instead of being refused."""
    cfg, eng = mt_engine
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=1, block_size=8, prefill_chunk=8,
        max_queue=2))
    r0 = _req(cfg, rng, 0, plen=8, max_new=4)
    sched.submit(r0)
    sched.step()
    be1 = _req(cfg, rng, 1, plen=8, max_new=4, priority=BEST_EFFORT)
    be2 = _req(cfg, rng, 2, plen=8, max_new=4, priority=BEST_EFFORT)
    sched.submit(be1)
    sched.submit(be2)
    lat = _req(cfg, rng, 3, plen=8, max_new=4, priority=LATENCY)
    sched.submit(lat)             # no raise: displaces be2
    assert 2 in sched.shed and sched.shed[2].finish_reason == "displaced"
    assert len(sched.queue) == 2
    fin = sched.run()
    sched.pool.check_no_leaks()
    assert sorted(fin) == [0, 1, 3]


def test_shutdown_graceful_finishes_inflight(rng, mt_engine):
    cfg, eng = mt_engine
    sched = _abort_sched(eng)
    reqs = [_req(cfg, rng, i, plen=8, max_new=4) for i in range(3)]
    for r in reqs:
        sched.submit(r)
    report = sched.shutdown(grace_ticks=100)
    assert report.clean and not report.shed_rids
    assert report.finished == 3 and sorted(sched.finished) == [0, 1, 2]
    with pytest.raises(ShedError) as ei:
        sched.submit(_req(cfg, rng, 9))
    assert ei.value.reason == "shutting_down"
    sched.pool.check_no_leaks()


def test_shutdown_short_grace_sheds_rest(rng, mt_engine):
    cfg, eng = mt_engine
    sched = _abort_sched(eng)
    reqs = [_req(cfg, rng, i, plen=16, max_new=8) for i in range(4)]
    for r in reqs:
        sched.submit(r)
    report = sched.shutdown(grace_ticks=2)
    assert report.clean, f"leaked at shutdown: {report.leak_findings}"
    assert report.shed_rids, "2 grace ticks cannot drain 4 requests"
    assert report.grace_ticks_used == 2
    done = set(sched.finished) | set(report.shed_rids)
    assert done == {0, 1, 2, 3}, "every request finished or was shed"
    for rid in report.shed_rids:
        assert sched.aborted[rid].finish_reason == "shutdown"
    sched.pool.check_no_leaks()


# ---------------------------------------------------------------------------
# tentpole: deterministic fault injection (chaos parity)
# ---------------------------------------------------------------------------

def _chaos_workload(cfg, seed, n=10, stochastic=False):
    """Deterministic arrivals; reconstructible for the fault-free twin."""
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


def _chaos_sched(eng):
    return ContinuousScheduler(eng, SchedulerConfig(
        num_slots=3, block_size=8,
        prefill_chunk=8, num_blocks=14))


def _assert_chaos_parity(eng, cfg, stochastic, plan_seed, wl_seed, n=10):
    baseline = _chaos_sched(eng).run_stream(
        _chaos_workload(cfg, wl_seed, n=n, stochastic=stochastic))
    sched = _chaos_sched(eng)
    plan = FaultPlan(seed=plan_seed, horizon=40,
                     p_exhaust=0.12, exhaust_pages=8, exhaust_ticks=3,
                     p_straggler=0.18, straggler_ms=0.5,
                     p_disconnect=0.10, p_malformed=0.18)
    res = run_chaos(sched, _chaos_workload(cfg, wl_seed, n=n,
                                           stochastic=stochastic), plan)
    inj = res["injector"]
    assert not res["leak_findings"], res["leak_findings"]
    sched.pool.check_no_leaks()
    assert not sched.busy(), "chaos run must drain"
    assert inj.malformed_ok, "a malformed submission slipped past validation"
    for kind in ("exhaust", "straggler", "disconnect", "malformed"):
        assert inj.applied[kind] > 0, f"fault kind {kind!r} never fired " \
            f"(applied: {inj.applied}) — retune plan seed/rates"
    survivors = set(res["finished"])
    assert survivors, "at least someone must survive the chaos"
    assert survivors == set(baseline) - set(inj.disconnected)
    for rid in survivors:
        np.testing.assert_array_equal(
            np.asarray(res["finished"][rid].out),
            np.asarray(baseline[rid].out),
            err_msg=f"survivor {rid} diverged under faults")
        if baseline[rid].samples is not None:
            for k, (a, b) in enumerate(zip(res["finished"][rid].samples,
                                           baseline[rid].samples)):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=f"survivor {rid} sample {k} diverged")
    return inj


def test_chaos_parity_greedy(mt_engine):
    cfg, eng = mt_engine
    _assert_chaos_parity(eng, cfg, stochastic=False, plan_seed=3, wl_seed=0)


def test_chaos_parity_stochastic(mt_engine):
    cfg, eng = mt_engine
    _assert_chaos_parity(eng, cfg, stochastic=True, plan_seed=3, wl_seed=1)


@pytest.mark.soak
def test_chaos_soak(mt_engine):
    """Longer seeded soak (CI runs it under the pallas-interpret job with
    ``-m soak``): more requests, more faults, same three invariants —
    drains, leak-free, survivors bitwise identical."""
    cfg, eng = mt_engine
    for plan_seed, wl_seed, stochastic in [(11, 5, False), (12, 6, True),
                                           (13, 7, True)]:
        _assert_chaos_parity(eng, cfg, stochastic=stochastic,
                             plan_seed=plan_seed, wl_seed=wl_seed, n=16)


def test_pool_seize_restore_accounting(mt_engine):
    """Seized pages are a visible leak-report finding until restored —
    a fault plan that forgets to give pages back fails loudly."""
    cfg, eng = mt_engine
    sched = _chaos_sched(eng)
    pages = sched.pool.seize_pages(4)
    assert len(pages) == 4 and sched.pool.num_seized() == 4
    report = sched.pool.leak_report()
    assert any("seized" in f for f in report)
    sched.pool.restore_pages(pages)
    sched.pool.check_no_leaks()


def test_total_exhaustion_self_preempts_not_crashes(rng, mt_engine):
    """With every free page seized, the sole running row parks itself in
    the queue (self-preempt) instead of raising, and resumes bitwise
    exact after the pages come back."""
    cfg, eng = mt_engine
    sched = _chaos_sched(eng)
    req = _req(cfg, rng, 0, plen=8, max_new=10)
    sched.submit(req)
    for _ in range(3):
        sched.step()
    assert req.state == "running"
    pages = sched.pool.seize_pages(sched.pool.free_blocks())
    for _ in range(6):            # decode crosses a page boundary here
        sched.step()
    sched.pool.restore_pages(pages)
    fin = sched.run()
    sched.pool.check_no_leaks()
    np.testing.assert_array_equal(np.asarray(fin[0].out), _ref(eng, req))
