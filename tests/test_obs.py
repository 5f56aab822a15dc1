"""Serve-path observability (repro.obs): metrics, tracing, SLO accounting.

The contracts under test:

  * NO HEISENBERG EFFECT — running the same request stream with
    observability fully enabled (metrics + tracing + lifecycle tracking)
    emits bitwise-identical tokens to a run with observability off.
    Instrumentation reads host scalars between device steps and never
    reaches inside jitted code, so this must hold exactly.
  * histogram bucket math matches a numpy oracle, and window percentiles
    match ``np.percentile``-style nearest-rank on the raw samples;
  * the tick trace is valid Chrome trace-event JSON (the subset Perfetto
    loads): every event carries name/ph/ts/pid/tid, complete events carry
    a duration, and the per-tick span anatomy
    (admission/pack/dispatch/postprocess) nests inside each tick span;
  * the drain-time leak sweep fires on an injected page leak and stays
    silent on clean drains, publishing the finding count through the
    metrics snapshot.
"""
import json

import numpy as np
import pytest

from repro.core import aot as A
from repro.obs import NULL_OBS, ServeObservability
from repro.obs.metrics import (Histogram, MetricsRegistry, NULL_COUNTER,
                               NULL_GAUGE, NULL_HISTOGRAM)
from repro.obs.slo import Lifecycle, SLOTracker
from repro.obs.tracing import TickTracer
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import (ContinuousScheduler, Request,
                                   SchedulerConfig)


@pytest.fixture(scope="module")
def obs_engine(tiny_lm):
    cfg, model, params = tiny_lm
    tasks = [A.random_fused(cfg, params["embed"]["tok"], seed=s)
             for s in range(3)]
    return cfg, ServeEngine(model, params, ServeConfig(max_len=48),
                            fused_tasks=tasks)


def _mk_requests(rng, cfg, n, sampled=False):
    reqs = []
    for i in range(n):
        plen = int(rng.integers(3, 17))
        sp = None
        if sampled:
            sp = SamplingParams(temperature=0.8, top_p=0.9, seed=100 + i)
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            task_id=int(rng.integers(0, 3)),
            max_new_tokens=int(rng.integers(1, 9)), sampling=sp))
    return reqs


def _serve(eng, reqs, obs=None, **sched_kw):
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=3, bucket_min=8, kv_layout="paged", block_size=8,
        prefill_chunk=8, **sched_kw), obs=obs)
    arrivals = [(i % 5, r) for i, r in enumerate(reqs)]
    return sched, sched.run_stream(arrivals)


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------

def test_histogram_matches_numpy_oracle(rng):
    bounds = [0.5, 1.0, 2.0, 5.0, 10.0]
    h = Histogram("h", bounds, window=10_000)
    vals = rng.exponential(2.0, size=1000)
    for v in vals:
        h.observe(v)
    # numpy oracle: np.histogram with the same (inclusive-upper) edges.
    # np.histogram bins are half-open [lo, hi) except the last; nudge the
    # edges up by the smallest representable step to model v <= bound
    edges = [-np.inf] + [np.nextafter(b, np.inf) for b in bounds] + [np.inf]
    want, _ = np.histogram(vals, bins=edges)
    assert h.bucket_counts == want.tolist()
    assert h.count == 1000
    np.testing.assert_allclose(h.sum, vals.sum(), rtol=1e-9)
    # exact percentiles over the retained window (nearest-rank)
    svals = sorted(vals)
    for q in (50, 95, 99):
        rank = int(round(q / 100.0 * (len(svals) - 1)))
        assert h.percentile(q) == svals[rank]


def test_histogram_ring_window_bounds_memory():
    h = Histogram("h", [10.0], window=8)
    for v in range(100):
        h.observe(float(v))
    assert len(h._ring) == 8
    assert h.count == 100                      # cumulative count keeps going
    assert sorted(h._ring) == [92.0, 93.0, 94.0, 95.0, 96.0, 97.0, 98.0, 99.0]
    assert h.percentile(50) == 96.0            # percentiles see the window


def test_registry_idempotent_and_typed():
    m = MetricsRegistry()
    c1 = m.counter("x_total")
    c2 = m.counter("x_total")
    assert c1 is c2
    with pytest.raises(AssertionError):
        m.gauge("x_total")                     # name already a counter


def test_disabled_registry_hands_out_nulls():
    m = MetricsRegistry(enabled=False)
    c, g, h = m.counter("c"), m.gauge("g"), m.histogram("h", [1.0])
    assert c is NULL_COUNTER and g is NULL_GAUGE and h is NULL_HISTOGRAM
    c.inc(5), g.set(3), h.observe(1.0)         # all swallowed
    assert NULL_COUNTER.value == 0 and NULL_GAUGE.value == 0
    assert NULL_HISTOGRAM.count == 0
    assert m.snapshot() == {}


def test_prometheus_text_format():
    m = MetricsRegistry()
    m.counter("req_total", "requests").inc(3)
    m.gauge("depth").set(7)
    h = m.histogram("lat_ms", [1.0, 10.0], "latency")
    h.observe(0.5), h.observe(5.0), h.observe(100.0)
    text = m.prometheus_text()
    assert "# TYPE req_total counter" in text
    assert "req_total 3" in text
    assert "depth 7" in text
    assert 'lat_ms_bucket{le="1"} 1' in text
    assert 'lat_ms_bucket{le="10"} 2' in text
    assert 'lat_ms_bucket{le="+Inf"} 3' in text
    assert "lat_ms_count 3" in text


def test_jsonl_sink(tmp_path):
    m = MetricsRegistry()
    m.counter("a_total").inc()
    path = str(tmp_path / "metrics.jsonl")
    m.write_jsonl(path, extra={"run": 1})
    m.counter("a_total").inc()
    m.write_jsonl(path, extra={"run": 2})
    lines = [json.loads(l) for l in open(path)]
    assert [l["run"] for l in lines] == [1, 2]
    assert [l["metrics"]["a_total"]["value"] for l in lines] == [1, 2]
    assert all("ts" in l for l in lines)


# ---------------------------------------------------------------------------
# SLO accounting
# ---------------------------------------------------------------------------

def test_lifecycle_derived_intervals():
    r = Lifecycle(rid=0, submit_tick=2, submit_wall=1.0)
    r.admit_tick, r.admit_wall = 4, 1.1
    r.first_tick, r.first_wall = 6, 1.25
    r.done_tick, r.done_wall = 10, 1.45
    r.tokens = 5
    assert r.queue_wait_ticks() == 2
    assert r.ttft_ticks() == 4
    assert r.ttft_ms() == pytest.approx(250.0)
    assert r.tpot_ticks() == pytest.approx(1.0)
    assert r.tpot_ms() == pytest.approx(50.0)
    assert r.e2e_ticks() == 8
    assert r.e2e_ms() == pytest.approx(450.0)
    one = Lifecycle(rid=1, tokens=1, submit_tick=0)
    assert one.tpot_ticks() is None            # TPOT needs >= 2 tokens


def test_slo_summary_percentiles_match_numpy():
    tr = SLOTracker()
    ttfts = [1, 1, 2, 3, 5, 8, 13, 21]
    for i, t in enumerate(ttfts):

        class _R:                              # duck-typed request
            rid, sample_idx, prompt, out = i, 0, np.zeros(4), [0, 0]
        tr.on_submit(_R, 0)
        tr.on_admit(_R, 0)
        tr.on_first_token(_R, t)
        tr.on_finish(_R, t + 2)
    s = tr.summary(targets={"ttft_ticks": 5})
    for q in (50, 95, 99):
        assert s["ttft_ticks"][f"p{q}"] == pytest.approx(
            float(np.percentile(np.asarray(ttfts, float), q)), abs=1e-3)
    assert s["slo_attainment"]["ttft_ticks<=5"] == pytest.approx(5 / 8)
    assert s["requests"] == len(ttfts)


def test_disabled_tracker_holds_no_state():
    tr = SLOTracker(enabled=False)

    class _R:
        rid, sample_idx, prompt, out = 0, 0, np.zeros(2), [1]
    tr.on_submit(_R, 0), tr.on_finish(_R, 3)
    assert tr.records == {} and tr.finished == []


# ---------------------------------------------------------------------------
# tick tracing
# ---------------------------------------------------------------------------

def _validate_chrome_trace(obj):
    """The trace-event-format subset chrome://tracing / Perfetto load."""
    assert isinstance(obj, dict) and "traceEvents" in obj
    events = obj["traceEvents"]
    assert isinstance(events, list) and events
    for ev in events:
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["ph"] in ("X", "B", "E", "i", "I", "C", "M")
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        if ev["ph"] != "M":
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        if "args" in ev:
            json.dumps(ev["args"])             # JSON-serializable args
    return events


def test_trace_schema_and_tick_anatomy(rng, obs_engine, tmp_path):
    cfg, eng = obs_engine
    obs = ServeObservability(metrics=True, trace=True)
    sched, fin = _serve(eng, _mk_requests(rng, cfg, 6), obs=obs)
    assert len(fin) == 6
    path = tmp_path / "trace.json"
    obs.tracer.write(str(path))
    events = _validate_chrome_trace(json.loads(path.read_text()))
    ticks = [e for e in events if e["name"] == "tick"]
    assert len(ticks) == sched.ticks
    # per-tick anatomy: every phase span nests inside some tick span
    phases = {"admission", "pack_budget_split", "dispatch", "postprocess"}
    seen = {e["name"] for e in events}
    assert phases <= seen, f"missing phase spans: {phases - seen}"
    for ev in events:
        if ev["ph"] == "X" and ev["name"] in phases:
            assert any(t["ts"] <= ev["ts"] and
                       ev["ts"] + ev["dur"] <= t["ts"] + t["dur"] + 1e-3
                       for t in ticks), f"{ev['name']} span outside any tick"
    # lifecycle instants: every request finished inside a trace
    finishes = [e for e in events if e["name"] == "finish"]
    assert len(finishes) == 6


def test_disabled_tracer_is_inert():
    tr = TickTracer(enabled=False)
    with tr.span("x", a=1):
        pass
    tr.instant("y")
    tr.counter("z", v=1)
    assert tr.events == []


# ---------------------------------------------------------------------------
# the no-Heisenberg contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "stochastic"])
def test_observability_does_not_change_tokens(rng, obs_engine, tmp_path,
                                              sampled):
    """Identical request streams with obs fully on vs off must produce
    bitwise-identical token streams — metrics read host scalars between
    device steps and never enter jitted code, and the engine's spans sit
    around its jitted calls, never inside them."""
    cfg, eng = obs_engine
    seed = int(rng.integers(0, 2**31))
    r1 = np.random.default_rng(seed)
    r2 = np.random.default_rng(seed)
    obs = ServeObservability(metrics=True, trace=True, check_leaks=True)
    _, fin_on = _serve(eng, _mk_requests(r1, cfg, 8, sampled), obs=obs)
    _, fin_off = _serve(eng, _mk_requests(r2, cfg, 8, sampled), obs=None)
    assert len(fin_on) == len(fin_off) == 8
    for rid in fin_off:
        np.testing.assert_array_equal(
            np.asarray(fin_on[rid].out), np.asarray(fin_off[rid].out),
            err_msg=f"req {rid}: observability changed the tokens "
                    f"({'stochastic' if sampled else 'greedy'})")
    # and the run actually observed something, down to the engine's spans
    snap = obs.metrics.snapshot()
    assert snap["sched_requests_finished_total"]["value"] == 8
    assert snap["sched_ticks_total"]["value"] > 0
    assert obs.slo.summary()["requests"] == 8
    spans = {e["name"] for e in obs.tracer.events}
    assert {"engine.inputs", "engine.launch", "engine.outputs"} <= spans


def test_null_obs_is_shared_and_stateless(rng, obs_engine):
    cfg, eng = obs_engine
    sched, fin = _serve(eng, _mk_requests(rng, cfg, 3))
    assert sched.obs is NULL_OBS
    assert NULL_OBS.metrics.snapshot() == {}
    assert NULL_OBS.tracer.events == []
    assert NULL_OBS.slo.records == {}


# ---------------------------------------------------------------------------
# drain-time leak sweep
# ---------------------------------------------------------------------------

def test_drain_leak_check_clean(rng, obs_engine):
    cfg, eng = obs_engine
    obs = ServeObservability(metrics=True, check_leaks=True)
    sched, fin = _serve(eng, _mk_requests(rng, cfg, 5), obs=obs)
    assert len(fin) == 5                       # check_leaks did not trip
    assert obs.metrics.snapshot()["kv_leak_findings"]["value"] == 0


def test_drain_leak_check_fires_on_injected_leak(rng, obs_engine):
    cfg, eng = obs_engine
    obs = ServeObservability(metrics=True)
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=3, bucket_min=8, kv_layout="paged", block_size=8,
        prefill_chunk=8, check_leaks=True), obs=obs)
    for r in _mk_requests(rng, cfg, 3):
        sched.submit(r)
    # inject a leak: a page vanishes from the free list without being
    # mapped anywhere (the shape of a lost-page bug)
    sched.pool._free_blocks.pop()
    with pytest.raises(RuntimeError, match="leaked"):
        sched.run()
    assert obs.metrics.snapshot()["kv_leak_findings"]["value"] >= 1
    report = sched.drain_check()
    assert any("leaked pages" in msg for msg in report)


def test_leak_report_refcount_desync(rng, obs_engine):
    cfg, eng = obs_engine
    sched, _ = _serve(eng, _mk_requests(rng, cfg, 3))
    pool = sched.pool
    assert pool.leak_report() == []
    pool._refs[1] += 1                         # corrupt a refcount
    assert any("refcounts out of sync" in m for m in pool.leak_report())
    pool._refs[1] -= 1
    assert pool.leak_report() == []


# ---------------------------------------------------------------------------
# scheduler-level accounting sanity
# ---------------------------------------------------------------------------

def test_pool_gauges_track_pages(rng, obs_engine):
    cfg, eng = obs_engine
    obs = ServeObservability(metrics=True)
    sched, fin = _serve(eng, _mk_requests(rng, cfg, 6), obs=obs)
    snap = obs.metrics.snapshot()
    # drained: everything claimed was freed, nothing left mapped
    assert snap["kv_pages_used"]["value"] == 0
    assert (snap["kv_pages_claimed_total"]["value"]
            == snap["kv_pages_freed_total"]["value"] > 0)
    assert snap["kv_pages_peak"]["value"] == sched.pool.peak_pages > 0
    assert snap["kv_pages_free"]["value"] == sched.pool.free_blocks()
    # one-dispatch-per-tick, now visible per kind
    assert (snap["engine_dispatch_serve_step_total"]["value"]
            == snap["sched_ticks_total"]["value"])


def test_slo_ttft_matches_external_measurement(rng, obs_engine):
    """The tracker's tick-based TTFT equals the external
    submit-tick/first-token-tick bookkeeping the benchmark used to
    hand-roll (same hooks, same tick counter)."""
    cfg, eng = obs_engine
    obs = ServeObservability(metrics=True)
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=3, bucket_min=8, kv_layout="paged", block_size=8,
        prefill_chunk=8), obs=obs)
    submit_tick, first_tick = {}, {}
    reqs = _mk_requests(rng, cfg, 6)
    for r in reqs:
        r.on_token = lambda req, tok: first_tick.setdefault(
            req.rid, sched.ticks)
    for r in reqs:
        submit_tick[r.rid] = sched.ticks
        sched.submit(r)
    sched.run()
    want = sorted(first_tick[rid] - submit_tick[rid] for rid in first_tick)
    got = sorted(r.ttft_ticks() for r in obs.slo.finished)
    assert got == want
