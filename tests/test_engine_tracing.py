"""The engine's place in the program's own trace.

  * ``ServeEngine`` records its host phases (``engine.inputs``,
    ``engine.launch``, ``engine.outputs``) on the scheduler's tracer, in
    order and nested in the scheduler's ``dispatch`` span; under an
    untraced scheduler it records nothing;
  * an enabled ``TickTracer`` span enters any collecting profiler session
    under its own name, with its arguments as event stats;
  * the programs a tick launches have stable names: the step's match the
    benchmark's step-program pattern, the watchdog's does not;
  * a tick's ``dispatch`` span counts the AoT bias rows its step gathers
    (``aot_rows``) where a fused table is served;
  * ``mixed_step`` and the draw carry the named scopes an operator reads
    in a chip profile (``aot_bias``, ``attention``, ``mlp``, ``logits``,
    ``sampling``);
  * the watchdog's ``finite_rows`` launch is counted, once per tick.
"""
import dataclasses
import glob
import re
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import aot as A
from repro.kernels.decode_attention import ragged_plan
from repro.models.model import Model, ModelOptions
from repro.obs import NULL_TRACER, ServeObservability
from repro.obs.tracing import TickTracer
from repro.serve.engine import ServeConfig, ServeEngine, finite_rows
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import (ContinuousScheduler, Request,
                                   SchedulerConfig)

REPO = Path(__file__).resolve().parents[1]
ENGINE_SPANS = ["engine.inputs", "engine.launch", "engine.outputs"]
SCOPES = ["aot_bias", "attention", "mlp", "logits", "sampling"]
SLOTS, BLOCK, MAX_LEN = 3, 8, 48


@pytest.fixture(scope="module")
def engine(tiny_lm):
    cfg, model, params = tiny_lm
    tasks = [A.random_fused(cfg, params["embed"]["tok"], seed=s)
             for s in range(2)]
    return cfg, ServeEngine(model, params, ServeConfig(max_len=MAX_LEN),
                            fused_tasks=tasks)


def _requests(cfg, n, sampled=False, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(
        rid=i, prompt=rng.integers(0, cfg.vocab_size, int(rng.integers(3, 17)))
        .astype(np.int32), task_id=i % 2,
        max_new_tokens=int(rng.integers(1, 7)),
        sampling=(SamplingParams(temperature=0.8, top_p=0.9, seed=7 + i)
                  if sampled else None)) for i in range(n)]


def _serve(eng, reqs, obs=None):
    sched = ContinuousScheduler(eng, SchedulerConfig(
        num_slots=SLOTS, bucket_min=8, kv_layout="paged", block_size=BLOCK,
        prefill_chunk=8), obs=obs)
    for r in reqs:
        sched.submit(r)
    sched.run()
    return sched


def _inside(ev, outer):
    return (outer["ts"] <= ev["ts"] and
            ev["ts"] + ev["dur"] <= outer["ts"] + outer["dur"] + 1e-3)


# ---------------------------------------------------------------------------
# engine spans
# ---------------------------------------------------------------------------

def test_engine_spans_nest_in_dispatch_in_order(engine):
    cfg, eng = engine
    obs = ServeObservability(metrics=False, trace=True)
    d0 = eng.dispatches
    _serve(eng, _requests(cfg, 5), obs)
    spans = [e for e in obs.tracer.events if e["ph"] == "X"]
    dispatches = [e for e in spans if e["name"] == "dispatch"]
    engine_spans = [e for e in spans if e["name"].startswith("engine.")]
    assert len(dispatches) == eng.dispatches - d0 > 0
    # each dispatch holds exactly the three phases, in order, back to back
    for d in dispatches:
        inner = sorted((e for e in engine_spans if _inside(e, d)),
                       key=lambda e: e["ts"])
        assert [e["name"] for e in inner] == ENGINE_SPANS
        for a, b in zip(inner, inner[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-3
    # and no engine span falls outside a dispatch
    assert len(engine_spans) == 3 * len(dispatches)


@pytest.fixture(scope="module")
def pallas_served(tiny_lm):
    """Requests served by an engine whose mixed_step runs the Pallas
    ragged kernel, traced: the config, the dispatch spans, each dispatch's
    packed (token_rows, token_pos) and the scheduler."""
    cfg, _, params = tiny_lm
    model = Model(cfg, ModelOptions(chunk_q=8, chunk_kv=8, mlstm_chunk=4,
                                    attn_impl="pallas"))
    eng = ServeEngine(model, params, ServeConfig(max_len=MAX_LEN),
                      fused_tasks=[A.random_fused(cfg, params["embed"]["tok"],
                                                  seed=s) for s in range(2)])
    packed = []
    serve_step = eng.serve_step

    def spy(tokens, token_rows, token_pos, *rest):
        packed.append((np.array(token_rows), np.array(token_pos)))
        return serve_step(tokens, token_rows, token_pos, *rest)

    eng.serve_step = spy
    obs = ServeObservability(metrics=False, trace=True)
    sched = _serve(eng, _requests(cfg, 3), obs)
    spans = [e for e in obs.tracer.events
             if e["ph"] == "X" and e["name"] == "dispatch"]
    return cfg, spans, packed, sched


def test_dispatch_span_counts_the_kernel_walk(pallas_served):
    """Each dispatch span carries what the Pallas ragged kernel walks for
    that tick's packed list (``ragged_plan``): its runs, and its KV steps
    over all KV heads. A tick of one token per slot is one run per
    token."""
    cfg, spans, packed, sched = pallas_served
    assert len(spans) == len(packed) > 0
    one_per_slot = 0
    for ev, (rows, pos) in zip(spans, packed):
        runs, steps, _ = ragged_plan(
            rows, pos, block_size=BLOCK, kv_heads=cfg.num_kv_heads,
            q_per_kv=cfg.num_heads // cfg.num_kv_heads,
            head_dim=cfg.head_dim, npages=sched.pool.block_tables.shape[1])
        assert ev["args"]["attn_runs"] == runs
        assert ev["args"]["attn_kv_steps"] == steps
        live = rows[pos >= 0]
        if len(set(live)) == len(live):
            one_per_slot += 1
            assert runs == len(live)
    assert one_per_slot > 0


def test_dispatch_span_counts_query_rows(pallas_served):
    """Each dispatch span carries the query rows the kernel's programs
    hold in a layer. Both packed widths here (3 decode rows; 2 decode
    rows plus an 8-token chunk) fit one query block of exactly the
    width, so the rows are KV heads x width x query heads per KV head:
    every head of every packed token, live or dead."""
    cfg, spans, packed, _ = pallas_served
    widths = set()
    for ev, (_, pos) in zip(spans, packed):
        args = ev["args"]
        widths.add(args["width"])
        assert args["width"] == len(pos)
        assert args["tokens"] == int((pos >= 0).sum())
        assert args["attn_q_rows"] == cfg.num_heads * args["width"]
    assert widths == {SLOTS, SLOTS - 1 + 8}


def test_dispatch_span_has_no_walk_off_the_kernel(engine):
    """Where mixed_step's attention runs in XLA (the fixture's chunked
    attention), the dispatch spans count no kernel walk."""
    cfg, eng = engine
    obs = ServeObservability(metrics=False, trace=True)
    _serve(eng, _requests(cfg, 2), obs)
    spans = [e for e in obs.tracer.events
             if e["ph"] == "X" and e["name"] == "dispatch"]
    assert spans
    for ev in spans:
        assert "attn_runs" not in ev["args"]
        assert "attn_kv_steps" not in ev["args"]
        assert "attn_q_rows" not in ev["args"]
        assert ev["args"]["tokens"] > 0


def test_dispatch_span_counts_aot_rows(engine):
    """Each dispatch span of a fused multi-task engine carries the bias
    rows its step gathers ahead of the layers: every layer's row for
    every packed token, live or dead."""
    cfg, eng = engine
    obs = ServeObservability(metrics=False, trace=True)
    _serve(eng, _requests(cfg, 4), obs)
    spans = [e for e in obs.tracer.events
             if e["ph"] == "X" and e["name"] == "dispatch"]
    assert spans
    for ev in spans:
        assert ev["args"]["aot_rows"] == cfg.num_layers * ev["args"]["width"]


def test_dispatch_span_has_no_aot_rows_without_a_table(tiny_lm):
    """An engine that serves no fused AoT table gathers no bias rows, and
    its dispatch spans say nothing of them."""
    cfg, model, params = tiny_lm
    eng = ServeEngine(model, params, ServeConfig(max_len=MAX_LEN))
    obs = ServeObservability(metrics=False, trace=True)
    _serve(eng, [dataclasses.replace(r, task_id=0)
                 for r in _requests(cfg, 2)], obs)
    spans = [e for e in obs.tracer.events
             if e["ph"] == "X" and e["name"] == "dispatch"]
    assert spans
    assert all("aot_rows" not in ev["args"] for ev in spans)


def test_untraced_scheduler_detaches_the_engine_tracer(engine):
    """An engine reused under an untraced scheduler records nothing, on
    the tracer it had before or on the shared null tracer."""
    cfg, eng = engine
    obs = ServeObservability(metrics=False, trace=True)
    _serve(eng, _requests(cfg, 2), obs)
    assert eng.tracer is obs.tracer
    n = len(obs.tracer.events)
    _serve(eng, _requests(cfg, 3, seed=1))
    assert eng.tracer is NULL_TRACER
    assert len(obs.tracer.events) == n
    assert NULL_TRACER.events == []


def test_span_enters_a_profiler_it_did_not_start(tmp_path):
    """A profiler session opened elsewhere (here jax.profiler itself)
    records an enabled tracer's spans under their own names, with their
    arguments as event stats."""
    from jax.profiler import ProfileData
    tr = TickTracer(enabled=True)           # no profile dir of its own
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("tick", tick=3, width=8):
            with tr.span("engine.launch"):
                jax.block_until_ready(jax.numpy.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("tick", "engine.launch"):
                    found[ev.name] = dict(ev.stats)
    assert found == {"tick": {"tick": 3, "width": 8}, "engine.launch": {}}
    assert [e["name"] for e in tr.events if e["ph"] == "X"] == [
        "engine.launch", "tick"]


# ---------------------------------------------------------------------------
# program names and scopes
# ---------------------------------------------------------------------------

def _step_program_pattern():
    sys.path.insert(0, str(REPO))
    try:
        from bench.readings import STEP_PROGRAM
    finally:
        sys.path.remove(str(REPO))
    return STEP_PROGRAM


def _shapes(eng, model):
    npages = MAX_LEN // BLOCK
    return eng.serve_step_shapes(
        model.paged_cache_specs(SLOTS * npages + 1, BLOCK), SLOTS, npages,
        SLOTS)


@pytest.mark.parametrize("program, is_step", [
    ("serve_step_greedy", True), ("serve_step_sampled", True),
    ("finite_rows", False)])
def test_program_names(engine, tiny_lm, program, is_step):
    """Programs are named jit_<function> in the device trace, followed by
    the fingerprint: the step's two traces match the benchmark's
    step-program pattern, the watchdog's does not."""
    cfg, eng = engine
    if program == "finite_rows":
        lowered = finite_rows.lower(jax.ShapeDtypeStruct(
            (SLOTS, cfg.vocab_size), np.float32))
    else:
        fn = {"serve_step_greedy": eng._serve_greedy,
              "serve_step_sampled": eng._serve_sampled}[program]
        lowered = fn.lower(*_shapes(eng, tiny_lm[1]))
    module = re.match(r"module @(\S+)", lowered.as_text()).group(1)
    assert module == f"jit_{program}"
    in_trace = f"{module}(5282668227497003359)"
    assert bool(re.search(_step_program_pattern(), in_trace)) is is_step


@pytest.fixture(scope="module")
def scope_names(engine, tiny_lm):
    """Every component of the name stacks of the sampled step's ops."""
    cfg, eng = engine
    text = eng._serve_sampled.lower(*_shapes(eng, tiny_lm[1])).as_text(
        debug_info=True)
    return {part for loc in re.findall(r'loc\("([^"]*)"', text)
            for part in loc.split("/")}


@pytest.mark.parametrize("scope", SCOPES)
def test_step_carries_named_scopes(scope_names, scope):
    assert scope in scope_names


# ---------------------------------------------------------------------------
# the watchdog, counted
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "stochastic"])
def test_watchdog_counted_once_per_tick(engine, sampled):
    cfg, eng = engine
    obs = ServeObservability(metrics=True)
    d0, w0 = eng.dispatches, eng.finite_rows_dispatches
    sched = _serve(eng, _requests(cfg, 5, sampled), obs)
    snap = obs.metrics.snapshot()
    ticks = snap["sched_ticks_total"]["value"]
    assert ticks == sched.ticks > 0
    assert snap["engine_dispatch_finite_rows_total"]["value"] == ticks
    assert snap["engine_dispatch_serve_step_total"]["value"] == ticks
    # dispatches still counts the step alone: one a tick
    assert eng.finite_rows_dispatches - w0 == eng.dispatches - d0 == ticks
