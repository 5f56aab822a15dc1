#!/usr/bin/env python3
"""Serve one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload smollm-360m.chat --seed 7 --seconds 51 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file and
a traffic mix. Set-up draws the weights and tables from the seed on the
device, builds the program's engine and scheduler and runs each of the
cell's serve_step programs once (from JAX's persistent compilation cache
at ``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is set).
The window then serves the mix for ``--seconds`` on the host clock. With
``--trace 1`` the profiler records a slice of the window and the result
holds the cell's per-layer metrics instead of its end-to-end ones.

After the window, a sample of the greedy requests it finished is checked
against the plain reference (``bench/check.py``). The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``breakdown`` when traced) and the
compared numbers with their limits under ``checks``; the same numbers
are the last lines of standard error. Without a TPU, or with fewer chips
than the cell asks for, it prints no result and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up runs from process start

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
TRACE_SECONDS = 10.0            # length of the traced slice


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def nearest_rank(values, q: float) -> float:
    """The q-quantile as the smallest sample with at least q of the
    samples at or below it."""
    xs = sorted(values)
    return xs[max(0, math.ceil(q * len(xs)) - 1)]


def end_to_end(win, drain_end: float) -> dict:
    """Every end-to-end number the window gives; the cell reports those
    ``BENCHMARK.json`` lists for it."""
    out = {}
    due = sorted((s for s in win.served.values() if s.spec.due is not None),
                 key=lambda s: s.t_due)
    if due:
        # a request with no first token counts at the drain's end: its
        # time to first token is at least that
        ttft = [((s.times[0] if s.times else drain_end) - s.t_due) * 1e3
                for s in due]
        out["ttft_p50_ms"] = statistics.median(ttft)
        out["ttft_mean_ms"] = statistics.fmean(ttft)
        out["ttft_p90_ms"] = nearest_rank(ttft, 0.90)
        log(f"ttft: {len(due)} requests due in the window, "
            f"{sum(1 for s in due if s.times)} with a first token; "
            f"median {out['ttft_p50_ms']:.1f} ms, mean "
            f"{out['ttft_mean_ms']:.1f} ms, p90 {out['ttft_p90_ms']:.1f} ms")
        log(f"ttft per request, in due order (ms): "
            f"{[round(t, 1) for t in ttft]}")
    gaps = []
    out_tokens = 0
    for s in win.served.values():
        ts = [t for t in s.times if t <= win.t_end]
        out_tokens += len(ts)
        gaps.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
    if gaps:
        out["itl_p95_ms"] = nearest_rank(gaps, 0.95)
        out["itl_p99_ms"] = nearest_rank(gaps, 0.99)
        out["itl_mean_ms"] = statistics.fmean(gaps)
        log(f"itl: {len(gaps)} gaps; median {statistics.median(gaps):.1f} "
            f"ms, mean {out['itl_mean_ms']:.2f} ms, p90 "
            f"{nearest_rank(gaps, 0.9):.1f} ms, p95 {out['itl_p95_ms']:.1f}"
            f" ms, p99 {out['itl_p99_ms']:.1f} ms; over 200 ms: "
            f"{sum(g > 200 for g in gaps) / len(gaps) * 100:.2f}%")
    prompt_tokens = sum(d.live - d.decode for d in win.dispatches
                        if d.t_end <= win.t_end)
    out["tokens_per_s"] = (prompt_tokens + out_tokens) / win.seconds
    log(f"tokens in the window: {prompt_tokens} prompt (as prefill chunks "
        f"ran) + {out_tokens} output over {win.seconds:.3f} s")
    return out


def traced_layers(bench, cell, tmpdir, win, system, peaks):
    """Per-layer metrics, ``busy_s``/``window_s`` and the breakdown from
    the profiler's trace of the slice."""
    from bench import readings, spec
    from bench import trace as T
    from bench.costs import Dims
    from bench.driver import WINDOW_SPAN
    files = glob.glob(os.path.join(tmpdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        log("trace: the profiler wrote no trace")
        return {}, {}, None
    tr = T.load(files[0])
    dims = Dims.of(system.cfg, system.sched_cfg.block_size)
    r = readings.build(tr, win.dispatches, dims, peaks, system.slots,
                       system.chunk)
    if r is None:
        log("trace: no traced slice found")
        return {}, {}, None
    log(f"trace: {len(tr.devices)} device plane(s), slice {r.window_s:.3f} s,"
        f" {len(r.ticks)} dispatches matched, "
        f"{sum(1 for t in r.ticks if t.step_ns > 0)} with a device step")
    metrics = {}
    for m in spec.per_layer(bench, cell["name"]):
        value = spec.metric_reader(ROOT, m["name"]).read(r)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    got = readings.ragged_bound(r)
    if got is not None:
        log(f"ragged kernel: roofline {got[0]:.6f} s of {got[1]:.6f} s "
            f"kernel time, {got[2]}-bound")
    busy = r.busy_s()
    dev = {"busy_s": busy, "window_s": r.window_s} if busy is not None else {}
    breakdown = {"device_ops": T.top_ops(tr, r.lo, r.hi),
                 "idle_gaps": T.idle_by_host(tr, r.thread, r.lo, r.hi,
                                             (WINDOW_SPAN,))}
    return metrics, dev, breakdown


def run(root: Path, name: str, seed: int, seconds: float, traced: bool,
        devices, peaks, t_start: float) -> dict:
    from bench import check, device, loadgen, spec, system as S
    from bench.driver import drive
    bench = spec.load_benchmark(root)
    cell = spec.workload(bench, name)
    cfile = spec.config(root, bench, cell["config"])
    mix = spec.traffic(root, cell["traffic"])
    t_built = time.perf_counter()
    system = S.build(cfile, seed)
    tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")
    obs = None
    if traced:
        from repro.obs import ServeObservability
        obs = ServeObservability(metrics=False, trace=True,
                                 jax_profile_dir=tmp.name)
    t_pool = time.perf_counter()
    sched = system.scheduler(obs)
    t_warm = time.perf_counter()
    S.warm_up(system, sched, mix.get("sampled_share", 0.0) > 0)
    setup_s = time.perf_counter() - t_start
    log(f"setup: {setup_s:.3f} s: JAX and devices {t_built - t_start:.3f},"
        f" weights and engine {t_pool - t_built:.3f}, scheduler and pool "
        f"{t_warm - t_pool:.3f}, warm-up {t_start + setup_s - t_warm:.3f}")

    n_tasks = cfile["serve"]["tasks"]
    vocab = system.cfg.vocab_size
    plan = source = None
    if mix["arrival"] == "poisson":
        rate = spec.offered_rate(root, name)
        plan = loadgen.poisson_plan(mix, rate, seconds, seed, n_tasks, vocab)
        log(f"traffic {mix['name']}: poisson {rate} req/s, {len(plan)} "
            f"requests due in {seconds} s")
    elif mix["arrival"] == "backlog":
        source = loadgen.backlog(mix, seed, n_tasks, vocab)
        log(f"traffic {mix['name']}: backlog of {mix['backlog']} waiting")
    else:
        raise spec.SpecError(f"unknown arrival kind {mix['arrival']!r}")
    profile = None
    if traced:
        start = max(0.0, seconds / 2 - TRACE_SECONDS / 2)
        profile = {"start": start, "stop": start + TRACE_SECONDS,
                   "tracer": sched.obs.tracer}
    win = drive(system, sched, mix, seconds, plan=plan, source=source,
                profile=profile)
    drain_end = time.perf_counter()
    dev = device.describe(devices)
    late = win.lateness()
    if len(late):
        log(f"generator: submitted {len(late)} requests late by p50 "
            f"{nearest_rank(late, 0.5) * 1e3:.2f} ms, p99 "
            f"{nearest_rank(late, 0.99) * 1e3:.2f} ms, max "
            f"{late.max() * 1e3:.2f} ms")
    log(f"window: {win.seconds:.3f} s, {len(win.dispatches)} dispatches, "
        f"{win.compiles} compile events inside, {sched.preemptions} "
        f"preemptions, peak bytes {dev['memory_peak_bytes']}")

    # a request fails when the system refuses, aborts or quarantines it,
    # or (Poisson traffic) when it never gets a first token
    requests = list(win.served.values())
    attempted = len(plan) if plan is not None else len(requests)
    failed = sum(1 for s in requests
                 if s.req.state in ("shed", "aborted", "quarantined")
                 or (plan is not None and not s.times))

    e2e = end_to_end(win, drain_end)
    metrics, breakdown = {}, None
    if traced:
        metrics, extra, breakdown = traced_layers(bench, cell, tmp.name, win,
                                                  system, peaks)
        dev.update(extra)
    else:
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        for m in spec.end_to_end(bench, name):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    tmp.cleanup()

    # the program's state goes before the reference runs beside the weights
    picked = check.sample(win.served, int(mix["check_requests"]), seed)
    del sched, obs, win
    system.engine = None
    gc.collect()
    k = int(mix["output_len"]["max"])
    t0 = time.perf_counter()
    per_req = check.gaps(system.params, system.table, cfile, picked, k)
    max_gap = max(per_req) if per_req else None     # nothing compared
    limit = float(cfile["correct"]["max_gap"])
    log(f"reference: {len(picked)} requests, "
        f"{sum(len(s.req.out) for s in picked)} served tokens compared in "
        f"{time.perf_counter() - t0:.2f} s; per request max gap "
        f"{[round(g, 5) for g in per_req]}")
    checks = {"max_gap": {"value": max_gap, "limit": limit},
              "failed_requests": {"value": failed, "limit": 0}}
    correct = max_gap is not None and max_gap <= limit and failed == 0
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for key, c in checks.items():
        log(f"check {key}: {c['value']} (limit {c['limit']})")
    return result


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401  (the system under test)
        from bench import device, spec
    except ImportError as e:
        log(f"bench: not inside a checkout of the program ({e})")
        return 2
    try:
        bench = spec.load_benchmark(ROOT)
        cell = spec.workload(bench, args.workload)
    except spec.SpecError as e:
        log(f"bench: {e}")
        return 2
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devices, peaks = device.require(int(cell["chips"]),
                                        ROOT / "bench" / "peaks.json")
    except (device.NoDevice, device.UnknownDevice) as e:
        log(f"bench: {e}")
        return 1
    log(f"device: {devices[0].device_kind} x {len(devices)} "
        f"({devices[0].platform})")
    result = run(ROOT, args.workload, args.seed, args.seconds,
                 bool(args.trace), devices, peaks, T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
