#!/usr/bin/env python3
"""Find a Poisson mix's knee on the chip: one process, several rates.

    python3 bench/sweep.py --workload smollm-360m.chat --rates 0.5,1,1.5 \
        --seconds 40 --seed 1

For each rate, the same scheduler (emptied: what the last rate left is
cancelled) serves the cell's mix for ``--seconds`` (open loop, as the
benchmark's window does, with the window's drain), and the
script prints the requests due and served, the time to first token, and
how the queue of waiting requests moved: its mean over the first and the
last third of the window and its slope over the last two thirds. The
knee is the highest rate at which the queue does not grow across the
window. The chosen cell rate (about 0.8 of the knee) goes into the
cell's ``bench/cells/<workload>.json`` by hand, with these readings in
PERF.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import numpy as np
    from bench import device, loadgen, spec, system as S
    from bench.driver import drive
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = spec.load_benchmark(ROOT)
    cell = spec.workload(bench, args.workload)
    devices, _ = device.require(int(cell["chips"]), ROOT / "bench" /
                                "peaks.json")
    cfile = spec.config(ROOT, bench, cell["config"])
    mix = dict(spec.traffic(ROOT, cell["traffic"]), drain_seconds=10)
    system = S.build(cfile, args.seed)
    sched = system.scheduler()
    S.warm_up(system, sched, mix.get("sampled_share", 0.0) > 0)
    depth = []
    inner = sched.step

    def step():
        inner()
        depth.append((time.perf_counter(), len(sched.queue)))
    sched.step = step
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        depth.clear()
        plan = loadgen.poisson_plan(mix, rate, args.seconds, args.seed,
                                    cfile["serve"]["tasks"],
                                    system.cfg.vocab_size)
        win = drive(system, sched, mix, args.seconds, plan=plan)
        t = np.array([a - win.t0 for a, _ in depth])
        q = np.array([b for _, b in depth], float)
        third = args.seconds / 3
        inside = t <= args.seconds
        first = q[inside & (t < third)]
        last = q[inside & (t >= 2 * third)]
        late = inside & (t >= third)
        slope = float(np.polyfit(t[late], q[late], 1)[0]) if late.sum() > 2 \
            else 0.0
        served = list(win.served.values())
        ttft = sorted((s.times[0] - s.t_due) for s in served if s.times)
        row = {"rate_rps": rate, "due": len(plan),
               "first_tokens": len(ttft),
               "finished": sum(1 for s in served
                               if s.req.state == "finished"),
               "ttft_p50_ms": ttft[len(ttft) // 2] * 1e3 if ttft else None,
               "ttft_mean_ms": float(np.mean(ttft)) * 1e3 if ttft else None,
               "ttft_p90_ms": (ttft[max(0, int(np.ceil(0.9 * len(ttft))) - 1)]
                               * 1e3 if ttft else None),
               "queue_first_third": float(first.mean()) if len(first) else 0.0,
               "queue_last_third": float(last.mean()) if len(last) else 0.0,
               "queue_slope_per_s": slope,
               "ticks": len(win.dispatches)}
        print(json.dumps(row), flush=True)
        rows.append(row)
        # the next rate starts from an empty scheduler: cancel what is left
        for s in served:
            if s.req.state not in ("finished", "aborted"):
                sched.abort(s.spec.rid)
    print(json.dumps({"workload": args.workload, "sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
