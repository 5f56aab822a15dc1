#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip, in one
process: the program's ``max_gap`` over many seeds, and the control's
(the reference in float8, bench/reference.py) on the same requests.

    python3 bench/calibrate.py --workload smollm-360m.chat \
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --seconds 30

Each seed draws its own weights and traffic, serves a window of
``--seconds`` at the cell's own load through the harness's driver, and
compares the same sample of finished greedy requests that a benchmark
run compares. The limit in the configuration file is then set between
the program's largest reading and the control's smallest (PERF.md keeps
both). The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import check, device, loadgen, spec, system as S
    from bench.driver import drive
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = spec.load_benchmark(ROOT)
    cell = spec.workload(bench, args.workload)
    device.require(int(cell["chips"]), ROOT / "bench" / "peaks.json")
    cfile = spec.config(ROOT, bench, cell["config"])
    mix = spec.traffic(ROOT, cell["traffic"])
    n_tasks, k = cfile["serve"]["tasks"], int(mix["output_len"]["max"])
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        system = S.build(cfile, seed)
        sched = system.scheduler()
        S.warm_up(system, sched, mix.get("sampled_share", 0.0) > 0)
        plan = source = None
        if mix["arrival"] == "poisson":
            rate = spec.offered_rate(ROOT, cell["name"])
            plan = loadgen.poisson_plan(mix, rate, args.seconds, seed,
                                        n_tasks, system.cfg.vocab_size)
        else:
            source = loadgen.backlog(mix, seed, n_tasks,
                                     system.cfg.vocab_size)
        win = drive(system, sched, mix, args.seconds, plan=plan,
                    source=source)
        picked = check.sample(win.served, int(mix["check_requests"]), seed)
        del sched, win
        system.engine = None
        gc.collect()
        prog = check.gaps(system.params, system.table, cfile, picked, k)
        ctl = check.gaps(system.params, system.table, cfile, picked, k,
                         quant="fp8")
        row = {"seed": seed, "requests": len(picked),
               "tokens": sum(len(s.req.out) for s in picked),
               "program_max_gap": max(prog), "control_max_gap": max(ctl),
               "program_per_request": prog, "control_per_request": ctl,
               "seconds": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
        rows.append(row)
        del system, picked
        gc.collect()
    print(json.dumps({
        "workload": args.workload,
        "program_max_gap": max(r["program_max_gap"] for r in rows),
        "control_min_gap": min(r["control_max_gap"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
