"""The control: the plain reference in the program's place, computed in
float8 (the nearest precision below the configuration's bfloat16), must
fail the limit that sound runs of the program pass. At this test's size;
the readings at the cells' own sizes come from bench/calibrate.py on the
chip."""
import pytest

import tiny
from bench import check, spec, system as S
from bench.driver import drive
from bench import loadgen


@pytest.mark.parametrize("seed", [1, 2, 2**35 + 9])
def test_control_fails_where_the_program_passes(tmp_path, seed):
    root = tiny.make(tmp_path)
    bench = spec.load_benchmark(root)
    cfile = spec.config(root, bench, "tiny")
    mix = spec.traffic(root, "tiny-chat")
    system = S.build(cfile, seed)
    sched = system.scheduler()
    S.warm_up(system, sched, True)
    plan = loadgen.poisson_plan(mix, 3.0, 3.0, seed, 2, 256)
    win = drive(system, sched, mix, 3.0, plan=plan)
    picked = check.sample(win.served, 4, seed)
    k = mix["output_len"]["max"]
    program = max(check.gaps(system.params, system.table, cfile, picked, k))
    control = max(check.gaps(system.params, system.table, cfile, picked, k,
                             quant="fp8"))
    limit = cfile["correct"]["max_gap"]
    assert program <= limit < control, (program, limit, control)
