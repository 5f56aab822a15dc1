"""The engine-span readers on a synthesised trace
(data/engine_trace.pbtxt): two decode ticks in the traced slice, each
with its engine.inputs / engine.launch / engine.outputs spans, its step
program and its watchdog program, and a third tick that ends after the
slice."""
from pathlib import Path

import numpy as np
import pytest

from bench import readings, spec, trace as T
from bench.costs import Dims
from bench.driver import Dispatch

ROOT = Path(__file__).parents[2]
DATA = Path(__file__).parent / "data"
DIMS = Dims(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4, d_ff=16,
            vocab=32, block_size=4)
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
NEW = ("engine_host_ms.chat", "engine_readback_ms.chat")


def _reads(name, n):
    from jax.profiler import ProfileData
    tr = T.from_profile(ProfileData.from_text_proto(
        (DATA / name).read_text()))
    ds = [Dispatch(t_end=0.0, live=2, decode=2, width=3, profiled=True,
                   token_rows=np.array([0, 1, 0]),
                   token_pos=np.array([5, 2, -1]),
                   logit_idx=np.array([0, 1, 0])) for _ in range(n)]
    return readings.build(tr, ds, DIMS, PEAKS, slots=3, chunk=4)


@pytest.fixture(scope="module")
def reads():
    return _reads("engine_trace.pbtxt", 3)


def _read(name, r):
    return spec.metric_reader(ROOT, name).read(r)


def test_engine_host_time(reads):
    # inputs + launch: 500 + 400 ns, then 700 + 600 ns; the third tick
    # ends after the slice
    assert _read("engine_host_ms.chat", reads) == pytest.approx(
        (900 + 1300) / 2 * 1e-6)


def test_engine_readback(reads):
    # outputs end 500 ns after the first tick's last op (the watchdog's,
    # at 9400), 1100 ns after the second's (at 23800)
    assert _read("engine_readback_ms.chat", reads) == pytest.approx(
        (500 + 1100) / 2 * 1e-6)


def test_three_way_split_of_a_tick(reads):
    # host work outside dispatch, as before: 10000 - 8000, 14000 - 12000
    assert _read("sched_host_ms.chat", reads) == pytest.approx(2000e-6)


def test_watchdog_is_not_step_time(reads):
    # jit_finite_rows does not match the step program's pattern
    assert [t.step_ns for t in reads.ticks] == [5000.0, 8600.0, 1000.0]
    assert _read("serve_step_ms.decode", reads) == pytest.approx(
        (5000 + 8600 + 1000) / 3 * 1e-6)


def test_idle_gaps_name_engine_spans(reads):
    idle = dict(T.idle_by_host(reads.trace, reads.thread, reads.lo,
                               reads.hi, ("bench.window",)))
    assert idle == pytest.approx({"tick": 7200e-9, "engine.outputs": 2700e-9,
                                  "(no host span)": 5000e-9})


@pytest.mark.parametrize("name", NEW)
def test_program_without_engine_spans_reads_nothing(name):
    # a program whose engine opens no spans (small_trace.pbtxt) reads
    # nothing, as does a trace with no device plane
    assert _read(name, _reads("small_trace.pbtxt", 2)) is None
    r = _reads("engine_trace.pbtxt", 3)
    r.trace.ops = {}
    assert _read(name, r) is None


@pytest.mark.parametrize("name", NEW)
def test_entries_match_readers(name):
    bench = spec.load_benchmark(ROOT)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    mod = spec.metric_reader(ROOT, name)
    assert (entry["layer"], entry["unit"], entry["source"], entry["moves"]) \
        == (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES)
    assert entry["workloads"] == ["smollm-360m.chat"]
