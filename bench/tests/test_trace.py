"""The trace reduction on a small synthesised trace (data/small_trace.pbtxt):
two serve_step ticks, their device ops, and the host spans around them."""
from pathlib import Path

import numpy as np
import pytest

from bench import readings, trace as T
from bench.costs import Dims, ragged_attention_need, step_flops
from bench.driver import Dispatch

DATA = Path(__file__).parent / "data" / "small_trace.pbtxt"
DIMS = Dims(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4, d_ff=16,
            vocab=32, block_size=4)
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
# tick 1: decode-only, width 3 (slots); tick 2: a chunk tick of width
# slots - 1 + chunk = 6 (chunk 4)
ROUTES = [
    (np.array([0, 1, 0]), np.array([5, 2, -1]), np.array([0, 1, 0])),
    (np.array([0, 2, 2, 2, 2, 0]), np.array([6, 0, 1, 2, 3, -1]),
     np.array([0, 0, 4])),
]


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    return T.from_profile(ProfileData.from_text_proto(DATA.read_text()))


@pytest.fixture(scope="module")
def reads(trace):
    ds = [Dispatch(t_end=0.0, live=int((p >= 0).sum()), decode=0,
                   width=len(p), profiled=True, token_rows=r, token_pos=p,
                   logit_idx=li) for r, p, li in ROUTES]
    return readings.build(trace, ds, DIMS, PEAKS, slots=3, chunk=4)


def test_lines_and_window(trace, reads):
    assert trace.devices == ["/device:TPU:0"]
    assert len(trace.modules["/device:TPU:0"]) == 3
    assert (reads.lo, reads.hi) == (0.0, 25000.0)


def test_busy_union_and_idle_share(trace, reads):
    # ops cover [1000, 6000), [6500, 7000), [10000, 20000) of [0, 25000]
    assert T.union(trace.ops["/device:TPU:0"], 0, 25000) == 15500
    assert reads.busy_s() == pytest.approx(15500e-9)
    assert readings.device_idle(reads) == pytest.approx(38.0)


def test_program_time_split_by_width(reads):
    # the finiteness-check module at 6500 lies outside both dispatches
    assert [(t.width, t.step_ns, t.kernel_ns) for t in reads.ticks] == [
        (3, 5000.0, 2000.0), (6, 10000.0, 6000.0)]
    for name, want in (("serve_step_ms.decode", 5000e-6),
                       ("serve_step_ms.chunk", 10000e-6)):
        from bench import spec
        mod = spec.metric_reader(Path(__file__).parents[2], name)
        assert mod.read(reads) == pytest.approx(want)


def test_scheduler_host_time(reads):
    from bench import spec
    mod = spec.metric_reader(Path(__file__).parents[2], "sched_host_ms.chat")
    # ticks of 7600 and 12200 ns around dispatches of 6700 and 11600 ns
    assert mod.read(reads) == pytest.approx((900 + 600) / 2 * 1e-6)


def test_kernel_roofline_names_its_bound(reads):
    need, secs, bound = readings.ragged_bound(reads)
    want = 0.0
    for rows, pos, _ in ROUTES:
        f, b = ragged_attention_need(DIMS, rows, pos)
        want += max(f / PEAKS["bf16_flops_per_s"],
                    b / PEAKS["hbm_bytes_per_s"])
    assert need == pytest.approx(want)
    assert secs == pytest.approx(8000e-9)
    assert bound == "memory"        # 1 GB/s against 1 TFLOP/s
    assert readings.ragged_roofline(reads) == pytest.approx(
        100 * want / 8000e-9)


def test_step_mfu(reads):
    flops = sum(step_flops(DIMS, r, p, li) for r, p, li in ROUTES)
    assert readings.step_mfu(reads) == pytest.approx(
        100 * flops / (15000e-9 * PEAKS["bf16_flops_per_s"]))


def test_breakdown(trace, reads):
    ops = dict(T.top_ops(trace, reads.lo, reads.hi))
    # by own time: the while loop holds the second tick's ops
    assert ops == pytest.approx({
        "%ragged_paged_attention.6 = bf16[1315,3,64]": 8000e-9,
        "%fusion.7 = bf16[8,960]": 7000e-9, "%reduce.2 = pred[8]": 500e-9,
        "%while.4": 0.0})
    idle = dict(T.idle_by_host(trace, reads.thread, reads.lo, reads.hi,
                               ("bench.window",)))
    # [0, 1000) and [6000, 6500) fall inside the first dispatch; the rest
    # of the idle time lies between and after the ticks
    assert idle == pytest.approx({"dispatch": 1500e-9,
                                  "(no host span)": 8000e-9})


def test_no_slice_reads_nothing():
    assert readings.build(T.Trace(), [], DIMS, PEAKS, 3, 4) is None
    empty = readings.Readings(DIMS, PEAKS, 3, 4, T.Trace(), [], 0.0, 1.0)
    for fn in (readings.step_mfu, readings.ragged_roofline,
               readings.device_idle):
        assert fn(empty) is None
