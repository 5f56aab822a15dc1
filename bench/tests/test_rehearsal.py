"""The harness end to end on the CPU at a tiny size (bench/tests/tiny.py):
the device check is skipped in the test, and the CPU stands in for the
chip. Pallas kernels run in interpret mode."""
import json
import subprocess
import sys

import numpy as np
import pytest

import tiny
from bench import device, loadgen, spec

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("bench"))


def test_result_line(root):
    res = tiny.run_cell(root, "tiny.tiny-chat", 2**33 + 17, 3.0)
    # the compared numbers come last, under a key of their own
    assert list(res) == KEYS
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 9            # 3 req/s over 3 s
    assert set(res["metrics"]) == {"setup_s", "itl_p95_ms"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["checks"]["max_gap"]["value"] <= res["checks"]["max_gap"][
        "limit"]
    json.dumps(res, allow_nan=False)


def test_traced_result_line(root):
    res = tiny.run_cell(root, "tiny.tiny-chat", 5, 3.0, traced=True)
    assert list(res) == KEYS[:5] + ["breakdown", "checks"]
    # the CPU has no device plane: the device metrics find nothing to read
    # and stay out of the line; the scheduler's host spans are read
    assert set(res["metrics"]) == {"sched_host_ms.chat"}
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_backlog_cell(root):
    res = tiny.run_cell(root, "tiny.tiny-backlog", 3, 2.0)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "tokens_per_s"}


def test_added_traffic_file_is_found_by_name(root):
    """A new mix and a new cell are new files and a new entry: no file
    that is there changes."""
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    mix = dict(tiny.TINY_CHAT, prompt_len={"dist": "uniform", "min": 40,
                                           "max": 60})
    (root / "bench" / "traffic" / "tiny-chat-long.json").write_text(
        json.dumps(mix))
    (root / "bench" / "cells" / "tiny.tiny-chat-long.json").write_text(
        json.dumps({"rate_rps": 4.0}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.tiny-chat-long",
                               "config": "tiny", "traffic": "tiny-chat-long",
                               "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert all(p.read_bytes() == b for p, b in before.items())
    assert spec.traffic(root, "tiny-chat-long")["prompt_len"]["min"] == 40
    res = tiny.run_cell(root, "tiny.tiny-chat-long", 11, 2.0)
    assert res["correct"] is True and res["attempted"] == 8


def test_same_seed_same_requests():
    mix = tiny.TINY_CHAT
    a = loadgen.poisson_plan(mix, 3.0, 10.0, 2**40 + 3, 2, 256)
    b = loadgen.poisson_plan(mix, 3.0, 10.0, 2**40 + 3, 2, 256)
    c = loadgen.poisson_plan(mix, 3.0, 10.0, 4, 2, 256)
    key = lambda p: [(s.due, s.prompt.tolist(), s.task, s.max_new,  # noqa
                      s.temperature, s.sample_seed) for s in p]
    assert key(a) == key(b)
    assert key(a) != key(c)
    # another seed offers the same set of requests and gaps in another
    # order, with other tokens and tasks
    work = lambda p: sorted((len(s.prompt), s.max_new, s.temperature)  # noqa
                            for s in p)
    # the gaps, with the last from the last arrival to the window's close
    gaps = lambda p: sorted(np.round(np.diff(  # noqa
        [0.0] + [s.due for s in p] + [10.0]), 9))
    assert work(a) == work(c) and len(a) == 30
    assert [(len(s.prompt), s.max_new) for s in a] != \
        [(len(s.prompt), s.max_new) for s in c]
    assert gaps(a) == gaps(c)
    assert [s.prompt.tolist() for s in a] != [s.prompt.tolist() for s in c]
    assert sorted(s.task for s in a) == [0] * 15 + [1] * 15
    assert all(0 < s.due < 10.0 for s in a)
    assert sum(s.temperature > 0 for s in a) == round(0.25 * 30)
    assert sum(s.temperature > 0 for s in c) == round(0.25 * 30)
    back = loadgen.backlog(tiny.TINY_BACKLOG, 9, 2, 256)
    sizes = [len(next(back).prompt) for _ in range(8)]
    assert sorted(sizes[:4]) == sorted(sizes[4:])


def test_no_tpu_no_result():
    """``bench/run.py`` on the CPU: non-zero exit, no result line."""
    p = subprocess.run([sys.executable, str(tiny.REPO / "bench" / "run.py"),
                        "--workload", "smollm-360m.chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, timeout=300,
                       env={**__import__("os").environ,
                            "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs a TPU" in p.stderr


def test_bare_directory_no_result(tmp_path):
    """Only BENCHMARK.json and bench/: no program, no result."""
    import shutil
    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(tiny.REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "smollm-360m.chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout


def test_unknown_device_kind_raises():
    with pytest.raises(device.UnknownDevice):
        device.peaks_for("TPU v99", tiny.REPO / "bench" / "peaks.json")
    assert device.peaks_for("TPU v5 lite", tiny.REPO / "bench" /
                            "peaks.json")["bf16_flops_per_s"] == 197e12
