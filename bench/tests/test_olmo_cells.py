"""The olmo-1b cells: each resolves its configuration, traffic, rate
and per-layer readers by name; both rehearse on the CPU at a tiny OLMo
size (non-parametric LayerNorm, full KV heads) through the harness's
``run``; and ``ragged_row_fill.chat`` reads the ragged kernel's query-row
fill from the dispatch spans' arguments in a synthesised trace
(data/row_fill_trace.pbtxt)."""
import json
import os
from pathlib import Path

import numpy as np
import pytest

import tiny
from bench import readings, spec, trace as T
from bench.costs import Dims
from bench.driver import Dispatch

ROOT = Path(__file__).parents[2]
DATA = Path(__file__).parent / "data"
CELLS = {"olmo-1b.chat": ("chat", "itl_p95_ms"),
         "olmo-1b.longprompt": ("longprompt", "tokens_per_s")}
FILL = "ragged_row_fill.chat"
# test_reference.py's tiny OLMo: 4 full heads of 16, non-parametric LN
OLMO = {"hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_hidden_layers": 2,
        "num_key_value_heads": 4, "max_position_embeddings": 128,
        "layer_norm_eps": 1e-05, "rope_theta": 10000.0,
        "tie_word_embeddings": True, "vocab_size": 256}
DIMS = Dims(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4, d_ff=16,
            vocab=32, block_size=4)
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_cell_resolves_by_name(name):
    mix, e2e = CELLS[name]
    bench = spec.load_benchmark(ROOT)
    cell = spec.workload(bench, name)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmo-1b", mix, 1)
    entry = next(c for c in bench["configs"] if c["name"] == "olmo-1b")
    assert entry["reduced"] == []
    cfile = spec.config(ROOT, bench, "olmo-1b")
    assert cfile["registry"] == "olmo-1b" and cfile["reduced"] == []
    assert cfile["config"]["num_key_value_heads"] == 16
    assert spec.traffic(ROOT, mix)["arrival"] == (
        "poisson" if mix == "chat" else "backlog")
    if mix == "chat":
        assert spec.offered_rate(ROOT, name) * 51 >= 24
    else:
        with pytest.raises(spec.SpecError):
            spec.offered_rate(ROOT, name)
    assert [m["name"] for m in spec.end_to_end(bench, name)] == [
        "setup_s", e2e]
    layers = spec.per_layer(bench, name)
    assert len(layers) >= 4
    for m in layers:
        mod = spec.metric_reader(ROOT, m["name"])
        assert (m["layer"], m["unit"], m["source"], m["moves"]) == (
            mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES)
        assert m["moves"] == e2e


def test_row_fill_entry():
    bench = spec.load_benchmark(ROOT)
    entry = bench["per_layer"][-1]
    assert entry["name"] == FILL
    assert entry["workloads"] == ["smollm-360m.chat", "olmo-1b.chat"]
    assert (entry["layer"], entry["source"], entry["unit"]) == (
        "kernels", "program_span", "%")


@pytest.fixture(scope="module")
def olmo_root(tmp_path_factory):
    """The repository's bench data with olmo-1b's configuration cut to
    the tiny OLMo size and the chat and longprompt mixes to tiny lengths;
    cells, rate file and readers are the repository's own."""
    root = tiny.make(tmp_path_factory.mktemp("olmo"))
    cfile = json.loads((ROOT / "bench" / "configs" / "olmo-1b.json")
                       .read_text())
    cfile["config"] = OLMO
    cfile["serve"] = dict(tiny.TINY_CONFIG["serve"])
    (root / "bench" / "configs" / "olmo-1b.json").write_text(
        json.dumps(cfile))
    for mix, tiny_mix in (("chat", tiny.TINY_CHAT),
                          ("longprompt", tiny.TINY_BACKLOG)):
        (root / "bench" / "traffic" / f"{mix}.json").write_text(
            json.dumps(tiny_mix))
    return root


@pytest.mark.parametrize("name,seconds", [("olmo-1b.chat", 4.0),
                                          ("olmo-1b.longprompt", 2.0)])
def test_olmo_cell_rehearsal(olmo_root, name, seconds):
    res = tiny.run_cell(olmo_root, name, 2**35 + 9, seconds)
    assert res["correct"] is True and res["failed"] == 0, res["checks"]
    assert set(res["metrics"]) == {"setup_s", CELLS[name][1]}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    json.dumps(res, allow_nan=False)


def test_olmo_chat_traced_rehearsal(olmo_root):
    res = tiny.run_cell(olmo_root, "olmo-1b.chat", 13, 3.0, traced=True)
    # the CPU has no device plane: the device metrics, and the row fill
    # (which needs the kernel's ops on a device), find nothing to read
    assert set(res["metrics"]) == {"sched_host_ms.chat"}


def _reads(name):
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto((DATA / name).read_text())
    ds = [Dispatch(t_end=0.0, live=2, decode=2, width=3, profiled=True,
                   token_rows=np.array([0, 1, 0]),
                   token_pos=np.array([5, 2, -1]),
                   logit_idx=np.array([0, 1, 0])) for _ in range(3)]
    return pd, readings.build(T.from_profile(pd), ds, DIMS, PEAKS, slots=3,
                              chunk=4)


def _written(tmp_path, monkeypatch, name, run="bench-trace-a1"):
    """The profile of ``name`` where bench/run.py's traced run leaves it:
    a temporary directory's plugins/profile/<run>/<host>.xplane.pb."""
    from jax.profiler import ProfileData
    d = tmp_path / run / "plugins" / "profile" / "r1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace((DATA / name).read_text()))
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    return d / "host.xplane.pb"


def test_row_fill_reads_the_dispatch_spans(tmp_path, monkeypatch):
    # live tokens x 2 heads over attn_q_rows: (2 + 5 + 3) * 2 / (6 + 12 + 6)
    _written(tmp_path, monkeypatch, "row_fill_trace.pbtxt")
    _, r = _reads("row_fill_trace.pbtxt")
    got = spec.metric_reader(ROOT, FILL).read(r)
    assert got == pytest.approx(100.0 * 20 / 24)


def test_row_fill_takes_its_own_profile(tmp_path, monkeypatch):
    """Of the trace directories present, the reader takes the profile
    whose traced slice is the one read, newest first."""
    own = _written(tmp_path, monkeypatch, "row_fill_trace.pbtxt")
    pd, r = _reads("row_fill_trace.pbtxt")
    other = _written(tmp_path, monkeypatch, "row_fill_trace.pbtxt",
                     "bench-trace-b2")
    os.utime(own, (1, 1))           # the other one is newer
    mod = spec.metric_reader(ROOT, FILL)
    r.lo += 1.0                     # a slice neither profile holds
    assert mod.read(r) is None
    r.lo -= 1.0
    assert mod.read(r) == pytest.approx(100.0 * 20 / 24)
    other.unlink()
    assert mod.read(r) == pytest.approx(mod.fill(pd, r.lo, r.hi, 2))


def test_row_fill_reads_nothing_without_query_rows(tmp_path, monkeypatch):
    # a program whose dispatch spans carry no attn_q_rows (the parent's;
    # or attention off the Pallas kernel)
    _written(tmp_path, monkeypatch, "engine_trace.pbtxt")
    _, r = _reads("engine_trace.pbtxt")
    assert any(t.kernel_ns > 0 for t in r.ticks)
    assert spec.metric_reader(ROOT, FILL).read(r) is None


def test_row_fill_reads_nothing_without_the_kernel_on_a_device(
        tmp_path, monkeypatch):
    _written(tmp_path, monkeypatch, "row_fill_trace.pbtxt")
    _, r = _reads("row_fill_trace.pbtxt")
    for t in r.ticks:
        t.kernel_ns = 0.0
    assert spec.metric_reader(ROOT, FILL).read(r) is None
