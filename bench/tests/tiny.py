"""A tiny copy of the benchmark for CPU tests: the repository's bench
data plus a small configuration, mixes and cells, in a directory of its
own, driven in-process with the device check stubbed."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "name": "tiny",
    "registry": "smollm-360m",
    "source": "a test size of smollm-360m's architecture",
    "config": {"hidden_act": "silu", "hidden_size": 64,
               "intermediate_size": 128, "num_attention_heads": 4,
               "num_hidden_layers": 2, "num_key_value_heads": 2,
               "max_position_embeddings": 128, "rms_norm_eps": 1e-05,
               "rope_theta": 10000.0, "tie_word_embeddings": True,
               "vocab_size": 256},
    "reduced": [],
    "serve": {"dtype": "bfloat16", "tasks": 2, "slots": 3, "block_size": 16,
              "max_len": 128, "prefill_chunk": 32},
    "correct": {"max_gap": 0.01},
}
TINY_CHAT = {
    "arrival": "poisson",
    "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                   "min": 4, "max": 80},
    "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.6,
                   "min": 2, "max": 24},
    "sampled_share": 0.25, "temperature": 0.8, "top_p": 0.95,
    "drain_seconds": 60, "check_requests": 4,
}
TINY_BACKLOG = {
    "arrival": "backlog", "backlog": 4, "block": 4,
    "prompt_len": {"dist": "lognormal", "median": 60, "sigma": 0.25,
                   "min": 40, "max": 90},
    "output_len": {"dist": "uniform", "min": 2, "max": 6},
    "sampled_share": 0.0, "drain_seconds": 60, "check_requests": 4,
}
TINY_CHAT_RATE = 3.0


def make(root: Path) -> Path:
    """Copy BENCHMARK.json and bench/ under ``root`` and add the tiny
    configuration with a chat cell and a backlog cell."""
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(TINY_CONFIG))
    (root / "bench" / "traffic" / "tiny-chat.json").write_text(
        json.dumps(TINY_CHAT))
    (root / "bench" / "traffic" / "tiny-backlog.json").write_text(
        json.dumps(TINY_BACKLOG))
    (root / "bench" / "cells" / "tiny.tiny-chat.json").write_text(
        json.dumps({"rate_rps": TINY_CHAT_RATE}))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    for mix in ("tiny-chat", "tiny-backlog"):
        bench["workloads"].append({"name": f"tiny.{mix}", "config": "tiny",
                                   "traffic": mix, "chips": 1, "why": "test"})
    # the tiny cells report what smollm-360m's chat and backlog cells do
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "smollm-360m.chat" in m.get("workloads", ()):
            m["workloads"].append("tiny.tiny-chat")
        if "smollm-360m.longprompt" in m.get("workloads", ()):
            m["workloads"].append("tiny.tiny-backlog")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def peaks() -> dict:
    return json.loads((REPO / "bench" / "peaks.json").read_text())[
        "TPU v5 lite"]


def run_cell(root: Path, name: str, seed: int, seconds: float,
             traced: bool = False) -> dict:
    """The harness's ``run`` on the CPU: the device check is skipped and
    the CPU device stands in for the chip."""
    import time
    from bench import run as harness
    return harness.run(root, name, seed, seconds, traced,
                       jax.devices()[:1], peaks(), time.perf_counter())
