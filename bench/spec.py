"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by name:

* ``bench/configs/<config>.json``: the configuration as it is run;
* ``bench/traffic/<mix>.json``: the traffic mix (lengths, arrivals,
  sampling), the same for every configuration that serves it;
* ``bench/cells/<workload>.json``: what belongs to one cell alone: an
  open-loop cell's fixed offered rate (``rate_rps``), from its
  configuration's knee (``BENCHMARK.json`` entries take no other keys);
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric.

A new cell, mix or metric is a new file and a new entry in
``BENCHMARK.json``; no existing file changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import List


class SpecError(ValueError):
    """A name in ``BENCHMARK.json`` resolves to nothing, or a data file is
    malformed."""


def load_benchmark(root: Path) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def workload(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SpecError(f"unknown workload {name!r}; known: "
                    f"{[c['name'] for c in bench['workloads']]}")


def config(root: Path, bench: dict, name: str) -> dict:
    for entry in bench["configs"]:
        if entry["name"] == name:
            return json.loads((root / entry["file"]).read_text())
    raise SpecError(f"unknown configuration {name!r}")


def traffic(root: Path, name: str) -> dict:
    """The mix ``bench/traffic/<name>.json``."""
    path = root / "bench" / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    mix["name"] = name
    return mix


def offered_rate(root: Path, cell: str) -> float:
    """An open-loop cell's fixed offered rate (requests/s), from
    ``bench/cells/<cell>.json``."""
    path = root / "bench" / "cells" / f"{cell}.json"
    if not path.is_file():
        raise SpecError(f"no offered rate for cell {cell!r} at {path}")
    return float(json.loads(path.read_text())["rate_rps"])


def metric_reader(root: Path, name: str) -> ModuleType:
    """Import ``bench/metrics/<name>.py`` by path (metric names hold dots,
    so they are not importable module names)."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader for per-layer metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for attr in ("LAYER", "UNIT", "SOURCE", "MOVES", "read"):
        if not hasattr(mod, attr):
            raise SpecError(f"metric reader {path} lacks {attr}")
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def end_to_end(bench: dict, cell: str) -> List[dict]:
    return [m for m in bench["end_to_end"] if _applies(m, cell)]


def per_layer(bench: dict, cell: str) -> List[dict]:
    return [m for m in bench["per_layer"] if _applies(m, cell)]

