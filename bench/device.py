"""The chips a run measures, and their peaks from ``bench/peaks.json``.

A run needs a TPU and as many chips as its cell asks for; there is no
fallback to the CPU. A device kind missing from the table is an error,
not a default.
"""
from __future__ import annotations

import json
from pathlib import Path


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


class UnknownDevice(KeyError):
    """The device kind has no row in the table of peaks."""


def peaks_for(kind: str, path: Path) -> dict:
    table = json.loads(path.read_text())
    if kind not in table:
        raise UnknownDevice(f"no peaks for device kind {kind!r} in {path}; "
                            f"known: {sorted(table)}")
    return table[kind]


def require(chips: int, peaks_path: Path):
    """(devices, peaks): the first ``chips`` TPU devices and their row."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoDevice(f"JAX found no devices: {e}") from e
    if devices[0].platform != "tpu":
        raise NoDevice(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoDevice(f"needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips], peaks_for(devices[0].device_kind, peaks_path)


def describe(devices) -> dict:
    """Platform, kind, count and the fullest chip's peak bytes in use."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
