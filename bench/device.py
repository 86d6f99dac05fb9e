"""The device a run is on, and the chip's peaks (``peaks.json``)."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def check_device(devices, chips: int = 1) -> dict:
    """The run's device report; raises :class:`NoChip` unless JAX's first
    device is a TPU and there are ``chips`` of them.  (Adapted from
    ``check_device`` in the repo root's ``chip_smoke.py``.)"""
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"JAX's first device is {dev.platform!r} "
                     f"({dev.device_kind}); the benchmark runs only on a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return describe(devices[:chips])


def describe(devices) -> dict:
    """``platform``, ``kind`` and ``count`` as JAX reports them."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``; 0 where the
    backend keeps no memory statistics (the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def peaks_for(kind: str, path: Path = PEAKS) -> dict:
    """The peak table row of ``kind``; a device missing from the table is
    an error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path.name}; "
                       f"known: {sorted(table)}")
    return table[kind]
