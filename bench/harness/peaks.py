"""The chip's published peaks, keyed by ``device_kind`` (``peaks.json``).
A device missing from the table is an error, never a default."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parents[1] / "peaks.json"


def of(device_kind: str) -> dict:
    devices = json.loads(TABLE.read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {TABLE.name}; known: {sorted(devices)}")
    return devices[device_kind]


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """(seconds, bound): the larger of operations over peak FLOP/s and
    bytes over peak HBM bandwidth, and which of the two it is."""
    tc = flops / peak["flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
