"""The numbers that decide ``correct`` and their limits.

A cell's limits live in ``bench/limits/<cell>.json``: for each number
compared, the limit and the two readings it was set from (see
``PERF.md``). A number passes when it is at most its limit.
"""
from __future__ import annotations

import json
from pathlib import Path

LIMITS = Path(__file__).resolve().parents[1] / "limits"


class Check:
    """One number compared with its limit (``value <= limit`` passes)."""

    def __init__(self, name: str, value: float, limit: float):
        self.name, self.value, self.limit = name, float(value), float(limit)

    @property
    def ok(self) -> bool:
        return self.value <= self.limit        # NaN fails

    def line(self) -> str:
        return (f"check {self.name}: {self.value!r} limit {self.limit!r} "
                f"{'ok' if self.ok else 'FAIL'}")


def limits(cell_name: str) -> dict:
    """name -> limit for the cell's compared numbers."""
    doc = json.loads((LIMITS / f"{cell_name}.json").read_text())
    return {k: float(v["limit"]) for k, v in doc.items()}
