"""The fused CD pass's share of its roofline on a mesh (device trace;
work from ``bench/cost/fused_cd_pass.py``); moves ``fit_s``.

Every device makes one kernel call a pass of its own while loop, so the
trace holds, at each level, the sum of the ``cascade.level`` span's
``passes_by_device``; that count must match, or the reading is void.
Each level's work is credited once: a sharded level's call does its
device's ``K / n_dev`` partitions, and a replicated level's work,
``cost(K, m, d)`` a pass, is split evenly over its ``n_dev`` copies. So
with devices that pass evenly every level counts ``cost(K, m, d)`` a
pass, whatever ``n_dev`` is, and a replicated tail's repeats read as a
lower share.
"""
from harness.layers import roofline_pct


def levels_of(spans) -> list:
    """``(K, m, layout, passes_by_device)`` of each level span that
    carries them."""
    return [(a["K"], a["m"], a["layout"], a["passes_by_device"])
            for a in (e.get("args", {}) for e in spans
                      if e["name"] == "cascade.level")
            if "passes_by_device" in a]


def work(mod, levels, d: int, B: int) -> list:
    """``(flops, bytes, calls)`` for the calls of ``levels``."""
    parts = []
    for K, m, layout, by_dev in levels:
        n_dev = len(by_dev)
        if layout == "sharded":
            flops, nbytes = mod.cost(K // n_dev, m, d, B)
        else:
            flops, nbytes = (v / n_dev for v in mod.cost(K, m, d, B))
        parts.append((flops, nbytes, sum(by_dev)))
    return parts


def read(r):
    c = r.counters
    levels = levels_of(r.spans)
    if not levels or "fit.features" not in c:
        return None

    def counted(mod, calls):
        parts = work(mod, levels, c["fit.features"], c["fit.block"])
        return parts if sum(n for *_, n in parts) == calls else None

    return roofline_pct(r, "fused_cd_pass", counted)
