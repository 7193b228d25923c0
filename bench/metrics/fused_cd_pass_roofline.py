"""The fused CD pass's share of its roofline (device trace; work from
``bench/cost/fused_cd_pass.py``); moves ``fit_s``.

Every pass of a level is one kernel call over its K partitions of m
rows; the passes per level come from the fits' reports. The trace's call
count must equal the passes counted, or the reading is void.
"""
from harness.layers import roofline_pct


def read(r):
    c = r.counters
    passes = c.get("fit.passes")
    if not passes:
        return None
    M, d, p, L = (c["fit.rows"], c["fit.features"], c["fit.p"],
                  c["fit.levels"])

    def work(mod, calls):
        parts = []
        for fit in passes:
            for i, n_pass in enumerate(fit):
                K = p ** (L - i)
                parts.append((*mod.cost(K, M // K, d, c["fit.block"]),
                              n_pass))
        return parts if sum(n for *_, n in parts) == calls else None

    return roofline_pct(r, "fused_cd_pass", work)
