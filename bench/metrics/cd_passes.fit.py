"""Fused CD passes per fit, summed over the levels (``FitReport.passes``):
a count that repeats exactly for a seed; moves ``fit_s``."""


def read(r):
    passes = r.counters.get("fit.passes")
    if not passes:
        return None
    return sum(sum(p) for p in passes) / len(passes)
