"""Share of the program's ``fit`` span not covered by its ``cascade.level``
spans: partitioning, merges, warm-start scaling and the artifact build,
on the host clock (each level span ends in a host sync). Moves ``fit_s``."""
from harness.layers import union_s


def read(r):
    fits = r.spans_named("fit")
    if not fits:
        return None
    total = sum(f["dur"] for f in fits) / 1e6
    levels = r.spans_named("cascade.level")
    inside = sum(union_s(levels, f["ts"], f["ts"] + f["dur"]) for f in fits)
    return 100.0 * (total - inside) / total
