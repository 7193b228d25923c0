"""Share of the fits' time spent in the replicated tail: the union of the
``cascade.level`` spans whose ``layout`` is ``replicated`` inside the
``fit`` spans, over the ``fit`` spans' own time (program spans, host
clock; each level span ends in a host sync). Every device solves those
levels whole, so this is the time a sharded tail could divide. Moves
``fit_s``.

No reading where the program's level spans carry no ``layout``."""
from harness.layers import union_s


def read(r):
    fits = r.spans_named("fit")
    levels = [e for e in r.spans_named("cascade.level")
              if "layout" in e.get("args", {})]
    if not fits or not levels:
        return None
    tail = [e for e in levels if e["args"]["layout"] == "replicated"]
    total = sum(f["dur"] for f in fits) / 1e6
    inside = sum(union_s(tail, f["ts"], f["ts"] + f["dur"]) for f in fits)
    return 100.0 * inside / total
