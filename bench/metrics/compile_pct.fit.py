"""Share of the fits' window under the program's ``compile.*`` spans
(tracing, lowering, backend compiles and persistent-cache loads, as JAX
reports them) inside the ``fit`` spans, on the host clock; the window
runs from the first ``fit`` span's start to the last one's end. Moves
``fit_s``.

No reading where the program records no compile spans at all; but a
program whose spans carry ids has the compile listener, and if it
compiled nothing in its fits it reads 0."""
from harness.layers import union_s

COMPILE = ("compile.trace", "compile.lower", "compile.backend",
           "compile.cache_load")


def read(r):
    fits = r.spans_named("fit")
    if not fits:
        return None
    compiles = [e for e in r.spans if e["name"] in COMPILE]
    if not compiles and "id" not in fits[0].get("args", {}):
        return None
    t0 = min(f["ts"] for f in fits)
    t1 = max(f["ts"] + f["dur"] for f in fits)
    inside = sum(union_s(compiles, f["ts"], f["ts"] + f["dur"])
                 for f in fits)
    return 100.0 * inside / ((t1 - t0) / 1e6)
