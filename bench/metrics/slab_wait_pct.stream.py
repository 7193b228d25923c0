"""Share of the streamed fits' window the slab loop spent inside the slab
iterator's ``next()`` (prefetch waits, carry copies, the label check):
the ``wait_s`` the program's ``dsvrg.pass`` spans add up on the host
clock, over the first ``fit`` span's start to the last one's end. Moves
``stream_rows_per_s``."""


def read(r):
    passes = r.spans_named("dsvrg.pass")
    fits = r.spans_named("fit")
    if not passes or not fits:
        return None
    t0 = min(f["ts"] for f in fits)
    t1 = max(f["ts"] + f["dur"] for f in fits)
    secs = sum(p["args"]["wait_s"] for p in passes)
    return 100.0 * secs / ((t1 - t0) / 1e6)
