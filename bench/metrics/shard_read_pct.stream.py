"""Share of the window covered by the data plane's ``data.shard`` spans
(shard reads on the prefetch thread, on the host clock); moves
``stream_rows_per_s``."""
from harness.layers import union_s


def read(r):
    shards = r.spans_named("data.shard")
    fits = r.spans_named("fit")
    if not shards or not fits:
        return None
    t0 = min(f["ts"] for f in fits)
    t1 = max(f["ts"] + f["dur"] for f in fits)
    return 100.0 * union_s(shards, t0, t1) / ((t1 - t0) / 1e6)
