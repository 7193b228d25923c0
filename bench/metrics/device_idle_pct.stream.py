"""Device idle share of the traced streaming window (device trace); moves
``stream_rows_per_s``."""
from harness.layers import idle_pct


def read(r):
    return idle_pct(r)
