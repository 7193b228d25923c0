"""Device idle share of the traced fit window (device trace); moves
``fit_s``."""
from harness.layers import idle_pct


def read(r):
    return idle_pct(r)
