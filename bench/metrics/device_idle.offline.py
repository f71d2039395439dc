"""Device idle share of the traced window, in percent: 1 - the union of
device-op intervals over the window's length."""

from bench.lib.readers import idle_pct


def read(rec):
    return idle_pct(rec)
