"""Share of the traced window in which no op ran on the device (%)."""
from bench.trace import idle_pct


def read(r):
    return idle_pct(r.trace)
