"""Device: share of the traced window in which no operation ran on the chip."""
from bench.trace import busy_ns


def read(run):
    lo, hi = run.window
    return 100.0 * (1.0 - busy_ns(run.trace, lo, hi) / (hi - lo))
