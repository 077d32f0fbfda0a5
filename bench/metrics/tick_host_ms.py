"""Tick host path: mean time of a tick span in which the device ran nothing
(drain, book delta upload, verify, stats, durable commit, dispatch)."""
from bench.trace import busy_ns


def read(run):
    spans = run.tick_spans
    if not spans:
        return None
    host = [(e - s) - busy_ns(run.trace, s, e) for s, e in spans]
    return sum(host) / len(host) / 1e6
