"""Tick host path: backend compiles whose end falls inside a window tick,
from the program's compile counter (``repro.tracing.compiles``, on the
harness's ``perf_counter`` clock).  Nothing where the program has no
counter."""


def read(run):
    ticks = run.ticks
    if not ticks:
        return None
    try:
        from repro import tracing
    except ImportError:
        return None
    ends = [t for t, _, _ in tracing.compiles()]
    return sum(any(k.start <= t <= k.end for k in ticks) for t in ends)
