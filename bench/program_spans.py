"""The program's own spans (``market.*``, written by ``repro/tracing.py``) in
a traced run, reduced to the numbers the per-layer readers report.

* per tick: the time of the spans of one name that start in the window,
  over the window's ``market.tick`` spans;
* per call: the same time over the number of those spans;
* self time: a span's time less the part its children cover (the spans
  nested in it on its thread);
* device-idle time in each span: the stretches in which the device ran
  nothing, each given to the innermost span open over it.

The spans are read from the run's ``.xplane.pb`` (under the harness's
``WORK``/``trace`` while the readers run), once per run.  A run whose trace
holds no program span, as that of a program without ``repro/tracing.py``,
gives nothing.  All times are nanoseconds on the profiler's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os

import numpy as np

from bench import trace as tracemod

SPAN_PREFIX = "market."
TICK = SPAN_PREFIX + "tick"


@dataclasses.dataclass
class Spans:
    """Program spans, sorted by start.  ``parent[i]`` is the index of the
    innermost span that holds span ``i`` on its thread, or -1."""

    name: list
    start: np.ndarray  # (n,) int64 ns
    end: np.ndarray  # (n,) int64 ns
    thread: np.ndarray  # (n,) int64: the trace line (thread) of each span
    stats: list  # dict of each span's stats
    parent: np.ndarray = dataclasses.field(init=False)

    def __post_init__(self) -> None:
        order = np.lexsort((-self.end, self.start))
        self.name = [self.name[i] for i in order]
        self.stats = [self.stats[i] for i in order]
        self.start, self.end, self.thread = self.start[order], self.end[order], self.thread[order]
        self._names = np.asarray(self.name, dtype=object)
        self.parent = np.full(len(self.name), -1, np.int64)
        open_by_thread: dict = {}
        for i in range(len(self.name)):
            stack = open_by_thread.setdefault(int(self.thread[i]), [])
            while stack and self.end[stack[-1]] <= self.start[i]:
                stack.pop()
            if stack:
                self.parent[i] = stack[-1]
            stack.append(i)

    @classmethod
    def of(cls, spans) -> "Spans":
        """From ``(name, start_ns, end_ns, thread, stats)`` tuples."""
        spans = list(spans)
        return cls([s[0] for s in spans], np.asarray([s[1] for s in spans], np.int64),
                   np.asarray([s[2] for s in spans], np.int64),
                   np.asarray([s[3] for s in spans], np.int64), [dict(s[4]) for s in spans])

    def __len__(self) -> int:
        return len(self.name)

    def named(self, name: str, lo: int, hi: int) -> np.ndarray:
        """Indices of the spans called ``name`` that start in ``[lo, hi)``."""
        return np.flatnonzero((self._names == name) & (self.start >= lo) & (self.start < hi))

    def self_ns(self) -> np.ndarray:
        """Each span's time less its children's."""
        dur = self.end - self.start
        own = dur.copy()
        kids = self.parent >= 0
        np.subtract.at(own, self.parent[kids], dur[kids])
        return own


def from_profile(profile) -> Spans:
    """The ``market.*`` events of a ``jax.profiler.ProfileData``'s host planes."""
    out = []
    line_id = 0
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            line_id += 1
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    s = int(e.start_ns)
                    out.append((e.name, s, s + int(e.duration_ns), line_id, dict(e.stats)))
    return Spans.of(out)


_cache: dict = {}


def load(run) -> Spans | None:
    """The program spans of the run the readers are reading, parsed once;
    None where the run has no trace file or its trace no program span."""
    if getattr(run, "cell", None) is None:
        return None
    from bench import harness

    paths = sorted(glob.glob(os.path.join(harness.WORK, "trace", "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return None
    st = os.stat(paths[-1])
    key = (paths[-1], st.st_mtime_ns, st.st_size)
    if key not in _cache:
        import jax

        _cache.clear()
        _cache[key] = from_profile(jax.profiler.ProfileData.from_file(paths[-1]))
    spans = _cache[key]
    return spans if len(spans) else None


# -- reductions ---------------------------------------------------------------


def per_tick_ns(spans: Spans, name: str, lo: int, hi: int) -> float | None:
    """Time of the ``name`` spans that start in ``[lo, hi)`` over the
    ``market.tick`` spans that do; None where no tick does."""
    ticks = spans.named(TICK, lo, hi)
    if not ticks.size:
        return None
    sel = spans.named(name, lo, hi)
    return float((spans.end[sel] - spans.start[sel]).sum()) / ticks.size


def per_call_ns(spans: Spans, name: str, lo: int, hi: int) -> float | None:
    """Mean time of the ``name`` spans that start in ``[lo, hi)``."""
    sel = spans.named(name, lo, hi)
    if not sel.size:
        return None
    return float((spans.end[sel] - spans.start[sel]).mean())


class Busy:
    """Device-busy time of one device over any interval, in O(log n): the
    union of its operations' intervals with a running sum of their lengths."""

    def __init__(self, ops: tracemod.Ops) -> None:
        self.s, self.e = tracemod.union(ops.start, ops.end)
        self.cum = np.concatenate([[0], np.cumsum(self.e - self.s)])

    def _before(self, t: np.ndarray) -> np.ndarray:
        """Busy nanoseconds before each time in ``t``."""
        if not self.s.size:
            return np.zeros(t.shape, np.int64)
        i = np.searchsorted(self.s, t, side="right")  # intervals that start by t
        j = np.maximum(i - 1, 0)
        part = np.clip(np.minimum(t, self.e[j]) - self.s[j], 0, None)
        return np.where(i > 0, self.cum[j] + part, 0)

    def ns(self, lo, hi) -> np.ndarray:
        lo, hi = np.asarray(lo, np.int64), np.asarray(hi, np.int64)
        return self._before(hi) - self._before(lo)


def idle_self_ns(spans: Spans, trace: tracemod.Trace) -> np.ndarray:
    """Device-idle nanoseconds in each span that no child of it holds: the
    idle time given to the innermost span.  Averaged over the devices."""
    if not trace.devices or not len(spans):
        return np.zeros(len(spans))
    out = np.zeros(len(spans))
    for d in trace.devices:
        busy = Busy(d)
        idle = (spans.end - spans.start) - busy.ns(spans.start, spans.end)
        own = idle.astype(np.float64)
        kids = spans.parent >= 0
        np.subtract.at(own, spans.parent[kids], idle[kids])
        out += own
    return out / len(trace.devices)


def idle_gaps(spans: Spans, trace: tracemod.Trace, lo: int, hi: int, n: int = 10) -> list:
    """The ``n`` longest stretches of ``[lo, hi)`` in which the first device
    ran nothing, each named by the innermost program span open at its
    midpoint (``"none"`` where none is): ``[[span, seconds], ...]``."""
    if not trace.devices:
        return []
    s, e = tracemod.union(trace.devices[0].start, trace.devices[0].end)
    keep = (e > lo) & (s < hi)
    s, e = np.maximum(s[keep], lo), np.minimum(e[keep], hi)
    gap_lo = np.concatenate([[lo], e])
    gap_hi = np.concatenate([s, [hi]])
    length = gap_hi - gap_lo
    out = []
    for i in np.argsort(-length, kind="stable")[:n]:
        if length[i] <= 0:
            break
        mid = (gap_lo[i] + gap_hi[i]) // 2
        held = np.flatnonzero((spans.start <= mid) & (mid < spans.end))
        name = spans.name[held[np.argmax(spans.start[held])]] if held.size else "none"
        out.append([name, float(length[i]) / 1e9])
    return out


# -- what a reader calls ------------------------------------------------------


def per_tick_ms(run, name: str) -> float | None:
    spans = load(run)
    if spans is None:
        return None
    v = per_tick_ns(spans, name, *run.window)
    return None if v is None else v / 1e6


def per_call(run, name: str, scale: float) -> float | None:
    """Mean time of one ``name`` span in the window, in seconds × ``scale``."""
    spans = load(run)
    if spans is None:
        return None
    v = per_call_ns(spans, name, *run.window)
    return None if v is None else v / 1e9 * scale
