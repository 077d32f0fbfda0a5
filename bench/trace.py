"""Reduction of a JAX profiler trace to the numbers the benchmark reports.

* device busy time: the union of the intervals in which an operation ran on a
  device, averaged over the devices;
* device time per XLA module, by the module's name (the union of its ops'
  intervals, so a module's time is time the device was busy with it);
* the idle gaps between device operations, each attributed to the host span
  (a ``jax.profiler.TraceAnnotation`` the benchmark writes, named ``bench.*``)
  open at that time.

Device operations are the events of the ``XLA Ops`` line of each
``/device:*`` plane.  Where the trace has no device plane, as on the CPU
backend, they are the host events that carry an ``hlo_op`` stat.  All times
are nanoseconds on the profiler's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os

import numpy as np

SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Ops:
    """Device operations of one device."""

    start: np.ndarray  # (n,) int64 ns
    end: np.ndarray  # (n,) int64 ns
    name: list
    module: list


@dataclasses.dataclass
class Trace:
    devices: list  # one Ops per device
    spans: list  # (name, start_ns, end_ns) of the host's bench.* annotations

    def span(self, name: str) -> tuple[int, int]:
        """The first span called ``name``."""
        for n, s, e in self.spans:
            if n == name:
                return s, e
        raise KeyError(name)

    def spans_named(self, name: str) -> list[tuple[int, int]]:
        return [(s, e) for n, s, e in self.spans if n == name]


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def _op_name(name: str) -> str:
    """``fusion.11`` of a TPU op event named ``%fusion.11 = f32[...] fusion(...)``."""
    return name.split(" = ", 1)[0].lstrip("%") if name.startswith("%") else name


def _ops_from(events) -> Ops:
    start, end, name, module = [], [], [], []
    for e, mod in events:
        s = int(e.start_ns)
        start.append(s)
        end.append(s + int(e.duration_ns))
        name.append(_op_name(e.name))
        module.append(mod)
    order = np.argsort(np.asarray(start, np.int64), kind="stable")
    return Ops(
        np.asarray(start, np.int64)[order],
        np.asarray(end, np.int64)[order],
        [name[i] for i in order],
        [module[i] for i in order],
    )


def _module_of(modules, s) -> str:
    """The ``XLA Modules`` event that contains time ``s``."""
    for ms, me, mn in modules:
        if ms <= s < me:
            return mn
    return ""


def from_profile(profile) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to device ops and host spans."""
    devices, host_ops, spans = [], [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            modules = []
            if "XLA Modules" in lines:
                modules = [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                           for e in lines["XLA Modules"].events]
            events = []
            for e in lines["XLA Ops"].events:
                mod = _stats(e).get("hlo_module") or _module_of(modules, int(e.start_ns))
                events.append((e, str(mod)))
            devices.append(_ops_from(events))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = int(e.start_ns)
                        spans.append((e.name, s, s + int(e.duration_ns)))
                        continue
                    st = _stats(e)
                    if "hlo_op" in st:
                        host_ops.append((e, str(st.get("hlo_module", ""))))
    if not devices and host_ops:
        devices = [_ops_from(host_ops)]
    spans.sort(key=lambda x: x[1])
    return Trace(devices, spans)


def load(log_dir: str) -> Trace:
    """Reduce the newest ``.xplane.pb`` under ``log_dir``."""
    import jax

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(jax.profiler.ProfileData.from_file(paths[-1]))


def union(start: np.ndarray, end: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Merge intervals (sorted by start or not) into disjoint sorted ones."""
    if start.size == 0:
        return start.astype(np.int64), end.astype(np.int64)
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return s[first], reach[last]


def covered(merged: tuple[np.ndarray, np.ndarray], lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi)`` that the merged intervals cover."""
    s, e = merged
    return int(np.maximum(np.minimum(e, hi) - np.maximum(s, lo), 0).sum())


def busy_ns(trace: Trace, lo: int, hi: int) -> float:
    """Device-busy nanoseconds in ``[lo, hi)``, averaged over the devices."""
    if not trace.devices:
        return 0.0
    return float(np.mean([covered(union(d.start, d.end), lo, hi) for d in trace.devices]))


def module_ns(trace: Trace, lo: int, hi: int) -> dict:
    """Device-busy nanoseconds in ``[lo, hi)`` per XLA module name, averaged
    over the devices."""
    out: dict = {}
    for d in trace.devices:
        mods = np.asarray(d.module, dtype=object)
        for m in set(d.module):
            sel = mods == m
            out[m] = out.get(m, 0) + covered(union(d.start[sel], d.end[sel]), lo, hi)
    n = max(len(trace.devices), 1)
    return {m: v / n for m, v in out.items()}


def top_ops(trace: Trace, lo: int, hi: int, n: int = 10) -> list:
    """The ``n`` device operations that took most time in ``[lo, hi)``:
    ``[[name, seconds], ...]`` summed over calls, averaged over devices."""
    tot: dict = {}
    for d in trace.devices:
        dur = np.maximum(np.minimum(d.end, hi) - np.maximum(d.start, lo), 0)
        for name, t in zip(d.name, dur.tolist()):
            if t:
                tot[name] = tot.get(name, 0) + t
    k = max(len(trace.devices), 1)
    best = sorted(tot.items(), key=lambda x: -x[1])[:n]
    return [[name, t / k / 1e9] for name, t in best]


def idle_gaps(trace: Trace, lo: int, hi: int, n: int = 10) -> list:
    """The ``n`` longest stretches of ``[lo, hi)`` in which the first device
    ran nothing, each named by the innermost ``bench.*`` span open at its
    midpoint (``"none"`` where none is): ``[[span, seconds], ...]``."""
    if not trace.devices:
        return []
    s, e = union(trace.devices[0].start, trace.devices[0].end)
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
        open_spans = [(sp_s, name) for name, sp_s, sp_e in trace.spans
                      if sp_s <= mid < sp_e and name != SPAN_PREFIX + "window"]
        name = max(open_spans)[1] if open_spans else "none"
        out.append([name, float(length[i]) / 1e9])
    return out
