"""Spans and a compile counter for the served market.

``span(name, **stats)`` is a ``jax.profiler.TraceAnnotation`` named
``market.<name>``.  It lands in the profiler's own trace, on the same clock
as the device operations, so a device-idle stretch can be put down to the
innermost span open over it.  Spans have no switch of their own: they
record while the profiler collects (``jax.profiler.trace(dir)``); otherwise
``span`` returns a shared no-op context and costs under a microsecond of
host time.  The ``stats`` are values the caller already holds (ints, a kind
string); they ride on the trace event.  Stats known only once the work is
done go through the entered span's ``set_metadata(**stats)``, which the
no-op context takes and drops.

``compiles()`` lists every backend compile of this process since the module
was imported, as ``(perf_counter_end, fun_name, seconds)``: one
``jax.monitoring`` listener, registered once per process, records the
``perf_counter`` time at which the compile ended, the name of the jitted
function JAX compiled and the compile's duration.
"""
from __future__ import annotations

import contextlib
import time

import jax

PREFIX = "market."
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

_compiles: list[tuple[float, str, float]] = []
_collecting = jax.profiler.TraceAnnotation.is_enabled


class _Off(contextlib.nullcontext):
    """A span while the profiler is off: a shared no-op context."""

    def __enter__(self):
        return self

    def set_metadata(self, **stats) -> None:
        pass


_OFF = _Off()


def span(name: str, **stats):
    """A trace span ``market.<name>`` carrying ``stats``, recorded while the
    profiler collects."""
    if not _collecting():
        return _OFF
    return jax.profiler.TraceAnnotation(PREFIX + name, **stats)


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == BACKEND_COMPILE:
        _compiles.append((time.perf_counter(), str(kwargs.get("fun_name", "")), duration))


def compiles() -> list[tuple[float, str, float]]:
    """The process's backend compiles so far: ``(end, fun_name, seconds)``,
    ``end`` on ``time.perf_counter``'s clock."""
    return list(_compiles)


# a module body runs once per process, and so does this registration: JAX
# keeps its listeners for the life of the process
jax.monitoring.register_event_duration_secs_listener(_on_duration)
