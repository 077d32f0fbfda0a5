"""The reduction of the program's own spans (``bench/program_spans.py``):
per-tick and per-call means, self time, device-idle time given to the
innermost span, the full/delta split of checkpoints; then the same on a
trace recorded on this CPU through ``harness.run``, where every reader of
a program span returns a number."""
import importlib.util
import os
import types

import numpy as np
import pytest

from bench import harness, run_cell
from bench import program_spans as P
from bench import trace as T

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SEED = 2**40 + 11
READERS = ["validate_us", "wal_append_us", "drain_ms", "scatter_ms", "clock_ms", "verify_ms",
           "stats_ms", "commit_ms", "checkpoint_full_ms", "tick_compiles"]
PHASES = ["drain", "scatter", "clock", "verify", "stats", "commit"]


def _hand_made():
    # thread 1: two ticks, the first with a full checkpoint, the second a
    # delta; a submit between them.  Thread 2: a span that overlaps the
    # first tick and nests in nothing.
    m = "market."
    rows = [
        (m + "tick", 0, 100, 1, {"epoch": 1}),
        (m + "drain", 0, 10, 1, {"rows": 3}),
        (m + "scatter", 10, 30, 1, {"kind": "delta", "bucket": 4}),
        (m + "clock", 30, 60, 1, {}),
        (m + "verify", 60, 70, 1, {}),
        (m + "stats", 70, 80, 1, {}),
        (m + "commit", 80, 98, 1, {}),
        (m + "checkpoint.full", 82, 96, 1, {"step": 2}),
        (m + "checkpoint.snapshot", 82, 88, 1, {}),
        (m + "checkpoint.write", 88, 96, 1, {}),
        (m + "submit", 150, 160, 1, {"epoch": 2}),
        (m + "wal_append", 151, 155, 1, {}),
        (m + "validate", 155, 159, 1, {}),
        (m + "tick", 200, 300, 1, {"epoch": 2}),
        (m + "drain", 200, 230, 1, {"rows": 1}),
        (m + "commit", 240, 290, 1, {}),
        (m + "checkpoint.delta", 240, 280, 1, {"step": 3}),
        (m + "checkpoint.write", 250, 280, 1, {}),
        (m + "checkpoint.write", 50, 90, 2, {}),
    ]
    ops = T.Ops(np.array([20, 35, 250]), np.array([25, 55, 260]), ["a", "b", "c"],
                ["jit_x", "jit_x", "jit_y"])
    return P.Spans.of(rows[::-1]), T.Trace([ops], [])


def _at(spans, name, start):
    return next(i for i in range(len(spans)) if spans.name[i] == name and spans.start[i] == start)


def test_parents_self_time_and_means_of_hand_made_spans():
    spans, _ = _hand_made()
    tick, commit = _at(spans, "market.tick", 0), _at(spans, "market.commit", 80)
    assert spans.parent[_at(spans, "market.clock", 30)] == tick
    assert spans.parent[_at(spans, "market.checkpoint.write", 88)] == _at(
        spans, "market.checkpoint.full", 82)
    assert spans.parent[_at(spans, "market.checkpoint.write", 50)] == -1  # another thread
    assert spans.parent[_at(spans, "market.validate", 155)] == _at(spans, "market.submit", 150)
    own = spans.self_ns()
    assert own[tick] == 100 - (10 + 20 + 30 + 10 + 10 + 18)
    assert own[commit] == 18 - 14
    assert own[_at(spans, "market.tick", 200)] == 100 - 30 - 50

    assert P.per_tick_ns(spans, "market.drain", 0, 400) == (10 + 30) / 2
    assert P.per_tick_ns(spans, "market.verify", 0, 400) == 10 / 2
    assert P.per_tick_ns(spans, "market.drain", 150, 400) == 30  # the window holds one tick
    assert P.per_tick_ns(spans, "market.drain", 110, 190) is None  # and here none
    assert P.per_call_ns(spans, "market.validate", 0, 400) == 4
    # the full/delta split: each kind has its own spans and its own mean
    assert P.per_call_ns(spans, "market.checkpoint.full", 0, 400) == 14
    assert P.per_call_ns(spans, "market.checkpoint.delta", 0, 400) == 40
    assert P.per_call_ns(spans, "market.checkpoint.full", 100, 400) is None


def test_device_idle_time_goes_to_the_innermost_span():
    spans, trace = _hand_made()
    busy = P.Busy(trace.devices[0])
    assert busy.ns([0, 20, 22, 30, 0], [400, 25, 40, 35, 0]).tolist() == [35, 5, 8, 0, 0]
    idle = P.idle_self_ns(spans, trace)
    assert idle[_at(spans, "market.scatter", 10)] == 20 - 5
    assert idle[_at(spans, "market.clock", 30)] == 30 - 20
    assert idle[_at(spans, "market.checkpoint.full", 82)] == 0  # its children hold it all
    assert idle[_at(spans, "market.checkpoint.snapshot", 82)] == 6
    assert idle[_at(spans, "market.tick", 0)] == 2  # the self time, all idle
    assert idle[_at(spans, "market.checkpoint.write", 250)] == 30 - 10
    # thread 1's spans share out its idle time without counting any twice
    thread1 = sum(v for i, v in enumerate(idle) if spans.thread[i] == 1)
    assert thread1 == (100 - 25) + (160 - 150) + (100 - 10)
    # each gap is named by the innermost span open at its midpoint: 152 is
    # in the submit's WAL append, 330 in no span, 10 in the scatter, 30 in
    # the clock (a span that starts there holds it)
    gaps = P.idle_gaps(spans, trace, 0, 400)
    assert [g[0] for g in gaps] == ["market.wal_append", "none", "market.scatter",
                                    "market.clock"]
    assert [g[1] for g in gaps] == pytest.approx([195e-9, 140e-9, 20e-9, 10e-9])


def test_a_run_without_program_spans_or_a_cell_reads_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "WORK", str(tmp_path))
    run = types.SimpleNamespace(window=(0, 10), ticks=[])
    assert P.load(run) is None
    run.cell = object()  # a run, but no trace file under WORK
    assert P.load(run) is None
    assert P.per_tick_ms(run, "market.drain") is None


def _reader(name):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_every_reader_reads_a_trace_recorded_on_this_cpu(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(harness, "configure_cache", lambda: None)
    spec = run_cell.load_cell("paper100k.stream", ROOT)
    # a full checkpoint every second tick, so the window holds one
    config = dict(spec["config"], agents=300, clusters=4,
                  service=dict(spec["config"]["service"], checkpoint_full_every=2))
    mix = dict(spec["mix"], rate_per_s=120)
    seen = {}

    def coverage(run):
        # the phases of each window tick cover (nearly) all of it
        spans = P.load(run)
        lo, hi = run.window
        ticks = spans.named(P.TICK, lo, hi)
        kids = sum(P.per_tick_ns(spans, f"market.{p}", lo, hi) for p in PHASES)
        seen["cover"] = kids / float((spans.end[ticks] - spans.start[ticks]).mean())
        seen["ticks"] = ticks.size

    readers = {name: ("x", _reader(name)) for name in READERS}
    readers["coverage"] = ("x", coverage)
    res = harness.run(config, mix, spec["limits"], spec["e2e"], readers, SEED, 1.2, True,
                      None, harness.clock())
    assert res["correct"], res["checks"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert sorted(got) == sorted(READERS), got
    assert got["tick_compiles"] == 0  # every shape was warmed up
    for name in READERS[:-1]:
        assert got[name] > 0, name
    assert seen["ticks"] >= 2 and seen["cover"] >= 0.95, seen


@pytest.mark.parametrize("name", READERS)
def test_a_reader_of_the_parent_program_reads_nothing(name, monkeypatch, tmp_path):
    # a program without spans or a compile counter: an empty trace directory
    # and no ``repro.tracing`` to import
    import builtins

    monkeypatch.setattr(harness, "WORK", str(tmp_path))
    real_import = builtins.__import__

    def no_tracing(mod, globals=None, locals=None, fromlist=(), level=0):
        if mod == "repro" and "tracing" in (fromlist or ()):
            raise ImportError("no repro.tracing")
        return real_import(mod, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_tracing)
    tick = types.SimpleNamespace(start=0.0, end=1e12)
    run = types.SimpleNamespace(cell=object(), window=(0, 10), ticks=[tick])
    assert _reader(name)(run) is None
