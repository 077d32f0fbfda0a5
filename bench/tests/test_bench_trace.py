"""The trace reduction: interval union, per-module time, gap attribution."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import trace as T


def _synthetic():
    # device: ops [0,10) [5,20) [30,40) [45,50); module of each; host spans
    ops = T.Ops(np.array([0, 5, 30, 45]), np.array([10, 20, 40, 50]),
                ["a", "b", "a", "c"], ["jit_x", "jit_x", "jit_y", "jit_y"])
    spans = [("bench.window", 0, 60), ("bench.tick", 0, 22), ("bench.ingest", 22, 44),
             ("bench.idle", 50, 60)]
    return T.Trace([ops], spans)


def test_union_merges_overlaps_and_keeps_gaps():
    s, e = T.union(np.array([30, 0, 5, 45]), np.array([40, 10, 20, 50]))
    assert s.tolist() == [0, 30, 45] and e.tolist() == [20, 40, 50]
    assert T.covered((s, e), 0, 60) == 35
    assert T.covered((s, e), 15, 35) == 10


def test_busy_modules_ops_and_gaps_of_a_hand_made_trace():
    tr = _synthetic()
    assert T.busy_ns(tr, 0, 60) == 35
    assert T.module_ns(tr, 0, 60) == {"jit_x": 20, "jit_y": 15}
    assert T.top_ops(tr, 0, 60, 2) == [["a", 20e-9], ["b", 15e-9]]
    # gaps: [20,30) in ingest, [40,45) in ingest, [50,60) idle
    assert T.idle_gaps(tr, 0, 60) == [["bench.ingest", 10e-9], ["bench.idle", 10e-9],
                                      ["bench.ingest", 5e-9]]


def test_reduction_of_a_trace_recorded_on_this_cpu(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.tick"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    tr = T.load(str(tmp_path))
    lo, hi = tr.span("bench.window")
    assert len(tr.spans_named("bench.tick")) == 2
    busy = T.busy_ns(tr, lo, hi)
    assert 0 < busy < hi - lo
    mods = T.module_ns(tr, lo, hi)
    assert any("lambda" in m for m in mods) and sum(mods.values()) >= busy
    gaps = T.idle_gaps(tr, lo, hi)
    assert gaps[0][0] == "bench.idle" and gaps[0][1] >= 0.015
    # every op of the module lies inside a tick span
    ticks = tr.spans_named("bench.tick")
    for s in tr.devices[0].start[(tr.devices[0].start >= lo) & (tr.devices[0].start < hi)]:
        assert any(a <= s < b for a, b in ticks)
