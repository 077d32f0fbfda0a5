"""The harness: latency from due time, readers that find nothing return
nothing, no run without a TPU, and the correctness check, which a sound
tiny run passes and each fault of the timed path (and the bfloat16 control)
fails.

The runs here skip the entry point's look for a chip and drive the rest of
a run on the CPU at a tiny size; they are the one file that runs the
harness, and each points its work directory at a temporary one."""
import importlib.util
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import harness, run_cell

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SEED = 2**40 + 5


def test_latencies_count_from_due_time():
    cell = object.__new__(harness.Cell)
    cell.base = 0
    cell.due = np.array([0.0, 0.1, 0.2, 0.3])
    cell.sent = np.array([0.5, 0.5, 0.6, 0.7]) + 10.0  # host clock: window starts at 10
    cell.acked = cell.sent + 0.001
    cell.ok = np.array([True, True, True, True])
    # tick 0 drains ops [0, 2), tick 1 (did not converge) ops [2, 3), tick 2 op 3
    mk = lambda s, e, b, c: harness.Tick(s, e, b, 1, c, None, None, None, None, True)
    cell.ticks = [mk(10.55, 10.8, 2, True), mk(10.8, 11.0, 3, False), mk(11.0, 11.3, 4, True)]
    cell.sched = types.SimpleNamespace(ops=[0] * 4)
    e2e = cell.end_to_end(10.0, 11.3)
    assert cell.attempted == 4 and cell.failed == 1  # carried by the failed tick
    ack = np.array([0.501, 0.401, 0.301])
    settle = np.array([0.8, 0.7, 1.0])
    assert e2e["ack_p95_ms"] == pytest.approx(np.percentile(np.append(ack, 0.401), 95) * 1e3)
    assert e2e["settle_p95_ms"] == pytest.approx(np.percentile(settle, 95) * 1e3)
    assert e2e["tick_ms"] == pytest.approx((0.25 + 0.2 + 0.3) / 3 * 1e3)
    assert e2e["bids_per_s"] == pytest.approx(3 / 1.3)


def _metric(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _per_layer():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [m["name"] for m in json.load(f)["per_layer"]]


@pytest.mark.parametrize("name", _per_layer())
def test_a_reader_that_finds_nothing_returns_nothing(name):
    from bench.trace import Ops, Trace

    # a window with no tick, no submit and no device op; the device-idle
    # reader alone has something to read there (an idle window reads 100 %)
    run = types.SimpleNamespace(
        trace=Trace([Ops(np.zeros(0, np.int64), np.zeros(0, np.int64), [], [])], []),
        window=(0, 10_000), ticks=[], tick_spans=[], submit_s=np.zeros(0), peaks=None)
    got = _metric(name).read(run)
    assert got == (100.0 if name == "device_idle_share" else None)


def test_run_cell_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run_cell.py"), "--workload",
                        "paper100k.stream", "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=ROOT, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout


def _tiny(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(harness, "configure_cache", lambda: None)
    spec = run_cell.load_cell("paper100k.stream", ROOT)
    config = dict(spec["config"], agents=300, clusters=4)
    mix = dict(spec["mix"], rate_per_s=120)
    return config, mix, spec["limits"], spec["e2e"]


def _run(monkeypatch, tmp_path, control=False):
    config, mix, limits, e2e = _tiny(monkeypatch, tmp_path)
    return harness.run(config, mix, limits, e2e, {}, SEED, 1.2, False, None,
                       harness.clock(), control=control)


def test_a_sound_tiny_run_is_correct_and_the_bfloat16_control_is_not(monkeypatch, tmp_path):
    # one run: the program's readings, then the control's in its place
    res = _run(monkeypatch, tmp_path, control=True)
    prog = res["program_checks"]
    assert all(v["value"] <= v["limit"] for v in prog.values()), prog
    assert res["attempted"] > 100 and res["failed"] == 0
    assert list(res["metrics"]) == ["ack_p95_ms", "settle_p95_ms", "tick_ms", "bids_per_s",
                                    "setup_s"]
    assert res["correct"] is False, res["checks"]
    json.dumps(res)


def _unchanged_state(market):
    # every tick leaves the book as it was: nothing is drained into it
    market.MarketService._drain = lambda self: (0, 0)


def _half_batch(market):
    drain = market.MarketService._drain

    def half(self):
        for k in list(self._pending)[::2]:
            del self._pending[k]
        return drain(self)

    market.MarketService._drain = half


def _answer_altered(market):
    clock_auction = market.clock_auction

    def altered(problem, start, *a, **kw):
        res = clock_auction(problem, start, *a, **kw)
        return res.__class__(**{**res.__dict__, "prices": res.prices.at[0].multiply(2.0)})

    market.clock_auction = altered


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch, _answer_altered])
def test_a_broken_timed_path_is_not_correct(monkeypatch, tmp_path, fault):
    from repro.serve import market

    monkeypatch.setattr(market.MarketService, "_drain", market.MarketService._drain)
    monkeypatch.setattr(market, "clock_auction", market.clock_auction)
    fault(market)
    res = _run(monkeypatch, tmp_path)
    assert not res["correct"], res["checks"]
