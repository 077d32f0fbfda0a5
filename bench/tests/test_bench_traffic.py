"""The open-loop generator: seeded, fixed work, bursts, Zipf, withdraw rules."""
import json
import os

import numpy as np
import pytest

from bench import traffic

MIXES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "traffic")


def _mix(name):
    return traffic.load_mix(os.path.join(MIXES, f"{name}.json"))


@pytest.mark.parametrize("name", ["stream"])
def test_same_seed_same_stream_and_other_seeds_same_amount(name):
    mix = _mix(name)
    agents = np.arange(5000) * 3
    seed = 2**40 + 17  # the driver's seeds exceed 32 bits
    a = traffic.window_schedule(mix, agents, 10.0, seed)
    b = traffic.window_schedule(mix, agents, 10.0, seed)
    c = traffic.window_schedule(mix, agents, 10.0, seed + 1)
    for f in ("agent", "kind", "scale"):
        assert np.array_equal(getattr(a.ops, f), getattr(b.ops, f))
    assert not np.array_equal(a.ops.agent, c.ops.agent)
    assert len(a.ops) == len(c.ops)
    assert set(np.unique(a.ops.agent)) <= set(agents.tolist())
    assert np.array_equal(a.due, b.due) and np.all(np.diff(a.due) >= 0)
    assert a.due.min() >= 0 and a.due.max() < 10.0


def test_poisson_rate_count_and_bursts():
    mix = _mix("stream")
    rng = np.random.default_rng(1)
    due = traffic.poisson_due(mix, 100.0, rng, rate=2000)
    assert due.size == 200_000
    b = mix["burst"]
    in_burst = (due % b["every_s"]) < b["seconds"]
    per_s_burst = in_burst.sum() / (100.0 / b["every_s"] * b["seconds"])
    per_s_base = (~in_burst).sum() / (100.0 - 100.0 / b["every_s"] * b["seconds"])
    assert per_s_burst / per_s_base == pytest.approx(b["factor"], rel=0.02)


def test_zipf_skew_and_hot_set_shift():
    mix = _mix("stream")
    agents = np.arange(10_000)
    s = traffic.window_schedule(mix, agents, 20.0, 5)
    period = (s.due // mix["hot_shift_s"]).astype(int)
    top = [np.bincount(s.ops.agent[period == p], minlength=agents.size).argmax()
           for p in range(4)]
    counts = np.bincount(s.ops.agent[period == 0])
    # Zipf(0.99) over 10^4: the hottest agent takes ~1/H(10^4) ≈ 10% of the ops
    assert 0.06 < counts.max() / counts.sum() < 0.14
    assert len(set(top)) > 1


def test_withdrawn_keys_reenter_and_are_never_withdrawn_twice():
    mix = json.loads(json.dumps(_mix("stream")))
    mix["ops"] = {"reprice": 0.5, "withdraw": 0.5}
    s = traffic.window_schedule(mix, np.arange(50), 2.0, 9, rate=2000)
    live = np.ones(50, bool)
    for a, k in zip(s.ops.agent, s.ops.kind):
        if k == traffic.WITHDRAW:
            assert live[a]
        live[a] = k == traffic.REPRICE
    assert (s.ops.kind == traffic.WITHDRAW).sum() > 100
