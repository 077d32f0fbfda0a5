"""Open-loop bid traffic, generated from a mix file and a seed.

A mix (``bench/traffic/<name>.json``) is data only; this module is the one
generator that reads it.  Arrivals are ``poisson``: a fixed number of bids,
``rate_per_s × seconds``, with due times drawn from a Poisson process whose
intensity rises by ``burst.factor`` for ``burst.seconds`` in every
``burst.every_s`` (the mean stays ``rate_per_s``).  Conditioning on the
count keeps every seed's amount of work the same; only the order and the
times differ.

Every op is a re-price of an agent's resting bid (the exported bid with its
willingness-to-pay × U(``reprice_scale``); the re-price generator of the
legacy ``benchmarks/run.py`` ``market_serve``) or a withdrawal.  Agents are
drawn Zipf(``zipf``) over a popularity order that is re-shuffled every
``hot_shift_s`` seconds of due time, so the hot set moves.  A withdrawal drawn for an agent whose bid is already
withdrawn becomes a re-price: withdrawn keys re-enter when drawn again, and
no op is one the service must refuse.

The program receives only the generated bids; nothing here imports it.
"""
from __future__ import annotations

import dataclasses
import json
import math

import numpy as np

REPRICE, WITHDRAW = 0, 1


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix["arrival"] != "poisson":
        raise ValueError(f"{path}: unknown arrival process {mix['arrival']!r}")
    share = float(mix["ops"]["reprice"]) + float(mix["ops"]["withdraw"])
    if not math.isclose(share, 1.0):
        raise ValueError(f"{path}: op shares sum to {share}, not 1")
    return mix


def seed_streams(seed: int, n: int) -> list[np.random.Generator]:
    """``n`` independent generators from any whole-number seed (the driver's
    seeds exceed 32 bits; SeedSequence takes them whole)."""
    return [np.random.default_rng(s) for s in np.random.SeedSequence(seed).spawn(n)]


@dataclasses.dataclass
class Ops:
    """A sequence of ops in the order they fall due."""

    agent: np.ndarray  # (n,) int64 agent index into the exported rows
    kind: np.ndarray  # (n,) int8: REPRICE or WITHDRAW
    scale: np.ndarray  # (n,) float32 multiplier on the resting bid's pi

    def __len__(self) -> int:
        return int(self.agent.size)

    @staticmethod
    def empty() -> "Ops":
        return Ops(np.zeros(0, np.int64), np.zeros(0, np.int8), np.zeros(0, np.float32))

    @staticmethod
    def concat(parts: list["Ops"]) -> "Ops":
        return Ops(
            np.concatenate([p.agent for p in parts]).astype(np.int64),
            np.concatenate([p.kind for p in parts]).astype(np.int8),
            np.concatenate([p.scale for p in parts]).astype(np.float32),
        )


class Zipf:
    """Zipf(s) ranks over ``n`` agents, mapped through a popularity order."""

    def __init__(self, n: int, s: float) -> None:
        w = np.arange(1, n + 1, dtype=np.float64) ** -float(s)
        self.cdf = np.cumsum(w) / w.sum()
        self.n = n

    def draw(self, rng: np.random.Generator, size: int) -> np.ndarray:
        ranks = np.searchsorted(self.cdf, rng.random(size), side="right")
        return np.minimum(ranks, self.n - 1)


class OpStream:
    """Draws ops in due order, tracking which agents' bids are withdrawn."""

    def __init__(self, mix: dict, agents: np.ndarray, seed: int) -> None:
        self.mix = mix
        self.agents = np.asarray(agents, np.int64)  # agents with a resting bid
        self.live = np.ones(self.agents.size, bool)  # not withdrawn
        self.zipf = Zipf(self.agents.size, mix["zipf"])
        self.withdraw_p = float(mix["ops"]["withdraw"])
        self.lo, self.hi = (float(x) for x in mix["reprice_scale"])
        self.seed = seed
        self._orders: dict[int, np.ndarray] = {}

    def _order(self, period: int) -> np.ndarray:
        """Popularity order of ``period``: rank → agent, re-shuffled per period."""
        if period not in self._orders:
            rng = np.random.default_rng(np.random.SeedSequence([self.seed, 1, period]))
            self._orders[period] = rng.permutation(self.agents.size)
        return self._orders[period]

    def draw(self, rng: np.random.Generator, periods: np.ndarray) -> Ops:
        """One op per entry of ``periods`` (the hot-set period of its due time)."""
        n = periods.size
        ranks = self.zipf.draw(rng, n)
        pos = np.empty(n, np.int64)
        for p in np.unique(periods):
            sel = periods == p
            pos[sel] = self._order(int(p))[ranks[sel]]
        kind = np.where(rng.random(n) < self.withdraw_p, WITHDRAW, REPRICE).astype(np.int8)
        scale = rng.uniform(self.lo, self.hi, n).astype(np.float32)
        live = self.live
        for j, a in enumerate(pos.tolist()):  # the state follows the ops in due order
            if kind[j] == WITHDRAW and not live[a]:
                kind[j] = REPRICE
            live[a] = kind[j] == REPRICE
        return Ops(self.agents[pos], kind, scale)


def poisson_due(mix: dict, seconds: float, rng: np.random.Generator, rate=None) -> np.ndarray:
    """Sorted due times in [0, seconds) of ``rate × seconds`` bids."""
    rate = float(mix["rate_per_s"] if rate is None else rate)
    n = int(round(rate * seconds))
    b = mix["burst"]
    factor, width, every = float(b["factor"]), float(b["seconds"]), float(b["every_s"])
    base = every / (every - width + factor * width)  # intensity outside a burst, per unit mean
    # cumulative intensity Λ(t) over one period, piecewise linear; invert it
    knots_t = np.array([0.0, width, every])
    knots_l = np.array([0.0, factor * base * width, factor * base * width + base * (every - width)])
    total = seconds / every * knots_l[-1]
    u = np.sort(rng.random(n)) * total
    whole, part = np.divmod(u, knots_l[-1])
    return whole * every + np.interp(part, knots_l, knots_t)


@dataclasses.dataclass
class Schedule:
    """The window's ops, and each one's due time in seconds from the window's
    start."""

    ops: Ops
    due: np.ndarray


def window_schedule(mix: dict, agents: np.ndarray, seconds: float, seed: int,
                    rate=None) -> Schedule:
    """The window's schedule over ``agents`` (those with a resting bid)."""
    rng_due, rng_ops = seed_streams(seed, 2)
    stream = OpStream(mix, agents, seed)
    due = poisson_due(mix, seconds, rng_due, rate)
    periods = np.floor(due / float(mix["hot_shift_s"])).astype(np.int64)
    return Schedule(stream.draw(rng_ops, periods), due)


def distinct_per_batch(ops: Ops, bounds: np.ndarray) -> np.ndarray:
    """Distinct agents in each slice ``ops[bounds[i]:bounds[i+1]]``."""
    return np.array(
        [np.unique(ops.agent[lo:hi]).size for lo, hi in zip(bounds[:-1], bounds[1:])],
        np.int64,
    )


def pow2_ceil(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()
