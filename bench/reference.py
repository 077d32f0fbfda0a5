"""Plain reference of the served market, independent of the program.

It imports nothing of the program.  Its inputs are the deployment's data as
the benchmark generated it: the agents' resting bids, the pools' capacity and
usage and their base costs, and the ops that the window sent.  From them it
builds its own bid book, its own reserve prices and operator supply rows,
and settles the book with its own ascending clock auction.

The book is a dense array per agent (plus one row per operator pool), so a
re-price overwrites the agent's row and a withdrawal clears it: the
last-write-wins semantics of a service that drains its queue per tick.

The clock is Algorithm 1 of arXiv 2503.17691 with the step of eq. (3) as the
configuration states it: each round every bidder picks its highest-surplus
bundle at the current prices and stays in while that surplus is >= 0; the
excess demand z sums the chosen bundles; pools with z > tol move up by
``min(max(alpha·z/s, floor)·c, delta·max(p, eps·c))``; the clock stops when
no pool has z > tol or after ``max_rounds``.  It runs on the device in
``dtype``: float32 is the reference, bfloat16 its control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.traffic import REPRICE, Ops


def exp_reserve(capacity, usage, base_cost, curve: dict) -> np.ndarray:
    """Reserve per pool, ``phi(psi)·c`` with ``phi = k^(psi^gamma − target^gamma)``
    (the paper's utilization-weighted reserve, §IV), pools flattened
    cluster-major.  ``base_cost`` is per resource type."""
    if curve["curve"] != "exp":
        raise ValueError(f"reference implements the exp reserve curve, not {curve['curve']!r}")
    cap = np.asarray(capacity, np.float64)
    psi = np.clip(np.asarray(usage, np.float64) / np.maximum(cap, 1e-9), 0.0, 1.0)
    k, t, g = float(curve["k"]), float(curve["target"]), float(curve["gamma"])
    phi = k ** (psi**g - t**g)
    return (phi * np.asarray(base_cost, np.float64)[None, :]).reshape(-1)


class Book:
    """Agent rows ``0..N-1`` then one sell row per pool with free capacity."""

    def __init__(self, idx, val, mask, pi, capacity, usage, reserve) -> None:
        n, b, k = idx.shape
        free = np.maximum(np.asarray(capacity, np.float64) - usage, 0.0).reshape(-1)
        pools = np.flatnonzero(free > 1e-9)
        o = pools.size
        # a re-price resubmits the resting bundles in order, packed from
        # position 0: keep the resting rows in that packed form
        order = np.argsort(~np.asarray(mask, bool), axis=1, kind="stable")
        self.rest_idx = np.take_along_axis(np.asarray(idx, np.int32), order[:, :, None], axis=1)
        self.rest_val = np.take_along_axis(np.asarray(val, np.float32), order[:, :, None], axis=1)
        self.rest_mask = np.take_along_axis(np.asarray(mask, bool), order, axis=1)
        self.rest_pi = np.take_along_axis(np.asarray(pi, np.float32), order, axis=1)
        self.idx = np.zeros((n + o, b, k), np.int32)
        self.val = np.zeros((n + o, b, k), np.float32)
        self.mask = np.zeros((n + o, b), bool)
        self.pi = np.zeros((n + o, b), np.float32)
        self.idx[:n], self.val[:n] = idx, val
        self.mask[:n], self.pi[:n] = mask, pi
        self.idx[n:, 0, 0] = pools
        self.val[n:, 0, 0] = (-free[pools]).astype(np.float32)
        self.mask[n:, 0] = True
        self.pi[n:, 0] = (-free[pools] * np.asarray(reserve, np.float64)[pools]).astype(np.float32)
        self.live = self.mask.any(axis=1)
        self.num_resources = int(np.asarray(reserve).size)

    def apply(self, ops: Ops) -> None:
        """Apply ops in order: the last op on an agent decides its row."""
        if not len(ops):
            return
        rev_agents = ops.agent[::-1]
        agents, first = np.unique(rev_agents, return_index=True)
        last = len(ops) - 1 - first
        kind, scale = ops.kind[last], ops.scale[last]
        rep = agents[kind == REPRICE]
        self.idx[rep], self.val[rep] = self.rest_idx[rep], self.rest_val[rep]
        self.mask[rep] = self.rest_mask[rep]
        self.pi[rep] = np.where(
            self.rest_mask[rep], self.rest_pi[rep] * scale[kind == REPRICE][:, None], 0.0
        ).astype(np.float32)
        self.live[agents] = kind == REPRICE

    def live_rows(self):
        """(idx, val, mask, pi) of the live rows."""
        rows = np.flatnonzero(self.live)
        return self.idx[rows], self.val[rows], self.mask[rows], self.pi[rows]

    def device_arrays(self):
        """Every row, with withdrawn rows masked out, so the shapes stay fixed."""
        return (jnp.asarray(self.idx), jnp.asarray(self.val),
                jnp.asarray(self.mask & self.live[:, None]), jnp.asarray(self.pi))

    def supply_scale(self) -> np.ndarray:
        idx, val, _, _ = self.live_rows()
        q = np.bincount(idx.reshape(-1), np.abs(val.reshape(-1)).astype(np.float64),
                        minlength=self.num_resources)
        return np.maximum(q.astype(np.float32), 1.0)

    def offered(self) -> np.ndarray:
        idx, val, _, _ = self.live_rows()
        return np.bincount(idx.reshape(-1), np.maximum(-val.reshape(-1).astype(np.float64), 0.0),
                           minlength=self.num_resources)


def _demand(idx, val, mask, pi, p, num_resources):
    """Each bidder's best bundle at ``p``; returns (z, chosen, active)."""
    cost = jnp.sum(val * p[idx], axis=-1)
    surplus = jnp.where(mask, pi - cost, -jnp.inf)
    chosen = jnp.argmax(surplus, axis=1)
    active = jnp.take_along_axis(surplus, chosen[:, None], axis=1)[:, 0] >= 0
    sel_idx = jnp.take_along_axis(idx, chosen[:, None, None], axis=1)[:, 0]
    sel_val = jnp.take_along_axis(val, chosen[:, None, None], axis=1)[:, 0]
    sel_val = jnp.where(active[:, None], sel_val, jnp.zeros((), val.dtype))
    pools = jnp.arange(num_resources, dtype=idx.dtype)
    z = jnp.sum(
        jnp.where(sel_idx[:, :, None] == pools, sel_val[:, :, None], jnp.zeros((), val.dtype)),
        axis=(0, 1),
    )
    return z, chosen, active


@functools.partial(jax.jit, static_argnames=("num_resources", "max_rounds", "dtype"))
def _clock(idx, val, mask, pi, c, s, p0, params, num_resources, max_rounds, dtype):
    val, pi, c, s = val.astype(dtype), pi.astype(dtype), c.astype(dtype), s.astype(dtype)
    alpha, delta, eps, floor, tol = (x.astype(dtype) for x in params)

    def cond(state):
        t, _, done = state
        return (~done) & (t < max_rounds)

    def body(state):
        t, p, _ = state
        z, _, _ = _demand(idx, val, mask, pi, p, num_resources)
        done = jnp.all(z <= tol)
        rel = jnp.maximum(alpha * jnp.maximum(z, 0) / s, floor)
        step = jnp.minimum(rel * c, delta * jnp.maximum(p, eps * c))
        p = jnp.where(done, p, jnp.where(z > tol, p + step, p))
        return t + 1, p, done

    rounds, p, _ = jax.lax.while_loop(cond, body, (jnp.int32(0), p0.astype(dtype), False))
    z, chosen, active = _demand(idx, val, mask, pi, p, num_resources)
    return p, rounds, jnp.all(z <= tol)


@functools.partial(jax.jit, static_argnames=("num_resources", "dtype"))
def _outcome(idx, val, mask, pi, p, num_resources, dtype):
    """Excess demand and settled buy units per pool at prices ``p``."""
    val, pi, p = val.astype(dtype), pi.astype(dtype), p.astype(dtype)
    z, chosen, active = _demand(idx, val, mask, pi, p, num_resources)
    sel_idx = jnp.take_along_axis(idx, chosen[:, None, None], axis=1)[:, 0]
    sel_val = jnp.take_along_axis(val, chosen[:, None, None], axis=1)[:, 0]
    won = jnp.where(active[:, None], jnp.maximum(sel_val, 0), jnp.zeros((), val.dtype))
    pools = jnp.arange(num_resources, dtype=idx.dtype)
    bought = jnp.sum(jnp.where(sel_idx[:, :, None] == pools, won[:, :, None],
                               jnp.zeros((), val.dtype)), axis=(0, 1))
    return z, bought


def settle(book: Book, base_cost, start, clock: dict, dtype=jnp.float32):
    """Settle the live book from ``start``: (prices, rounds, converged), float64
    on the host."""
    idx, val, mask, pi = book.device_arrays()
    params = tuple(jnp.asarray(clock[k], jnp.float32)
                   for k in ("alpha", "delta", "price_floor_frac", "step_floor_frac", "tol"))
    with jax.default_matmul_precision("highest"):
        p, rounds, conv = _clock(
            idx, val, mask, pi, jnp.asarray(base_cost, jnp.float32), jnp.asarray(book.supply_scale()),
            jnp.asarray(start, jnp.float32), params, book.num_resources,
            int(clock["max_rounds"]), dtype,
        )
    return np.asarray(p.astype(jnp.float32), np.float64), int(rounds), bool(conv)


def outcome(book: Book, prices, dtype=jnp.float32):
    """(excess demand z, settled utilization psi) of the book at ``prices``."""
    idx, val, mask, pi = book.device_arrays()
    with jax.default_matmul_precision("highest"):
        z, bought = _outcome(idx, val, mask, pi, jnp.asarray(prices, jnp.float32),
                             book.num_resources, dtype)
    offered = book.offered()
    psi = np.divide(np.asarray(bought.astype(jnp.float32), np.float64), offered,
                    out=np.zeros_like(offered), where=offered > 0)
    return np.asarray(z.astype(jnp.float32), np.float64), psi


def clock_step_bound(clock: dict, prices, base_cost) -> np.ndarray:
    """Largest move one clock round can make per pool: ``delta·max(p, eps·c)``."""
    return float(clock["delta"]) * np.maximum(
        np.asarray(prices, np.float64), float(clock["price_floor_frac"]) * np.asarray(base_cost)
    )
