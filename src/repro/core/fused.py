"""One fused epoch program: device-resident market state, donated buffers.

The staged epoch path (:meth:`repro.core.economy.Economy._settle_epoch`)
crosses the host boundary several times per epoch: numpy bid packing, the
host ``surplus_and_trade`` reduction, and the numpy settlement apply.  This
module collapses pack → clock → settle → verify → surplus → apply into ONE
jitted program over device-resident population state, compiled exactly once
per economy shape:

* the bid book is assembled in-trace on a **fixed slot layout** — slot ``p``
  (p < R) is pool p's operator lot, slots ``R + 2i`` / ``R + 2i + 1`` are
  agent i's sell and buy rows — padded with dead rows (idx 0, val 0, mask
  False, π = −inf) exactly like the padded packers pad, so the selection,
  settle, and verify programs see bit-identical live rows at a static shape;
* the epoch's dynamic row count ``U`` never changes the trace: the blocked
  excess-demand fold scatters per-user demand rows into their staged block
  positions (computed from the *exclusive cumsum* of slot presence, which
  equals the staged row index), and the staged numpy ``surplus_and_trade``
  pairwise reduction is reproduced in-trace with a fixed fold;
* mutable market state (``placed``/``home``/``fill_rate``/``usage``/
  ``belief``) enters as **donated buffers** and leaves as the corresponding
  ``*_new`` outputs, so state stays device-resident across epochs with no
  host round-trip and no per-epoch re-jit.

Bit-parity contract: for books with ``U_cap = R + 2N ≤ 128`` (the regime the
parity suite pins, e.g. the fleet protocol economies) every output is
bit-identical to the staged vectorized path — same prices, payments,
EpochStats, and end state.  Beyond 128 rows the program is the same market
(and the fast path for the 100k-agent benchmark) but the surplus fold and
the zero-extended block sums may differ from staged numpy by
reduction-order ulps; the staged path remains the oracle there.

Numerics notes (all empirically pinned by the parity/property suites):

* ``_exact_mul`` guards products that feed an add against FMA contraction
  (XLA may contract ``a*b + c``; numpy never does);
* multiplications by exactly-representable factors (0.25, 0.5, 0.75,
  powers of two, 0/1 masks) are contraction-safe unguarded;
* scatter-adds (``.at[].add``) are sequential in operand order on CPU,
  matching ``np.add.at`` bit for bit; out-of-bounds indices drop, which is
  how masked rows are discarded without data-dependent shapes;
* the staged numpy ``np.sum`` over the (U,) surplus contributions is
  mirrored by ``_npsum_f32`` — numpy's unrolled-8 pairwise summation with a
  dynamic length over a static 128-slot buffer.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .auction import (
    ClockConfig,
    _chain_sum,
    _run_clock,
    _sparse_selection,
    _sparse_settle,
    _user_rows,
    escalate_clock,
    sparse_bundle_costs,
)

# staged constants mirrored verbatim (economy.py / verify defaults)
SELL_DISCOUNT = 1.0 - 0.15
FILL_EMA = 0.5
VERIFY_ATOL = 1e-3
# largest book (rows) for which the in-trace surplus fold and zero-extended
# block sums are pinned bit-identical to staged numpy on this backend
PARITY_MAX_ROWS = 128


def _exact_mul(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a * b`` with FMA contraction blocked (parity-grade product).

    Routing the product through a comparison forces XLA to materialize the
    rounded product instead of contracting it into a downstream add.  The
    products guarded here are finite, so the NaN arm is dead.
    """
    p = a * b
    return jnp.where(p == p, p, jnp.zeros_like(p))


def _npsum_f32(buf: jax.Array, n: jax.Array) -> jax.Array:
    """numpy ``np.sum``'s pairwise f32 fold over ``buf[:n]``, in-trace.

    ``buf`` is a static ``(128,)`` f32 buffer whose first ``n`` (dynamic)
    entries are the summands and whose tail is zero.  Mirrors numpy's
    unrolled-8 accumulator loop for n ≤ 128: eight lanes fold the main body
    ``n - n % 8`` in row order, combine pairwise, then the ≤7-element tail
    adds sequentially.  For n < 8 the main body is empty and the tail alone
    reproduces numpy's sequential small-n fold (up to +0.0-vs-−0.0 on an
    all-negative-zero sum, which washes out of every downstream comparison).
    """
    n_main = n - n % 8
    iota = jnp.arange(128)
    masked = jnp.where(iota < n_main, buf, jnp.float32(0.0))
    lanes = masked.reshape(16, 8)
    r = lanes[0]
    for c in range(1, 16):
        r = r + lanes[c]
    res = ((r[0] + r[1]) + (r[2] + r[3])) + ((r[4] + r[5]) + (r[6] + r[7]))
    for k in range(7):
        pos = n_main + k
        res = res + jnp.where(
            pos < n, buf[jnp.clip(pos, 0, 127)], jnp.float32(0.0)
        )
    return res


@dataclasses.dataclass
class DeviceMarketState:
    """Device-resident twin of the economy's mutable market state.

    One jax array per field, living on device across epochs; the fused
    program donates them in and returns the next epoch's arrays.  Host
    mirrors stay authoritative for RNG-free bookkeeping (faults, policies,
    agent arrival/departure) — ``dirty`` marks mirrors that must re-upload.
    """

    placed: jax.Array  # (N,) int64
    home: jax.Array  # (N,) int64
    fill_rate: jax.Array  # (N,) float64
    usage: jax.Array  # (C, T) float64
    belief: jax.Array  # (R,) float64

    @classmethod
    def from_host(
        cls,
        pop,
        usage: np.ndarray,
        belief: np.ndarray,
        capacity: int | None = None,
    ):
        """Upload host mirrors; ``capacity > len(pop)`` pads the per-agent
        fields with inert slots (placed/home −1, fill_rate 1.0) so a
        slack-padded fused program (``Economy(fused_slack=True)``) keeps one
        compiled trace across bounded population churn.  Inert slots carry
        ``dropout=True`` on dispatch, which zeroes their presence mask."""
        n = int(len(pop.placed))
        cap = n if capacity is None else int(capacity)
        if cap < n:
            raise ValueError(f"device capacity {cap} < population {n}")
        placed, home, fill = pop.placed, pop.home, pop.fill_rate
        if cap > n:
            pad_i = np.full(cap - n, -1, dtype=placed.dtype)
            placed = np.concatenate([placed, pad_i])
            home = np.concatenate([home, pad_i])
            fill = np.concatenate([fill, np.ones(cap - n, fill.dtype)])
        with jax.enable_x64(True):
            return cls(
                placed=jnp.asarray(placed),
                home=jnp.asarray(home),
                fill_rate=jnp.asarray(fill),
                usage=jnp.asarray(usage),
                belief=jnp.asarray(belief),
            )


def build_fused_epoch(
    *,
    num_agents: int,
    num_clusters: int,
    num_rtypes: int,
    clock: ClockConfig,
    clock_retries: int = 0,
    ration_fallback: bool = False,
    settle_blocks: int = 8,
    backend: str | None = "jnp",
):
    """Compile-once fused epoch program for a fixed economy shape.

    Returns a jitted callable ``fused(const, state, inputs) -> outputs``
    where ``const`` is the tuple of immutable population arrays, ``state``
    the donated :class:`DeviceMarketState` buffers, and ``inputs`` the
    per-epoch host-computed overlays (reserve curve, start prices, fault
    views, policy overlays, epoch randomness).  Every array is always
    passed — overlay defaults are bit-neutral — so fault and no-fault
    epochs, warm and cold starts, policies on and off all share ONE trace.

    ``backend`` routes the in-loop excess-demand evaluation through
    :mod:`repro.kernels.ops` (``"pallas"`` / ``"interpret"``): the kernel's
    O(nnz) scatter z replaces the blocked fold *inside the price loop*,
    while selection, settlement, and the convergence check stay on the
    parity-exact jnp path.  ``"jnp"`` is the bit-parity program.
    """
    if clock.break_ties:
        raise ValueError(
            "fused epochs do not support break_ties: the tie jitter is "
            "indexed by global row position, which the fused slot layout "
            "does not preserve for dynamic books"
        )
    N, C, T = int(num_agents), int(num_clusters), int(num_rtypes)
    R = C * T
    K = max(T, 1)
    U_cap = R + 2 * N
    nb = int(settle_blocks)
    m_cap = (U_cap + nb - 1) // nb
    # statically pre-escalated configs for the bounded-retry ladder: stage k
    # re-runs the clock only if stage k-1 left excess demand, via lax.cond,
    # so the escalation path is part of the single compiled program
    cfgs = [clock]
    for _ in range(int(clock_retries)):
        cfgs.append(escalate_clock(cfgs[-1]))

    from ..kernels.ops import fused_epoch_z_fn

    kernel_z = fused_epoch_z_fn(backend, R)

    def _demand(idx, val, mask, pi, prices, q, present, U):
        """Blocked settlement demand at the static slot shape.

        Per-user rows scatter into their *staged* block positions — block
        ``q // ceil(U / nb)``, offset ``q % ceil(U / nb)`` — so the fixed
        left-fold over blocks reproduces the staged
        ``sparse_proxy_demand_blocked`` z for the dynamic row count.
        Absent slots scatter out of bounds and drop.
        """
        sel_idx, sel_val, chosen, active = _sparse_selection(
            idx, val, mask, pi, prices
        )
        x = _user_rows(sel_idx, sel_val, R)  # (U_cap, R) f32
        m_st = (U + nb - 1) // nb
        blk = jnp.where(present, q // m_st, nb)  # nb = out of bounds: dropped
        off = jnp.where(present, q % m_st, 0)
        buf = jnp.zeros((nb, m_cap, R), jnp.float32).at[blk, off].add(x)
        z = _chain_sum(buf.sum(axis=1))
        return z, chosen, active

    def fused_epoch(const, state, inputs):
        (req, value, reloc, mobility, budget) = const
        (placed, home, fill_rate, usage, belief) = state
        (
            u_arb, perm_keys, pi_scale, arb, margin, dropout,
            cap_eff, free_basis, tilde_p, start, base_cost_flat,
        ) = inputs

        f32, f64 = jnp.float32, jnp.float64
        t_ar = jnp.arange(T, dtype=jnp.int64)
        c_ar = jnp.arange(C, dtype=jnp.int64)

        # ---- pack: who bids, and what (staged packer, in-trace) -----------
        psi_flat = jnp.clip(
            usage / jnp.maximum(cap_eff, 1e-9), 0.0, 1.0
        ).reshape(-1)
        free = jnp.maximum(free_basis - usage, 0.0).reshape(-1)
        pl_safe = jnp.clip(placed, 0, C - 1)
        psi_home0 = psi_flat[pl_safe * T]
        sells = (
            (placed >= 0) & (arb > 0) & (u_arb < arb) & (psi_home0 > 0.75)
        ) & ~dropout
        wants = ((placed < 0) | sells) & ~dropout

        # believed bundle costs, the staged f64 t-order fold (FMA-guarded)
        p_ct = belief.reshape(C, T)
        believed = jnp.zeros((N, C), f64)
        for t in range(T):
            believed = believed + _exact_mul(req[:, t, None], p_ct[None, :, t])

        # reach: stable argsort of the epoch keys, home first, reach-truncated
        perm = jnp.argsort(perm_keys, axis=1)
        pos = jnp.argsort(perm, axis=1)  # exact inverse permutation
        n_reach = jnp.minimum(
            jnp.maximum(1, jnp.rint(mobility * C).astype(jnp.int64)), C
        )
        key = pos.astype(f64)
        key = jnp.where(pos >= n_reach[:, None], jnp.inf, key)
        at_home = (home >= 0)[:, None] & (c_ar[None, :] == home[:, None])
        key = jnp.where(at_home, -1.0, key)
        order = jnp.argsort(key, axis=1).astype(jnp.int64)
        valid = c_ar[None, :] < n_reach[:, None]

        raw_value = value[:, None] - reloc[:, None] * (
            c_ar[None, :] != home[:, None]
        ).astype(f64)
        pi_nc = jnp.minimum(
            jnp.minimum(raw_value, believed * (1.0 + margin)[:, None]),
            budget[:, None],
        )
        pi_nc = pi_nc * pi_scale[:, None]
        bcc = jnp.where(valid, order, 0)
        pi_buy = jnp.where(
            valid,
            jnp.take_along_axis(pi_nc, bcc, axis=1).astype(f32),
            f32(-jnp.inf),
        )
        exp_rev = jnp.take_along_axis(believed, pl_safe[:, None], axis=1)[:, 0]
        pi_sell = ((-exp_rev) * SELL_DISCOUNT).astype(f32)

        # ---- slot-layout book (U_cap, C, K): ops, then sell/buy per agent --
        present_op = free > 1e-9
        neg_free32 = (-free).astype(f32)
        tilde64 = tilde_p.astype(f64)
        idx_op = jnp.zeros((R, C, K), jnp.int32)
        idx_op = idx_op.at[:, 0, 0].set(
            jnp.where(present_op, jnp.arange(R, dtype=jnp.int32), 0)
        )
        val_op = jnp.zeros((R, C, K), f32)
        val_op = val_op.at[:, 0, 0].set(
            jnp.where(present_op, neg_free32, f32(0.0))
        )
        mask_op = jnp.zeros((R, C), bool).at[:, 0].set(present_op)
        pi_op = jnp.full((R, C), -jnp.inf, f32)
        pi_op = pi_op.at[:, 0].set(
            jnp.where(
                present_op, ((-free) * tilde64).astype(f32), f32(-jnp.inf)
            )
        )

        sell_idx = (pl_safe[:, None] * T + t_ar[None, :]).astype(jnp.int32)
        sell_val = (-req).astype(f32)
        idx_sell = jnp.zeros((N, C, K), jnp.int32)
        idx_sell = idx_sell.at[:, 0, :].set(
            jnp.where(sells[:, None], sell_idx, 0)
        )
        val_sell = jnp.zeros((N, C, K), f32)
        val_sell = val_sell.at[:, 0, :].set(
            jnp.where(sells[:, None], sell_val, f32(0.0))
        )
        mask_sell = jnp.zeros((N, C), bool).at[:, 0].set(sells)
        pi_sell_row = jnp.full((N, C), -jnp.inf, f32)
        pi_sell_row = pi_sell_row.at[:, 0].set(
            jnp.where(sells, pi_sell, f32(-jnp.inf))
        )

        live_buy = wants[:, None] & valid
        idx_buy = jnp.where(
            live_buy[:, :, None],
            (bcc[:, :, None] * T + t_ar[None, None, :]).astype(jnp.int32),
            0,
        )
        val_buy = jnp.where(
            live_buy[:, :, None],
            jnp.broadcast_to(req.astype(f32)[:, None, :], (N, C, K)),
            f32(0.0),
        )
        pi_buy_row = jnp.where(live_buy, pi_buy, f32(-jnp.inf))

        idx = jnp.concatenate(
            [idx_op, jnp.stack([idx_sell, idx_buy], 1).reshape(2 * N, C, K)]
        )
        val = jnp.concatenate(
            [val_op, jnp.stack([val_sell, val_buy], 1).reshape(2 * N, C, K)]
        )
        mask = jnp.concatenate(
            [mask_op, jnp.stack([mask_sell, live_buy], 1).reshape(2 * N, C)]
        )
        pi = jnp.concatenate(
            [pi_op, jnp.stack([pi_sell_row, pi_buy_row], 1).reshape(2 * N, C)]
        )
        present = jnp.concatenate(
            [present_op, jnp.stack([sells, wants], 1).reshape(2 * N)]
        )
        q = jnp.cumsum(present) - present  # exclusive: the staged row index
        U = present.sum()

        # supply normalizer: same f32 running scatter as the staged CSR pack
        # (dead entries add exact +0.0 at pool 0 — float no-ops)
        supply = jnp.maximum(
            jnp.zeros((R,), f32)
            .at[idx.reshape(-1)]
            .add(jnp.abs(val.reshape(-1))),
            1.0,
        )

        # ---- clock + bounded-retry escalation ladder ----------------------
        def excess(prices):
            if kernel_z is not None:
                return kernel_z(idx, val, mask, pi, prices)
            z, _, _ = _demand(idx, val, mask, pi, prices, q, present, U)
            return z

        tol = f32(clock.tol)
        rounds, prices = _run_clock(excess, start, cfgs[0], base_cost_flat, supply)
        conv = jnp.all(excess(prices) <= tol)
        esc = jnp.int32(0)
        for cfg_k in cfgs[1:]:
            do = ~conv
            esc = esc + do.astype(jnp.int32)

            def _stage(p, _cfg=cfg_k):
                return _run_clock(excess, p, _cfg, base_cost_flat, supply)

            rounds_k, prices = jax.lax.cond(
                do, _stage, lambda p: (rounds, p), prices
            )
            rounds = jnp.where(do, rounds_k, rounds)
            conv = jnp.all(excess(prices) <= tol)

        z, chosen, active = _demand(idx, val, mask, pi, prices, q, present, U)
        converged = jnp.all(z <= tol)
        _, _, payments = _sparse_settle(idx, val, prices, chosen, active, R, exact=True)

        # ---- SYSTEM verify (vector-π checks; dead rows are vacuous) -------
        costs = sparse_bundle_costs(idx, val, mask, prices)
        surplus_m = jnp.where(mask, pi - costs, -jnp.inf)
        best = jnp.max(surplus_m, axis=1)
        won_sur = jnp.take_along_axis(
            surplus_m, jnp.maximum(chosen, 0)[:, None], axis=1
        )[:, 0]
        scale_v = 1.0 + jnp.abs(payments)
        atol = VERIFY_ATOL
        sys_ok = (
            jnp.all(jnp.where(active, chosen >= 0, True))
            & jnp.all(z <= atol)
            & jnp.all(jnp.where(active, won_sur >= -atol * scale_v, True))
            & jnp.all(jnp.where(active, won_sur >= best - atol * scale_v, True))
            & jnp.all(jnp.where(~active, best < atol * scale_v, True))
            & jnp.all(prices >= -atol)
        )

        # ---- surplus & value-of-trade: staged host np.sum, mirrored -------
        pi_taken = jnp.take_along_axis(
            pi, jnp.maximum(chosen, 0)[:, None], axis=1
        )[:, 0]
        c_surplus = jnp.where(active, pi_taken - payments, f32(0.0))
        c_trade = jnp.where(active & (payments > 0), payments, f32(0.0))
        if U_cap <= PARITY_MAX_ROWS:
            slot = jnp.where(present, q, PARITY_MAX_ROWS)
            surplus = _npsum_f32(
                jnp.zeros((PARITY_MAX_ROWS + 1,), f32).at[slot].set(c_surplus)[:128],
                U,
            )
            trade = _npsum_f32(
                jnp.zeros((PARITY_MAX_ROWS + 1,), f32).at[slot].set(c_trade)[:128],
                U,
            )
        else:  # beyond the parity regime: one flat fold (float-close)
            surplus = jnp.sum(c_surplus)
            trade = jnp.sum(c_trade)

        # ---- apply: usage commit, placements, fills, beliefs --------------
        agent_act = active[R:].reshape(N, 2)
        won_sell, won_buy = agent_act[:, 0], agent_act[:, 1]
        pay_agent = payments[R:].reshape(N, 2)
        pi_agent = pi_taken[R:].reshape(N, 2)
        chosen_buy = chosen[R:].reshape(N, 2)[:, 1]
        bc_sel = jnp.take_along_axis(
            order, jnp.maximum(chosen_buy, 0)[:, None], axis=1
        )[:, 0]

        oob = jnp.int64(C)  # scatter target for masked rows: dropped
        delta = jnp.zeros((C, T), f64)
        delta = delta.at[jnp.where(won_sell, placed, oob)].add(-req)
        placed_eff = jnp.where(won_sell, -1, placed)
        old = placed_eff
        move = won_buy & (old >= 0) & (old != bc_sel)

        if ration_fallback:
            released = delta.at[jnp.where(move, old, oob)].add(-req)
            room = jnp.maximum(
                cap_eff - jnp.maximum(usage + released, 0.0), 0.0
            )
            claim = (
                jnp.zeros((C, T), f64)
                .at[jnp.where(won_buy, bc_sel, oob)]
                .add(req)
            )
            frac = jnp.where(
                claim > 1e-12,
                jnp.minimum(room / jnp.maximum(claim, 1e-12), 1.0),
                1.0,
            )
            per = jnp.where(req > 0, frac[bc_sel], 1.0)
            scale_r = per.min(axis=1)
            ration_on = ~converged  # staged: ration_fallback and not converged
            buy_scale = jnp.where(ration_on & won_buy, scale_r, 1.0)
            rationed = jnp.where(
                ration_on,
                (won_buy & (scale_r < 1.0 - 1e-12)).sum(),
                0,
            ).astype(jnp.int64)
        else:
            buy_scale = jnp.ones((N,), f64)
            rationed = jnp.int64(0)

        delta = delta.at[jnp.where(won_buy, bc_sel, oob)].add(
            _exact_mul(buy_scale[:, None], req)
        )
        delta = delta.at[jnp.where(move, old, oob)].add(-req)
        usage_new = jnp.clip(usage + delta, 0.0, cap_eff)

        placed_new = jnp.where(won_buy, bc_sel, jnp.where(won_sell, -1, placed))
        home_new = jnp.where(won_buy, bc_sel, home)
        fill_new = jnp.where(
            wants,
            (1.0 - FILL_EMA) * fill_rate + FILL_EMA * won_buy.astype(f64),
            fill_rate,
        )
        belief_new = 0.25 * belief + (f32(0.75) * prices).astype(f64)

        return {
            "prices": prices,
            "rounds": rounds,
            "converged": converged,
            "escalations": esc,
            "system_ok": sys_ok,
            "surplus": surplus,
            "value_of_trade": trade,
            "sells": sells,
            "wants": wants,
            "won_sell": won_sell,
            "won_buy": won_buy,
            "pay_sell": pay_agent[:, 0],
            "pay_buy": pay_agent[:, 1],
            "pi_sell": pi_agent[:, 0],
            "pi_buy": pi_agent[:, 1],
            "buy_cluster": bc_sel,
            "buy_scale": buy_scale,
            "rationed_rows": rationed,
            "placed_new": placed_new,
            "home_new": home_new,
            "fill_new": fill_new,
            "usage_new": usage_new,
            "belief_new": belief_new,
        }

    # donate the mutable market state and the consumed epoch randomness:
    # state buffers are replaced by the *_new outputs (device-resident
    # chain), u_arb's buffer is recycled for a same-shape output
    return jax.jit(fused_epoch, donate_argnums=(1,))


def fused_program_cache_size(fn: Any) -> int:
    """Number of compiled variants a fused program holds (recompile guard)."""
    return int(fn._cache_size())
