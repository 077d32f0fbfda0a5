"""Ascending clock auction (paper §III, Algorithm 1) — fully vectorized JAX.

The auctioneer holds a price clock p ∈ ℝ^R.  Each simulated round, every
bidder proxy reports its demand at the current prices:

    G_u(p) = q̂_u · 1[q̂_uᵀ p ≤ π_u],      q̂_u = argmin_{q ∈ Q_u} qᵀ p    (eq. 1-2)

If the excess demand z = Σ_u x_u has any positive component, those prices tick
up by  g(x, p) = min(α·z⁺/s · c,  δ·max(p, ε·c))  (eq. 3 plus the paper's
base-cost normalization and fixed-fraction cap) and the loop repeats.  The
whole multi-round clock is a single ``jax.lax.while_loop`` — one XLA program,
no host round-trips — so settlement for 10⁵ bidders × 10³ pools runs in
milliseconds (paper §III.C.4 reports minutes for 10²×10² in plain Python).

Two proxy semantics are supported:

* scalar π (paper-exact): proxies chase the *cheapest* bundle in Q_u and stay
  in while it is affordable;
* vector π (U, B) (the extension the paper notes "does not significantly
  change our results"): proxies chase the *highest-surplus* bundle
  argmax_b (π_b − q_bᵀp) and stay in while surplus ≥ 0.  The economy layer
  uses this to express per-cluster relocation costs.

Because z = Σ_u x_u is a pure sum over bidders, the clock shards over a
device mesh: :func:`sharded_clock_auction` splits users across a ``users``
axis, evaluates per-shard demand with the same sparse kernels, and reduces z
across shards *inside* the ``lax.while_loop`` — the whole multi-round clock
stays one XLA program per device.  The cross-shard reduction is an
``all_gather`` of per-block partial sums followed by a fixed left-fold (our
deterministic psum), so settlement on 1 and N devices is bit-identical —
see :func:`sparse_proxy_demand_blocked`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .types import (
    AuctionProblem,
    AuctionResult,
    CSRAuctionProblem,
    CSRDemandAux,
    SparseAuctionProblem,
    SparseAuctionResult,
    csr_demand_aux,
    csr_padded_views,
    pad_users,
    padded_from_csr,
)

# dense demand_fn(bundles, mask, pi, prices) -> (x (U,R), chosen (U,), active (U,))
# sparse demand_fn(idx, val, mask, pi, prices, num_resources)
#     -> (z (R,), chosen (U,), active (U,))   [tagged sparse_signature=True]
DemandFn = Callable[..., tuple[jax.Array, jax.Array, jax.Array]]


def bundle_costs(bundles: jax.Array, mask: jax.Array, prices: jax.Array) -> jax.Array:
    """(U,B,R)·(R,) → (U,B) with +inf on padded XOR slots."""
    costs = jnp.einsum(
        "ubr,r->ub", bundles, prices, preferred_element_type=jnp.float32
    )
    return jnp.where(mask, costs, jnp.inf)


def proxy_demand(
    bundles: jax.Array, mask: jax.Array, pi: jax.Array, prices: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Paper eq. (1)-(2) bidder proxies, vectorized over all users.

    With scalar π (pi.ndim == 1) this is exactly the paper's rule.  With
    per-bundle π (pi.ndim == 2) the proxy maximizes surplus instead.
    """
    costs = bundle_costs(bundles, mask, prices)  # (U, B)
    if pi.ndim == 1:
        bhat = jnp.argmin(costs, axis=1)  # cheapest alternative
        cost_hat = jnp.take_along_axis(costs, bhat[:, None], axis=1)[:, 0]
        active = cost_hat <= pi  # affordable?  (also correct for sellers)
    else:
        surplus = jnp.where(mask, pi - costs, -jnp.inf)  # (U, B)
        bhat = jnp.argmax(surplus, axis=1)
        s_hat = jnp.take_along_axis(surplus, bhat[:, None], axis=1)[:, 0]
        active = s_hat >= 0.0
    x = jnp.take_along_axis(bundles, bhat[:, None, None], axis=1)[:, 0, :]
    x = x * active[:, None].astype(x.dtype)
    chosen = jnp.where(active, bhat, -1)
    return x, chosen, active


def sparse_bundle_costs(
    idx: jax.Array, val: jax.Array, mask: jax.Array, prices: jax.Array
) -> jax.Array:
    """O(U·B·K) bundle costs: gather prices by idx, per-bundle dot.

    Padded slots (idx=0, val=0) gather pool 0's price and contribute exactly
    0, and nonzeros are stored in ascending pool order, so the K-term fold
    matches the dense row reduction bit for bit.
    """
    gathered = prices.astype(jnp.float32)[idx]  # (U, B, K)
    costs = jnp.sum(val.astype(jnp.float32) * gathered, axis=-1)  # (U, B)
    return jnp.where(mask, costs, jnp.inf)


def _sparse_selection(
    idx: jax.Array, val: jax.Array, mask: jax.Array, pi: jax.Array, prices: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-user bundle choice shared by every sparse proxy variant.

    Returns (sel_idx (U, K), sel_val (U, K) with inactive users zeroed,
    chosen (U,), active (U,)).  All ops are per-user, so evaluating a shard
    of users produces bit-identical rows to evaluating the full problem.
    """
    costs = sparse_bundle_costs(idx, val, mask, prices)  # (U, B)
    if pi.ndim == 1:
        bhat = jnp.argmin(costs, axis=1)
        cost_hat = jnp.take_along_axis(costs, bhat[:, None], axis=1)[:, 0]
        active = cost_hat <= pi
    else:
        surplus = jnp.where(mask, pi - costs, -jnp.inf)
        bhat = jnp.argmax(surplus, axis=1)
        s_hat = jnp.take_along_axis(surplus, bhat[:, None], axis=1)[:, 0]
        active = s_hat >= 0.0
    sel_idx = jnp.take_along_axis(idx, bhat[:, None, None], axis=1)[:, 0, :]
    sel_val = jnp.take_along_axis(val, bhat[:, None, None], axis=1)[:, 0, :]
    sel_val = sel_val.astype(jnp.float32) * active[:, None]
    chosen = jnp.where(active, bhat, -1)
    return sel_idx, sel_val, chosen, active


def sparse_proxy_demand(
    idx: jax.Array,
    val: jax.Array,
    mask: jax.Array,
    pi: jax.Array,
    prices: jax.Array,
    num_resources: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sparse twin of :func:`proxy_demand` — returns (z, chosen, active).

    Excess demand is scattered straight into the (R,) accumulator
    (``segment_sum`` over the selected bundles' nonzeros); the (U, R) demand
    matrix is never materialized.  Supports scalar-π (cheapest affordable
    bundle) and vector-π (max-surplus bundle) semantics, like the dense path.
    """
    sel_idx, sel_val, chosen, active = _sparse_selection(idx, val, mask, pi, prices)
    z = (
        jnp.zeros((num_resources,), jnp.float32)
        .at[sel_idx.reshape(-1)]
        .add(sel_val.reshape(-1))
    )
    return z, chosen, active


sparse_proxy_demand.sparse_signature = True  # type: ignore[attr-defined]


# Below this resource count _user_rows trades the scatter for K one-hot
# compare-and-add passes: bit-identical output (adding an exact 0.0 between
# matching terms is a float no-op, so every (u, r) cell accumulates the same
# nonzero values in the same k order), but vectorizable where CPU/TPU
# scatter serializes.  Economy books (R = clusters × rtypes, tens of pools)
# live far below it; kilopools markets keep the O(U·K) scatter.
_ONEHOT_ROWS_MAX_R = 128


def _user_rows(sel_idx: jax.Array, sel_val: jax.Array, num_resources: int) -> jax.Array:
    """(U, R) demand rows from the selected bundles (duplicate idx sum)."""
    num_users, k = sel_idx.shape
    if num_resources <= _ONEHOT_ROWS_MAX_R:
        r_iota = jnp.arange(num_resources, dtype=sel_idx.dtype)[None, :]
        x = jnp.zeros((num_users, num_resources), jnp.float32)
        for kk in range(k):
            x = x + jnp.where(
                r_iota == sel_idx[:, kk, None],
                sel_val[:, kk, None].astype(jnp.float32),
                0.0,
            )
        return x
    rows = jnp.repeat(jnp.arange(num_users), k)
    return (
        jnp.zeros((num_users, num_resources), jnp.float32)
        .at[rows, sel_idx.reshape(-1)]
        .add(sel_val.reshape(-1))
    )


def sparse_proxy_demand_exact(
    idx: jax.Array,
    val: jax.Array,
    mask: jax.Array,
    pi: jax.Array,
    prices: jax.Array,
    num_resources: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Bit-compatible twin of :func:`sparse_proxy_demand`.

    A direct (nnz,)→(R,) scatter-add associates the per-resource sum
    differently from the dense path's (U, R) column reduction, which shifts z
    by ~1 ulp and lets clock trajectories drift.  This variant scatters the
    selected bundles into per-user rows first and column-sums them — the
    identical reduction the dense reference runs — so swapping a dense
    problem for its sparsified twin reproduces prices bit for bit.  Costs and
    selection stay O(U·B·K); only z accumulation pays the O(U·R) the dense
    baseline paid.  Use the default scatter variant at planet scale.
    """
    sel_idx, sel_val, chosen, active = _sparse_selection(idx, val, mask, pi, prices)
    x = _user_rows(sel_idx, sel_val, num_resources)
    return x.sum(axis=0), chosen, active


sparse_proxy_demand_exact.sparse_signature = True  # type: ignore[attr-defined]
sparse_proxy_demand_exact.exact_settlement = True  # type: ignore[attr-defined]


def _chain_sum(partials: jax.Array) -> jax.Array:
    """Left-fold ``((p₀ + p₁) + p₂) + …`` with a fixed, unrolled association.

    This is the one cross-block reduction every settlement path shares.  XLA
    is free to pick any association for ``x.sum(axis=0)``, and a psum's
    reduction order is backend-defined — but an explicit unrolled fold is the
    same expression tree no matter how the blocks were produced, which is
    what makes 1-device and N-device settlement bit-identical.
    """
    z = partials[0]
    for i in range(1, partials.shape[0]):
        z = z + partials[i]
    return z


def _user_block_partials(
    sel_idx: jax.Array, sel_val: jax.Array, num_resources: int, num_blocks: int
) -> jax.Array:
    """(num_blocks, R) partial demand sums over contiguous user blocks.

    Users are zero-padded up to a multiple of ``num_blocks`` and each block
    of ``U_pad / num_blocks`` per-user rows is column-summed on its own.  The
    per-block reduce extent is therefore independent of how many devices the
    users were split across — a shard holding ``num_blocks / D`` blocks
    computes bit-identical partials to the same blocks of the unsharded run.
    """
    x = _user_rows(sel_idx, sel_val, num_resources)
    pad = -x.shape[0] % num_blocks
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, num_resources), jnp.float32)])
    return x.reshape(num_blocks, -1, num_resources).sum(axis=1)


def _blocked_demand_parts(
    idx: jax.Array,
    val: jax.Array,
    mask: jax.Array,
    pi: jax.Array,
    prices: jax.Array,
    num_resources: int,
    num_blocks: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(block partials (num_blocks, R), chosen, active) — the sharded clock
    calls this per shard with its local slice of blocks."""
    sel_idx, sel_val, chosen, active = _sparse_selection(idx, val, mask, pi, prices)
    partials = _user_block_partials(sel_idx, sel_val, num_resources, num_blocks)
    return partials, chosen, active


def sparse_proxy_demand_blocked(
    idx: jax.Array,
    val: jax.Array,
    mask: jax.Array,
    pi: jax.Array,
    prices: jax.Array,
    num_resources: int,
    num_blocks: int = 8,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Settlement-grade sparse demand whose z is device-count-invariant.

    Same selection and per-user rows as :func:`sparse_proxy_demand_exact`,
    but z is accumulated as a fixed left-fold over ``num_blocks`` contiguous
    user-block partials instead of one flat column sum.
    :func:`sharded_clock_auction` computes the identical block partials
    shard-locally, all_gathers them, and runs the identical fold — so prices,
    allocations, and payments from 1 device and from any D | ``num_blocks``
    devices agree bit for bit (verified on 2/4/8 virtual CPU devices).  This
    is what :meth:`repro.core.economy.Economy.run_epoch` settles with.
    """
    partials, chosen, active = _blocked_demand_parts(
        idx, val, mask, pi, prices, num_resources, num_blocks
    )
    return _chain_sum(partials), chosen, active


sparse_proxy_demand_blocked.sparse_signature = True  # type: ignore[attr-defined]
sparse_proxy_demand_blocked.exact_settlement = True  # type: ignore[attr-defined]
sparse_proxy_demand_blocked.partials_fn = _blocked_demand_parts  # type: ignore[attr-defined]
sparse_proxy_demand_blocked.num_blocks = 8  # type: ignore[attr-defined]


@functools.lru_cache(maxsize=None)
def blocked_demand_fn(num_blocks: int = 8) -> DemandFn:
    """:func:`sparse_proxy_demand_blocked` with a non-default block count.

    Cached so repeated calls return the identical object — the demand fn is a
    static jit argument, and a fresh partial per epoch would retrace the
    whole clock every auction.
    """
    if num_blocks == 8:
        return sparse_proxy_demand_blocked
    fn = functools.partial(sparse_proxy_demand_blocked, num_blocks=num_blocks)
    fn.sparse_signature = True  # type: ignore[attr-defined]
    fn.exact_settlement = True  # type: ignore[attr-defined]
    fn.partials_fn = _blocked_demand_parts  # type: ignore[attr-defined]
    fn.num_blocks = num_blocks  # type: ignore[attr-defined]
    return fn


# ---------------------------------------------------------------------------
# Variable-K CSR demand evaluation
# ---------------------------------------------------------------------------


def csr_proxy_demand(
    problem: CSRAuctionProblem,
    prices: jax.Array,
    aux: CSRDemandAux | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """O(nnz) proxy demand on the flat CSR encoding → (z, chosen, active).

    Without ``aux`` this is the readable segment formulation: per-element
    price gathers, a sorted ``segment_sum`` into per-bundle costs, and a
    keep-masked scatter into z — the right shape for TPU, where scatters
    vectorize.  With ``aux`` (see :class:`~repro.core.types.CSRDemandAux`)
    every large scatter is replaced by pack-time reorderings: costs fold as
    ``k_bound`` prefix-slice adds over the count-sorted k-major stream, and z
    reduces pool-major in dense ``chunk``-wide tiles — which is what makes
    the CSR round beat the K_max-padded round on CPU instead of losing to
    it.  Both variants select identically; z differs from the padded
    scatter's association only within a pool (float-close, like every
    non-exact demand path).  Scalar-π and vector-π are both supported.
    """
    mask, pi = problem.bundle_mask, problem.pi
    num_users, num_bundles = mask.shape
    num_res = problem.num_resources
    prices = prices.astype(jnp.float32)

    if problem.nnz == 0:
        costs = jnp.zeros((num_users, num_bundles), jnp.float32)
    elif aux is None:
        prod = problem.val * prices[problem.idx]
        costs = jax.ops.segment_sum(
            prod,
            problem.rows,
            num_segments=num_users * num_bundles,
            indices_are_sorted=True,
        ).reshape(num_users, num_bundles)
    else:
        prod = aux.kmaj_val * prices[aux.kmaj_idx]
        costs_sorted = jnp.zeros((num_users * num_bundles,), jnp.float32)
        off = 0
        for m in aux.m_k:
            costs_sorted = costs_sorted.at[:m].add(
                jax.lax.dynamic_slice(prod, (off,), (m,))
            )
            off += m
        costs = costs_sorted[aux.inv_count_perm].reshape(num_users, num_bundles)
    costs = jnp.where(mask, costs, jnp.inf)

    if pi.ndim == 1:
        bhat = jnp.argmin(costs, axis=1)
        cost_hat = jnp.take_along_axis(costs, bhat[:, None], axis=1)[:, 0]
        active = cost_hat <= pi
    else:
        surplus = jnp.where(mask, pi - costs, -jnp.inf)
        bhat = jnp.argmax(surplus, axis=1)
        s_hat = jnp.take_along_axis(surplus, bhat[:, None], axis=1)[:, 0]
        active = s_hat >= 0.0
    chosen = jnp.where(active, bhat, -1)

    if problem.nnz == 0:
        z = jnp.zeros((num_res,), jnp.float32)
        return z, chosen, active
    b_of = problem.rows % num_bundles
    u_of = problem.rows // num_bundles
    kept = jnp.where(chosen[u_of] == b_of, problem.val, 0.0)  # -1 never matches
    if aux is None:
        z = jnp.zeros((num_res,), jnp.float32).at[problem.idx].add(kept)
    else:
        chunk_sums = (
            jnp.where(aux.pool_live, kept[aux.pool_pos], 0.0)
            .reshape(-1, aux.chunk)
            .sum(axis=1)
        )
        z = jnp.zeros((num_res,), jnp.float32).at[aux.chunk_pool].add(chunk_sums)
    return z, chosen, active


csr_proxy_demand.csr_signature = True  # type: ignore[attr-defined]
csr_proxy_demand.csr_wants_aux = True  # type: ignore[attr-defined]


def _csr_settle(
    problem: CSRAuctionProblem,
    prices: jax.Array,
    chosen: jax.Array,
    active: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Award the chosen bundles from the flat streams → padded (U, k_bound)
    allocations, same result layout as the padded settle."""
    num_users, num_bundles = problem.bundle_mask.shape
    k = problem.k_bound
    starts = problem.offsets[:-1].reshape(num_users, num_bundles)
    counts = (problem.offsets[1:] - problem.offsets[:-1]).reshape(
        num_users, num_bundles
    )
    bsel = jnp.maximum(chosen, 0)
    start_u = jnp.take_along_axis(starts, bsel[:, None], axis=1)[:, 0]
    count_u = jnp.take_along_axis(counts, bsel[:, None], axis=1)[:, 0]
    kk = jnp.arange(k, dtype=start_u.dtype)
    live = kk[None, :] < count_u[:, None]
    if problem.nnz == 0:
        alloc_idx = jnp.zeros((num_users, k), jnp.int32)
        alloc_val = jnp.zeros((num_users, k), jnp.float32)
    else:
        pos = jnp.clip(start_u[:, None] + kk[None, :], 0, problem.nnz - 1)
        alloc_idx = jnp.where(live, problem.idx[pos], 0)
        alloc_val = jnp.where(live, problem.val[pos], 0.0)
    alloc_val = alloc_val.astype(jnp.float32) * active[:, None]
    payments = jnp.sum(alloc_val * prices[alloc_idx], axis=-1)
    return alloc_idx, alloc_val, payments


@dataclasses.dataclass(frozen=True)
class ClockConfig:
    """Auction hyper-parameters (paper §III.C.2)."""

    alpha: float = 0.08  # price step per unit of normalized excess demand
    delta: float = 0.08  # max fractional price move per round (eq. 3 cap)
    max_rounds: int = 10_000
    tol: float = 0.0  # convergence: z_r ≤ tol ∀r
    price_floor_frac: float = 1e-3  # ε: cap floor so p=0 pools can still move
    # progress guarantee: as z → 0⁺ the proportional step vanishes and the
    # clock can crawl forever just below the marginal bidder's drop-out price
    # (found by hypothesis).  Any resource with excess demand moves at least
    # step_floor_frac·c(r) per round; refine_rounds polishes the overshoot.
    step_floor_frac: float = 5e-3
    # paper §III.B (ties): with exact-tie bids the only "fair" outcome is that
    # all tied bidders lose.  break_ties perturbs π by a tiny user-indexed
    # epsilon so one of them wins instead of the resource going unallocated.
    break_ties: bool = False
    tie_eps: float = 1e-5
    # beyond-paper: after the coarse clock stops, bisect between the last two
    # price vectors for the minimal clearing point.  Sharpens prices to
    # ~delta/2^k and is what lets a tie_eps-perturbed tie actually split
    # (without it the final coarse step drops all tied bidders together).
    refine_rounds: int = 0
    # Adaptive step schedule (both default to 1.0 = off, which keeps the loop
    # body — and therefore every pinned price trajectory — bit-identical to
    # the fixed schedule).  alpha_growth > 1 multiplies a per-resource step
    # accelerator every consecutive round a resource stays over-demanded
    # (capped at accel_cap, reset to 1 the moment it is not), so a clock that
    # would crawl at the step floor covers the same ground geometrically.
    # delta_decay < 1 shrinks that resource's per-round cap fraction each
    # time its excess-demand sign flips from + to ≤ 0 (floored at
    # delta_floor_frac·delta), so re-entrant demand is approached in ever
    # finer steps — bisection-like convergence instead of limit-cycling at
    # the coarse tick.
    alpha_growth: float = 1.0
    accel_cap: float = 64.0
    delta_decay: float = 1.0
    delta_floor_frac: float = 0.05

    @property
    def adaptive(self) -> bool:
        return self.alpha_growth != 1.0 or self.delta_decay != 1.0


def escalate_clock(config: ClockConfig, factor: int = 2) -> ClockConfig:
    """Degraded-mode escalation for a round-starved clock.

    Returns a config with ``factor``× the round budget and the adaptive
    step schedule switched on (or kept, when the caller already runs
    adaptive): per-resource step acceleration covers ground a crawling
    clock cannot, and delta decay stops limit-cycling at the coarse tick.
    Used by the economy's bounded-retry path (``Economy(clock_retries=k)``)
    — the escalated clock *continues* from the truncated price trajectory,
    which is sound because the clock is ascending-only.
    """
    return dataclasses.replace(
        config,
        max_rounds=config.max_rounds * factor,
        alpha_growth=config.alpha_growth if config.alpha_growth > 1.0 else 1.6,
        delta_decay=config.delta_decay if config.delta_decay < 1.0 else 0.6,
    )


def _apply_tie_jitter(pi: jax.Array, config: ClockConfig) -> jax.Array:
    """π perturbation for ``break_ties`` — indexed by *global* user position,
    so it must run on the full (unpadded, unsharded) π."""
    u = jnp.arange(pi.shape[0], dtype=jnp.float32)
    jitter = config.tie_eps * (1.0 + u / pi.shape[0])
    if pi.ndim == 2:
        jitter = jitter[:, None]
    return pi + jnp.sign(pi) * jitter * jnp.abs(pi)


def _run_clock(
    excess: Callable[[jax.Array], jax.Array],
    start_prices: jax.Array,
    config: ClockConfig,
    c: jax.Array,
    s: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Algorithm 1's price loop (plus the λ-bisection refiner) → (rounds, p*).

    Shared verbatim between :func:`clock_auction` and
    :func:`sharded_clock_auction`: only ``excess`` differs, so the price
    trajectory is identical whenever the two paths produce identical z.

    With ``config.adaptive`` the loop carries two extra per-resource state
    vectors — a step accelerator and a decaying cap fraction (see
    :class:`ClockConfig`) — and a warm or cold start converges in a fraction
    of the fixed schedule's rounds.  The non-adaptive branch below is the
    original loop body, untouched, so default-config trajectories stay
    bit-identical.
    """
    alpha = jnp.float32(config.alpha)
    delta = jnp.float32(config.delta)
    eps = jnp.float32(config.price_floor_frac)
    tol = jnp.float32(config.tol)
    floor = jnp.float32(config.step_floor_frac)

    t0 = jnp.int32(0)
    done0 = jnp.asarray(False)
    p0 = start_prices.astype(jnp.float32)

    # eq. (3): additive step ∝ normalized excess demand, capped at a fixed
    # fraction of the current price, scaled by base cost (the paper's
    # normalization so cheap resources don't outrun expensive ones).
    if not config.adaptive:

        def cond2(state):
            t, _, _, done = state
            return jnp.logical_and(~done, t < config.max_rounds)

        def body2(state):
            t, p, p_prev, _ = state
            z = excess(p)
            done = jnp.all(z <= tol)
            rel = jnp.maximum(alpha * jnp.maximum(z, 0.0) / s, floor)
            step = jnp.minimum(rel * c, delta * jnp.maximum(p, eps * c))
            p_next = jnp.where(z > tol, p + step, p)
            return t + 1, jnp.where(done, p, p_next), jnp.where(done, p_prev, p), done

        rounds, prices, p_prev, _ = jax.lax.while_loop(
            cond2, body2, (t0, p0, p0, done0)
        )
    else:
        growth = jnp.float32(config.alpha_growth)
        decay = jnp.float32(config.delta_decay)
        accel_cap = jnp.float32(config.accel_cap)
        dfloor = jnp.float32(config.delta_floor_frac) * delta

        def cond2(state):
            t = state[0]
            done = state[3]
            return jnp.logical_and(~done, t < config.max_rounds)

        def body2(state):
            t, p, p_prev, _, accel, dcap, prev_pos = state
            z = excess(p)
            done = jnp.all(z <= tol)
            pos = z > tol
            # this round steps with the accumulated accelerator; the state
            # update below grows it while the sign holds and resets it the
            # moment the resource clears
            rel = jnp.maximum(alpha * jnp.maximum(z, 0.0) / s, floor) * accel
            step = jnp.minimum(rel * c, dcap * jnp.maximum(p, eps * c))
            p_next = jnp.where(pos, p + step, p)
            accel_n = jnp.where(
                pos & prev_pos, jnp.minimum(accel * growth, accel_cap), 1.0
            )
            dcap_n = jnp.where(
                prev_pos & ~pos, jnp.maximum(dcap * decay, dfloor), dcap
            )
            return (
                t + 1,
                jnp.where(done, p, p_next),
                jnp.where(done, p_prev, p),
                done,
                accel_n,
                dcap_n,
                pos,
            )

        accel0 = jnp.ones_like(p0)
        dcap0 = jnp.full_like(p0, delta)
        pos0 = jnp.zeros(p0.shape, bool)
        rounds, prices, p_prev, _, _, _, _ = jax.lax.while_loop(
            cond2, body2, (t0, p0, p0, done0, accel0, dcap0, pos0)
        )

    if config.refine_rounds > 0:
        # λ-bisection on the final segment: λ=1 clears (post-loop prices),
        # λ=0 is the last infeasible point; find the smallest clearing λ.
        delta_p = prices - p_prev

        def refine(i, lohi):
            lo, hi = lohi
            mid = 0.5 * (lo + hi)
            ok = jnp.all(excess(p_prev + mid * delta_p) <= tol)
            return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

        _, lam = jax.lax.fori_loop(
            0, config.refine_rounds, refine, (jnp.float32(0.0), jnp.float32(1.0))
        )
        prices = p_prev + lam * delta_p
    return rounds, prices


def _sparse_settle(
    idx: jax.Array,
    val: jax.Array,
    prices: jax.Array,
    chosen: jax.Array,
    active: jax.Array,
    num_resources: int,
    exact: bool,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Award bundles and compute payments — per-user, so shard-invariant."""
    bsel = jnp.maximum(chosen, 0)
    alloc_idx = jnp.take_along_axis(idx, bsel[:, None, None], axis=1)[:, 0, :]
    alloc_val = jnp.take_along_axis(val, bsel[:, None, None], axis=1)[:, 0, :]
    alloc_val = alloc_val.astype(jnp.float32) * active[:, None]
    if exact:
        # Rebuild the *chosen* bundle's dense (U, R) row and pay through the
        # dense row·price reduction, so duplicate pool indices within a
        # bundle settle exactly like their dense sum.  Scattering only the
        # selected (idx, val) pair accumulates the same updates in the same
        # k order as scattering all B alternatives and selecting after —
        # identical rows, at O(U·R) instead of O(U·B·R).  The per-user dot
        # is an explicit last-axis reduce rather than a matvec: XLA tiles a
        # dot's contraction by operand shape, so `x @ p` can differ by an
        # ulp between a full problem and its shard — a fixed (row ×
        # price).sum keeps payments bit-identical for every users-axis
        # split.  Planet-scale settlement uses the sparse fold below.
        sel = _user_rows(alloc_idx, alloc_val, num_resources)
        payments = jnp.sum(sel * prices[None, :], axis=-1)
    else:
        payments = jnp.sum(alloc_val * prices[alloc_idx], axis=-1)
    return alloc_idx, alloc_val, payments


def clock_auction(
    problem: AuctionProblem | SparseAuctionProblem | CSRAuctionProblem,
    start_prices: jax.Array,
    config: ClockConfig = ClockConfig(),
    demand_fn: DemandFn | None = None,
    csr_aux: CSRDemandAux | None = None,
) -> AuctionResult | SparseAuctionResult:
    """Run Algorithm 1 to convergence (or ``max_rounds``) and settle.

    Dense problems evaluate demand in O(U·B·R) and settle to an
    ``AuctionResult``; sparse problems evaluate in O(U·B·K) and settle to a
    ``SparseAuctionResult`` whose allocations stay in (idx, val) form.  The
    demand_fn must match the problem encoding (sparse demand fns carry a
    ``sparse_signature`` attribute, CSR demand fns ``csr_signature``;
    ``None`` selects the matching pure-jnp proxy).

    CSR problems settle two ways.  A ``csr_signature`` demand fn (default:
    :func:`csr_proxy_demand`) evaluates the flat streams natively in O(nnz);
    ``csr_aux`` (built automatically for concrete problems) supplies the
    scatter-free layouts.  A padded ``sparse_signature`` demand fn (the
    exact/blocked settlement family) runs on the in-trace padded
    reconstruction instead — the identical program the K_max-padded book
    compiles — so CSR settlement through those fns is *bit-identical* to
    padded settlement of the same book.
    """
    if isinstance(problem, CSRAuctionProblem):
        if demand_fn is None:
            demand_fn = csr_proxy_demand
        if getattr(demand_fn, "sparse_signature", False):
            # settlement-grade padded fns: reconstruct the padded layout
            # in-trace and run the unchanged padded program (bit-identical)
            return _clock_auction_csr_padded(problem, start_prices, config, demand_fn)
        if not getattr(demand_fn, "csr_signature", False):
            raise TypeError(
                f"demand_fn {demand_fn} does not match the CSR problem encoding"
            )
        if (
            csr_aux is None
            and getattr(demand_fn, "csr_wants_aux", False)
            and not isinstance(problem.idx, jax.core.Tracer)
        ):
            # only fns that consume the scatter-free layouts pay the pack-time
            # argsorts (the kernel adapters' compare-and-add z never scatters)
            csr_aux = csr_demand_aux(problem)
        return _clock_auction_csr_native(
            problem, start_prices, config, demand_fn, csr_aux
        )
    if getattr(demand_fn, "csr_signature", False):
        raise TypeError(
            f"demand_fn {demand_fn} evaluates CSR problems, got "
            f"{type(problem).__name__}"
        )
    return _clock_auction_jit(problem, start_prices, config, demand_fn)


@functools.partial(
    jax.jit, static_argnames=("config", "demand_fn"), donate_argnums=()
)
def _clock_auction_jit(
    problem: AuctionProblem | SparseAuctionProblem,
    start_prices: jax.Array,
    config: ClockConfig = ClockConfig(),
    demand_fn: DemandFn | None = None,
) -> AuctionResult | SparseAuctionResult:
    is_sparse = isinstance(problem, SparseAuctionProblem)
    mask, pi = problem.bundle_mask, problem.pi
    if config.break_ties:
        pi = _apply_tie_jitter(pi, config)
    if demand_fn is None:
        demand_fn = sparse_proxy_demand if is_sparse else proxy_demand
    if is_sparse != bool(getattr(demand_fn, "sparse_signature", False)):
        raise TypeError(
            f"demand_fn {demand_fn} does not match the "
            f"{'sparse' if is_sparse else 'dense'} problem encoding"
        )
    if is_sparse:
        idx, val = problem.idx, problem.val

        def demand(prices):
            return demand_fn(idx, val, mask, pi, prices, problem.num_resources)

    else:
        bundles = problem.bundles

        def demand(prices):
            x, chosen, active = demand_fn(bundles, mask, pi, prices)
            return x.sum(axis=0), chosen, active

    def excess(prices):
        z, _, _ = demand(prices)
        return z

    rounds, prices = _run_clock(
        excess, start_prices, config, problem.base_cost, problem.supply_scale
    )
    tol = jnp.float32(config.tol)

    if is_sparse:
        z, chosen, active = demand(prices)
        alloc_idx, alloc_val, payments = _sparse_settle(
            idx, val, prices, chosen, active, problem.num_resources,
            exact=bool(getattr(demand_fn, "exact_settlement", False)),
        )
        return SparseAuctionResult(
            prices=prices,
            alloc_idx=alloc_idx,
            alloc_val=alloc_val,
            chosen_bundle=chosen,
            won=active,
            payments=payments,
            excess_demand=z,
            rounds=rounds,
            converged=jnp.all(z <= tol),
        )
    x, chosen, active = demand_fn(bundles, mask, pi, prices)
    z = x.sum(axis=0)
    payments = x @ prices
    return AuctionResult(
        prices=prices,
        allocations=x,
        chosen_bundle=chosen,
        won=active,
        payments=payments,
        excess_demand=z,
        rounds=rounds,
        converged=jnp.all(z <= tol),
    )


@functools.partial(jax.jit, static_argnames=("config", "demand_fn"))
def _clock_auction_csr_padded(
    problem: CSRAuctionProblem,
    start_prices: jax.Array,
    config: ClockConfig,
    demand_fn: DemandFn,
) -> SparseAuctionResult:
    """CSR settlement through a padded-signature demand fn.

    The padded (U, B, k_bound) views are reconstructed once in-trace —
    loop-invariant, so the clock never re-gathers them — and from there the
    program is the padded clock verbatim: identical selection, identical z
    fold, identical settle, hence bit-identical output.
    """
    idx, val = csr_padded_views(problem)
    padded = SparseAuctionProblem(
        idx=idx,
        val=val,
        bundle_mask=problem.bundle_mask,
        pi=problem.pi,
        base_cost=problem.base_cost,
        supply_scale=problem.supply_scale,
        num_resources=problem.num_resources,
    )
    return _clock_auction_jit(padded, start_prices, config, demand_fn)


@functools.partial(jax.jit, static_argnames=("config", "demand_fn"))
def _clock_auction_csr_native(
    problem: CSRAuctionProblem,
    start_prices: jax.Array,
    config: ClockConfig,
    demand_fn: DemandFn,
    aux: CSRDemandAux | None,
) -> SparseAuctionResult:
    pi = problem.pi
    if config.break_ties:
        pi = _apply_tie_jitter(pi, config)
        problem = dataclasses.replace(problem, pi=pi)

    def demand(prices):
        return demand_fn(problem, prices, aux)

    def excess(prices):
        z, _, _ = demand(prices)
        return z

    rounds, prices = _run_clock(
        excess, start_prices, config, problem.base_cost, problem.supply_scale
    )
    tol = jnp.float32(config.tol)
    z, chosen, active = demand(prices)
    alloc_idx, alloc_val, payments = _csr_settle(problem, prices, chosen, active)
    return SparseAuctionResult(
        prices=prices,
        alloc_idx=alloc_idx,
        alloc_val=alloc_val,
        chosen_bundle=chosen,
        won=active,
        payments=payments,
        excess_demand=z,
        rounds=rounds,
        converged=jnp.all(z <= tol),
    )


# ---------------------------------------------------------------------------
# Multi-device settlement: the clock sharded over users
# ---------------------------------------------------------------------------


def users_mesh(num_devices: int | None = None, axis_name: str = "users") -> Mesh:
    """1-D mesh over the first ``num_devices`` local devices (default: all).

    Simulate multi-host settlement on CPU with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    devices = jax.devices()
    n = len(devices) if num_devices is None else num_devices
    if not 1 <= n <= len(devices):
        raise ValueError(f"num_devices={n} not in [1, {len(devices)}]")
    return Mesh(np.asarray(devices[:n]), (axis_name,))


@functools.partial(
    jax.jit,
    static_argnames=("config", "demand_fn", "mesh", "axis_name", "num_blocks"),
)
def _sharded_clock_impl(
    problem: SparseAuctionProblem,
    start_prices: jax.Array,
    config: ClockConfig,
    demand_fn: DemandFn,
    mesh: Mesh,
    axis_name: str,
    num_blocks: int,
):
    ndev = mesh.shape[axis_name]
    num_users = problem.num_users
    num_res = problem.num_resources
    pi = problem.pi
    if config.break_ties:
        pi = _apply_tie_jitter(pi, config)  # global user index — pre-padding

    # Pad users to a multiple of num_blocks (hence of ndev): padded rows
    # never activate and contribute exact zeros.
    padded = pad_users(dataclasses.replace(problem, pi=pi), num_blocks)
    idx, val, mask, pi = padded.idx, padded.val, padded.bundle_mask, padded.pi

    partials_fn = getattr(demand_fn, "partials_fn", None)
    exact = bool(getattr(demand_fn, "exact_settlement", False))
    tol = jnp.float32(config.tol)

    def shard_body(idx, val, mask, pi, p0, c, s):
        def demand(prices):
            if partials_fn is not None:
                partials, chosen, active = partials_fn(
                    idx, val, mask, pi, prices, num_res, num_blocks // ndev
                )
            else:
                z_local, chosen, active = demand_fn(
                    idx, val, mask, pi, prices, num_res
                )
                partials = z_local[None]
            # Deterministic psum: gather every shard's block partials and run
            # the same fixed left-fold the unsharded blocked proxy runs.
            gathered = jax.lax.all_gather(partials, axis_name, tiled=True)
            return _chain_sum(gathered), chosen, active

        def excess(prices):
            z, _, _ = demand(prices)
            return z

        rounds, prices = _run_clock(excess, p0, config, c, s)
        z, chosen, active = demand(prices)
        alloc_idx, alloc_val, payments = _sparse_settle(
            idx, val, prices, chosen, active, num_res, exact=exact
        )
        return (
            prices,
            alloc_idx,
            alloc_val,
            chosen,
            active,
            payments,
            z,
            rounds,
            jnp.all(z <= tol),
        )

    ax = axis_name
    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(ax), P(ax), P(ax), P(ax), P(), P(), P()),
        out_specs=(P(), P(ax), P(ax), P(ax), P(ax), P(ax), P(), P(), P()),
        check_vma=False,  # prices/z are replicated by construction (all_gather)
    )
    prices, alloc_idx, alloc_val, chosen, active, payments, z, rounds, conv = sharded(
        idx,
        val,
        mask,
        pi,
        start_prices.astype(jnp.float32),
        problem.base_cost,
        problem.supply_scale,
    )
    return SparseAuctionResult(
        prices=prices,
        alloc_idx=alloc_idx[:num_users],
        alloc_val=alloc_val[:num_users],
        chosen_bundle=chosen[:num_users],
        won=active[:num_users],
        payments=payments[:num_users],
        excess_demand=z,
        rounds=rounds,
        converged=conv,
    )


def sharded_clock_auction(
    problem: SparseAuctionProblem,
    start_prices: jax.Array,
    config: ClockConfig = ClockConfig(),
    demand_fn: DemandFn | None = None,
    mesh: Mesh | None = None,
    axis_name: str = "users",
    num_blocks: int = 8,
) -> SparseAuctionResult:
    """Run Algorithm 1 with bidders sharded over a device mesh.

    The ``SparseAuctionProblem`` (idx/val/mask/π) is padded to a multiple of
    ``num_blocks`` users and split over the mesh's ``axis_name`` axis; each
    device evaluates demand for its shard with the same sparse kernels the
    single-device path uses, and z is reduced across shards *inside* the
    ``lax.while_loop`` — the whole multi-round clock is one XLA program per
    device, no host round-trips.

    With the default demand fn (:func:`sparse_proxy_demand_blocked`) the
    cross-shard reduction is an all_gather of per-block partials followed by
    a fixed left-fold, which makes prices/allocations/payments bit-identical
    to ``clock_auction(problem, ..., demand_fn=sparse_proxy_demand_blocked)``
    on one device, for every device count dividing ``num_blocks``.  Other
    sparse demand fns (e.g. the Pallas kernel adapters from
    ``kernels.ops.sparse_bid_demand_fn``) contribute one partial per shard
    and agree across device counts to normal float tolerance.

    ``mesh=None`` shards over all local devices (``users_mesh()``).
    """
    if isinstance(problem, CSRAuctionProblem):
        # CSR's variable-length rows don't split evenly over a mesh axis;
        # shard the padded reconstruction instead.  The conversion is exact
        # (see csr_padded_views), so the cross-device bit-identity guarantee
        # carries over to CSR books unchanged.
        problem = padded_from_csr(problem)
    if not isinstance(problem, SparseAuctionProblem):
        raise TypeError(
            "sharded_clock_auction needs a SparseAuctionProblem — dense "
            "(U, B, R) bundles would shard U·B·R bytes per round; sparsify() "
            "first"
        )
    if mesh is None:
        mesh = users_mesh(axis_name=axis_name)
    if axis_name not in mesh.shape:
        raise ValueError(f"mesh {mesh} has no axis {axis_name!r}")
    ndev = mesh.shape[axis_name]
    if num_blocks < 1:
        raise ValueError(f"num_blocks={num_blocks} must be >= 1")
    if demand_fn is None:
        demand_fn = blocked_demand_fn(num_blocks)
    if not getattr(demand_fn, "sparse_signature", False):
        raise TypeError(f"demand_fn {demand_fn} is not a sparse demand fn")
    fn_blocks = getattr(demand_fn, "num_blocks", None)
    if fn_blocks is not None and fn_blocks != num_blocks:
        raise ValueError(
            f"demand_fn folds z over {fn_blocks} user blocks but "
            f"num_blocks={num_blocks} was requested — the sharded fold would "
            "silently diverge from the fn's own single-device fold; pass "
            f"num_blocks={fn_blocks} (or demand_fn=blocked_demand_fn("
            f"{num_blocks}))"
        )
    if num_blocks % ndev:
        raise ValueError(
            f"device count {ndev} must divide num_blocks={num_blocks} so each "
            "shard holds whole user blocks (that is what keeps settlement "
            "bit-identical across device counts)"
        )
    return _sharded_clock_impl(
        problem, start_prices, config, demand_fn, mesh, axis_name, num_blocks
    )


# ---------------------------------------------------------------------------
# SYSTEM feasibility verification (paper §III.B constraints (1)-(6))
# ---------------------------------------------------------------------------


def verify_system(
    problem: AuctionProblem | SparseAuctionProblem | CSRAuctionProblem,
    result: AuctionResult | SparseAuctionResult,
    atol: float = 1e-3,
) -> dict[str, bool]:
    """Check the settled (x, p) against every SYSTEM constraint.

    Accepts either encoding (sparse results are checked on their (idx, val)
    allocations directly).  Returns a dict of named booleans;
    ``all(verify_system(...).values())`` means the clock found a feasible
    point of SYSTEM.  The array work runs as one jitted program — at
    10⁵-user books the op-by-op eager version cost more than settlement.
    """
    checks = _verify_system_checks(problem, result, atol)
    return {k: bool(v) for k, v in checks.items()}


@functools.partial(jax.jit, static_argnames=("atol",))
def _verify_system_checks(
    problem: AuctionProblem | SparseAuctionProblem | CSRAuctionProblem,
    result: AuctionResult | SparseAuctionResult,
    atol: float,
) -> dict[str, jax.Array]:
    mask, pi = problem.bundle_mask, problem.pi
    p, won = result.prices, result.won
    if isinstance(problem, CSRAuctionProblem):
        vidx, vval = csr_padded_views(problem)  # same checks as padded, exactly
        costs = sparse_bundle_costs(vidx, vval, mask, p)
        lost_zero = jnp.all(result.alloc_val == 0, axis=1)
    elif isinstance(problem, SparseAuctionProblem):
        costs = sparse_bundle_costs(problem.idx, problem.val, mask, p)
        lost_zero = jnp.all(result.alloc_val == 0, axis=1)
    else:
        costs = bundle_costs(problem.bundles, mask, p)  # (U, B)
        lost_zero = jnp.all(result.allocations == 0, axis=1)
    min_cost = jnp.min(costs, axis=1)  # min_q qᵀp (inf if no valid bundle)
    pay = result.payments
    scale = 1.0 + jnp.abs(pay)
    if pi.ndim == 2:
        # vector-π extension: winners must have the best (max-surplus) bundle
        # and nonneg surplus; losers must have no bundle with positive surplus.
        surplus = jnp.where(mask, pi - costs, -jnp.inf)
        best = jnp.max(surplus, axis=1)
        won_sur = jnp.take_along_axis(
            surplus, jnp.maximum(result.chosen_bundle, 0)[:, None], axis=1
        )[:, 0]
        checks = {
            "c1_bundle_integrality": jnp.all(
                jnp.where(won, result.chosen_bundle >= 0, True)
            ),
            "c2_no_excess_demand": jnp.all(result.excess_demand <= atol),
            "c3_winners_afford": jnp.all(jnp.where(won, won_sur >= -atol * scale, True)),
            "c4_winners_best_bundle": jnp.all(
                jnp.where(won, won_sur >= best - atol * scale, True)
            ),
            "c5_losers_below": jnp.all(jnp.where(~won, best < atol * scale, True)),
            "c6_prices_nonneg": jnp.all(p >= -atol),
        }
        return checks
    checks = {
        # (1) x_u ∈ {0 ∪ Q_u}: allocation is the chosen bundle or zero.
        "c1_bundle_integrality": jnp.all(
            jnp.where(won, result.chosen_bundle >= 0, lost_zero)
        ),
        # (2) Σ_u x_u ≤ 0 : no shortages created.
        "c2_no_excess_demand": jnp.all(result.excess_demand <= atol),
        # (3) π_u ≥ x_uᵀp for winners.
        "c3_winners_afford": jnp.all(jnp.where(won, pi >= pay - atol * scale, True)),
        # (4) winners pay exactly their cheapest bundle's cost.
        "c4_winners_cheapest": jnp.all(
            jnp.where(won, jnp.abs(pay - min_cost) <= atol * scale, True)
        ),
        # (5) losers bid strictly below their cheapest bundle's cost.
        "c5_losers_below": jnp.all(
            jnp.where(~won, pi < min_cost + atol * scale, True)
        ),
        # (6) p ≥ 0.
        "c6_prices_nonneg": jnp.all(p >= -atol),
    }
    return checks


def surplus_and_trade(
    problem: AuctionProblem | SparseAuctionProblem | CSRAuctionProblem,
    result: AuctionResult | SparseAuctionResult,
):
    """Realized total surplus and value-of-trade (paper §III.B objectives).

    Computed on host numpy: these are flat (U,) reductions over settlement
    output that may live sharded across devices, and a device-side sum's
    association would change with the device count — host reduction keeps
    the totals bit-identical however settlement was sharded.
    """
    pi = np.asarray(problem.pi)
    if pi.ndim == 2:
        pi = np.take_along_axis(
            pi, np.maximum(np.asarray(result.chosen_bundle), 0)[:, None], axis=1
        )[:, 0]
    won = np.asarray(result.won)
    pay = np.asarray(result.payments)
    surplus = np.sum(np.where(won, pi - pay, 0.0))
    value_of_trade = np.sum(np.where(won & (pay > 0), pay, 0.0))
    return surplus, value_of_trade
