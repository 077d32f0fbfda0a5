"""Pallas TPU kernel: fused bidder-proxy evaluation for the clock auction.

One clock round must evaluate, for every user u:  the cost of each XOR
alternative  (a (U·B, R)×(R,) matvec),  the cheapest valid alternative
(masked argmin over B), the affordability test against π_u, and the selected
bundle's contribution to the excess-demand vector z (a masked one-hot matmul
plus a cross-user reduction).  At planet scale (U ~ 10⁵–10⁶ bids, R ~ 10³
pools) this is the settlement hot loop — the paper ran it in minutes in plain
Python at 10²×10².

TPU mapping: users are blocked over the grid; each grid step loads a
(BU, B, R⁺) bundle tile into VMEM (R⁺ = R padded to the 128-lane boundary)
and walks its B bundles one (BU, R⁺) slice at a time: costs are lane
reductions of bundle × price on the VPU/XLU, the cheapest valid bundle is a
running first-minimum over the B slices, and the selected bundle comes from a
B-step masked select (no gather, no batched one-hot matmul — Mosaic accepts
neither here).  The tile's demand accumulates into a single (1, R⁺) fp32
output block that every grid step revisits (sequential TPU grid ⇒ safe
accumulation).  Per-user winners are written to a (BU, 1) int32 block.  The
VMEM budget picks BU from the padded tile: B rounds up to 8 sublanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
_VMEM_TILE_BYTES = 4 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pick_block_u(num_bundles: int, r_padded: int) -> int:
    """Largest power-of-two user block whose bundle tile fits the VMEM budget.

    A (BU, B, R⁺) block pads its last two dims to (8, 128) on the TPU, so the
    tile holds ⌈B/8⌉·8 rows of R⁺ lanes per user; double-buffering doubles it.
    """
    per_user = 2 * _round_up(num_bundles, 8) * r_padded * 4
    bu = _VMEM_TILE_BYTES // max(per_user, 1)
    bu = max(8, min(1024, bu))
    # round down to a power of two
    p = 8
    while p * 2 <= bu:
        p *= 2
    return p


def _bid_eval_kernel(prices_ref, pi_ref, mask_ref, bundles_ref, z_ref, chosen_ref):
    i = pl.program_id(0)
    bu, nb, rp = bundles_ref.shape
    prices = prices_ref[...]  # (1, Rp)
    big = jnp.float32(3.0e38)

    # cost of every alternative, and the first cheapest valid one
    cost_hat = jnp.full((bu, 1), big, jnp.float32)
    bhat = jnp.zeros((bu, 1), jnp.int32)
    for b in range(nb):
        cost = jnp.sum(bundles_ref[:, b, :] * prices, axis=1, keepdims=True)
        cost = jnp.where(mask_ref[:, b : b + 1] > 0, cost, big)
        better = cost < cost_hat
        bhat = jnp.where(better, b, bhat)
        cost_hat = jnp.where(better, cost, cost_hat)

    pi = pi_ref[...]  # (BU, 1)
    active = jnp.logical_and(cost_hat <= pi, cost_hat < big)

    # selected bundle via a B-step masked select
    sel = jnp.zeros((bu, rp), jnp.float32)
    for b in range(nb):
        sel = jnp.where(jnp.logical_and(active, bhat == b), bundles_ref[:, b, :], sel)
    z_tile = jnp.sum(sel, axis=0, keepdims=True)  # (1, Rp)

    @pl.when(i == 0)
    def _init():
        z_ref[...] = jnp.zeros_like(z_ref)

    z_ref[...] += z_tile
    chosen_ref[...] = jnp.where(active, bhat, -1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bid_eval(
    bundles: jax.Array,  # (U, B, R)
    mask: jax.Array,  # (U, B)
    pi: jax.Array,  # (U,)
    prices: jax.Array,  # (R,)
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused proxy evaluation. Returns (z (R,), chosen (U,) int32, -1 = out).

    Pads U to the block size and R to the lane width; padded users carry an
    all-invalid mask (they never activate), padded resources carry zero
    bundles and zero prices (they contribute nothing).  Bundles enter the
    kernel as f32, whatever their dtype.
    """
    u, b, r = bundles.shape
    rp = _round_up(max(r, LANE), LANE)
    bu = pick_block_u(b, rp)
    up = _round_up(max(u, bu), bu)

    bundles_p = jnp.zeros((up, b, rp), jnp.float32).at[:u, :, :r].set(
        bundles.astype(jnp.float32)
    )
    mask_p = jnp.zeros((up, b), jnp.int32).at[:u].set(mask.astype(jnp.int32))
    pi_p = jnp.full((up, 1), -3.0e38, jnp.float32).at[:u, 0].set(pi.astype(jnp.float32))
    prices_p = jnp.zeros((1, rp), jnp.float32).at[0, :r].set(prices.astype(jnp.float32))

    grid = (up // bu,)
    z, chosen = pl.pallas_call(
        _bid_eval_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, rp), lambda i: (0, 0)),  # prices: broadcast
            pl.BlockSpec((bu, 1), lambda i: (i, 0)),  # pi
            pl.BlockSpec((bu, b), lambda i: (i, 0)),  # mask
            pl.BlockSpec((bu, b, rp), lambda i: (i, 0, 0)),  # bundles
        ],
        out_specs=[
            pl.BlockSpec((1, rp), lambda i: (0, 0)),  # z: revisited/accumulated
            pl.BlockSpec((bu, 1), lambda i: (i, 0)),  # chosen
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, rp), jnp.float32),
            jax.ShapeDtypeStruct((up, 1), jnp.int32),
        ],
        interpret=interpret,
    )(prices_p, pi_p, mask_p, bundles_p)
    return z[0, :r], chosen[:u, 0]
