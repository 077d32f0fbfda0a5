"""Pallas TPU kernel: sparse-bundle bidder-proxy evaluation, O(U·B·K).

The dense twin (``clock_bid_eval``) streams a (U, B, R) bundle tensor through
every clock round — at 10⁵ bids × 10³ pools that is ~1.6 GB of mostly-zero
HBM traffic per round, since a real bid touches only K ≈ 3–6 pools.  This
kernel takes the sparse (idx, val) encoding instead (K padded to ``K_max``),
so the whole round moves O(U·B·K) bytes.

TPU mapping:

* users lie on the 128 lanes: the wrapper lays idx/val out as (K, B, U) and
  mask/π as (B, U), so every per-user quantity is a lane vector and slot k
  of all B bundles is one (B, 128) tile.  Users are blocked over a 1-D
  sequential grid of BU-lane blocks, and a loop inside the block walks its
  128-lane slices;
* bundle costs come from lane gathers (``jnp.take_along_axis`` on the minor
  axis — Mosaic's dynamic_gather, which gathers within one vreg only) of a
  (B, 128) broadcast of each 128-pool chunk of the price table, selected by
  chunk, followed by a K-term sum on the VPU;
* selection is the iota-min trick over the B sublanes, with the vector-π
  surplus rule (argmax_b π_b − cost_b, active while surplus ≥ 0);
* the chosen bundle's K (idx, val) pairs come out of a masked sublane
  reduction (exactly one bundle row matches), and excess demand accumulates into a
  revisited (R⁸, 128) z block by K compare-and-add passes
  (``z[r, l] += val_k·[idx_k == r]``) — a scatter without one-hot matmuls
  or host round-trips.  The wrapper folds the 128 lane partials.  The
  sequential TPU grid makes the read-modify-write safe.

Duplicate indices inside one bundle are legal (both the cost sum and the
compare-and-add scatter sum them), matching the jnp oracle and the semantics
of a dense bundle whose entry is the sum of the duplicates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE = 8
_VMEM_TILE_BYTES = 4 * 1024 * 1024
_MAX_BLOCK_U = 2048
_BIG = 3.0e38  # stand-in for ±inf inside the kernel (python float, not traced)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def lane_block(bytes_per_lane: int, num_users: int) -> int:
    """Largest power-of-two multiple of 128 lanes whose double-buffered
    blocks fit the VMEM budget, no wider than the (lane-padded) user count."""
    bu = _VMEM_TILE_BYTES // max(2 * bytes_per_lane, 1)
    bu = min(bu, _MAX_BLOCK_U, _round_up(max(num_users, 1), LANE))
    p = LANE
    while p * 2 <= bu:
        p *= 2
    return p


def pick_block_u(num_bundles: int, k_max: int, vector_pi: bool, num_users: int) -> int:
    """User block (lanes) for the (K, B, BU) idx/val tiles.

    On the TPU every tile pads its last two dims to (8, 128): a (B, BU) tile
    holds ⌈B/8⌉·8 sublanes whatever B is, so the budget counts padded rows —
    idx and val (K tiles each), mask, π and the chosen row.
    """
    rows_b = _round_up(num_bundles, SUBLANE)
    rows = 2 * k_max * rows_b + rows_b + (rows_b if vector_pi else SUBLANE) + SUBLANE
    return lane_block(4 * rows, num_users)


def price_table(prices: jax.Array, num_resources: int) -> jax.Array:
    """(R,) prices → (⌈R/128⌉, 128) f32 table: row c holds pools 128c…128c+127."""
    nc = _round_up(max(num_resources, 1), LANE) // LANE
    flat = jnp.zeros((nc * LANE,), jnp.float32)
    return flat.at[:num_resources].set(prices.astype(jnp.float32)).reshape(nc, LANE)


def gather_rows(table, ii: jax.Array) -> jax.Array:
    """``table.reshape(-1)[ii]`` for an index tile ``ii`` of shape (n, 128).

    ``table`` is a (rows, 128) ref or array.  Mosaic gathers lanes within one
    vreg, so each 128-wide table row is broadcast to the tile's shape,
    lane-gathered by ``ii % 128`` and kept where ``ii // 128`` names that row.
    """
    lo = jnp.bitwise_and(ii, LANE - 1)
    hi = jnp.right_shift(ii, 7)
    out = None
    for c in range(table.shape[0]):
        row = jnp.broadcast_to(table[c : c + 1, :], ii.shape)
        g = jnp.take_along_axis(row, lo, axis=1)
        out = g if out is None else jnp.where(hi == c, g, out)
    return out


def select_bundle(costs, valid, pi, scalar_pi: bool):
    """Proxy choice per lane from (B, 128) costs/validity.

    Scalar π: the cheapest valid bundle, active while affordable.  Vector π:
    the largest-surplus valid bundle, active while surplus ≥ 0.  Ties take
    the first bundle.  Returns ``pick`` (B, 128) — True on the chosen row of
    an active user — plus ``bhat`` and ``active`` as (1, 128) rows.
    """
    nb = costs.shape[0]
    iota_b = jax.lax.broadcasted_iota(jnp.int32, costs.shape, 0)
    big = jnp.float32(_BIG)
    if scalar_pi:
        costs = jnp.where(valid, costs, big)
        hat = jnp.min(costs, axis=0, keepdims=True)
        bhat = jnp.min(jnp.where(costs == hat, iota_b, nb), axis=0, keepdims=True)
        active = jnp.logical_and(hat <= pi, hat < big)
    else:
        surplus = jnp.where(valid, pi - costs, -big)
        hat = jnp.max(surplus, axis=0, keepdims=True)
        bhat = jnp.min(jnp.where(surplus == hat, iota_b, nb), axis=0, keepdims=True)
        active = jnp.logical_and(hat >= 0.0, hat > -big)
    bhat = jnp.minimum(bhat, nb - 1)
    pick = jnp.logical_and(iota_b == bhat, active)
    return pick, bhat, active


def scatter_z(z_ref, sel_idx: jax.Array, sel_val: jax.Array) -> None:
    """``z_ref[r, l] += sel_val[l]·[sel_idx[l] == r]`` for (1, 128) rows."""
    iota_r = jax.lax.broadcasted_iota(jnp.int32, z_ref.shape, 0)
    z_ref[...] += jnp.where(iota_r == sel_idx, sel_val, 0.0)


def _sparse_bid_eval_kernel(
    table_ref, pi_ref, mask_ref, idx_ref, val_ref, z_ref, chosen_ref, *, scalar_pi
):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        z_ref[...] = jnp.zeros_like(z_ref)

    kk = idx_ref.shape[0]

    def body(s, carry):
        lanes = pl.ds(pl.multiple_of(s * LANE, LANE), LANE)
        costs = None
        for k in range(kk):
            term = val_ref[k, :, lanes] * gather_rows(table_ref, idx_ref[k, :, lanes])
            costs = term if costs is None else costs + term
        pick, bhat, active = select_bundle(
            costs, mask_ref[:, lanes] > 0, pi_ref[:, lanes], scalar_pi
        )
        for k in range(kk):
            sel_idx = jnp.max(
                jnp.where(pick, idx_ref[k, :, lanes], 0), axis=0, keepdims=True
            )
            sel_val = jnp.sum(
                jnp.where(pick, val_ref[k, :, lanes], 0.0), axis=0, keepdims=True
            )
            scatter_z(z_ref, sel_idx, sel_val)
        chosen_ref[:, lanes] = jnp.where(active, bhat, -1)
        return carry

    jax.lax.fori_loop(0, idx_ref.shape[-1] // LANE, body, 0)


def pad_pi(pi: jax.Array, num_users: int, padded_users: int) -> jax.Array:
    """π as (1, U⁺) or (B, U⁺) lane rows; padded users get π = −3e38 (never
    active)."""
    rows = pi.reshape(num_users, -1).T.astype(jnp.float32)
    out = jnp.full((rows.shape[0], padded_users), -3.0e38, jnp.float32)
    return out.at[:, :num_users].set(rows)


@functools.partial(jax.jit, static_argnames=("num_resources", "interpret"))
def sparse_bid_eval(
    idx: jax.Array,  # (U, B, K) int32
    val: jax.Array,  # (U, B, K)
    mask: jax.Array,  # (U, B)
    pi: jax.Array,  # (U,) or (U, B)
    prices: jax.Array,  # (R,)
    num_resources: int,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused sparse proxy evaluation. Returns (z (R,), chosen (U,), -1 = out).

    Pads U to the block size; padded users carry an all-invalid mask and
    π = −3e38 (they never activate), and their (idx=0, val=0) slots scatter
    nothing.
    """
    u, b, k = idx.shape
    r = num_resources
    scalar_pi = pi.ndim == 1
    bu = pick_block_u(b, k, not scalar_pi, u)
    up = _round_up(max(u, 1), bu)
    rz = _round_up(max(r, 1), SUBLANE)

    def lanes_last(x, dtype):
        x = jnp.transpose(x.astype(dtype), (2, 1, 0))
        return jnp.zeros((k, b, up), dtype).at[:, :, :u].set(x)

    idx_t = lanes_last(idx, jnp.int32)
    val_t = lanes_last(val, jnp.float32)
    mask_t = jnp.zeros((b, up), jnp.int32).at[:, :u].set(mask.astype(jnp.int32).T)
    pi_t = pad_pi(pi, u, up)
    table = price_table(prices, r)

    # traced with x64 off: Mosaic lowers no 64-bit value, and the fused
    # epoch calls this kernel inside an x64 program
    with jax.enable_x64(False):
        z, chosen = pl.pallas_call(
            functools.partial(_sparse_bid_eval_kernel, scalar_pi=scalar_pi),
            grid=(up // bu,),
            in_specs=[
                pl.BlockSpec(table.shape, lambda i: (0, 0)),  # price table
                pl.BlockSpec((pi_t.shape[0], bu), lambda i: (0, i)),  # pi
                pl.BlockSpec((b, bu), lambda i: (0, i)),  # mask
                pl.BlockSpec((k, b, bu), lambda i: (0, 0, i)),  # idx
                pl.BlockSpec((k, b, bu), lambda i: (0, 0, i)),  # val
            ],
            out_specs=[
                pl.BlockSpec((rz, LANE), lambda i: (0, 0)),  # z partials: revisited
                pl.BlockSpec((1, bu), lambda i: (0, i)),  # chosen
            ],
            out_shape=[
                jax.ShapeDtypeStruct((rz, LANE), jnp.float32),
                jax.ShapeDtypeStruct((1, up), jnp.int32),
            ],
            compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(table, pi_t, mask_t, idx_t, val_t)
    return z.sum(axis=1)[:r], chosen[0, :u]
