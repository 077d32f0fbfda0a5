"""Pallas TPU kernel: segment-offset (CSR) bidder-proxy evaluation, O(nnz).

The padded twin (``sparse_bid_eval``) pays O(U·B·K_max) per round — every
bundle is padded to the densest bundle's nnz, so a skewed book (K ∈ {1..16},
mean 4) streams and masks 4× its true nonzeros.  This variant takes the flat
CSR encoding instead: ``idx``/``val`` are (nnz,) element streams and each
bundle owns the slice ``offsets[row] : offsets[row+1]``, so HBM traffic per
round is the book's true nnz.

TPU mapping:

* users lie on the 128 lanes, exactly like the padded kernel: per block the
  (B, BU) ``starts``/``counts`` tiles say where each bundle's elements live
  in the flat streams, and a loop walks the block's 128-lane slices;
* the flat idx/val streams are whole VMEM residents, laid out as
  (rows, 128).  The 128 users of a slice own a contiguous run of at most
  128·B·k_bound elements, so each slice loads a window of
  ``B·k_bound + 8`` rows starting at a scalar-prefetched, 8-aligned row;
  element ``k`` of every bundle is fetched from that window by per-row lane
  gathers, and dead (bundle, k) slots cost a mask, not a DMA.  The fetched
  elements are kept for the chosen bundle's scatter;
* selection and the compare-and-add z scatter are shared with the padded
  kernel: iota-min tie-breaks, scalar-π affordability or vector-π surplus,
  k_bound passes of ``z[r, l] += val_k·[idx_k == r]`` into the revisited z
  block.

Keeping the flat streams VMEM-resident caps nnz at ``MAX_NNZ`` (2²⁰, about
1M elements); :mod:`repro.kernels.ops` refuses a longer stream.  Streaming
past the cap needs scalar-prefetched window DMAs from HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .sparse_bid_eval import (
    LANE,
    SUBLANE,
    _round_up,
    gather_rows,
    lane_block,
    pad_pi,
    price_table,
    scatter_z,
    select_bundle,
)

MAX_NNZ = 1 << 20  # VMEM-resident stream cap (elements)
_MIB = 1024 * 1024


def window_rows(num_bundles: int, k_bound: int) -> int:
    """Rows of the per-slice stream window: 128 users' B·k_bound elements
    span at most B·k_bound + 1 rows from any start, plus 7 rows of the
    8-row alignment of the window's first row."""
    return _round_up(num_bundles * k_bound + SUBLANE, SUBLANE)


def _window_gather(w_idx, w_val, rel):
    """``(w_idx.reshape(-1)[rel], w_val.reshape(-1)[rel])`` for a
    window-relative position tile ``rel`` (n, 128)."""
    row = jnp.right_shift(rel, 7)
    lane = jnp.bitwise_and(rel, LANE - 1)
    ii = jnp.zeros(rel.shape, jnp.int32)
    vv = jnp.zeros(rel.shape, jnp.float32)
    for j in range(w_idx.shape[0]):
        hit = row == j
        src_i = jnp.broadcast_to(w_idx[j : j + 1, :], rel.shape)
        src_v = jnp.broadcast_to(w_val[j : j + 1, :], rel.shape)
        ii = jnp.where(hit, jnp.take_along_axis(src_i, lane, axis=1), ii)
        vv = jnp.where(hit, jnp.take_along_axis(src_v, lane, axis=1), vv)
    return ii, vv


def _sparse_bid_eval_csr_kernel(
    base_ref,
    table_ref,
    fidx_ref,
    fval_ref,
    pi_ref,
    mask_ref,
    starts_ref,
    counts_ref,
    z_ref,
    chosen_ref,
    *,
    scalar_pi,
    k_bound,
    window,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        z_ref[...] = jnp.zeros_like(z_ref)

    slices = starts_ref.shape[-1] // LANE

    def body(s, carry):
        lanes = pl.ds(pl.multiple_of(s * LANE, LANE), LANE)
        row0 = pl.multiple_of(base_ref[i * slices + s], SUBLANE)
        w_idx = fidx_ref[pl.ds(row0, window), :]
        w_val = fval_ref[pl.ds(row0, window), :]
        starts = starts_ref[:, lanes] - row0 * LANE  # window-relative (B, 128)
        counts = counts_ref[:, lanes]

        costs = jnp.zeros(starts.shape, jnp.float32)
        elements = []
        for k in range(k_bound):
            live = counts > k
            ii, vv = _window_gather(w_idx, w_val, jnp.where(live, starts + k, 0))
            vv = jnp.where(live, vv, 0.0)
            costs += vv * gather_rows(table_ref, ii)
            elements.append((ii, vv))
        pick, bhat, active = select_bundle(
            costs, mask_ref[:, lanes] > 0, pi_ref[:, lanes], scalar_pi
        )

        # the chosen bundle's elements (exactly one row of pick is set)
        for ii, vv in elements:
            sel_idx = jnp.max(jnp.where(pick, ii, 0), axis=0, keepdims=True)
            sel_val = jnp.sum(jnp.where(pick, vv, 0.0), axis=0, keepdims=True)
            scatter_z(z_ref, sel_idx, sel_val)
        chosen_ref[:, lanes] = jnp.where(active, bhat, -1)
        return carry

    jax.lax.fori_loop(0, slices, body, 0)


def pick_block_u(num_bundles: int, vector_pi: bool, num_users: int) -> int:
    """User block (lanes) for the (B, BU) starts/counts/mask/π tiles, rows
    padded to 8 sublanes as on the TPU."""
    rows_b = _round_up(num_bundles, SUBLANE)
    rows = 3 * rows_b + (rows_b if vector_pi else SUBLANE) + SUBLANE
    return lane_block(4 * rows, num_users)


@functools.partial(
    jax.jit, static_argnames=("num_resources", "k_bound", "interpret")
)
def sparse_bid_eval_csr(
    idx: jax.Array,  # (nnz,) int32 — flat pool indices, bundle-major
    val: jax.Array,  # (nnz,) — flat quantities
    offsets: jax.Array,  # (U·B + 1,) int32 — per-bundle element boundaries
    mask: jax.Array,  # (U, B)
    pi: jax.Array,  # (U,) or (U, B)
    prices: jax.Array,  # (R,)
    num_resources: int,
    k_bound: int,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused CSR proxy evaluation. Returns (z (R,), chosen (U,), -1 = out).

    ``k_bound`` is the static per-bundle nnz ceiling (the loop extent), and
    ``offsets`` must be non-decreasing — bundle-major CSR.  Pads U to the
    block size; padded users carry zero counts, an all-invalid mask, and
    π = −3e38, so they never activate and scatter nothing.
    """
    u, b = mask.shape
    r = num_resources
    nnz = idx.shape[0]
    scalar_pi = pi.ndim == 1
    bu = pick_block_u(b, not scalar_pi, u)
    up = _round_up(max(u, 1), bu)
    rz = _round_up(max(r, 1), SUBLANE)
    window = window_rows(b, k_bound)
    stream_rows = _round_up(-(-nnz // LANE), SUBLANE) + window

    offsets = offsets.astype(jnp.int32)
    starts = offsets[:-1].reshape(u, b).T
    counts = (offsets[1:] - offsets[:-1]).reshape(u, b).T
    starts_t = jnp.zeros((b, up), jnp.int32).at[:, :u].set(starts)
    counts_t = jnp.zeros((b, up), jnp.int32).at[:, :u].set(counts)
    mask_t = jnp.zeros((b, up), jnp.int32).at[:, :u].set(mask.astype(jnp.int32).T)
    pi_t = pad_pi(pi, u, up)
    table = price_table(prices, r)
    fidx = jnp.zeros((stream_rows * LANE,), jnp.int32).at[:nnz].set(
        idx.astype(jnp.int32)
    )
    fval = jnp.zeros((stream_rows * LANE,), jnp.float32).at[:nnz].set(
        val.astype(jnp.float32)
    )
    # first window row of each 128-user slice: its first element's row,
    # aligned down to 8 (slices past U start at the stream's end)
    first = jnp.minimum(jnp.arange(up // LANE, dtype=jnp.int32) * LANE, u) * b
    base = offsets[first] // LANE // SUBLANE * SUBLANE

    stream_bytes = 2 * stream_rows * LANE * 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(up // bu,),
        in_specs=[
            pl.BlockSpec(table.shape, lambda i, base: (0, 0)),  # price table
            pl.BlockSpec(memory_space=pltpu.VMEM),  # flat idx: resident
            pl.BlockSpec(memory_space=pltpu.VMEM),  # flat val: resident
            pl.BlockSpec((pi_t.shape[0], bu), lambda i, base: (0, i)),  # pi
            pl.BlockSpec((b, bu), lambda i, base: (0, i)),  # mask
            pl.BlockSpec((b, bu), lambda i, base: (0, i)),  # starts
            pl.BlockSpec((b, bu), lambda i, base: (0, i)),  # counts
        ],
        out_specs=[
            pl.BlockSpec((rz, LANE), lambda i, base: (0, 0)),  # z: revisited
            pl.BlockSpec((1, bu), lambda i, base: (0, i)),  # chosen
        ],
    )
    with jax.enable_x64(False):  # Mosaic lowers no 64-bit value
        z, chosen = pl.pallas_call(
            functools.partial(
                _sparse_bid_eval_csr_kernel,
                scalar_pi=scalar_pi,
                k_bound=k_bound,
                window=window,
            ),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((rz, LANE), jnp.float32),
                jax.ShapeDtypeStruct((1, up), jnp.int32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=stream_bytes + 32 * _MIB,
            ),
            interpret=interpret,
        )(
            base,
            table,
            fidx.reshape(stream_rows, LANE),
            fval.reshape(stream_rows, LANE),
            pi_t,
            mask_t,
            starts_t,
            counts_t,
        )
    return z.sum(axis=1)[:r], chosen[0, :u]
