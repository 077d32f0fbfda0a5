"""Core datatypes for the market-economy provisioning layer.

Terminology follows the paper (Stokely et al.):

* A *resource pool* ``r`` is a (cluster, resource-type) pair — e.g.
  ``("cluster-3", "tpu_chips")`` — with a known base cost ``c(r)`` and a
  pre-auction utilization ``psi(r)``.
* A *user* ``u`` submits one bid ``B_u = {Q_u, pi_u}``: an XOR-set of bundle
  vectors over the R pools (positive components = buy, negative = sell) and a
  scalar willingness-to-pay (negative = minimum acceptable revenue).

Three device-ready encodings exist:

* dense ``AuctionProblem``: bundles ``(U, B, R)`` float32 — simple, but a real
  bid touches only K ≈ 3–6 of the R = clusters×rtypes pools, so at planet
  scale this streams gigabytes of zeros through every clock round;
* sparse ``SparseAuctionProblem``: per-bundle ``(idx, val)`` nonzero pairs
  padded to ``K_max`` — ``idx (U, B, K) int32`` / ``val (U, B, K) float32`` —
  which makes one proxy-evaluation round O(U·B·K) instead of O(U·B·R);
* CSR ``CSRAuctionProblem``: the same nonzeros stored *flat* (``idx/val
  (nnz,)``) with per-bundle ``offsets`` — no ``K_max`` padding at all, so a
  book whose bundle sizes are skewed (K ∈ {1..16}, mean 4) stores and moves
  only its true nnz.  ``pack_bids_csr`` builds it directly,
  ``csr_from_padded``/``padded_from_csr`` convert, and ``csr_padded_views``
  reconstructs the padded layout in-trace (bit-identically) so the
  settlement-grade blocked/exact demand paths run unchanged on CSR books.

Padded ``(idx, val)`` slots carry ``idx = 0, val = 0`` (they gather pool 0's
price, multiply by zero, and scatter nothing), and nonzeros are stored in
ascending pool order so sparse cost sums fold in the same order as a dense
row reduction.  CSR stores the identical nonzeros in the identical (u, b, k)
order, minus the padding.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from typing import Sequence

import jax
import numpy as np
import jax.numpy as jnp

from ..tracing import span


@dataclasses.dataclass(frozen=True)
class ResourcePool:
    """One sellable pool: a (cluster, resource-type) pair."""

    cluster: str
    rtype: str  # "tpu_chips" | "hbm_gb" | "ici_gbps" | "cpu" | "ram_gb" | "disk_tb"
    base_cost: float  # c(r): $ per unit per epoch
    utilization: float  # psi(r) in [0, 1], pre-auction
    supply: float = 0.0  # operator-sellable units this epoch
    # delivered-vs-promised capacity EMA (1.0 = always delivers) — feeds the
    # reputation-weighted reserve curve, see repro.core.reserve
    reliability: float = 1.0

    @property
    def name(self) -> str:
        return f"{self.cluster}/{self.rtype}"


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AuctionProblem:
    """Dense, device-ready encoding of all bids for one auction.

    Attributes:
      bundles: (U, B, R) quantities; row ``u, b`` is the b-th XOR alternative of
        user u.  Positive = demanded, negative = offered.  Padded rows are 0.
      bundle_mask: (U, B) True for valid XOR alternatives.
      pi: (U,) max willingness-to-pay (buyers, +) / min acceptable (sellers, −).
      base_cost: (R,) c(r), used for price normalization.
      supply_scale: (R,) normalization for excess demand (≈ total tradeable
        units of r); keeps the price-update step dimensionless.
    """

    bundles: jax.Array
    bundle_mask: jax.Array
    pi: jax.Array
    base_cost: jax.Array
    supply_scale: jax.Array

    @property
    def num_users(self) -> int:
        return self.bundles.shape[0]

    @property
    def num_bundles(self) -> int:
        return self.bundles.shape[1]

    @property
    def num_resources(self) -> int:
        return self.bundles.shape[2]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AuctionResult:
    """Output of one clock auction settlement."""

    prices: jax.Array  # (R,) final uniform unit prices p*
    allocations: jax.Array  # (U, R) awarded bundle (0 if lost)
    chosen_bundle: jax.Array  # (U,) int index into Q_u, -1 if lost
    won: jax.Array  # (U,) bool
    payments: jax.Array  # (U,) x_uᵀ p*  (negative = revenue to seller)
    excess_demand: jax.Array  # (R,) z at convergence (≤ 0 iff converged)
    rounds: jax.Array  # () int32 — clock rounds executed
    converged: jax.Array  # () bool

    def premium(self, pi: jax.Array) -> jax.Array:
        """Paper eq. (5): gamma_u = |pi_u − x_uᵀp| / |x_uᵀp| for winners."""
        pay = self.payments
        denom = jnp.where(jnp.abs(pay) > 0, jnp.abs(pay), 1.0)
        gamma = jnp.abs(pi - pay) / denom
        return jnp.where(self.won & (jnp.abs(pay) > 0), gamma, jnp.nan)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("idx", "val", "bundle_mask", "pi", "base_cost", "supply_scale"),
    meta_fields=("num_resources",),
)
@dataclasses.dataclass(frozen=True)
class SparseAuctionProblem:
    """Sparse, device-ready encoding of all bids for one auction.

    Attributes:
      idx: (U, B, K) int32 pool indices of each bundle's nonzeros, ascending;
        padded slots are 0.
      val: (U, B, K) quantities at those pools.  Positive = demanded,
        negative = offered.  Padded slots are 0.
      bundle_mask: (U, B) True for valid XOR alternatives.
      pi: (U,) scalar willingness-to-pay, or (U, B) per-bundle (vector-π).
      base_cost: (R,) c(r), used for price normalization.
      supply_scale: (R,) normalization for excess demand.
      num_resources: R — static; the index arrays don't carry it.
    """

    idx: jax.Array
    val: jax.Array
    bundle_mask: jax.Array
    pi: jax.Array
    base_cost: jax.Array
    supply_scale: jax.Array
    num_resources: int

    @property
    def num_users(self) -> int:
        return self.idx.shape[0]

    @property
    def num_bundles(self) -> int:
        return self.idx.shape[1]

    @property
    def k_max(self) -> int:
        return self.idx.shape[2]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparseAuctionResult:
    """Output of one clock auction settled on a SparseAuctionProblem.

    The awarded bundle stays in (idx, val) form — materializing a (U, R)
    allocation matrix at planet scale would undo the O(nnz) win.
    """

    prices: jax.Array  # (R,) final uniform unit prices p*
    alloc_idx: jax.Array  # (U, K) pool indices of the awarded bundle
    alloc_val: jax.Array  # (U, K) awarded quantities (0 if lost)
    chosen_bundle: jax.Array  # (U,) int index into Q_u, -1 if lost
    won: jax.Array  # (U,) bool
    payments: jax.Array  # (U,) x_uᵀ p*  (negative = revenue to seller)
    excess_demand: jax.Array  # (R,) z at convergence (≤ 0 iff converged)
    rounds: jax.Array  # () int32 — clock rounds executed
    converged: jax.Array  # () bool

    def premium(self, pi: jax.Array) -> jax.Array:
        """Paper eq. (5): gamma_u = |pi_u − x_uᵀp| / |x_uᵀp| for winners."""
        pay = self.payments
        denom = jnp.where(jnp.abs(pay) > 0, jnp.abs(pay), 1.0)
        gamma = jnp.abs(pi - pay) / denom
        return jnp.where(self.won & (jnp.abs(pay) > 0), gamma, jnp.nan)

    def allocations_dense(self, num_resources: int) -> jax.Array:
        """(U, R) dense allocation matrix (duplicate indices accumulate)."""
        u = self.alloc_idx.shape[0]
        rows = jnp.repeat(jnp.arange(u), self.alloc_idx.shape[1])
        return (
            jnp.zeros((u, num_resources), jnp.float32)
            .at[rows, self.alloc_idx.reshape(-1)]
            .add(self.alloc_val.reshape(-1).astype(jnp.float32))
        )


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=(
        "idx", "val", "rows", "offsets", "bundle_mask", "pi", "base_cost",
        "supply_scale",
    ),
    meta_fields=("num_resources", "k_bound"),
)
@dataclasses.dataclass(frozen=True)
class CSRAuctionProblem:
    """Variable-K CSR encoding of all bids for one auction.

    The flat twin of :class:`SparseAuctionProblem`: bundle ``(u, b)`` owns the
    slice ``offsets[u*B+b] : offsets[u*B+b+1]`` of the flat ``idx``/``val``
    streams, in the same ascending-pool order the padded layout stores, with
    no K_max padding anywhere.  ``rows`` is the flat bundle id of each
    element (``u*B + b``, redundant with ``offsets`` but carried so O(nnz)
    demand evaluation never rebuilds it).

    Attributes:
      idx: (nnz,) int32 pool indices, bundle-major, ascending within a bundle.
      val: (nnz,) float32 quantities.  Positive = demanded, negative = offered.
      rows: (nnz,) int32 flat bundle id (u·B + b) of each element.
      offsets: (U·B + 1,) int32 bundle boundaries into idx/val.
      bundle_mask: (U, B) True for valid XOR alternatives.
      pi: (U,) scalar willingness-to-pay, or (U, B) per-bundle (vector-π).
      base_cost: (R,) c(r), used for price normalization.
      supply_scale: (R,) normalization for excess demand.
      num_resources: R — static.
      k_bound: static upper bound on any bundle's nnz (the padded layout this
        book would round-trip to has K_max = k_bound); loop extent for the
        in-trace padded reconstruction and the Pallas CSR kernel.
    """

    idx: jax.Array
    val: jax.Array
    rows: jax.Array
    offsets: jax.Array
    bundle_mask: jax.Array
    pi: jax.Array
    base_cost: jax.Array
    supply_scale: jax.Array
    num_resources: int
    k_bound: int

    @property
    def num_users(self) -> int:
        return self.bundle_mask.shape[0]

    @property
    def num_bundles(self) -> int:
        return self.bundle_mask.shape[1]

    @property
    def nnz(self) -> int:
        return self.idx.shape[0]


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=(
        "kmaj_idx", "kmaj_val", "inv_count_perm", "pool_pos", "pool_live",
        "chunk_pool",
    ),
    meta_fields=("m_k", "chunk"),
)
@dataclasses.dataclass(frozen=True)
class CSRDemandAux:
    """Pack-time layouts that make one CSR proxy round scatter-free.

    CPU (and any backend with serialized scatter) pays ~100 ns per scattered
    element, which makes the naive segment-sum CSR round *slower* than the
    padded one it replaces.  Two host-precomputed reorderings remove every
    large scatter from the round:

    * bundle costs — bundles are sorted by nnz (descending); pass ``k`` then
      touches exactly the first ``m_k[k]`` sorted bundles, so the K-term cost
      fold becomes ``k_bound`` *prefix-slice* adds over the k-major element
      stream (``kmaj_idx``/``kmaj_val``), no scatter, O(nnz) total work;
    * excess demand z — elements are sorted by pool and each pool's run is
      padded to a multiple of ``chunk``; the selected values are gathered
      into that layout, chunk-summed by a dense reshape, and only the
      ~nnz/chunk chunk sums hit a scatter.

    Both reorderings are pure data layout: selection is unchanged, and z
    reassociates only across elements of one pool (float-close, like every
    non-exact demand path).  ``m_k`` is static metadata, so a jit'd demand
    round specializes on the book's bundle-size profile.
    """

    kmaj_idx: jax.Array  # (nnz,) int32 — k-major, count-sorted element stream
    kmaj_val: jax.Array  # (nnz,) float32
    inv_count_perm: jax.Array  # (U·B,) int32 — sorted-bundle pos of each bundle
    pool_pos: jax.Array  # (chunks·chunk,) int32 — flat element pos, pool-major
    pool_live: jax.Array  # (chunks·chunk,) bool — False on pool-run padding
    chunk_pool: jax.Array  # (chunks,) int32 — owning pool of each chunk
    m_k: tuple  # static: #bundles with nnz > k, for k in range(k_bound)
    chunk: int  # static: z chunk width


def csr_demand_aux(problem: CSRAuctionProblem, chunk: int = 128) -> CSRDemandAux:
    """Build the scatter-free demand layouts for a (concrete) CSR problem.

    Host-side numpy — call it once per packed book, next to the packer, not
    inside a trace.
    """
    idx = np.asarray(problem.idx)
    val = np.asarray(problem.val)
    offsets = np.asarray(problem.offsets).astype(np.int64)
    counts = offsets[1:] - offsets[:-1]  # (U·B,)
    ub = counts.shape[0]
    nnz = idx.shape[0]

    perm = np.argsort(-counts, kind="stable")  # bundles by nnz, descending
    inv_perm = np.empty_like(perm)
    inv_perm[perm] = np.arange(ub)
    sorted_counts = counts[perm]
    m_k = tuple(int((sorted_counts > k).sum()) for k in range(problem.k_bound))
    kmaj_idx = np.concatenate(
        [idx[offsets[:-1][perm[: m_k[k]]] + k] for k in range(problem.k_bound)]
        or [np.zeros(0, np.int32)]
    )
    kmaj_val = np.concatenate(
        [val[offsets[:-1][perm[: m_k[k]]] + k] for k in range(problem.k_bound)]
        or [np.zeros(0, np.float32)]
    )

    pool_order = np.argsort(idx, kind="stable")
    pool_counts = np.bincount(idx, minlength=problem.num_resources)
    pool_chunks = (pool_counts + chunk - 1) // chunk
    n_chunks = int(pool_chunks.sum())
    pool_pos = np.zeros(max(n_chunks, 1) * chunk, np.int32)
    pool_live = np.zeros(max(n_chunks, 1) * chunk, bool)
    chunk_pool = np.repeat(
        np.arange(problem.num_resources), pool_chunks
    ).astype(np.int32)
    if nnz:
        sorted_pools = idx[pool_order]
        elem_off = np.zeros(problem.num_resources + 1, np.int64)
        elem_off[1:] = np.cumsum(pool_counts)
        write_off = np.zeros(problem.num_resources + 1, np.int64)
        write_off[1:] = np.cumsum(pool_chunks) * chunk
        rank = np.arange(nnz) - elem_off[sorted_pools]
        wpos = write_off[sorted_pools] + rank
        pool_pos[wpos] = pool_order.astype(np.int32)
        pool_live[wpos] = True
    return CSRDemandAux(
        kmaj_idx=jnp.asarray(kmaj_idx.astype(np.int32)),
        kmaj_val=jnp.asarray(kmaj_val.astype(np.float32)),
        inv_count_perm=jnp.asarray(inv_perm.astype(np.int32)),
        pool_pos=jnp.asarray(pool_pos),
        pool_live=jnp.asarray(pool_live),
        chunk_pool=jnp.asarray(chunk_pool),
        m_k=m_k,
        chunk=chunk,
    )


def csr_padded_views(problem: CSRAuctionProblem) -> tuple[jax.Array, jax.Array]:
    """In-trace (U, B, k_bound) idx/val views of a CSR problem.

    Bit-identical to the padded layout the same book packs to: live slots
    gather the flat nonzeros in ascending k order, dead slots are
    ``(idx=0, val=0)`` exactly like ``pack_bids_sparse`` padding.  This is
    how the settlement-grade (exact/blocked) demand paths — whose fold order
    defines bit-reproducibility — run on CSR books without a second
    numerics contract: reconstruct once, then execute the identical padded
    program.
    """
    u, b = problem.bundle_mask.shape
    k = problem.k_bound
    if problem.nnz == 0:
        return (
            jnp.zeros((u, b, k), jnp.int32),
            jnp.zeros((u, b, k), jnp.float32),
        )
    # The TPU compiler's time for this gather swings with the book's shape,
    # up to minutes and 100 MB of code at some row counts that are not a
    # multiple of 128.  Rows are padded to whole 128-row tiles (as empty
    # bundles gathering element 0), gathered behind a barrier that keeps the
    # final slice from folding back into the gather, and dropped.  Some
    # shapes still take about 100 s.
    up = u + (-u % 128)
    offsets = jnp.pad(problem.offsets, (0, (up - u) * b), mode="edge")
    start = offsets[:-1].reshape(up, b)
    count = (offsets[1:] - offsets[:-1]).reshape(up, b)
    kk = jnp.arange(k, dtype=offsets.dtype)
    live = kk[None, None, :] < count[:, :, None]
    pos = jnp.clip(start[:, :, None] + kk[None, None, :], 0, problem.nnz - 1)
    idx, val = jax.lax.optimization_barrier((problem.idx[pos], problem.val[pos]))
    idx = jnp.where(live, idx, 0)[:u]
    val = jnp.where(live, val, 0.0)[:u]
    return idx, val


def padded_from_csr(problem: CSRAuctionProblem) -> SparseAuctionProblem:
    """CSR → K_max-padded conversion (exact; arrays stay on device)."""
    idx, val = csr_padded_views(problem)
    return SparseAuctionProblem(
        idx=idx,
        val=val,
        bundle_mask=problem.bundle_mask,
        pi=problem.pi,
        base_cost=problem.base_cost,
        supply_scale=problem.supply_scale,
        num_resources=problem.num_resources,
    )


def csr_from_padded(problem: SparseAuctionProblem) -> CSRAuctionProblem:
    """Padded → CSR conversion (host-side, vectorized).

    A slot counts as live up to the bundle's last ``(idx, val) != (0, 0)``
    entry; interior explicit-zero entries are kept, trailing padding is
    dropped.  Dropping a trailing all-zero slot is exact — it gathered pool
    0's price and contributed 0.0 — and the reconstruction
    (:func:`csr_padded_views`) regenerates it as ``(0, 0)`` bit for bit.
    """
    idx = np.asarray(problem.idx)
    val = np.asarray(problem.val)
    u, b, k = idx.shape
    live = (idx != 0) | (val != 0)
    any_live = live.any(axis=-1)
    counts = np.where(
        any_live, k - np.argmax(live[..., ::-1], axis=-1), 0
    ).reshape(-1)
    offsets = np.zeros(u * b + 1, np.int32)
    offsets[1:] = np.cumsum(counts)
    nnz = int(offsets[-1])
    flat_idx = np.zeros(nnz, np.int32)
    flat_val = np.zeros(nnz, np.float32)
    starts = offsets[:-1]
    kk = np.arange(k)
    take = kk[None, :] < counts[:, None]  # (U·B, K)
    wpos = (starts[:, None] + kk[None, :])[take]
    flat_idx[wpos] = idx.reshape(u * b, k)[take]
    flat_val[wpos] = val.reshape(u * b, k)[take]
    rows = np.repeat(np.arange(u * b, dtype=np.int32), counts)
    return CSRAuctionProblem(
        idx=jnp.asarray(flat_idx),
        val=jnp.asarray(flat_val),
        rows=jnp.asarray(rows),
        offsets=jnp.asarray(offsets),
        bundle_mask=problem.bundle_mask,
        pi=problem.pi,
        base_cost=problem.base_cost,
        supply_scale=problem.supply_scale,
        num_resources=problem.num_resources,
        k_bound=max(k, 1),
    )


def csr_problem_from_arrays(
    idx: np.ndarray,
    val: np.ndarray,
    offsets: np.ndarray,
    bundle_mask: np.ndarray,
    pi: np.ndarray,
    base_cost: np.ndarray,
    supply_scale: np.ndarray | None = None,
    k_bound: int | None = None,
) -> CSRAuctionProblem:
    """Wrap pre-assembled flat CSR arrays into a CSRAuctionProblem.

    The fast path for vectorized packers (the ``AgentPopulation`` bid-book
    builder emits this layout directly).  Only cheap invariants are checked —
    index range, monotone offsets, shape agreement — so a 10⁶-row book wraps
    in O(nnz) with no per-row Python.
    """
    idx = np.asarray(idx, np.int32)
    val = np.asarray(val, np.float32)
    offsets = np.asarray(offsets, np.int32)
    bundle_mask = np.asarray(bundle_mask, bool)
    num_res = int(np.asarray(base_cost).shape[0])
    if idx.shape != val.shape or idx.ndim != 1:
        raise ValueError(f"idx {idx.shape} / val {val.shape} must be flat (nnz,)")
    u, b = bundle_mask.shape
    if offsets.shape != (u * b + 1,):
        raise ValueError(f"offsets {offsets.shape} != ({u * b + 1},)")
    counts = offsets[1:].astype(np.int64) - offsets[:-1].astype(np.int64)
    if offsets[0] != 0 or offsets[-1] != idx.shape[0] or (counts < 0).any():
        raise ValueError("offsets must grow monotonically from 0 to nnz")
    if idx.size and (idx.min() < 0 or idx.max() >= num_res):
        raise ValueError(
            f"bundle pool indices must be in [0, {num_res}), got "
            f"[{idx.min()}, {idx.max()}]"
        )
    if k_bound is None:
        k_bound = int(counts.max()) if counts.size else 1
    elif counts.size and k_bound < counts.max():
        raise ValueError(f"k_bound={k_bound} < densest bundle nnz={counts.max()}")
    if supply_scale is None:
        # same f32 running accumulation as sparse_supply_scale — the flat
        # stream is the padded (u, b, k) order minus its zeros, and skipping
        # an exact +0.0 preserves every partial sum bit for bit, so CSR and
        # padded packs of one book normalize identically
        acc = np.zeros((num_res,), np.float32)
        np.add.at(acc, idx, np.abs(val))
        supply_scale = np.maximum(acc, 1.0)
    rows = np.repeat(np.arange(u * b, dtype=np.int32), counts)
    return CSRAuctionProblem(
        idx=jnp.asarray(idx),
        val=jnp.asarray(val),
        rows=jnp.asarray(rows),
        offsets=jnp.asarray(offsets),
        bundle_mask=jnp.asarray(bundle_mask),
        pi=jnp.asarray(np.asarray(pi, np.float32)),
        base_cost=jnp.asarray(np.asarray(base_cost, np.float32)),
        supply_scale=jnp.asarray(np.asarray(supply_scale, np.float32)),
        num_resources=num_res,
        k_bound=max(int(k_bound), 1),
    )


def pack_bids_csr(
    bundle_lists: Sequence[Sequence],
    pis: Sequence[float] | np.ndarray,
    base_cost: np.ndarray,
    supply_scale: np.ndarray | None = None,
) -> CSRAuctionProblem:
    """Pack per-user XOR bundle lists straight into a CSRAuctionProblem.

    Accepts the same inputs as :func:`pack_bids_sparse` (dense ``(R,)``
    vectors or ``(idx, val)`` pairs) and produces a book whose settlement is
    bit-identical to the padded pack of the same lists — the supply_scale
    normalizer folds the identical |q| stream (padding zeros add exact 0.0),
    and :func:`csr_padded_views` reconstructs the identical padded arrays.

    Assembles the flat CSR streams directly: a book of U·B bundles costs
    O(nnz) host memory, never the ``(U, B, K_max)`` padded intermediate —
    one dense K_max bundle next to a million single-pool bundles no longer
    inflates every row.  Each bundle is trimmed to its last live
    ``(idx, val) != (0, 0)`` entry (the same trailing-zero rule
    :func:`csr_from_padded` applies), while ``k_bound`` stays the densest
    bundle's *untrimmed* length so the padded reconstruction round-trips.
    """
    num_users = len(bundle_lists)
    num_res = int(np.asarray(base_cost).shape[0])
    parts_i: list[np.ndarray] = []
    parts_v: list[np.ndarray] = []
    entries: list[tuple[int, int, int]] = []  # (user, bundle, count)
    max_b = 1
    k_bound = 1
    for u, bl in enumerate(bundle_lists):
        max_b = max(max_b, len(bl))
        for b, q in enumerate(bl):
            if isinstance(q, tuple):
                ii, vv = q
                ii = np.asarray(ii, np.int32)
                if ii.size and (ii.min() < 0 or ii.max() >= num_res):
                    raise ValueError(
                        f"bundle pool indices must be in [0, {num_res}), got "
                        f"[{ii.min()}, {ii.max()}] — host and device scatter "
                        "paths disagree on out-of-range indices"
                    )
                order = np.argsort(ii, kind="stable")
                ii = ii[order]
                vv = np.asarray(vv, np.float32)[order]
            else:
                q = np.asarray(q)
                ii = np.flatnonzero(q).astype(np.int32)
                vv = q[ii].astype(np.float32)
            k_bound = max(k_bound, len(ii))
            live = np.flatnonzero((ii != 0) | (vv != 0))
            n = int(live[-1]) + 1 if live.size else 0
            parts_i.append(ii[:n])
            parts_v.append(vv[:n])
            entries.append((u, b, n))
    counts = np.zeros((num_users, max_b), np.int64)
    mask = np.zeros((num_users, max_b), bool)
    for u, b, n in entries:
        counts[u, b] = n
        mask[u, b] = True
    offsets = np.zeros(num_users * max_b + 1, np.int32)
    offsets[1:] = np.cumsum(counts.reshape(-1))
    flat_idx = (
        np.concatenate(parts_i) if parts_i else np.zeros(0, np.int32)
    ).astype(np.int32)
    flat_val = (
        np.concatenate(parts_v) if parts_v else np.zeros(0, np.float32)
    ).astype(np.float32)
    return csr_problem_from_arrays(
        flat_idx,
        flat_val,
        offsets,
        mask,
        np.asarray(pis, np.float32),
        base_cost,
        supply_scale=supply_scale,
        k_bound=k_bound,
    )


def pack_bids(
    bundle_lists: Sequence[Sequence[np.ndarray]],
    pis: Sequence[float],
    base_cost: np.ndarray,
    supply_scale: np.ndarray | None = None,
    dtype=jnp.float32,
) -> AuctionProblem:
    """Pack per-user XOR bundle lists into a dense AuctionProblem."""
    num_users = len(bundle_lists)
    num_res = int(np.asarray(base_cost).shape[0])
    max_b = max((len(bl) for bl in bundle_lists), default=1) or 1
    bundles = np.zeros((num_users, max_b, num_res), dtype=np.float32)
    mask = np.zeros((num_users, max_b), dtype=bool)
    for u, bl in enumerate(bundle_lists):
        for b, q in enumerate(bl):
            bundles[u, b] = np.asarray(q, dtype=np.float32)
            mask[u, b] = True
    if supply_scale is None:
        # total offered + demanded volume per resource, floored at 1.
        supply_scale = np.maximum(np.abs(bundles).sum(axis=(0, 1)), 1.0)
    return AuctionProblem(
        bundles=jnp.asarray(bundles, dtype=dtype),
        bundle_mask=jnp.asarray(mask),
        pi=jnp.asarray(np.asarray(pis, dtype=np.float32)),
        base_cost=jnp.asarray(np.asarray(base_cost, dtype=np.float32)),
        supply_scale=jnp.asarray(np.asarray(supply_scale, dtype=np.float32)),
    )


def sparse_supply_scale(idx: np.ndarray, val: np.ndarray, num_res: int) -> np.ndarray:
    """|q| volume per resource from (idx, val) pairs, floored at 1.

    Accumulates in (u, b, k) order — the same fold order as the dense
    ``np.abs(bundles).sum(axis=(0, 1))`` — so dense and sparse packers of the
    same bid book produce bit-identical normalizers.  Public because packers
    that assemble the (U, B, K) arrays directly (e.g. the vectorized
    ``AgentPopulation`` bid-book builder) must normalize exactly like
    :func:`pack_bids_sparse` does.
    """
    acc = np.zeros((num_res,), np.float32)
    np.add.at(acc, idx.reshape(-1), np.abs(val.astype(np.float32)).reshape(-1))
    return np.maximum(acc, 1.0)


_sparse_supply_scale = sparse_supply_scale  # internal alias kept for callers


def bundle_cluster_costs(req: np.ndarray, prices_flat: np.ndarray) -> np.ndarray:
    """(N, C) $ cost of each agent's bundle in each cluster at flat prices.

    ``out[n, c] = Σ_t req[n, t] · prices_flat[c·T + t]`` accumulated in t
    order (float64) — the single bundle-pricing fold every consumer (the
    economy's trader and buy paths, and the bidder policies pricing last
    epoch's settlement) shares, so identical inputs always produce
    bit-identical costs.  ``prices_flat`` is any (C·T,) per-pool price
    vector: the belief curve, a settled price vector, or a reserve curve.
    """
    req = np.asarray(req, np.float64)
    p = np.asarray(prices_flat, np.float64).reshape(-1, req.shape[1])  # (C, T)
    out = np.zeros((req.shape[0], p.shape[0]), np.float64)
    for t in range(req.shape[1]):
        out += req[:, t, None] * p[None, :, t]
    return out


def pack_bids_sparse(
    bundle_lists: Sequence[Sequence],
    pis: Sequence[float] | np.ndarray,
    base_cost: np.ndarray,
    supply_scale: np.ndarray | None = None,
    k_max: int | None = None,
    dtype=jnp.float32,
) -> SparseAuctionProblem:
    """Pack per-user XOR bundle lists straight into a SparseAuctionProblem.

    Each bundle may be either a dense ``(R,)`` vector (nonzeros are
    extracted) or an ``(idx, val)`` pair of 1-D arrays (stored as given, in
    ascending-index order).  O(nnz) host work per sparse-pair bundle — no
    ``(R,)`` row is ever materialized for them.
    """
    num_users = len(bundle_lists)
    num_res = int(np.asarray(base_cost).shape[0])
    rows: list[list[tuple[np.ndarray, np.ndarray]]] = []
    nnz_max = 1
    max_b = 1
    for bl in bundle_lists:
        row = []
        for q in bl:
            if isinstance(q, tuple):
                ii, vv = q
                ii = np.asarray(ii, np.int32)
                if ii.size and (ii.min() < 0 or ii.max() >= num_res):
                    raise ValueError(
                        f"bundle pool indices must be in [0, {num_res}), got "
                        f"[{ii.min()}, {ii.max()}] — host and device scatter "
                        "paths disagree on out-of-range indices"
                    )
                order = np.argsort(ii, kind="stable")
                ii = ii[order]
                vv = np.asarray(vv, np.float32)[order]
            else:
                q = np.asarray(q)
                ii = np.flatnonzero(q).astype(np.int32)
                vv = q[ii].astype(np.float32)
            row.append((ii, vv))
            nnz_max = max(nnz_max, len(ii))
        rows.append(row)
        max_b = max(max_b, len(row))
    if k_max is None:
        k_max = nnz_max
    elif k_max < nnz_max:
        raise ValueError(f"k_max={k_max} < densest bundle nnz={nnz_max}")

    idx = np.zeros((num_users, max_b, k_max), np.int32)
    val = np.zeros((num_users, max_b, k_max), np.float32)
    mask = np.zeros((num_users, max_b), bool)
    for u, row in enumerate(rows):
        for b, (ii, vv) in enumerate(row):
            idx[u, b, : len(ii)] = ii
            val[u, b, : len(ii)] = vv
            mask[u, b] = True
    if supply_scale is None:
        supply_scale = _sparse_supply_scale(idx, val, num_res)
    return SparseAuctionProblem(
        idx=jnp.asarray(idx),
        val=jnp.asarray(val, dtype=dtype),
        bundle_mask=jnp.asarray(mask),
        pi=jnp.asarray(np.asarray(pis, dtype=np.float32)),
        base_cost=jnp.asarray(np.asarray(base_cost, dtype=np.float32)),
        supply_scale=jnp.asarray(np.asarray(supply_scale, dtype=np.float32)),
        num_resources=num_res,
    )


def sparse_problem_from_arrays(
    idx: np.ndarray,
    val: np.ndarray,
    bundle_mask: np.ndarray,
    pi: np.ndarray,
    base_cost: np.ndarray,
    supply_scale: np.ndarray | None = None,
) -> SparseAuctionProblem:
    """Wrap pre-assembled (U, B, K) arrays into a SparseAuctionProblem.

    The fast path for vectorized packers (``AgentPopulation`` bid books) that
    already emit ``pack_bids_sparse``'s exact layout: idx int32 ascending per
    bundle with 0-padding, val float32 with 0-padding, π padded with −inf.
    Only cheap invariants are checked — index range and shape agreement — so
    a 10⁶-row book wraps in O(nnz) with no per-row Python.
    """
    idx = np.asarray(idx, np.int32)
    val = np.asarray(val, np.float32)
    num_res = int(np.asarray(base_cost).shape[0])
    if idx.shape != val.shape or idx.ndim != 3:
        raise ValueError(f"idx {idx.shape} / val {val.shape} must be (U, B, K)")
    if bundle_mask.shape != idx.shape[:2]:
        raise ValueError(f"bundle_mask {bundle_mask.shape} != {idx.shape[:2]}")
    if idx.size and (idx.min() < 0 or idx.max() >= num_res):
        raise ValueError(
            f"bundle pool indices must be in [0, {num_res}), got "
            f"[{idx.min()}, {idx.max()}]"
        )
    if supply_scale is None:
        supply_scale = sparse_supply_scale(idx, val, num_res)
    return SparseAuctionProblem(
        idx=jnp.asarray(idx),
        val=jnp.asarray(val),
        bundle_mask=jnp.asarray(np.asarray(bundle_mask, bool)),
        pi=jnp.asarray(np.asarray(pi, np.float32)),
        base_cost=jnp.asarray(np.asarray(base_cost, np.float32)),
        supply_scale=jnp.asarray(np.asarray(supply_scale, np.float32)),
        num_resources=num_res,
    )


def pad_users(problem: SparseAuctionProblem, multiple: int) -> SparseAuctionProblem:
    """Zero-pad the user dimension up to a multiple of ``multiple``.

    Padded rows carry ``bundle_mask=False``, so their proxies never activate
    and they contribute exact zeros everywhere — settlement results on the
    first ``num_users`` rows are unchanged.  Pure ``jnp`` (traceable), which
    is how ``sharded_clock_auction`` evens out the users axis before
    splitting it over a device mesh.
    """
    pad = -problem.num_users % multiple
    if pad == 0:
        return problem
    return dataclasses.replace(
        problem,
        idx=jnp.pad(problem.idx, ((0, pad), (0, 0), (0, 0))),
        val=jnp.pad(problem.val, ((0, pad), (0, 0), (0, 0))),
        bundle_mask=jnp.pad(problem.bundle_mask, ((0, pad), (0, 0))),
        pi=jnp.pad(problem.pi, ((0, pad),) + ((0, 0),) * (problem.pi.ndim - 1)),
    )


def sparsify(problem: AuctionProblem, k_max: int | None = None) -> SparseAuctionProblem:
    """Dense → sparse conversion (host-side, vectorized).

    Nonzeros keep ascending pool order so sparse cost sums fold in the same
    order as the dense row reduction.  ``k_max`` below the densest bundle's
    nnz raises rather than silently truncating bids.
    """
    bundles = np.asarray(problem.bundles)
    u, b, r = bundles.shape
    nz = bundles != 0
    counts = nz.sum(axis=-1)
    nnz_max = max(int(counts.max()) if counts.size else 0, 1)
    if k_max is None:
        k_max = nnz_max
    elif k_max < nnz_max:
        raise ValueError(f"k_max={k_max} < densest bundle nnz={nnz_max}")
    # stable sort moves nonzero positions to the front, ascending
    order = np.argsort(~nz, axis=-1, kind="stable")[..., :k_max]
    val = np.take_along_axis(bundles, order, axis=-1)
    live = np.arange(k_max)[None, None, :] < counts[..., None]
    return SparseAuctionProblem(
        idx=jnp.asarray(np.where(live, order, 0).astype(np.int32)),
        val=jnp.asarray(np.where(live, val, 0.0).astype(np.float32)),
        bundle_mask=problem.bundle_mask,
        pi=problem.pi,
        base_cost=problem.base_cost,
        supply_scale=problem.supply_scale,
        num_resources=r,
    )


def densify(problem: SparseAuctionProblem) -> AuctionProblem:
    """Sparse → dense conversion (duplicate indices within a bundle sum)."""
    idx = np.asarray(problem.idx)
    val = np.asarray(problem.val)
    u, b, k = idx.shape
    bundles = np.zeros((u, b, problem.num_resources), np.float32)
    uu, bb = np.meshgrid(np.arange(u), np.arange(b), indexing="ij")
    np.add.at(
        bundles,
        (
            uu[..., None].repeat(k, -1).reshape(-1),
            bb[..., None].repeat(k, -1).reshape(-1),
            idx.reshape(-1),
        ),
        val.reshape(-1),
    )
    return AuctionProblem(
        bundles=jnp.asarray(bundles),
        bundle_mask=problem.bundle_mask,
        pi=problem.pi,
        base_cost=problem.base_cost,
        supply_scale=problem.supply_scale,
    )


# ---------------------------------------------------------------------------
# Incremental (always-on) CSR bid book
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _book_static_layout(rows_cap: int, b: int, k: int):
    """(rows, offsets) of the fixed-count-K book layout — constant per shape."""
    offsets = (np.arange(rows_cap * b + 1, dtype=np.int64) * k).astype(np.int32)
    rows = np.repeat(np.arange(rows_cap * b, dtype=np.int32), k)
    return jnp.asarray(rows), jnp.asarray(offsets)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _csr_apply_row_deltas(
    idx: jax.Array,  # (rows_cap·B·K,) int32 — donated
    val: jax.Array,  # (rows_cap·B·K,) float32 — donated
    mask: jax.Array,  # (rows_cap, B) bool — donated
    pi: jax.Array,  # (rows_cap, B) float32 — donated
    rows: jax.Array,  # (D,) int32 — target row slots (duplicates allowed iff
    #     they carry identical payloads; the book pads delta batches that way)
    idx_rows: jax.Array,  # (D, B, K) int32
    val_rows: jax.Array,  # (D, B, K) float32
    mask_rows: jax.Array,  # (D, B) bool
    pi_rows: jax.Array,  # (D, B) float32
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Overwrite ``D`` whole row slots of a device-resident CSR book in place.

    This is the delta-application kernel of the always-on market service: the
    four big buffers are donated, so applying a tick's Δ bid changes costs
    O(Δ·B·K) device work and **zero** host↔device traffic for the unchanged
    rows — instead of the O(N) re-upload a from-scratch repack pays.  Shapes
    are static per (capacity, delta-bucket), so bounded churn reuses one
    compiled program.
    """
    d, b, k = idx_rows.shape
    flat = (
        rows[:, None, None] * (b * k)
        + jnp.arange(b, dtype=rows.dtype)[None, :, None] * k
        + jnp.arange(k, dtype=rows.dtype)[None, None, :]
    ).reshape(-1)
    idx = idx.at[flat].set(idx_rows.reshape(-1), unique_indices=False)
    val = val.at[flat].set(val_rows.reshape(-1), unique_indices=False)
    mask = mask.at[rows].set(mask_rows, unique_indices=False)
    pi = pi.at[rows].set(pi_rows, unique_indices=False)
    return idx, val, mask, pi


class MarketBook:
    """Persistent slotted CSR bid book with amortized-O(Δ) delta application.

    The always-on twin of the per-epoch packers: instead of rebuilding the
    flat ``idx``/``val`` streams from scratch every auction, the book owns
    ``rows_cap`` fixed-width row slots — slot ``s`` holds one account's XOR
    bid in elements ``[s·B·K, (s+1)·B·K)`` — and arrivals / departures / bid
    updates land as whole-row insert/delete/update writes.  ``offsets`` are
    the static ``arange·K`` ladder (every bundle region is exactly ``K``
    wide, zero-padded inside — explicit ``(idx=0, val=0)`` elements gather
    pool 0's price and contribute exact ``0.0``, the same bit-neutral padding
    contract every packer in this repo relies on), so the
    :class:`CSRAuctionProblem` this book emits has a **stable shape** per
    capacity and the jitted settlement program compiles once per
    capacity-doubling, not once per churn event.

    Host numpy arrays are the master copy (validation, oracle); a device
    mirror is maintained by :func:`_csr_apply_row_deltas` with donated
    buffers, so per-tick device work is O(Δ·B·K).

    Parity oracle: :meth:`rebuilt` re-packs every live account from its raw
    submission into the *same slot* of a fresh zeroed book — the full-repack
    twin of ``packer="loop"`` — and :meth:`parity_check` asserts the
    incremental arrays are bit-identical to it.  ``supply_scale`` is carried
    as an exact float64 per-pool |q| ledger (adds on insert, subtracts on
    delete); within the service's validated quantity range every ledger op is
    exact in float64, so the incremental ledger equals the oracle's
    from-scratch sum bit for bit.
    """

    def __init__(
        self,
        base_cost: np.ndarray,
        num_bundles: int,
        k_bound: int,
        rows_cap: int = 64,
    ) -> None:
        if num_bundles < 1 or k_bound < 1:
            raise ValueError("num_bundles and k_bound must be >= 1")
        self.base_cost = np.asarray(base_cost, np.float32)
        self.num_resources = int(self.base_cost.shape[0])
        self.num_bundles = int(num_bundles)
        self.k_bound = int(k_bound)
        self.rows_cap = 1
        while self.rows_cap < max(int(rows_cap), 1):
            self.rows_cap *= 2
        self._alloc_arrays(self.rows_cap)
        self._key_slot: dict = {}
        self._slot_key: list = [None] * self.rows_cap
        self._accounts: dict = {}  # key -> (bundles tuple, pi tuple) as packed
        self._raw_queue: dict = {}  # slot -> raw account its columns await
        self._next_slot = 0
        self._free: list[int] = []  # LIFO of freed slots below _next_slot
        self._ledger = np.zeros(self.num_resources, np.float64)
        # offered-supply twin of the |q| ledger: per-pool sum of |q| over the
        # *sell-side* elements only (q < 0) — real utilization telemetry for
        # the service (settled demand / offered supply) without an O(nnz) scan
        self._sell_ledger = np.zeros(self.num_resources, np.float64)
        self._generation = 0  # bumps on every growth (device full re-upload)
        self._dev: dict | None = None
        self._dev_generation = -1
        self._dev_pending: list[int] = []  # slots written since last sync
        # slots written since the last checkpoint export — a separate set from
        # _dev_pending because the two clear at different times (device sync
        # per tick vs. durable commit)
        self._ckpt_dirty: set[int] = set()
        self.deltas_applied = 0  # lifetime upsert+remove count (telemetry)

    # -- storage ------------------------------------------------------------

    def _alloc_arrays(self, rows_cap: int) -> None:
        b, k = self.num_bundles, self.k_bound
        self.idx = np.zeros(rows_cap * b * k, np.int32)
        self.val = np.zeros(rows_cap * b * k, np.float32)
        self.mask = np.zeros((rows_cap, b), bool)
        self.pi = np.zeros((rows_cap, b), np.float32)
        # the account mirror: each slot's submission in columns the
        # checkpoint encoder gathers from.  ``kind`` is -1 for an empty slot,
        # 0 for a raw (bundles, pi) submission (``count`` bundles, ``nnz``
        # elements each, in submitted order; ``pi`` broadcast to the
        # bundles), 1 for a pre-packed (idx, val, mask, pi) payload.  It is
        # written from the submissions, never from the slot arrays above, so
        # parity_check on a restored book stays an independent oracle.
        self._acct = {
            "kind": np.full(rows_cap, -1, np.int8),
            "count": np.zeros(rows_cap, np.int32),
            "nnz": np.zeros((rows_cap, b), np.int32),
            "idx": np.zeros((rows_cap, b, k), np.int32),
            "val": np.zeros((rows_cap, b, k), np.float32),
            "pi": np.zeros((rows_cap, b), np.float32),
            "mask": np.zeros((rows_cap, b), bool),
        }

    def _slot_arrays(self) -> tuple[np.ndarray, ...]:
        return (self.idx, self.val, self.mask, self.pi, *self._acct.values())

    def _grow(self, new_cap: int) -> None:
        """Reallocate every per-slot array at ``new_cap`` slots, keeping
        the first ``rows_cap`` slots' contents."""
        old = self._slot_arrays()
        self._alloc_arrays(new_cap)
        for new, a in zip(self._slot_arrays(), old):
            new[: a.shape[0]] = a
        self._slot_key.extend([None] * (new_cap - self.rows_cap))
        self.rows_cap = new_cap

    def _ensure_rows(self, extra: int) -> None:
        need = self._next_slot - len(self._free) + extra
        if need <= self.rows_cap:
            return
        new_cap = self.rows_cap
        while new_cap < need:
            new_cap *= 2
        self._grow(new_cap)
        self._generation += 1  # stale device mirror: full re-upload
        self._dev = None
        self._dev_pending.clear()

    @property
    def num_rows(self) -> int:
        """Live account count."""
        return len(self._key_slot)

    @property
    def nnz_cap(self) -> int:
        return self.rows_cap * self.num_bundles * self.k_bound

    # -- row packing --------------------------------------------------------

    def _pack_row(self, bundles, pi):
        """One account's raw submission → (idx (B,K), val (B,K), mask (B,),
        pi (B,)) row payload.  Nonzeros are sorted ascending by pool (the
        fold-order contract every demand path shares) and zero-padded to K.
        """
        b_cap, k_cap = self.num_bundles, self.k_bound
        if len(bundles) == 0 or len(bundles) > b_cap:
            raise ValueError(f"bundle count must be in [1, {b_cap}], got {len(bundles)}")
        pi_arr = np.broadcast_to(np.asarray(pi, np.float32), (len(bundles),))
        idx_row = np.zeros((b_cap, k_cap), np.int32)
        val_row = np.zeros((b_cap, k_cap), np.float32)
        mask_row = np.zeros(b_cap, bool)
        pi_row = np.zeros(b_cap, np.float32)
        for b, q in enumerate(bundles):
            ii, vv = q
            ii = np.asarray(ii, np.int32)
            vv = np.asarray(vv, np.float32)
            if ii.shape != vv.shape or ii.ndim != 1:
                raise ValueError("each bundle must be a flat (idx, val) pair")
            if len(ii) > k_cap:
                raise ValueError(f"bundle nnz {len(ii)} > k_bound {k_cap}")
            if ii.size and (ii.min() < 0 or ii.max() >= self.num_resources):
                raise ValueError(
                    f"bundle pool indices must be in [0, {self.num_resources})"
                )
            if not np.isfinite(vv).all():
                raise ValueError("bundle quantities must be finite")
            order = np.argsort(ii, kind="stable")
            idx_row[b, : len(ii)] = ii[order]
            val_row[b, : len(ii)] = vv[order]
            mask_row[b] = True
            pi_row[b] = pi_arr[b]
        if not np.isfinite(pi_row).all():
            raise ValueError("pi must be finite")
        return idx_row, val_row, mask_row, pi_row

    # -- delta application --------------------------------------------------

    def upsert(self, key, bundles, pi) -> None:
        """Insert or replace one account's bid.  Amortized O(B·K)."""
        row = self._pack_row(bundles, pi)
        slots = self._write_rows([key], *(a[None] for a in row))
        acct = (tuple(
            (np.array(ii, np.int32), np.array(vv, np.float32)) for ii, vv in bundles
        ), np.asarray(pi, np.float32))
        self._accounts[key] = acct
        self._mirror_raw(slots, [acct])

    def upsert_rows(self, keys, idx_rows, val_rows, mask_rows, pi_rows, raw=None):
        """Vectorized multi-account upsert of pre-packed row payloads.

        ``raw`` optionally carries the original (bundles, pi) submissions,
        each one :meth:`_pack_row` accepted, so :meth:`rebuilt` can re-pack
        them; when omitted the payload itself is stored (already
        canonical)."""
        slots = self._write_rows(keys, idx_rows, val_rows, mask_rows, pi_rows)
        if raw is not None:
            self._accounts.update(zip(keys, raw))
            self._mirror_raw(slots, raw)
            return
        for i, key in enumerate(keys):
            self._accounts[key] = (
                idx_rows[i].copy(), val_rows[i].copy(),
                mask_rows[i].copy(), pi_rows[i].copy(),
            )
        self._mirror_packed(slots, idx_rows, val_rows, mask_rows, pi_rows)

    def _mirror_raw(self, slots, accts) -> None:
        """Mirror raw (bundles, pi) submissions into ``slots``: the kind at
        once, the columns at the next export (:meth:`_flush_raw`).  A drain
        pays one dict update; each account's bundles are flattened once, in
        the checkpoint snapshot that follows."""
        slots = np.asarray(slots, np.int64)
        self._acct["kind"][slots] = 0
        self._raw_queue.update(zip(slots.tolist(), accts))

    def _flush_raw(self) -> None:
        """Write the queued raw submissions into the mirror's columns: one
        concatenate of their bundles and one scatter per column."""
        if not self._raw_queue:
            return
        b, k, m = self.num_bundles, self.k_bound, self._acct
        slots = np.fromiter(self._raw_queue, np.int64, len(self._raw_queue))
        accts = list(self._raw_queue.values())
        bundles = [q for acct in accts for q in acct[0]]
        counts = np.fromiter((len(acct[0]) for acct in accts), np.int64, len(accts))
        nnz = np.fromiter((len(ii) for ii, _ in bundles), np.int64, len(bundles))
        pi_len = np.fromiter((np.size(acct[1]) for acct in accts), np.int64, len(accts))
        if counts.max() > b or nnz.max() > k or not np.all((pi_len == 1) | (pi_len == counts)):
            raise ValueError("raw submission does not fit the book (pack it first)")
        self._raw_queue.clear()
        # bundle j of account a lands in row slots[a]·B + j, its elements in
        # that row's first nnz columns; a scalar pi serves every bundle
        j = np.arange(len(bundles)) - np.repeat(np.cumsum(counts) - counts, counts)
        rows = np.repeat(slots, counts) * b + j
        el = np.repeat(rows * k, nnz) + np.arange(int(nnz.sum())) - np.repeat(
            np.cumsum(nnz) - nnz, nnz
        )
        pi_at = np.repeat(np.cumsum(pi_len) - pi_len, counts) + np.where(
            np.repeat(pi_len, counts) == 1, 0, j
        )
        pis = np.concatenate([acct[1] for acct in accts], axis=None)
        m["count"][slots] = counts
        m["nnz"][slots] = 0
        m["nnz"].reshape(-1)[rows] = nnz
        m["idx"].reshape(-1)[el] = np.concatenate([ii for ii, _ in bundles])
        m["val"].reshape(-1)[el] = np.concatenate([vv for _, vv in bundles])
        m["pi"].reshape(-1)[rows] = pis.astype(np.float32)[pi_at]

    def _mirror_packed(self, slots, idx_rows, val_rows, mask_rows, pi_rows) -> None:
        """Mirror pre-packed row payloads into ``slots``."""
        self._flush_raw()  # a queued raw write must not land over these
        b, k, m = self.num_bundles, self.k_bound, self._acct
        slots = np.asarray(slots, np.int64)
        m["kind"][slots] = 1
        m["idx"][slots] = np.asarray(idx_rows, np.int32).reshape(-1, b, k)
        m["val"][slots] = np.asarray(val_rows, np.float32).reshape(-1, b, k)
        m["mask"][slots] = np.asarray(mask_rows, bool)
        m["pi"][slots] = np.asarray(pi_rows, np.float32)

    def _mirror_accounts(self, pairs) -> None:
        """Mirror decoded ``(slot, account)`` pairs of either kind."""
        packed = [(s, a) for s, a in pairs if len(a) != 2]
        raw = [(s, a) for s, a in pairs if len(a) == 2]
        if packed:
            self._mirror_packed(
                [s for s, _ in packed],
                *(np.stack([a[j] for _, a in packed]) for j in range(4)),
            )
        self._mirror_raw([s for s, _ in raw], [a for _, a in raw])

    def _write_rows(self, keys, idx_rows, val_rows, mask_rows, pi_rows) -> np.ndarray:
        """Write pre-packed rows into the keys' slots (allocating new keys'
        slots); returns the slots."""
        d = len(keys)
        if len(set(keys)) != d:
            # the ledger reads each slot's old contents once per batch, so a
            # key repeated within one batch would double-retire them
            raise ValueError("duplicate keys in one delta batch (dedupe first)")
        idx_rows = np.asarray(idx_rows, np.int32)
        val_rows = np.asarray(val_rows, np.float32)
        mask_rows = np.asarray(mask_rows, bool)
        pi_rows = np.asarray(pi_rows, np.float32)
        new = [k for k in keys if k not in self._key_slot]
        self._ensure_rows(len(new))
        slots = np.empty(d, np.int64)
        for i, key in enumerate(keys):
            s = self._key_slot.get(key)
            if s is None:
                s = self._free.pop() if self._free else self._next_slot
                if s == self._next_slot:
                    self._next_slot += 1
                self._key_slot[key] = s
                self._slot_key[s] = key
            slots[i] = s
        b, k = self.num_bundles, self.k_bound
        el = (
            slots[:, None, None] * (b * k)
            + np.arange(b)[None, :, None] * k
            + np.arange(k)[None, None, :]
        ).reshape(d, -1)
        old_val = self.val[el]
        old_idx = self.idx[el]
        # exact f64 ledgers: retire the old elements' |q|, credit the new
        self._ledger -= np.bincount(
            old_idx.reshape(-1),
            weights=np.abs(old_val.reshape(-1), dtype=np.float64),
            minlength=self.num_resources,
        )
        self._ledger += np.bincount(
            idx_rows.reshape(-1).astype(np.int64),
            weights=np.abs(val_rows.reshape(-1), dtype=np.float64),
            minlength=self.num_resources,
        )
        self._sell_ledger -= np.bincount(
            old_idx.reshape(-1),
            weights=np.maximum(-old_val.reshape(-1).astype(np.float64), 0.0),
            minlength=self.num_resources,
        )
        self._sell_ledger += np.bincount(
            idx_rows.reshape(-1).astype(np.int64),
            weights=np.maximum(-val_rows.reshape(-1).astype(np.float64), 0.0),
            minlength=self.num_resources,
        )
        flat = el.reshape(-1)
        self.idx[flat] = idx_rows.reshape(-1)
        self.val[flat] = val_rows.reshape(-1)
        self.mask[slots] = mask_rows
        self.pi[slots] = pi_rows
        self._dev_pending.extend(int(s) for s in slots)
        self._ckpt_dirty.update(int(s) for s in slots)
        self.deltas_applied += d
        return slots

    def remove(self, key) -> bool:
        """Withdraw one account's bid; frees its slot (LIFO reuse).  O(B·K)."""
        s = self._key_slot.pop(key, None)
        if s is None:
            return False
        b, k = self.num_bundles, self.k_bound
        lo, hi = s * b * k, (s + 1) * b * k
        self._ledger -= np.bincount(
            self.idx[lo:hi].astype(np.int64),
            weights=np.abs(self.val[lo:hi], dtype=np.float64),
            minlength=self.num_resources,
        )
        self._sell_ledger -= np.bincount(
            self.idx[lo:hi].astype(np.int64),
            weights=np.maximum(-self.val[lo:hi].astype(np.float64), 0.0),
            minlength=self.num_resources,
        )
        self.idx[lo:hi] = 0
        self.val[lo:hi] = 0.0
        self.mask[s] = False
        self.pi[s] = 0.0
        self._slot_key[s] = None
        self._accounts.pop(key, None)
        self._acct["kind"][s] = -1
        self._raw_queue.pop(s, None)
        self._free.append(s)
        self._dev_pending.append(s)
        self._ckpt_dirty.add(int(s))
        self.deltas_applied += 1
        return True

    def __contains__(self, key) -> bool:
        return key in self._key_slot

    def __len__(self) -> int:
        return self.num_rows

    # -- problem views ------------------------------------------------------

    def supply_scale(self) -> np.ndarray:
        return np.maximum(self._ledger.astype(np.float32), 1.0)

    def problem(self) -> CSRAuctionProblem:
        """Host-array snapshot as a CSRAuctionProblem (fresh upload)."""
        rows, offsets = _book_static_layout(
            self.rows_cap, self.num_bundles, self.k_bound
        )
        return CSRAuctionProblem(
            idx=jnp.asarray(self.idx),
            val=jnp.asarray(self.val),
            rows=rows,
            offsets=offsets,
            bundle_mask=jnp.asarray(self.mask),
            pi=jnp.asarray(self.pi),
            base_cost=jnp.asarray(self.base_cost),
            supply_scale=jnp.asarray(self.supply_scale()),
            num_resources=self.num_resources,
            k_bound=self.k_bound,
        )

    def device_problem(self) -> CSRAuctionProblem:
        """Device-resident view, synced by O(Δ) donated row scatters.

        On first use (and after every capacity doubling) the whole book is
        uploaded once; afterwards each call flushes only the slots written
        since the last sync, with the delta batch padded to a power-of-two
        bucket (idempotent duplicate writes of the first slot) so churn
        reuses a handful of compiled scatter programs per capacity.  The
        work runs under the ``market.scatter`` span (``kind``: full, delta
        or none; ``bucket``: the rows written).
        """
        if self._dev is None or self._dev_generation != self._generation:
            kind, d = "full", self.rows_cap
        elif self._dev_pending:
            slots = sorted(set(self._dev_pending))
            kind, d = "delta", 1
            while d < len(slots):  # rows_cap is a power of two, so d <= rows_cap
                d *= 2
        else:
            kind, d = "none", 0
        with span("scatter", kind=kind, bucket=d):
            if kind == "full":
                self._dev = {
                    "idx": jnp.asarray(self.idx),
                    "val": jnp.asarray(self.val),
                    "mask": jnp.asarray(self.mask),
                    "pi": jnp.asarray(self.pi),
                }
                self._dev_generation = self._generation
                self._dev_pending.clear()
            elif kind == "delta":
                padded = np.full(d, slots[0], np.int32)
                padded[: len(slots)] = slots
                b, k = self.num_bundles, self.k_bound
                el = (
                    padded.astype(np.int64)[:, None, None] * (b * k)
                    + np.arange(b)[None, :, None] * k
                    + np.arange(k)[None, None, :]
                ).reshape(d, b, k)
                new = _csr_apply_row_deltas(
                    self._dev["idx"], self._dev["val"], self._dev["mask"],
                    self._dev["pi"], jnp.asarray(padded),
                    jnp.asarray(self.idx[el.reshape(d, -1)].reshape(d, b, k)),
                    jnp.asarray(self.val[el.reshape(d, -1)].reshape(d, b, k)),
                    jnp.asarray(self.mask[padded]),
                    jnp.asarray(self.pi[padded]),
                )
                self._dev = dict(zip(("idx", "val", "mask", "pi"), new))
                self._dev_pending.clear()
            rows, offsets = _book_static_layout(
                self.rows_cap, self.num_bundles, self.k_bound
            )
            return CSRAuctionProblem(
                idx=self._dev["idx"],
                val=self._dev["val"],
                rows=rows,
                offsets=offsets,
                bundle_mask=self._dev["mask"],
                pi=self._dev["pi"],
                base_cost=jnp.asarray(self.base_cost),
                supply_scale=jnp.asarray(self.supply_scale()),
                num_resources=self.num_resources,
                k_bound=self.k_bound,
            )

    # -- full-repack oracle -------------------------------------------------

    def rebuilt(self) -> "MarketBook":
        """From-scratch repack: every live account re-packed from its raw
        submission into the *same slot* of a fresh zeroed book — the
        ``packer="loop"`` analogue.  Dead slots stay zeroed, so any stale
        element an incremental delete left behind shows up as a mismatch."""
        fresh = MarketBook(
            self.base_cost, self.num_bundles, self.k_bound, self.rows_cap
        )
        for s in range(self._next_slot):
            key = self._slot_key[s]
            if key is None:
                continue
            acct = self._accounts[key]
            if len(acct) == 2:  # (bundles, pi) raw submission
                row = fresh._pack_row(*acct)
            else:  # pre-packed payload from upsert_rows
                row = acct
            fresh._key_slot[key] = s
            fresh._slot_key[s] = key
            fresh._accounts[key] = acct
            b, k = fresh.num_bundles, fresh.k_bound
            lo = s * b * k
            fresh.idx[lo : lo + b * k] = np.asarray(row[0], np.int32).reshape(-1)
            fresh.val[lo : lo + b * k] = np.asarray(row[1], np.float32).reshape(-1)
            fresh.mask[s] = row[2]
            fresh.pi[s] = row[3]
            fresh._ledger += np.bincount(
                np.asarray(row[0], np.int64).reshape(-1),
                weights=np.abs(np.asarray(row[1], np.float64)).reshape(-1),
                minlength=fresh.num_resources,
            )
            fresh._sell_ledger += np.bincount(
                np.asarray(row[0], np.int64).reshape(-1),
                weights=np.maximum(
                    -np.asarray(row[1], np.float64).reshape(-1), 0.0
                ),
                minlength=fresh.num_resources,
            )
        fresh._next_slot = self._next_slot
        fresh._free = [s for s in range(self._next_slot) if self._slot_key[s] is None]
        self._flush_raw()
        fresh._acct = {name: a.copy() for name, a in self._acct.items()}
        return fresh

    def parity_check(self) -> None:
        """Assert the incremental book is bit-identical to a full repack."""
        oracle = self.rebuilt()
        for name in ("idx", "val", "mask", "pi"):
            a, b = getattr(self, name), getattr(oracle, name)
            if not np.array_equal(a, b):
                where = np.flatnonzero((a != b).reshape(-1))[:8]
                raise AssertionError(
                    f"incremental book diverged from full repack in {name!r} "
                    f"at flat positions {where.tolist()}"
                )
        if not np.array_equal(self.supply_scale(), oracle.supply_scale()):
            raise AssertionError(
                "incremental supply_scale ledger diverged from full repack"
            )
        if not np.array_equal(self._sell_ledger, oracle._sell_ledger):
            raise AssertionError(
                "incremental offered-supply ledger diverged from full repack"
            )

    # -- crash-recoverable state ---------------------------------------------

    def offered_supply(self) -> np.ndarray:
        """Per-pool units offered for sale across all live rows (exact f64)."""
        return self._sell_ledger.copy()

    def _encode_accounts(
        self, live: np.ndarray
    ) -> tuple[list, dict[str, np.ndarray]]:
        """CSR-flatten the raw accounts behind ``live`` (ascending slot
        order, every slot live) into O(1) npz-able arrays: gathers over the
        account mirror, slots → bundles < count → elements < nnz in C order.
        Shared by the full and dirty-row exporters so both spell the
        identical on-disk encoding."""
        self._flush_raw()
        live = np.asarray(live, np.int64)
        keys = [self._slot_key[s] for s in live.tolist()]
        try:
            json.dumps(keys)
        except TypeError:
            for key in keys:  # name the first key that is not JSON-able
                try:
                    json.dumps(key)
                except TypeError:
                    raise TypeError(
                        f"book key {key!r} is not JSON-serializable — durable "
                        "books require str/int keys"
                    ) from None
            raise
        m = self._acct
        kinds = m["kind"][live]
        raw, packed = live[kinds == 0], live[kinds == 1]
        counts = m["count"][raw]
        nnz = m["nnz"][raw]  # 0 past each account's bundle count
        bundles = np.arange(self.num_bundles) < counts[:, None]
        elements = np.arange(self.k_bound) < nnz[..., None]
        return keys, {
            "slots": live,
            "kinds": kinds,
            "raw_counts": counts,
            "raw_nnz": nnz[bundles],
            "raw_idx": m["idx"][raw][elements],
            "raw_val": m["val"][raw][elements],
            "raw_pi": m["pi"][raw][bundles],
            "packed_idx": m["idx"][packed],
            "packed_val": m["val"][packed],
            "packed_mask": m["mask"][packed],
            "packed_pi": m["pi"][packed],
        }

    @staticmethod
    def _decode_accounts(arrays: dict, keys: list):
        """Inverse of :meth:`_encode_accounts`: yields (key, slot, account)
        triples in encoding order."""
        slots = np.asarray(arrays["slots"], np.int64)
        kinds = np.asarray(arrays["kinds"], np.int8)
        if not (len(keys) == slots.shape[0] == kinds.shape[0]):
            raise ValueError("account encoding length mismatch")
        raw_counts = np.asarray(arrays["raw_counts"], np.int32)
        raw_nnz = np.asarray(arrays["raw_nnz"], np.int32)
        raw_idx = np.asarray(arrays["raw_idx"], np.int32)
        raw_val = np.asarray(arrays["raw_val"], np.float32)
        raw_pi = np.asarray(arrays["raw_pi"], np.float32)
        c_raw = c_bundle = c_el = c_pi = c_packed = 0
        for key, s, kind in zip(keys, slots, kinds):
            if kind == 0:
                nb = int(raw_counts[c_raw])
                c_raw += 1
                bundles = []
                for j in range(nb):
                    n = int(raw_nnz[c_bundle + j])
                    bundles.append(
                        (
                            raw_idx[c_el : c_el + n].copy(),
                            raw_val[c_el : c_el + n].copy(),
                        )
                    )
                    c_el += n
                c_bundle += nb
                pi = raw_pi[c_pi : c_pi + nb].copy()
                c_pi += nb
                acct = (tuple(bundles), pi)
            else:
                acct = (
                    np.asarray(arrays["packed_idx"][c_packed], np.int32).copy(),
                    np.asarray(arrays["packed_val"][c_packed], np.float32).copy(),
                    np.asarray(arrays["packed_mask"][c_packed], bool).copy(),
                    np.asarray(arrays["packed_pi"][c_packed], np.float32).copy(),
                )
                c_packed += 1
            yield key, int(s), acct

    def export_state(
        self, clear_dirty: bool = False
    ) -> tuple[dict[str, np.ndarray], dict]:
        """Full mutable state as (flat arrays, JSON-able metadata).

        The encoding is O(1) npz entries regardless of book size: raw
        (bundles, pi) submissions are CSR-flattened across accounts and
        pre-packed payloads are stacked, so a 100k-row book checkpoints as
        ~15 arrays instead of ~300k tiny zip members.  Accounts are stored
        *independently* of the slot arrays, so :meth:`parity_check` on the
        restored book is a real oracle (a corrupt array region cannot hide
        behind accounts re-derived from the same bytes).  Keys must be
        JSON-serializable (the service uses strings throughout).

        With ``clear_dirty=True`` the checkpoint-dirty set is reset, making
        this export the new baseline the next :meth:`export_dirty_state`
        delta chains from.  The returned arrays alias live book storage —
        callers persisting them asynchronously must copy first.
        """
        live = np.flatnonzero(self._acct["kind"][: self._next_slot] >= 0)
        keys, acct_arrays = self._encode_accounts(live)
        arrays = {
            "idx": self.idx,
            "val": self.val,
            "mask": self.mask,
            "pi": self.pi,
            "ledger": self._ledger,
            "sell_ledger": self._sell_ledger,
            "free": np.asarray(self._free, np.int64),
            **acct_arrays,
            "base_cost": self.base_cost,
        }
        meta = {
            "keys": keys,
            "num_bundles": self.num_bundles,
            "k_bound": self.k_bound,
            "rows_cap": self.rows_cap,
            "num_resources": self.num_resources,
            "next_slot": self._next_slot,
            "generation": self._generation,
            "deltas_applied": self.deltas_applied,
        }
        if clear_dirty:
            self._ckpt_dirty.clear()
        return arrays, meta

    @property
    def dirty_rows(self) -> int:
        """Slots written since the last checkpoint export (delta size)."""
        return len(self._ckpt_dirty)

    def mark_dirty(self, slots) -> None:
        """Re-mark rows checkpoint-dirty — the undo for a cleared export
        whose record never became durable (failed background save)."""
        self._ckpt_dirty.update(int(s) for s in slots)

    def export_dirty_state(
        self, clear: bool = True
    ) -> tuple[dict[str, np.ndarray], dict]:
        """Only the rows written since the last export, as a delta record.

        The payload carries each dirty slot's row arrays (fancy-indexed —
        already a stable copy, safe to serialize asynchronously), the full
        f64 ledgers and freelist (O(R + frees), tiny next to the rows), and
        the raw accounts behind the dirty *live* slots in the identical
        encoding :meth:`export_state` uses.  ``meta["row_keys"]`` records
        each dirty slot's occupant (``None`` = tombstone), so
        :meth:`apply_dirty_state` can evict superseded keys before
        installing the new ones.  With ``clear=True`` the dirty set resets,
        chaining the next delta off this one.
        """
        rows = sorted(self._ckpt_dirty)
        b, k = self.num_bundles, self.k_bound
        sl = np.asarray(rows, np.int64)
        el = (
            sl[:, None] * (b * k) + np.arange(b * k, dtype=np.int64)[None, :]
        ).reshape(-1)
        keys, acct_arrays = self._encode_accounts(sl[self._acct["kind"][sl] >= 0])
        arrays = {
            "rows": sl,
            "idx": self.idx[el],
            "val": self.val[el],
            "mask": self.mask[sl],
            "pi": self.pi[sl],
            "ledger": self._ledger.copy(),
            "sell_ledger": self._sell_ledger.copy(),
            "free": np.asarray(self._free, np.int64),
            **acct_arrays,
        }
        meta = {
            "keys": keys,
            "row_keys": [self._slot_key[s] for s in rows],
            "num_bundles": self.num_bundles,
            "k_bound": self.k_bound,
            "rows_cap": self.rows_cap,
            "num_resources": self.num_resources,
            "next_slot": self._next_slot,
            "generation": self._generation,
            "deltas_applied": self.deltas_applied,
        }
        if clear:
            self._ckpt_dirty.clear()
        return arrays, meta

    def apply_dirty_state(self, arrays: dict, meta: dict) -> None:
        """Replay one :meth:`export_dirty_state` record onto this book.

        The record must be the next delta in the chain that produced this
        book's state (base + ordered replay).  Capacity growth recorded in
        the delta is re-applied; superseded occupants of dirty slots are
        evicted before the new keys install, so remove→re-add slot swaps
        within one delta window land exactly.  The device mirror is
        invalidated (full re-upload on next ``device_problem``).
        """
        if (
            int(meta["num_bundles"]) != self.num_bundles
            or int(meta["k_bound"]) != self.k_bound
            or int(meta["num_resources"]) != self.num_resources
        ):
            raise ValueError("delta record shape does not match this book")
        new_cap = int(meta["rows_cap"])
        if new_cap < self.rows_cap:
            raise ValueError("delta record predates this book (rows_cap shrank)")
        if new_cap > self.rows_cap:
            self._grow(new_cap)
        rows = np.asarray(arrays["rows"], np.int64)
        b, k = self.num_bundles, self.k_bound
        el = (
            rows[:, None] * (b * k) + np.arange(b * k, dtype=np.int64)[None, :]
        ).reshape(-1)
        self.idx[el] = np.asarray(arrays["idx"], np.int32).reshape(-1)
        self.val[el] = np.asarray(arrays["val"], np.float32).reshape(-1)
        self.mask[rows] = np.asarray(arrays["mask"], bool)
        self.pi[rows] = np.asarray(arrays["pi"], np.float32)
        for s in rows:  # evict every dirty slot's previous occupant first
            old = self._slot_key[int(s)]
            if old is not None:
                self._key_slot.pop(old, None)
                self._accounts.pop(old, None)
                self._slot_key[int(s)] = None
        for s, key in zip(rows, meta["row_keys"]):
            if key is not None:
                self._slot_key[int(s)] = key
                self._key_slot[key] = int(s)
        self._acct["kind"][rows] = -1
        pairs = []
        for key, s, acct in self._decode_accounts(arrays, meta["keys"]):
            self._accounts[key] = acct
            pairs.append((s, acct))
        self._mirror_accounts(pairs)
        self._ledger = np.asarray(arrays["ledger"], np.float64).copy()
        self._sell_ledger = np.asarray(arrays["sell_ledger"], np.float64).copy()
        self._free = [int(x) for x in arrays["free"]]
        self._next_slot = int(meta["next_slot"])
        self._generation = int(meta["generation"])
        self.deltas_applied = int(meta["deltas_applied"])
        self._dev = None
        self._dev_pending.clear()

    @classmethod
    def from_state(cls, arrays: dict, meta: dict) -> "MarketBook":
        """Rebuild a book bit-identically from :meth:`export_state` output.

        The device mirror starts cold (full upload on first
        ``device_problem``); everything host-side — slot arrays, both f64
        ledgers, key↔slot maps, freelist order (LIFO reuse determinism),
        generation, and the raw accounts behind the :meth:`rebuilt`
        oracle — is restored exactly.
        """
        book = cls(
            np.asarray(arrays["base_cost"], np.float32),
            int(meta["num_bundles"]),
            int(meta["k_bound"]),
            int(meta["rows_cap"]),
        )
        if book.rows_cap != int(meta["rows_cap"]):
            raise ValueError(
                f"rows_cap {meta['rows_cap']} is not the power of two the "
                "book would allocate — corrupt metadata"
            )
        book.idx = np.asarray(arrays["idx"], np.int32).copy()
        book.val = np.asarray(arrays["val"], np.float32).copy()
        book.mask = np.asarray(arrays["mask"], bool).copy()
        book.pi = np.asarray(arrays["pi"], np.float32).copy()
        book._ledger = np.asarray(arrays["ledger"], np.float64).copy()
        book._sell_ledger = np.asarray(
            arrays["sell_ledger"], np.float64
        ).copy()
        book._free = [int(s) for s in arrays["free"]]
        book._next_slot = int(meta["next_slot"])
        book._generation = int(meta["generation"])
        book.deltas_applied = int(meta["deltas_applied"])
        pairs = []
        for key, s, acct in cls._decode_accounts(arrays, meta["keys"]):
            book._key_slot[key] = s
            book._slot_key[s] = key
            book._accounts[key] = acct
            pairs.append((s, acct))
        book._mirror_accounts(pairs)
        return book


def operator_supply_bids(
    pools: Sequence[ResourcePool],
    reserve_prices: np.ndarray,
    lots: int = 1,
) -> tuple[list[list[np.ndarray]], list[float]]:
    """Encode operator supply as pure-seller users (paper §II).

    Each pool's supply is split into ``lots`` equal sell bids so the market can
    clear partial supply (the paper's no-scaling constraint applies per bid).
    A seller proxy stays in whenever p_r ≥ reserve, because
    qᵀp = −(supply/lots)·p_r ≤ pi = −(supply/lots)·reserve_r  ⇔  p_r ≥ reserve_r.
    """
    bundle_lists: list[list[np.ndarray]] = []
    pis: list[float] = []
    num_res = len(pools)
    for r, pool in enumerate(pools):
        if pool.supply <= 0:
            continue
        lot = pool.supply / lots
        for _ in range(lots):
            q = np.zeros((num_res,), dtype=np.float32)
            q[r] = -lot
            bundle_lists.append([q])
            pis.append(float(-lot * reserve_prices[r]))
    return bundle_lists, pis
