"""Jit'd public wrappers for the Pallas kernels and their jnp references.

``backend``:
  * ``None`` / ``"pallas"`` — compiled Pallas kernel (TPU).  Anywhere else
    it is refused with an error: a kernel never gives way to its reference
    in silence;
  * ``"interpret"`` — Pallas interpreter (CPU correctness testing);
  * ``"jnp"`` — pure-JAX reference path (what the multi-pod dry-run
    compiles, since Pallas custom calls target TPU).

Every path honors the requested backend (vector-π dense bids are served by
the sparse kernel on every kernel backend).
"""
from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp

from . import ref
from . import clock_bid_eval as _cbe
from . import sparse_bid_eval as _sbe
from . import sparse_bid_eval_csr as _sbec
from . import wkv6 as _wkv6

Backend = Literal["jnp", "pallas", "interpret"]

CSR_MAX_NNZ = _sbec.MAX_NNZ


def _interpret(backend: Backend | None) -> bool:
    """``interpret=`` flag for a kernel backend (``None`` is ``"pallas"``)."""
    if backend == "interpret":
        return True
    if backend not in (None, "pallas"):
        raise ValueError(f"unknown kernel backend {backend!r}")
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            "backend='pallas' compiles a Mosaic TPU kernel, but JAX's device "
            f"is {platform!r}; pass backend='interpret' for the Pallas "
            "interpreter or backend='jnp' for the reference"
        )
    return False


def bid_eval(bundles, mask, pi, prices, backend: Backend | None = None):
    """(z, chosen) — one clock-auction proxy round.  See kernels.ref.bid_eval.

    Dense scalar-π only; vector-π and sparse bundles go through
    :func:`sparse_bid_eval` (the dense Pallas kernel lacks the surplus rule).
    """
    if backend == "jnp":
        return ref.bid_eval(bundles, mask, pi, prices)
    return _cbe.bid_eval(bundles, mask, pi, prices, interpret=_interpret(backend))


def sparse_bid_eval(
    idx, val, mask, pi, prices, num_resources: int, backend: Backend | None = None
):
    """(z, chosen) — one proxy round over sparse (idx, val) bundles, O(U·B·K).

    Supports scalar-π and vector-π on every backend; see
    kernels.ref.sparse_bid_eval for semantics.
    """
    if backend == "jnp":
        return ref.sparse_bid_eval(idx, val, mask, pi, prices, num_resources)
    return _sbe.sparse_bid_eval(
        idx, val, mask, pi, prices, num_resources, interpret=_interpret(backend)
    )


def sparse_bid_eval_csr(
    idx,
    val,
    rows,
    offsets,
    mask,
    pi,
    prices,
    num_resources: int,
    k_bound: int,
    backend: Backend | None = None,
):
    """(z, chosen) — one proxy round over flat CSR bundles, O(nnz).

    The variable-K twin of :func:`sparse_bid_eval`: no K_max padding, so a
    skewed book moves only its true nonzeros.  ``rows`` feeds the jnp
    oracle's segment reduction; ``offsets``/``k_bound`` feed the kernel's
    segment-offset addressing.  Scalar-π and vector-π on every backend.

    The kernel keeps both flat streams resident in VMEM, so a stream longer
    than ``CSR_MAX_NNZ`` elements is refused before it reaches the compiler.
    """
    if backend == "jnp":
        return ref.sparse_bid_eval_csr(
            idx, val, rows, mask, pi, prices, num_resources
        )
    if idx.shape[0] > CSR_MAX_NNZ:
        raise ValueError(
            f"CSR stream of {idx.shape[0]} elements exceeds the kernel's "
            f"VMEM-resident cap of {CSR_MAX_NNZ}; use the padded kernel "
            "(sparse_bid_eval) or backend='jnp' for a book this large"
        )
    interpret = _interpret(backend)
    return _sbec.sparse_bid_eval_csr(
        idx,
        val,
        offsets,
        mask,
        pi,
        prices,
        num_resources,
        k_bound,
        interpret=interpret,
    )


def csr_bid_demand_fn(backend: Backend | None = None):
    """Adapter with the auction's CSR DemandFn signature (z, chosen, active).

    Takes the :class:`~repro.core.types.CSRAuctionProblem` directly (CSR
    demand fns close over no layout aux; the optional scatter-free aux is
    ignored here — the kernel's compare-and-add z never scatters anyway).
    """

    def demand(problem, prices, aux=None):
        z, chosen = sparse_bid_eval_csr(
            problem.idx,
            problem.val,
            problem.rows,
            problem.offsets,
            problem.bundle_mask,
            problem.pi,
            prices,
            problem.num_resources,
            problem.k_bound,
            backend=backend,
        )
        active = chosen >= 0
        return z, chosen, active

    demand.csr_signature = True  # type: ignore[attr-defined]
    return demand


def _dense_to_sparse(bundles):
    """In-trace dense → (idx, val) with K = R (exact, no truncation).

    Used only by the dense-input vector-π adapter below; workloads that are
    actually sparse should carry a SparseAuctionProblem end-to-end instead.
    """
    u, b, r = bundles.shape
    nz = bundles != 0
    iota = jax.lax.broadcasted_iota(jnp.int32, (u, b, r), 2)
    # stable sort key: nonzero positions first, each group ascending
    order = jnp.argsort(jnp.where(nz, iota, iota + r), axis=-1)
    val = jnp.take_along_axis(bundles, order, axis=-1)
    idx = jnp.where(val != 0, order, 0)
    val = jnp.where(val != 0, val, 0)
    return idx.astype(jnp.int32), val


def bid_demand_fn(backend: Backend | None = None):
    """Adapter with the auction's dense DemandFn signature (x, chosen, active)."""

    def demand(bundles, mask, pi, prices):
        if pi.ndim != 1:
            # vector-π: the dense kernel lacks the surplus rule, so route
            # through the sparse kernel on the *requested* backend.
            if backend == "jnp":
                from ..core.auction import proxy_demand

                return proxy_demand(bundles, mask, pi, prices)
            idx, val = _dense_to_sparse(bundles)
            z, chosen = sparse_bid_eval(
                idx, val, mask, pi, prices, bundles.shape[-1], backend=backend
            )
            active = chosen >= 0
        else:
            _, chosen = bid_eval(bundles, mask, pi, prices, backend)
            active = chosen >= 0
        sel = jnp.take_along_axis(
            bundles, jnp.maximum(chosen, 0)[:, None, None], axis=1
        )[:, 0, :]
        x = sel.astype(jnp.float32) * active[:, None]
        return x, chosen, active

    return demand


def sparse_bid_demand_fn(backend: Backend | None = None):
    """Adapter with the auction's sparse DemandFn signature (z, chosen, active)."""

    def demand(idx, val, mask, pi, prices, num_resources):
        z, chosen = sparse_bid_eval(
            idx, val, mask, pi, prices, num_resources, backend=backend
        )
        active = chosen >= 0
        return z, chosen, active

    demand.sparse_signature = True  # type: ignore[attr-defined]
    return demand


def settlement_demand_fn(backend: Backend | None = None, exact: bool = True):
    """Demand fn for ``clock_auction`` / ``sharded_clock_auction`` settlement.

    ``exact=True`` returns the blocked settlement proxy
    (``core.auction.sparse_proxy_demand_blocked``): selection is the same
    O(U·B·K) evaluation, and z is a fixed block-fold that is bit-identical
    across device counts — this is what ``Economy.run_epoch`` settles with.
    It is pure jnp (no kernel-backed blocked fold exists), so requesting a
    backend with it is an error rather than a silent reroute.
    ``exact=False`` returns the kernel adapter on the requested backend
    (Pallas on TPU): the O(nnz) scatter z is the fast planet-scale path,
    reproducible per device count but only float-close across different
    ones.
    """
    if exact:
        if backend is not None:
            raise ValueError(
                f"backend={backend!r} has no effect on the exact blocked "
                "proxy (pure jnp); pass exact=False for the kernel path or "
                "drop the backend argument"
            )
        from ..core.auction import sparse_proxy_demand_blocked

        return sparse_proxy_demand_blocked
    return sparse_bid_demand_fn(backend)


def fused_epoch_z_fn(backend: Backend | None, num_resources: int):
    """In-loop excess-demand evaluator for the fused epoch program.

    The fused epoch (:mod:`repro.core.fused`) spends almost all of its
    clock rounds evaluating z.  ``"jnp"`` returns None: the fused program
    keeps its own blocked fold, the parity-exact mirror of
    ``sparse_proxy_demand_blocked`` that EpochStats bit-parity rests on.
    ``None`` / ``"pallas"`` / ``"interpret"`` return the kernel adapter's O(nnz)
    scatter z for the price loop only — selection, settlement, and the
    convergence check stay on the exact jnp path, so the settled point is
    still verified and applied exactly, but the price *trajectory* is only
    float-close to the staged oracle (the scatter's reduction order is not
    the blocked fold's).  Use it where throughput beats bit-parity — the
    planet-scale benchmark books — never under the parity suite.
    """
    if backend == "jnp":
        return None
    _interpret(backend)  # refuse a kernel that cannot run here, up front

    def z_fn(idx, val, mask, pi, prices):
        z, _ = sparse_bid_eval(
            idx, val, mask, pi, prices, num_resources, backend=backend
        )
        return z

    return z_fn


def wkv6(r, k, v, w, u, state=None, chunk: int = 32, backend: Backend | None = None):
    """Chunked RWKV-6 recurrence.  See kernels.ref.wkv6 for semantics."""
    if backend == "jnp":
        return ref.wkv6(r, k, v, w, u, state)
    return _wkv6.wkv6(
        r, k, v, w, u, state, chunk=chunk, interpret=_interpret(backend)
    )
