"""The market's kernels and settle program compile for a TPU v5e.

Each test compiles one program at deployment width for one chip of a
described (not attached) ``v5e:2x2`` topology, with the installed TPU
compiler: Mosaic refuses here what it would refuse on the chip.  Nothing
runs, so results are checked by the interpret-mode kernel tests and on the
chip by ``chip_smoke.py``.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.auction import ClockConfig, blocked_demand_fn, clock_auction
from repro.core.types import CSRAuctionProblem
from repro.kernels import ops
from repro.kernels.clock_bid_eval import bid_eval
from repro.kernels.sparse_bid_eval import sparse_bid_eval
from repro.kernels.sparse_bid_eval_csr import sparse_bid_eval_csr


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one, so keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


# (users, bundles, K, pools): the 100k-agent service book, and a wide market
@pytest.mark.parametrize(
    "u,b,k,r,vector_pi",
    [(131072, 6, 3, 18, True), (131072, 6, 3, 18, False), (100000, 4, 8, 1000, True)],
)
def test_sparse_bid_eval_compiles(one_chip, u, b, k, r, vector_pi):
    compiled = _compile(
        lambda i, v, m, p, pr: sparse_bid_eval(i, v, m, p, pr, r),
        one_chip,
        ((u, b, k), jnp.int32),
        ((u, b, k), jnp.float32),
        ((u, b), jnp.bool_),
        ((u, b) if vector_pi else (u,), jnp.float32),
        ((r,), jnp.float32),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("b,k_bound,r", [(6, 3, 18), (4, 16, 1000)])
def test_sparse_bid_eval_csr_compiles_at_cap(one_chip, b, k_bound, r):
    """At the largest stream ``ops`` admits, the VMEM-resident streams fit."""
    nnz = ops.CSR_MAX_NNZ
    u = nnz // (b * k_bound)
    compiled = _compile(
        lambda i, v, o, m, p, pr: sparse_bid_eval_csr(i, v, o, m, p, pr, r, k_bound),
        one_chip,
        ((nnz,), jnp.int32),
        ((nnz,), jnp.float32),
        ((u * b + 1,), jnp.int32),
        ((u, b), jnp.bool_),
        ((u, b), jnp.float32),
        ((r,), jnp.float32),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_clock_bid_eval_compiles(one_chip):
    u, b, r = 100000, 4, 1000
    compiled = _compile(
        bid_eval,
        one_chip,
        ((u, b, r), jnp.float32),
        ((u, b), jnp.bool_),
        ((u,), jnp.float32),
        ((r,), jnp.float32),
    )
    assert "tpu_custom_call" in compiled.as_text()


# (row slots, bundles, k_bound, pools, nnz, clock): the MarketService book of
# fleet_economy(100_000, 6) — a power-of-two slot count — and the packed CSR
# book the staged epoch of fleet_economy(100_000) settles: 64 691 rows, not a
# multiple of 128, on which the padded-view gather once compiled for a
# minute into ~110 MB of TPU code
@pytest.mark.parametrize(
    "rows,b,k,r,nnz,cfg",
    [
        (131072, 6, 3, 18, 131072 * 18, ClockConfig()),
        (
            64691, 8, 3, 24, 854535,
            ClockConfig(
                max_rounds=2000, alpha=0.6, delta=0.25, alpha_growth=1.6, delta_decay=0.6
            ),
        ),
    ],
    ids=["service", "staged"],
)
def test_settle_program_compiles(one_chip, rows, b, k, r, nnz, cfg):
    """The clock settle of a CSR book through the blocked fold, as
    ``MarketService._settle`` and ``Economy.run_epoch`` run it.  Pure XLA —
    no kernel is expected."""

    def settle(idx, val, rws, offsets, mask, pi, base_cost, supply, start):
        problem = CSRAuctionProblem(
            idx=idx, val=val, rows=rws, offsets=offsets, bundle_mask=mask,
            pi=pi, base_cost=base_cost, supply_scale=supply,
            num_resources=r, k_bound=k,
        )
        res = clock_auction(problem, start, cfg, demand_fn=blocked_demand_fn(8))
        return res.prices, res.won, res.payments, res.converged

    compiled = _compile(
        settle,
        one_chip,
        ((nnz,), jnp.int32),
        ((nnz,), jnp.float32),
        ((nnz,), jnp.int32),
        ((rows * b + 1,), jnp.int32),
        ((rows, b), jnp.bool_),
        ((rows, b), jnp.float32),
        ((r,), jnp.float32),
        ((r,), jnp.float32),
        ((r,), jnp.float32),
    )
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2**30
    assert mem.generated_code_size_in_bytes < 32 * 2**20
