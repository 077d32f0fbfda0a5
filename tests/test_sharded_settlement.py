"""Multi-device clock settlement: bit-identical sharding.

The acceptance bar for the sharded path is *bit*-identity, not tolerance:
``sharded_clock_auction`` on 2/4/8 virtual CPU devices must produce the same
prices/won/payments — and ``Economy.run_epoch`` the same ``EpochStats`` —
as the single-device sparse settlement, for seeds 0/3/7.  Multi-device runs
happen in a subprocess with ``--xla_force_host_platform_device_count=8``
(the test session itself must not pollute the global device count).
"""
import os
import subprocess
import sys

import numpy as np
import pytest


def _run(script, timeout=580):
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)  # the scripts set their own device count
    return subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, cwd=os.getcwd(), timeout=timeout,
    )


# ---------------------------------------------------------------------------
# single-device invariants (run in-process, 1 CPU device)
# ---------------------------------------------------------------------------


def _contested_problem(u=57, r=11, seed=0):
    from repro.core import random_market

    # scarce supply keeps the clock ticking for many rounds
    return random_market(u, r, seed=seed, supply=(2.0, 6.0))


def test_blocked_demand_matches_exact_selection():
    """Blocked z re-associates the reduction but must not move selection, and
    z itself stays float-close to the exact column sum."""
    import jax.numpy as jnp
    from repro.core import sparse_proxy_demand_blocked, sparse_proxy_demand_exact

    sp = _contested_problem(seed=5)
    prices = jnp.full((sp.num_resources,), 0.7)
    z_e, ch_e, act_e = sparse_proxy_demand_exact(
        sp.idx, sp.val, sp.bundle_mask, sp.pi, prices, sp.num_resources
    )
    z_b, ch_b, act_b = sparse_proxy_demand_blocked(
        sp.idx, sp.val, sp.bundle_mask, sp.pi, prices, sp.num_resources
    )
    np.testing.assert_array_equal(np.asarray(ch_e), np.asarray(ch_b))
    np.testing.assert_array_equal(np.asarray(act_e), np.asarray(act_b))
    np.testing.assert_allclose(np.asarray(z_e), np.asarray(z_b), rtol=1e-5, atol=1e-5)


def test_sharded_one_device_matches_unsharded():
    """On a single device the sharded clock must reproduce the plain
    clock_auction with the blocked demand fn bit for bit."""
    import jax.numpy as jnp
    from repro.core import (
        ClockConfig, clock_auction, sharded_clock_auction,
        sparse_proxy_demand_blocked, users_mesh,
    )

    sp = _contested_problem()
    p0 = jnp.full((sp.num_resources,), 0.1)
    cfg = ClockConfig(max_rounds=2000, alpha=0.6, delta=0.25)
    ref = clock_auction(sp, p0, cfg, demand_fn=sparse_proxy_demand_blocked)
    res = sharded_clock_auction(sp, p0, cfg, mesh=users_mesh(1))
    assert int(ref.rounds) > 10  # the market actually ticked
    for f in (
        "prices",
        "alloc_idx",
        "alloc_val",
        "chosen_bundle",
        "won",
        "payments",
        "excess_demand",
        "rounds",
        "converged",
    ):
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, f)), np.asarray(getattr(res, f)), err_msg=f
        )


def test_sharded_rejects_dense_problem_and_bad_blocks():
    import jax.numpy as jnp
    from repro.core import (
        blocked_demand_fn, densify, sharded_clock_auction, users_mesh,
    )

    sp = _contested_problem(u=6, r=4)
    p0 = jnp.full((4,), 0.5)
    with pytest.raises(TypeError):
        sharded_clock_auction(densify(sp), p0)
    with pytest.raises(ValueError):
        sharded_clock_auction(sp, p0, mesh=users_mesh(1), num_blocks=0)
    # a demand fn with a baked-in block count must not be silently re-blocked
    with pytest.raises(ValueError):
        sharded_clock_auction(
            sp, p0, demand_fn=blocked_demand_fn(16), mesh=users_mesh(1)
        )
    res = sharded_clock_auction(
        sp, p0, demand_fn=blocked_demand_fn(16), mesh=users_mesh(1), num_blocks=16
    )
    assert bool(res.converged)


def test_settlement_demand_fn_dispatch():
    from repro.core import sparse_proxy_demand_blocked
    from repro.kernels import ops

    assert ops.settlement_demand_fn() is sparse_proxy_demand_blocked
    fast = ops.settlement_demand_fn(backend="jnp", exact=False)
    assert getattr(fast, "sparse_signature", False)
    assert not getattr(fast, "exact_settlement", False)
    with pytest.raises(ValueError):
        ops.settlement_demand_fn(backend="pallas")  # no silent jnp reroute


def test_economy_sharded_one_device_matches_unsharded():
    """Economy auto-path on 1 device (plain clock_auction) vs an explicit
    1-device settle mesh (shard_map path): EpochStats must be bit-identical."""
    import dataclasses

    from repro.core import users_mesh
    from repro.core.economy import make_fleet_economy

    eco_a = make_fleet_economy(seed=3)
    eco_b = make_fleet_economy(seed=3)
    eco_b.settle_mesh = users_mesh(1)
    for _ in range(2):
        sa, sb = eco_a.run_epoch(), eco_b.run_epoch()
        for k, va in dataclasses.asdict(sa).items():
            vb = dataclasses.asdict(sb)[k]
            if isinstance(va, np.ndarray):
                np.testing.assert_array_equal(va, vb, err_msg=k)
            elif isinstance(va, float) and np.isnan(va):
                assert np.isnan(vb), k
            else:
                assert va == vb, k


# ---------------------------------------------------------------------------
# multi-device bit-identity (8 virtual devices, subprocess)
# ---------------------------------------------------------------------------

SHARDED_AUCTION_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import (ClockConfig, clock_auction, random_market,
                        sharded_clock_auction, sparse_proxy_demand_blocked,
                        users_mesh)
from repro.kernels import ops

assert jax.device_count() == 8

def make(seed, u=203, r=37):
    return random_market(u, r, seed=seed, supply=(2.0, 6.0))

cfg = ClockConfig(max_rounds=3000, alpha=0.6, delta=0.25)
fields = ("prices", "alloc_idx", "alloc_val", "chosen_bundle", "won",
          "payments", "excess_demand", "rounds", "converged")
for seed in (0, 3, 7):
    prob = make(seed)
    p0 = jnp.full((prob.num_resources,), 0.1)
    # unsharded reference computed in this same 8-device process
    ref = clock_auction(prob, p0, cfg, demand_fn=sparse_proxy_demand_blocked)
    assert int(ref.rounds) > 10, "market must actually tick"
    for D in (1, 2, 4, 8):
        res = sharded_clock_auction(prob, p0, cfg, mesh=users_mesh(D))
        for f in fields:
            a, b = np.asarray(getattr(ref, f)), np.asarray(getattr(res, f))
            assert a.shape == b.shape and (a == b).all(), (seed, D, f)
    # kernel-adapter demand (interpret backend) per shard: reproducible per
    # device count and float-close to the blocked reference across counts
    res_k = sharded_clock_auction(
        prob, p0, cfg, mesh=users_mesh(4),
        demand_fn=ops.sparse_bid_demand_fn("interpret"),
    )
    np.testing.assert_allclose(np.asarray(res_k.prices), np.asarray(ref.prices),
                               rtol=1e-4, atol=1e-4)
    assert (np.asarray(res_k.won) == np.asarray(ref.won)).all()
print("SHARDED_AUCTION_OK")
"""


def test_sharded_auction_bit_identical_2_4_8():
    out = _run(SHARDED_AUCTION_SCRIPT)
    assert "SHARDED_AUCTION_OK" in out.stdout, out.stdout + "\n" + out.stderr


SHARDED_ECONOMY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import numpy as np, jax
from repro.core import users_mesh
from repro.core.economy import make_fleet_economy

assert jax.device_count() == 8

def run(seed, mesh, epochs):
    eco = make_fleet_economy(seed=seed)
    eco.settle_mesh = mesh
    return [eco.run_epoch() for _ in range(epochs)]

EPOCHS = 3
for seed in (0, 3, 7):
    ref = run(seed, users_mesh(1), EPOCHS)
    for D in (2, 4, 8):
        stats = run(seed, users_mesh(D), EPOCHS)
        for e, (sa, sb) in enumerate(zip(ref, stats)):
            da, db = dataclasses.asdict(sa), dataclasses.asdict(sb)
            for k, va in da.items():
                vb = db[k]
                if isinstance(va, np.ndarray):
                    ok = va.shape == vb.shape and (va == vb).all()
                elif isinstance(va, float):
                    ok = (va == vb) or (np.isnan(va) and np.isnan(vb))
                else:
                    ok = va == vb
                assert ok, (seed, D, e, k, va, vb)
print("SHARDED_ECONOMY_OK")
"""


@pytest.mark.slow
def test_economy_epochstats_bit_identical_across_device_counts():
    out = _run(SHARDED_ECONOMY_SCRIPT)
    assert "SHARDED_ECONOMY_OK" in out.stdout, out.stdout + "\n" + out.stderr
