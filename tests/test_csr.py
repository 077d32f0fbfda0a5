"""Variable-K CSR settlement: converters, demand parity, bit-identity.

The CSR encoding is the variable-K successor to the K_max-padded layout, so
its contract has two halves:

* *exactness* — settlement through the padded-signature demand fns
  (exact/blocked) must be **bit-identical** to settling the padded layout of
  the same book, on uniform-K and skewed-K books alike, on one device and
  across 1/2/4/8 virtual devices via ``sharded_clock_auction``;
* *speed* — the native O(nnz) proxy (``csr_proxy_demand``, with and without
  the scatter-free ``CSRDemandAux`` layouts) and the segment-offset Pallas
  kernel must agree with the padded reference to float tolerance.
"""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    ClockConfig,
    clock_auction,
    csr_demand_aux,
    csr_from_padded,
    csr_padded_views,
    csr_problem_from_arrays,
    csr_proxy_demand,
    pack_bids,
    pack_bids_csr,
    pack_bids_sparse,
    padded_from_csr,
    proxy_demand,
    random_market,
    sharded_clock_auction,
    sparse_proxy_demand,
    sparse_proxy_demand_blocked,
    sparsify,
    surplus_and_trade,
    users_mesh,
    verify_system,
)
from repro.kernels import ops, ref
from repro.kernels.sparse_bid_eval_csr import (
    sparse_bid_eval_csr as pallas_sparse_bid_eval_csr,
)

RESULT_FIELDS = (
    "prices",
    "alloc_idx",
    "alloc_val",
    "chosen_bundle",
    "won",
    "payments",
    "excess_demand",
    "rounds",
    "converged",
)


def _random_problem(U, B, R, nnz=3, seed=0, uniform_k=False):
    """Random dense problem; ``uniform_k`` gives every bundle exactly nnz
    nonzeros (the acceptance case), else sizes are skewed in [1, nnz]."""
    rng = np.random.default_rng(seed)
    bl, pis = [], []
    for _ in range(U):
        n_alt = int(rng.integers(1, B + 1))
        alts = []
        for _ in range(n_alt):
            q = np.zeros(R, np.float32)
            k = nnz if uniform_k else int(rng.integers(1, nnz + 1))
            q[rng.choice(R, size=k, replace=False)] = rng.uniform(-2, 4, size=k)
            alts.append(q)
        bl.append(alts)
        pis.append(float(rng.uniform(-5, 15)))
    return pack_bids(bl, pis, base_cost=np.ones(R, np.float32))


def _prices(R, seed=0):
    return jnp.asarray(
        np.abs(np.random.default_rng(seed).normal(size=R)).astype(np.float32)
    )


# ---------------------------------------------------------------------------
# converters and packers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("uniform_k", [False, True])
def test_padded_csr_roundtrip(uniform_k):
    sp = sparsify(_random_problem(23, 3, 17, seed=1, uniform_k=uniform_k))
    csr = csr_from_padded(sp)
    back = padded_from_csr(csr)
    for f in ("idx", "val", "bundle_mask", "pi", "base_cost", "supply_scale"):
        np.testing.assert_array_equal(
            np.asarray(getattr(sp, f)), np.asarray(getattr(back, f)), err_msg=f
        )
    # flat streams are the padded nonzeros in (u, b, k) order
    counts = np.asarray(csr.offsets[1:] - csr.offsets[:-1])
    assert counts.sum() == csr.nnz
    assert csr.k_bound == sp.k_max


def test_csr_padded_views_traceable_and_exact():
    sp = sparsify(_random_problem(16, 2, 9, seed=2))
    csr = csr_from_padded(sp)
    vidx, vval = csr_padded_views(csr)
    np.testing.assert_array_equal(np.asarray(sp.idx), np.asarray(vidx))
    np.testing.assert_array_equal(np.asarray(sp.val), np.asarray(vval))


def test_pack_bids_csr_matches_pack_bids_sparse():
    rng = np.random.default_rng(3)
    R = 11
    bl, pis = [], []
    for _ in range(6):
        q = np.zeros(R, np.float32)
        q[rng.choice(R, 2, replace=False)] = rng.uniform(1, 3, 2)
        bl.append([q, (np.array([4], np.int32), np.array([1.5], np.float32))])
        pis.append(1.0)
    sp = pack_bids_sparse(bl, pis, base_cost=np.ones(R, np.float32))
    csr = pack_bids_csr(bl, pis, base_cost=np.ones(R, np.float32))
    back = padded_from_csr(csr)
    np.testing.assert_array_equal(np.asarray(sp.idx), np.asarray(back.idx))
    np.testing.assert_array_equal(np.asarray(sp.val), np.asarray(back.val))
    np.testing.assert_array_equal(
        np.asarray(sp.supply_scale), np.asarray(csr.supply_scale)
    )


def test_csr_problem_from_arrays_validates():
    base = np.ones(3, np.float32)
    mask = np.ones((1, 1), bool)
    with pytest.raises(ValueError):  # non-monotone offsets
        csr_problem_from_arrays(
            np.array([0], np.int32), np.array([1.0], np.float32),
            np.array([1, 0], np.int32), mask, [1.0], base,
        )
    with pytest.raises(ValueError):  # out-of-range pool index
        csr_problem_from_arrays(
            np.array([3], np.int32), np.array([1.0], np.float32),
            np.array([0, 1], np.int32), mask, [1.0], base,
        )
    with pytest.raises(ValueError):  # k_bound below densest bundle
        csr_problem_from_arrays(
            np.array([0, 1], np.int32), np.array([1.0, 1.0], np.float32),
            np.array([0, 2], np.int32), mask, [1.0], base, k_bound=1,
        )


def test_csr_supply_scale_matches_padded_bitwise():
    sp = sparsify(_random_problem(40, 3, 21, seed=4))
    csr = csr_from_padded(sp)
    rebuilt = csr_problem_from_arrays(
        np.asarray(csr.idx), np.asarray(csr.val), np.asarray(csr.offsets),
        np.asarray(csr.bundle_mask), np.asarray(csr.pi),
        np.asarray(csr.base_cost),
    )
    np.testing.assert_array_equal(
        np.asarray(sp.supply_scale), np.asarray(rebuilt.supply_scale)
    )


# ---------------------------------------------------------------------------
# demand parity: native CSR proxy vs padded reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("vector_pi", [False, True])
@pytest.mark.parametrize("with_aux", [False, True])
def test_csr_demand_matches_padded(vector_pi, with_aux):
    prob = _random_problem(64, 3, 30, seed=11)
    if vector_pi:
        piv = jnp.asarray(
            np.random.default_rng(11)
            .uniform(-5, 15, size=(64, prob.num_bundles))
            .astype(np.float32)
        )
        prob = dataclasses.replace(prob, pi=piv)
    sp = sparsify(prob)
    csr = csr_from_padded(sp)
    prices = _prices(30, seed=11)
    z_p, ch_p, act_p = sparse_proxy_demand(
        sp.idx, sp.val, sp.bundle_mask, sp.pi, prices, 30
    )
    aux = csr_demand_aux(csr) if with_aux else None
    z_c, ch_c, act_c = csr_proxy_demand(csr, prices, aux)
    np.testing.assert_array_equal(np.asarray(ch_p), np.asarray(ch_c))
    np.testing.assert_array_equal(np.asarray(act_p), np.asarray(act_c))
    np.testing.assert_allclose(
        np.asarray(z_p), np.asarray(z_c), rtol=1e-5, atol=1e-5
    )


def test_csr_ref_oracle_matches_padded_oracle():
    sp = sparsify(_random_problem(50, 4, 25, seed=12))
    csr = csr_from_padded(sp)
    prices = _prices(25, seed=12)
    z0, c0 = ref.sparse_bid_eval(sp.idx, sp.val, sp.bundle_mask, sp.pi, prices, 25)
    z1, c1 = ref.sparse_bid_eval_csr(
        csr.idx, csr.val, csr.rows, csr.bundle_mask, csr.pi, prices, 25
    )
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
    np.testing.assert_allclose(np.asarray(z0), np.asarray(z1), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# segment-offset Pallas kernel (interpret mode) vs jnp oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("U,B,R,K", [(4, 1, 3, 1), (33, 3, 18, 4), (130, 5, 200, 8)])
@pytest.mark.parametrize("vector_pi", [False, True])
def test_csr_kernel_matches_oracle(U, B, R, K, vector_pi):
    rng = np.random.default_rng(U + K)
    counts = rng.integers(0, K + 1, size=(U, B)).astype(np.int64)
    counts[0, 0] = K  # keep k_bound honest
    offsets = np.zeros(U * B + 1, np.int64)
    offsets[1:] = np.cumsum(counts.reshape(-1))
    nnz = int(offsets[-1])
    idx = rng.integers(0, R, size=nnz).astype(np.int32)
    val = (rng.normal(size=nnz) * 2).astype(np.float32)
    rows = np.repeat(np.arange(U * B, dtype=np.int32), counts.reshape(-1))
    mask = rng.random((U, B)) < 0.85
    mask[:, 0] = True
    if vector_pi:
        pi = (rng.normal(size=(U, B)) * 5).astype(np.float32)
    else:
        pi = (rng.normal(size=(U,)) * 5).astype(np.float32)
    prices = np.abs(rng.normal(size=R)).astype(np.float32)
    ji, jv, jr, jo, jm, jp, jpr = map(
        jnp.asarray, (idx, val, rows, offsets.astype(np.int32), mask, pi, prices)
    )
    z0, c0 = ref.sparse_bid_eval_csr(ji, jv, jr, jm, jp, jpr, R)
    z1, c1 = pallas_sparse_bid_eval_csr(ji, jv, jo, jm, jp, jpr, R, K, interpret=True)
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
    np.testing.assert_allclose(np.asarray(z0), np.asarray(z1), rtol=3e-3, atol=3e-3)


def test_ops_csr_backend_dispatch():
    sp = sparsify(_random_problem(16, 2, 9, seed=13))
    csr = csr_from_padded(sp)
    prices = _prices(9, seed=13)
    args = (csr.idx, csr.val, csr.rows, csr.offsets, csr.bundle_mask, csr.pi,
            prices, 9, csr.k_bound)
    za, ca = ops.sparse_bid_eval_csr(*args, backend="jnp")
    zb, cb = ops.sparse_bid_eval_csr(*args, backend="interpret")
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))
    np.testing.assert_allclose(np.asarray(za), np.asarray(zb), rtol=1e-4, atol=1e-4)


def test_ops_csr_refuses_stream_over_vmem_cap():
    """The kernel keeps both flat streams resident in VMEM: a longer stream
    is refused before anything is traced or compiled."""
    nnz = ops.CSR_MAX_NNZ + 1
    u, b = 1, 1
    idx = jnp.zeros((nnz,), jnp.int32)
    val = jnp.zeros((nnz,), jnp.float32)
    rows = jnp.zeros((nnz,), jnp.int32)
    offsets = jnp.asarray([0, nnz], jnp.int32)
    mask = jnp.ones((u, b), bool)
    pi = jnp.ones((u,), jnp.float32)
    prices = jnp.ones((3,), jnp.float32)
    for backend in (None, "pallas", "interpret"):
        with pytest.raises(ValueError, match="VMEM-resident cap"):
            ops.sparse_bid_eval_csr(
                idx, val, rows, offsets, mask, pi, prices, 3, nnz, backend=backend
            )


# ---------------------------------------------------------------------------
# end-to-end: the clock on CSR books
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", (0, 3, 7))
@pytest.mark.parametrize("uniform_k", [True, False], ids=["uniformK", "skewedK"])
def test_clock_csr_blocked_bit_identical_to_padded(seed, uniform_k):
    """The acceptance bar: CSR settlement through the blocked settlement fn
    reproduces padded settlement bit for bit, uniform-K and skewed-K."""
    prob = _random_problem(57, 3, 15, seed=seed, uniform_k=uniform_k)
    sp = sparsify(prob)
    csr = csr_from_padded(sp)
    p0 = jnp.full((15,), 0.1)
    cfg = ClockConfig(max_rounds=3000, alpha=0.6, delta=0.25)
    r_pad = clock_auction(sp, p0, cfg, demand_fn=sparse_proxy_demand_blocked)
    r_csr = clock_auction(csr, p0, cfg, demand_fn=sparse_proxy_demand_blocked)
    for f in RESULT_FIELDS:
        a, b = np.asarray(getattr(r_pad, f)), np.asarray(getattr(r_csr, f))
        assert a.shape == b.shape and (a == b).all(), f
    assert verify_system(csr, r_csr) == verify_system(sp, r_pad)
    np.testing.assert_array_equal(
        np.asarray(surplus_and_trade(csr, r_csr)),
        np.asarray(surplus_and_trade(sp, r_pad)),
    )


@pytest.mark.parametrize("vector_pi", [False, True])
def test_clock_csr_native_matches_padded(vector_pi):
    """Native O(nnz) clock vs padded clock on a converging contested market
    (float-close, like the kernel-adapter demand fns — ulp-level z
    differences on an unclearable book would bifurcate both trajectories)."""
    sp = random_market(203, 37, seed=17, supply=(2.0, 6.0))
    if vector_pi:
        # same stay-in semantics expressed per-bundle: π_b = π for all b
        piv = jnp.broadcast_to(sp.pi[:, None], (sp.num_users, sp.num_bundles))
        sp = dataclasses.replace(sp, pi=jnp.asarray(piv))
    csr = csr_from_padded(sp)
    p0 = jnp.full((37,), 0.1)
    cfg = ClockConfig(max_rounds=3000, alpha=0.6, delta=0.25)
    r_pad = clock_auction(sp, p0, cfg)
    r_nat = clock_auction(csr, p0, cfg)  # native O(nnz) proxy + aux
    assert bool(r_pad.converged) and bool(r_nat.converged)
    np.testing.assert_allclose(
        np.asarray(r_pad.prices), np.asarray(r_nat.prices), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_array_equal(np.asarray(r_pad.won), np.asarray(r_nat.won))
    np.testing.assert_allclose(
        np.asarray(r_pad.payments), np.asarray(r_nat.payments),
        rtol=1e-4, atol=1e-4,
    )


def test_clock_csr_kernel_demand_fn():
    sp = sparsify(_random_problem(24, 2, 10, seed=19))
    csr = csr_from_padded(sp)
    p0 = jnp.full((10,), 0.5)
    cfg = ClockConfig(max_rounds=2000)
    r_jnp = clock_auction(csr, p0, cfg)
    r_krn = clock_auction(csr, p0, cfg, demand_fn=ops.csr_bid_demand_fn("interpret"))
    np.testing.assert_allclose(
        np.asarray(r_jnp.prices), np.asarray(r_krn.prices), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_array_equal(np.asarray(r_jnp.won), np.asarray(r_krn.won))


def test_clock_rejects_mismatched_csr_demand_fn():
    sp = sparsify(_random_problem(4, 1, 3, seed=23))
    csr = csr_from_padded(sp)
    p0 = jnp.full((3,), 0.5)
    with pytest.raises(TypeError):
        clock_auction(csr, p0, ClockConfig(), demand_fn=proxy_demand)
    with pytest.raises(TypeError):
        clock_auction(sp, p0, ClockConfig(), demand_fn=csr_proxy_demand)


# ---------------------------------------------------------------------------
# sharded settlement on CSR books: bit-identity across device counts
# ---------------------------------------------------------------------------


def test_sharded_csr_one_device_matches_padded():
    sp = random_market(57, 11, seed=0, supply=(2.0, 6.0))
    csr = csr_from_padded(sp)
    p0 = jnp.full((11,), 0.1)
    cfg = ClockConfig(max_rounds=2000, alpha=0.6, delta=0.25)
    ref_res = clock_auction(sp, p0, cfg, demand_fn=sparse_proxy_demand_blocked)
    res = sharded_clock_auction(csr, p0, cfg, mesh=users_mesh(1))
    assert int(ref_res.rounds) > 10
    for f in RESULT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref_res, f)), np.asarray(getattr(res, f)), err_msg=f
        )


SHARDED_CSR_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import (ClockConfig, clock_auction, csr_from_padded,
                        random_market, sharded_clock_auction,
                        sparse_proxy_demand_blocked, users_mesh)

assert jax.device_count() == 8
cfg = ClockConfig(max_rounds=3000, alpha=0.6, delta=0.25)
fields = ("prices", "alloc_idx", "alloc_val", "chosen_bundle", "won",
          "payments", "excess_demand", "rounds", "converged")
for seed in (0, 3, 7):
    prob = random_market(203, 37, seed=seed, supply=(2.0, 6.0))
    csr = csr_from_padded(prob)
    p0 = jnp.full((prob.num_resources,), 0.1)
    ref = clock_auction(prob, p0, cfg, demand_fn=sparse_proxy_demand_blocked)
    assert int(ref.rounds) > 10, "market must actually tick"
    for D in (1, 2, 4, 8):
        res = sharded_clock_auction(csr, p0, cfg, mesh=users_mesh(D))
        for f in fields:
            a, b = np.asarray(getattr(ref, f)), np.asarray(getattr(res, f))
            assert a.shape == b.shape and (a == b).all(), (seed, D, f)
print("SHARDED_CSR_OK")
"""


def test_sharded_csr_bit_identical_1_2_4_8():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SHARDED_CSR_SCRIPT], capture_output=True,
        text=True, env=env, cwd=os.getcwd(), timeout=580,
    )
    assert "SHARDED_CSR_OK" in out.stdout, out.stdout + "\n" + out.stderr
