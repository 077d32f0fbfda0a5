"""Sparse-bundle settlement engine: sparse vs dense parity + kernel checks.

The sparse path is the primary settlement encoding, so every behavior the
dense reference defines must be reproduced: z / chosen / active agreement in
scalar-π and vector-π modes, padded XOR slots, all-invalid users, duplicate
pool indices within one bundle, and the Pallas kernel under interpret=True.
"""
import dataclasses

import numpy as np
import jax
import jax.extend
import jax.numpy as jnp
import pytest
from jax import lax

from repro.core import (
    ClockConfig,
    SparseAuctionProblem,
    clock_auction,
    csr_padded_views,
    csr_problem_from_arrays,
    densify,
    pack_bids,
    pack_bids_sparse,
    proxy_demand,
    sparse_proxy_demand,
    sparsify,
    surplus_and_trade,
    verify_system,
)
from repro.core import auction
from repro.core.auction import blocked_demand_fn, sparse_proxy_demand_exact
from repro.kernels import ops, ref
from repro.kernels.sparse_bid_eval import sparse_bid_eval as pallas_sparse_bid_eval

RNG = np.random.default_rng(7)


def _random_problem(U, B, R, nnz=3, pad_prob=0.25, seed=None):
    """Random dense problem with ≤nnz nonzeros per bundle + padded XOR slots."""
    rng = np.random.default_rng(seed) if seed is not None else RNG
    bl, pis = [], []
    for _ in range(U):
        n_alt = int(rng.integers(1, B + 1))
        alts = []
        for _ in range(n_alt):
            q = np.zeros(R, np.float32)
            k = int(rng.integers(1, nnz + 1))
            q[rng.choice(R, size=k, replace=False)] = rng.uniform(-2, 4, size=k)
            alts.append(q)
        bl.append(alts)
        pis.append(float(rng.uniform(-5, 15)))
    prob = pack_bids(bl, pis, base_cost=np.ones(R, np.float32))
    return prob


def _prices(R, seed=0):
    return jnp.asarray(
        np.abs(np.random.default_rng(seed).normal(size=R)).astype(np.float32)
    )


# ---------------------------------------------------------------------------
# converters
# ---------------------------------------------------------------------------


def test_sparsify_densify_roundtrip():
    prob = _random_problem(23, 3, 17, seed=0)
    sp = sparsify(prob)
    back = densify(sp)
    np.testing.assert_array_equal(np.asarray(prob.bundles), np.asarray(back.bundles))
    np.testing.assert_array_equal(
        np.asarray(prob.bundle_mask), np.asarray(back.bundle_mask)
    )


def test_pack_bids_sparse_matches_sparsify():
    rng = np.random.default_rng(1)
    R = 11
    bl = [[np.zeros(R, np.float32)] for _ in range(4)]
    for row in bl:
        row[0][rng.choice(R, 2, replace=False)] = rng.uniform(1, 3, 2)
    dense = pack_bids(bl, [1.0] * 4, base_cost=np.ones(R, np.float32))
    sp_a = sparsify(dense)
    sp_b = pack_bids_sparse(bl, [1.0] * 4, base_cost=np.ones(R, np.float32))
    np.testing.assert_array_equal(np.asarray(sp_a.idx), np.asarray(sp_b.idx))
    np.testing.assert_array_equal(np.asarray(sp_a.val), np.asarray(sp_b.val))
    np.testing.assert_array_equal(
        np.asarray(sp_a.supply_scale), np.asarray(sp_b.supply_scale)
    )


def test_pack_bids_sparse_accepts_idx_val_pairs():
    R = 9
    bl = [[(np.array([7, 2]), np.array([1.5, -2.0]))]]  # unsorted on purpose
    sp = pack_bids_sparse(bl, [3.0], base_cost=np.ones(R, np.float32))
    np.testing.assert_array_equal(np.asarray(sp.idx[0, 0]), [2, 7])
    np.testing.assert_array_equal(np.asarray(sp.val[0, 0]), [-2.0, 1.5])


def test_pack_bids_sparse_rejects_out_of_range_indices():
    R = 3
    for bad in ([-1], [R]):
        with pytest.raises(ValueError):
            pack_bids_sparse(
                [[(np.array(bad), np.array([1.0]))]],
                [1.0],
                base_cost=np.ones(R, np.float32),
            )


def test_sparsify_k_max_too_small_raises():
    prob = _random_problem(5, 2, 10, nnz=4, seed=2)
    with pytest.raises(ValueError):
        sparsify(prob, k_max=1)


# ---------------------------------------------------------------------------
# demand parity: scalar-π and vector-π
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("U,B,R", [(4, 1, 3), (33, 3, 18), (120, 4, 130)])
def test_sparse_demand_matches_dense_scalar_pi(U, B, R):
    prob = _random_problem(U, B, R, seed=U)
    sp = sparsify(prob)
    prices = _prices(R, seed=U)
    x, ch_d, act_d = proxy_demand(prob.bundles, prob.bundle_mask, prob.pi, prices)
    z_s, ch_s, act_s = sparse_proxy_demand(
        sp.idx, sp.val, sp.bundle_mask, sp.pi, prices, R
    )
    np.testing.assert_array_equal(np.asarray(ch_d), np.asarray(ch_s))
    np.testing.assert_array_equal(np.asarray(act_d), np.asarray(act_s))
    np.testing.assert_allclose(
        np.asarray(x.sum(0)), np.asarray(z_s), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("U,B,R", [(4, 1, 3), (33, 3, 18), (120, 4, 130)])
def test_sparse_demand_matches_dense_vector_pi(U, B, R):
    prob = _random_problem(U, B, R, seed=U + 1)
    piv = jnp.asarray(
        np.random.default_rng(U).uniform(-5, 15, size=(U, prob.num_bundles)).astype(
            np.float32
        )
    )
    sp = sparsify(prob)
    prices = _prices(R, seed=U + 1)
    x, ch_d, act_d = proxy_demand(prob.bundles, prob.bundle_mask, piv, prices)
    z_s, ch_s, act_s = sparse_proxy_demand(
        sp.idx, sp.val, sp.bundle_mask, piv, prices, R
    )
    np.testing.assert_array_equal(np.asarray(ch_d), np.asarray(ch_s))
    np.testing.assert_array_equal(np.asarray(act_d), np.asarray(act_s))
    np.testing.assert_allclose(
        np.asarray(x.sum(0)), np.asarray(z_s), rtol=1e-5, atol=1e-5
    )


def test_sparse_demand_exact_is_bitwise():
    """The exact variant must reproduce the dense column reduction bit for bit
    (that is its contract — the Economy swap depends on it)."""
    prob = _random_problem(64, 3, 21, seed=5)
    sp = sparsify(prob)
    prices = _prices(21, seed=5)
    x, _, _ = proxy_demand(prob.bundles, prob.bundle_mask, prob.pi, prices)
    z_e, _, _ = sparse_proxy_demand_exact(
        sp.idx, sp.val, sp.bundle_mask, sp.pi, prices, 21
    )
    np.testing.assert_array_equal(np.asarray(x.sum(0)), np.asarray(z_e))


def test_all_invalid_user_drops_out():
    prob = _random_problem(8, 2, 6, seed=3)
    mask = np.asarray(prob.bundle_mask).copy()
    mask[3, :] = False
    prob = dataclasses.replace(prob, bundle_mask=jnp.asarray(mask))
    sp = sparsify(prob)
    prices = _prices(6, seed=3)
    for pi in (prob.pi, jnp.zeros((8, prob.num_bundles), jnp.float32)):
        z, ch, act = sparse_proxy_demand(
            sp.idx, sp.val, sp.bundle_mask, pi, prices, 6
        )
        assert int(ch[3]) == -1 and not bool(act[3])
        zk, chk = ops.sparse_bid_eval(
            sp.idx, sp.val, sp.bundle_mask, pi, prices, 6, backend="interpret"
        )
        assert int(chk[3]) == -1


def test_duplicate_indices_within_bundle():
    """Duplicate pool indices in one bundle sum — same as a dense bundle whose
    entry is the sum of the duplicates — in cost, z, and settlement."""
    R = 5
    idx = np.array([[[2, 2, 4]]], np.int32)
    val = np.array([[[1.0, 2.0, 0.5]]], np.float32)
    sp = SparseAuctionProblem(
        idx=jnp.asarray(idx),
        val=jnp.asarray(val),
        bundle_mask=jnp.asarray([[True]]),
        pi=jnp.asarray([100.0], jnp.float32),
        base_cost=jnp.ones((R,), jnp.float32),
        supply_scale=jnp.ones((R,), jnp.float32),
        num_resources=R,
    )
    dense = densify(sp)
    assert float(dense.bundles[0, 0, 2]) == 3.0
    prices = _prices(R, seed=9)
    x, ch_d, _ = proxy_demand(dense.bundles, dense.bundle_mask, dense.pi, prices)
    z_s, ch_s, _ = sparse_proxy_demand(
        sp.idx, sp.val, sp.bundle_mask, sp.pi, prices, R
    )
    np.testing.assert_array_equal(np.asarray(ch_d), np.asarray(ch_s))
    np.testing.assert_allclose(np.asarray(x.sum(0)), np.asarray(z_s), rtol=1e-6)
    zk, chk = ops.sparse_bid_eval(
        sp.idx, sp.val, sp.bundle_mask, sp.pi, prices, R, backend="interpret"
    )
    np.testing.assert_allclose(np.asarray(z_s), np.asarray(zk), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ch_s), np.asarray(chk))


# ---------------------------------------------------------------------------
# Pallas kernel (interpret mode on CPU) vs jnp oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("U,B,R,K", [(4, 1, 3, 1), (33, 3, 18, 4), (130, 5, 200, 8)])
@pytest.mark.parametrize("vector_pi", [False, True])
def test_sparse_kernel_matches_oracle(U, B, R, K, vector_pi):
    rng = np.random.default_rng(U + K)
    idx = rng.integers(0, R, size=(U, B, K)).astype(np.int32)
    idx.sort(axis=-1)
    val = (rng.normal(size=(U, B, K)) * 2).astype(np.float32)
    # knock out some slots (padding) and some whole bundles (XOR padding)
    val[rng.random((U, B, K)) < 0.3] = 0.0
    mask = rng.random((U, B)) < 0.85
    mask[:, 0] = True
    if vector_pi:
        pi = (rng.normal(size=(U, B)) * 5).astype(np.float32)
    else:
        pi = (rng.normal(size=(U,)) * 5).astype(np.float32)
    prices = np.abs(rng.normal(size=R)).astype(np.float32)
    args = tuple(map(jnp.asarray, (idx, val, mask, pi, prices)))
    z0, c0 = ref.sparse_bid_eval(*args, R)
    z1, c1 = pallas_sparse_bid_eval(*args, R, interpret=True)
    np.testing.assert_allclose(np.asarray(z0), np.asarray(z1), rtol=3e-3, atol=3e-3)
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))


def test_ops_sparse_backend_dispatch():
    prob = _random_problem(16, 2, 9, seed=11)
    sp = sparsify(prob)
    prices = _prices(9, seed=11)
    za, ca = ops.sparse_bid_eval(
        sp.idx, sp.val, sp.bundle_mask, sp.pi, prices, 9, backend="jnp"
    )
    zb, cb = ops.sparse_bid_eval(
        sp.idx, sp.val, sp.bundle_mask, sp.pi, prices, 9, backend="interpret"
    )
    np.testing.assert_allclose(np.asarray(za), np.asarray(zb), rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))


def test_ops_dense_vector_pi_routes_through_sparse_kernel():
    """The old silent fallback is gone: vector-π with backend='interpret'
    must run the sparse kernel and still agree with the jnp proxy."""
    prob = _random_problem(12, 3, 7, seed=13)
    piv = jnp.asarray(
        np.random.default_rng(13).uniform(-5, 15, size=(12, prob.num_bundles)).astype(
            np.float32
        )
    )
    prices = _prices(7, seed=13)
    x_ref, ch_ref, act_ref = proxy_demand(
        prob.bundles, prob.bundle_mask, piv, prices
    )
    demand = ops.bid_demand_fn(backend="interpret")
    x, ch, act = demand(prob.bundles, prob.bundle_mask, piv, prices)
    np.testing.assert_array_equal(np.asarray(ch_ref), np.asarray(ch))
    np.testing.assert_array_equal(np.asarray(act_ref), np.asarray(act))
    np.testing.assert_allclose(np.asarray(x_ref), np.asarray(x), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# end-to-end: clock auction on the sparse encoding
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("vector_pi", [False, True])
def test_clock_auction_sparse_matches_dense(vector_pi):
    prob = _random_problem(40, 3, 15, seed=17)
    if vector_pi:
        piv = jnp.asarray(
            np.random.default_rng(17)
            .uniform(-5, 15, size=(40, prob.num_bundles))
            .astype(np.float32)
        )
        prob = dataclasses.replace(prob, pi=piv)
    sp = sparsify(prob)
    p0 = jnp.full((15,), 0.5)
    cfg = ClockConfig(max_rounds=3000)
    rd = clock_auction(prob, p0, cfg)
    rs = clock_auction(sp, p0, cfg)
    np.testing.assert_allclose(
        np.asarray(rd.prices), np.asarray(rs.prices), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(rd.won), np.asarray(rs.won))
    np.testing.assert_array_equal(
        np.asarray(rd.chosen_bundle), np.asarray(rs.chosen_bundle)
    )
    np.testing.assert_allclose(
        np.asarray(rd.payments), np.asarray(rs.payments), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(rd.allocations),
        np.asarray(rs.allocations_dense(15)),
        rtol=1e-5,
        atol=1e-5,
    )
    assert verify_system(prob, rd) == verify_system(sp, rs)
    sd, td = surplus_and_trade(prob, rd)
    ss, ts = surplus_and_trade(sp, rs)
    np.testing.assert_allclose(float(sd), float(ss), rtol=1e-4)
    np.testing.assert_allclose(float(td), float(ts), rtol=1e-4)


def test_clock_auction_sparse_with_kernel_demand_fn():
    prob = _random_problem(24, 2, 10, seed=19)
    sp = sparsify(prob)
    p0 = jnp.full((10,), 0.5)
    cfg = ClockConfig(max_rounds=2000)
    r_jnp = clock_auction(sp, p0, cfg)
    r_krn = clock_auction(sp, p0, cfg, demand_fn=ops.sparse_bid_demand_fn("interpret"))
    np.testing.assert_allclose(
        np.asarray(r_jnp.prices), np.asarray(r_krn.prices), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_array_equal(np.asarray(r_jnp.won), np.asarray(r_krn.won))


def test_clock_auction_rejects_mismatched_demand_fn():
    prob = _random_problem(4, 1, 3, seed=23)
    sp = sparsify(prob)
    p0 = jnp.full((3,), 0.5)
    with pytest.raises(TypeError):
        clock_auction(sp, p0, ClockConfig(), demand_fn=proxy_demand)
    with pytest.raises(TypeError):
        clock_auction(prob, p0, ClockConfig(), demand_fn=sparse_proxy_demand)


# ---------------------------------------------------------------------------
# bundle pick: exact selects in place of the bundle-axis gather
# ---------------------------------------------------------------------------


def _gather_bundle(x, b):
    """The oracle: ``take_along_axis`` along the bundle axis."""
    ix = b.reshape(b.shape + (1,) * (x.ndim - 1))
    return jnp.take_along_axis(x, ix, axis=1)[:, 0]


def _bits(x):
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.floating):
        x = lax.bitcast_convert_type(x, jnp.int32)
    return np.asarray(x)


def _assert_same_bits(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(_bits(g), _bits(w))


def _padded_book(U, B, K, R, vector_pi, seed):
    """A random padded book with padded slots, fully masked users and
    duplicate pools inside a bundle."""
    rng = np.random.default_rng(seed)
    idx = np.sort(rng.integers(0, R, (U, B, K)), axis=-1).astype(np.int32)
    val = rng.uniform(-2, 4, (U, B, K)).astype(np.float32)
    pad = rng.random((U, B, K)) < 0.3  # padded slots: idx 0, val 0
    idx[pad], val[pad] = 0, 0.0
    if K > 1:  # duplicate pools inside a bundle
        dup = rng.random((U, B)) < 0.2
        idx[..., 1] = np.where(dup, idx[..., 0], idx[..., 1])
    mask = rng.random((U, B)) < 0.75
    mask[rng.random(U) < 0.15] = False  # fully masked users
    idx[~mask], val[~mask] = 0, 0.0
    shape = (U, B) if vector_pi else (U,)
    pi = rng.uniform(-5, 15, shape).astype(np.float32)
    return SparseAuctionProblem(
        idx=jnp.asarray(idx),
        val=jnp.asarray(val),
        bundle_mask=jnp.asarray(mask),
        pi=jnp.asarray(pi),
        base_cost=jnp.ones(R, jnp.float32),
        supply_scale=jnp.ones(R, jnp.float32),
        num_resources=R,
    )


def _fixed_k_csr(prob):
    """The padded book as the always-on book stores it: every bundle owns
    exactly K entries of the flat streams, padding included."""
    U, B, K = prob.idx.shape
    return csr_problem_from_arrays(
        np.asarray(prob.idx).reshape(-1),
        np.asarray(prob.val).reshape(-1),
        np.arange(U * B + 1, dtype=np.int32) * K,
        np.asarray(prob.bundle_mask),
        np.asarray(prob.pi),
        np.asarray(prob.base_cost),
        np.asarray(prob.supply_scale),
        k_bound=K,
    )


def _bundle_pick_outputs(prob, result, prices):
    """Selection, both settles and the SYSTEM checks, each jitted through a
    fresh wrapper so that it is traced anew with whatever
    ``auction._take_bundle`` is at call time."""
    r = prob.num_resources
    args = (prob.idx, prob.val, prob.bundle_mask, prob.pi, prices)
    sel = jax.jit(lambda *a: auction._sparse_selection(*a))(*args)
    settles = [
        jax.jit(
            lambda i, v, p, c, a, exact=exact: auction._sparse_settle(
                i, v, p, c, a, num_resources=r, exact=exact
            )
        )(prob.idx, prob.val, prices, sel[2], sel[3])
        for exact in (False, True)
    ]
    checks = jax.jit(
        lambda pr, res: auction._verify_system_checks.__wrapped__(pr, res, 1e-3)
    )(prob, result)
    return sel, settles, checks


@pytest.mark.parametrize("B", [1, 6, 8])
@pytest.mark.parametrize("vector_pi", [False, True], ids=["scalar_pi", "vector_pi"])
def test_bundle_pick_is_bit_identical_to_take_along_axis(monkeypatch, B, vector_pi):
    """Selection, settlement and verification pick bundles with selects; the
    result is the gather's bit for bit (padded slots, fully masked users and
    duplicate pools included)."""
    prob = _padded_book(96, B, 3, 24, vector_pi, seed=B)
    prices = _prices(24, seed=B)
    result = clock_auction(prob, prices, ClockConfig(max_rounds=50))
    # a second result whose winners report a neighbouring bundle, so the
    # checks that read the winner's surplus see other values
    rotated = dataclasses.replace(
        result,
        chosen_bundle=jnp.where(
            result.won, (result.chosen_bundle + 1) % B, result.chosen_bundle
        ),
    )
    got = [_bundle_pick_outputs(prob, res, prices) for res in (result, rotated)]
    monkeypatch.setattr(auction, "_take_bundle", _gather_bundle)
    want = [_bundle_pick_outputs(prob, res, prices) for res in (result, rotated)]
    assert np.asarray(~prob.bundle_mask.any(axis=1)).any()  # masked users exist
    for (g_sel, g_settles, g_checks), (w_sel, w_settles, w_checks) in zip(got, want):
        _assert_same_bits(g_sel, w_sel)
        for g, w in zip(g_settles, w_settles):
            _assert_same_bits(g, w)
        assert {k: bool(v) for k, v in g_checks.items()} == {
            k: bool(v) for k, v in w_checks.items()
        }


@pytest.mark.parametrize("B", [1, 6, 8])
@pytest.mark.parametrize("vector_pi", [False, True], ids=["scalar_pi", "vector_pi"])
def test_fixed_k_csr_settles_as_its_padded_book(B, vector_pi):
    """A CSR book whose bundles all hold K entries (the always-on book's
    layout, whose padded views are a reshape rather than a gather) gives the
    padded book's views, settlement and checks bit for bit."""
    prob = _padded_book(96, B, 3, 24, vector_pi, seed=20 + B)
    csr = _fixed_k_csr(prob)
    assert csr.nnz == prob.idx.size
    _assert_same_bits(
        jax.jit(csr_padded_views)(csr), (prob.idx, prob.val)
    )
    start = _prices(24, seed=B)
    fn = blocked_demand_fn(8)
    got = clock_auction(csr, start, ClockConfig(max_rounds=50), demand_fn=fn)
    want = clock_auction(prob, start, ClockConfig(max_rounds=50), demand_fn=fn)
    _assert_same_bits(jax.tree.leaves(got), jax.tree.leaves(want))
    assert verify_system(csr, got) == verify_system(prob, want)


@pytest.mark.parametrize("shape", [(40, 8), (40, 8, 3), (40, 1, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bool_])
def test_take_bundle_matches_gather_bits(shape, dtype):
    """Signed zeros, infinities and NaNs pass through the selects unchanged."""
    rng = np.random.default_rng(len(shape))
    x = rng.normal(size=shape).astype(np.float32)
    if dtype == jnp.float32:
        flat = x.reshape(-1)
        flat[::7], flat[1::7], flat[2::7], flat[3::7] = -0.0, np.inf, -np.inf, np.nan
    x = jnp.asarray(x > 0 if dtype == jnp.bool_ else x * 100).astype(dtype)
    b = jnp.asarray(rng.integers(0, shape[1], shape[0]), jnp.int32)
    got = jax.jit(auction._take_bundle)(x, b)
    want = jax.jit(_gather_bundle)(x, b)
    _assert_same_bits([got], [want])


def _jaxpr_eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in its equations
    (jit, while cond and body, cond branches)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _jaxpr_eqns(sub)


@pytest.mark.parametrize("encoding", ["padded", "csr"])
@pytest.mark.parametrize("vector_pi", [False, True], ids=["scalar_pi", "vector_pi"])
def test_served_programs_gather_only_prices(vector_pi, encoding):
    """The served clock (blocked fold) and the SYSTEM checks keep one gather:
    ``prices[idx]``, a 1-D operand of length R.  No gather runs over the
    bundle axis or over the book's flat streams, in the clock's while body
    or anywhere else.  ``csr`` is the always-on book's encoding, the one
    ``MarketService`` settles."""
    R = 24
    prob = _padded_book(64, 8, 3, R, vector_pi, seed=11)
    if encoding == "csr":
        prob = _fixed_k_csr(prob)
    start = _prices(R, seed=3)
    fn = blocked_demand_fn(8)
    clock = jax.make_jaxpr(
        lambda p, s: clock_auction(p, s, ClockConfig(), demand_fn=fn)
    )(prob, start)
    result = clock_auction(prob, start, ClockConfig(max_rounds=20), demand_fn=fn)
    verify = jax.make_jaxpr(auction._verify_system_checks, static_argnums=(2,))(
        prob, result, 1e-3
    )
    for closed in (clock, verify):
        eqns = list(_jaxpr_eqns(closed.jaxpr))
        assert any(e.primitive.name == "while" for e in eqns) == (closed is clock)
        operands = [e.invars[0].aval.shape for e in eqns if e.primitive.name == "gather"]
        assert operands and all(s == (R,) for s in operands), operands
