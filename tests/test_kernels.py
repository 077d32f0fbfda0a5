"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.clock_bid_eval import bid_eval as pallas_bid_eval
from repro.kernels.wkv6 import wkv6 as pallas_wkv6


RNG = np.random.default_rng(0)


def _bid_case(U, B, R, dtype):
    bundles = (RNG.normal(size=(U, B, R)) * 3).astype(dtype)
    mask = RNG.random((U, B)) < 0.8
    mask[:, 0] = True
    pi = (RNG.normal(size=(U,)) * 5).astype(np.float32)
    prices = np.abs(RNG.normal(size=(R,))).astype(np.float32)
    return bundles, mask, pi, prices


@pytest.mark.parametrize("U,B,R", [(4, 1, 3), (33, 3, 18), (128, 8, 130), (517, 5, 200)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_clock_bid_eval_matches_oracle(U, B, R, dtype):
    bundles, mask, pi, prices = _bid_case(U, B, R, dtype)
    z0, c0 = ref.bid_eval(*map(jnp.asarray, (bundles, mask, pi, prices)))
    z1, c1 = pallas_bid_eval(*map(jnp.asarray, (bundles, mask, pi, prices)), interpret=True)
    np.testing.assert_allclose(np.asarray(z0), np.asarray(z1), rtol=3e-3, atol=3e-3)
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))


def test_clock_bid_eval_all_masked_user():
    bundles, mask, pi, prices = _bid_case(8, 2, 5, np.float32)
    mask[3, :] = False
    z0, c0 = ref.bid_eval(*map(jnp.asarray, (bundles, mask, pi, prices)))
    z1, c1 = pallas_bid_eval(*map(jnp.asarray, (bundles, mask, pi, prices)), interpret=True)
    assert c0[3] == -1 and c1[3] == -1
    np.testing.assert_allclose(np.asarray(z0), np.asarray(z1), rtol=1e-4, atol=1e-4)


def test_ops_backend_dispatch():
    bundles, mask, pi, prices = _bid_case(16, 2, 6, np.float32)
    za, _ = ops.bid_eval(*map(jnp.asarray, (bundles, mask, pi, prices)), backend="jnp")
    zb, _ = ops.bid_eval(*map(jnp.asarray, (bundles, mask, pi, prices)), backend="interpret")
    np.testing.assert_allclose(np.asarray(za), np.asarray(zb), rtol=1e-4, atol=1e-4)


def test_ops_without_backend_refuses_off_tpu():
    """No backend means the compiled kernel; off the TPU that is a clear
    error, never a silent switch to the jnp reference."""
    import jax

    if jax.devices()[0].platform == "tpu":
        pytest.skip("the compiled kernel runs here")
    bundles, mask, pi, prices = map(jnp.asarray, _bid_case(16, 2, 6, np.float32))
    with pytest.raises(RuntimeError, match="backend='interpret'"):
        ops.bid_eval(bundles, mask, pi, prices)
    with pytest.raises(RuntimeError, match="Mosaic TPU kernel"):
        ops.fused_epoch_z_fn(None, 6)
    with pytest.raises(ValueError, match="unknown kernel backend"):
        ops.bid_eval(bundles, mask, pi, prices, backend="cuda")


def _wkv_case(T, H, K, V, dtype=np.float32, strong_decay=True):
    r = RNG.normal(size=(T, H, K)).astype(dtype)
    k = (RNG.normal(size=(T, H, K)) * 0.5).astype(dtype)
    v = RNG.normal(size=(T, H, V)).astype(dtype)
    scale = 1.0 if strong_decay else 0.1
    w = np.exp(-np.exp(RNG.normal(size=(T, H, K)) * scale)).astype(dtype)
    u = (RNG.normal(size=(H, K)) * 0.3).astype(dtype)
    s0 = (RNG.normal(size=(H, K, V)) * 0.2).astype(np.float32)
    return r, k, v, w, u, s0


@pytest.mark.parametrize("T,H,K,V,chunk", [
    (8, 1, 8, 8, 8), (16, 2, 8, 16, 8), (33, 1, 16, 16, 16),
    (64, 3, 32, 64, 32), (100, 2, 64, 64, 32),
])
def test_wkv6_pallas_matches_oracle(T, H, K, V, chunk):
    args = _wkv_case(T, H, K, V)
    o0, s0 = ref.wkv6(*map(jnp.asarray, args))
    o1, s1 = pallas_wkv6(*map(jnp.asarray, args), chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(o0), np.asarray(o1), rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), rtol=3e-4, atol=3e-4)


def test_wkv6_pallas_bf16_inputs():
    """bf16 r/k/v/w inputs (the TPU layout), fp32 accumulation inside."""
    r, k, v, w, u, s0 = _wkv_case(32, 2, 16, 16)
    cast = lambda x: jnp.asarray(x, jnp.bfloat16)
    o0, sf0 = ref.wkv6(cast(r), cast(k), cast(v), cast(w), cast(u), jnp.asarray(s0))
    o1, sf1 = pallas_wkv6(
        cast(r), cast(k), cast(v), cast(w), cast(u), jnp.asarray(s0),
        chunk=16, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(o0), np.asarray(o1), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(sf0), np.asarray(sf1), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("chunk", [4, 16, 64])
def test_wkv6_chunked_jnp_matches_sequential(chunk):
    args = _wkv_case(50, 2, 16, 32)
    o0, s0 = ref.wkv6(*map(jnp.asarray, args))
    o1, s1 = ref.wkv6_chunked(*map(jnp.asarray, args), chunk=chunk)
    np.testing.assert_allclose(np.asarray(o0), np.asarray(o1), rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), rtol=3e-4, atol=3e-4)


def test_wkv6_state_continuity():
    """Running [0:T/2] then [T/2:T] from the carried state == one pass."""
    args = _wkv_case(40, 2, 16, 16)
    r, k, v, w, u, s0 = map(jnp.asarray, args)
    o_full, s_full = ref.wkv6_chunked(r, k, v, w, u, s0, chunk=8)
    o_a, s_a = ref.wkv6_chunked(r[:20], k[:20], v[:20], w[:20], u, s0, chunk=8)
    o_b, s_b = ref.wkv6_chunked(r[20:], k[20:], v[20:], w[20:], u, s_a, chunk=8)
    np.testing.assert_allclose(np.asarray(o_full), np.concatenate([o_a, o_b]), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_full), np.asarray(s_b), rtol=2e-4, atol=2e-4)


def test_wkv6_decode_step_equals_scan_step():
    """The closed-form S=1 decode update matches the sequential oracle."""
    args = _wkv_case(1, 2, 8, 8)
    o0, s0 = ref.wkv6(*map(jnp.asarray, args))
    o1, s1 = ref.wkv6_chunked(*map(jnp.asarray, args), chunk=1)
    np.testing.assert_allclose(np.asarray(o0), np.asarray(o1), rtol=1e-5, atol=1e-5)
