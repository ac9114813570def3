"""Pallas conv1d BRGEMM kernels vs pure-jnp oracle (interpret mode on CPU).

Sweeps shapes/dtypes per the repo contract, plus custom_vjp gradient checks
against jax-AD-through-the-oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import conv1d_brgemm as k
from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


def _rand(rng, shape, dtype):
    x = rng.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


# paper sweep slices: S in {1,5,9,51}, d in {1,2,8,16}, C/K in {1,15,16,64}
SWEEP = [
    # (N, C, K, S, d, Q, wblk)
    (1, 1, 1, 1, 1, 128, 128),
    (2, 15, 15, 5, 8, 300, 128),
    (2, 16, 32, 9, 2, 512, 256),
    (1, 64, 64, 51, 1, 1000, 256),
    (3, 8, 4, 15, 16, 640, 128),
    (1, 15, 15, 51, 8, 1000, 512),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,C,K,S,d,Q,wblk", SWEEP)
def test_fwd_matches_oracle(N, C, K, S, d, Q, wblk, dtype):
    # the tap loop sums the taps in the oracle's order, hence the tight
    # fp32 bound; the packed formulation, which untuned skinny passes
    # take, is checked against the oracle in tests/test_tap_packed.py
    rng = np.random.default_rng(0)
    W = Q + (S - 1) * d
    x = _rand(rng, (N, C, W), dtype)
    w = _rand(rng, (S, K, C), dtype)
    got = ops.conv1d(x, w, dilation=d, padding="VALID", backend="pallas",
                     wblk=wblk, alg="tap_loop", interpret=True)
    want = ref.conv1d_ref(x, w, dilation=d)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("N,C,K,S,d,Q,wblk", SWEEP[:4])
def test_fwd_matches_xla(N, C, K, S, d, Q, wblk):
    rng = np.random.default_rng(1)
    W = Q + (S - 1) * d
    x = _rand(rng, (N, C, W), jnp.float32)
    w = _rand(rng, (S, K, C), jnp.float32)
    got = ops.conv1d(x, w, dilation=d, padding="VALID", backend="pallas",
                     wblk=wblk, interpret=True)
    want = ref.xla_conv1d(x, w, dilation=d)
    # accumulation-order differences across S*C up to 3264 fp32 terms
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("padding", ["SAME", "CAUSAL", "VALID"])
def test_padding_modes(padding):
    rng = np.random.default_rng(2)
    N, C, K, S, d, W = 2, 8, 8, 5, 2, 200
    x = _rand(rng, (N, C, W), jnp.float32)
    w = _rand(rng, (S, K, C), jnp.float32)
    got = ops.conv1d(x, w, dilation=d, padding=padding, backend="pallas", interpret=True)
    lo, hi = ops._pad_amounts(S, d, padding)
    xp = jnp.pad(x, ((0, 0), (0, 0), (lo, hi)))
    want = ref.conv1d_ref(xp, w, dilation=d)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    if padding != "VALID":
        assert got.shape[-1] == W  # width preserved


@pytest.mark.parametrize("N,C,K,S,d,Q,wblk", SWEEP[1:5])
def test_custom_vjp_matches_autodiff_of_oracle(N, C, K, S, d, Q, wblk):
    rng = np.random.default_rng(3)
    W = Q + (S - 1) * d
    x = _rand(rng, (N, C, W), jnp.float32)
    w = _rand(rng, (S, K, C), jnp.float32)
    cot = _rand(rng, (N, K, Q), jnp.float32)

    def f_pallas(x, w):
        return jnp.vdot(ops.conv1d(x, w, dilation=d, padding="VALID",
                                   backend="pallas", wblk=wblk, interpret=True), cot)

    def f_ref(x, w):
        return jnp.vdot(ref.conv1d_ref(x, w, dilation=d), cot)

    gx, gw = jax.grad(f_pallas, argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(f_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(gx, gx_r, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(gw, gw_r, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("padding", ["SAME", "CAUSAL", "VALID"])
def test_custom_vjp_padding_modes(padding, dtype):
    """jax.grad through the Pallas custom_vjp for every padding mode (the
    SAME/CAUSAL pads happen outside the kernels — the VJP must still match
    autodiff-through-the-oracle on the *unpadded* inputs)."""
    rng = np.random.default_rng(9)
    N, C, K, S, d, W = 2, 8, 8, 5, 2, 200
    x = _rand(rng, (N, C, W), dtype)
    w = _rand(rng, (S, K, C), dtype)
    lo, hi = ops._pad_amounts(S, d, padding)
    Q = W if padding != "VALID" else W - (S - 1) * d
    cot = _rand(rng, (N, K, Q), dtype)

    def f_pallas(x, w):
        y = ops.conv1d(x, w, dilation=d, padding=padding, backend="pallas",
                       interpret=True)
        return jnp.vdot(y.astype(jnp.float32), cot.astype(jnp.float32))

    def f_ref(x, w):
        xp = jnp.pad(x, ((0, 0), (0, 0), (lo, hi)))
        return jnp.vdot(ref.conv1d_ref(xp, w, dilation=d).astype(jnp.float32),
                        cot.astype(jnp.float32))

    gx, gw = jax.grad(f_pallas, argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(f_ref, argnums=(0, 1))(x, w)
    # bf16 cotangents round differently under the two accumulation orders
    tol = (dict(rtol=5e-2, atol=8e-2) if dtype == jnp.bfloat16
           else dict(rtol=1e-4, atol=1e-4))
    np.testing.assert_allclose(np.asarray(gx, np.float32),
                               np.asarray(gx_r, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(gw, np.float32),
                               np.asarray(gw_r, np.float32), **tol)


def test_bwd_weight_kernel_direct():
    rng = np.random.default_rng(4)
    N, C, K, S, d, Q, wblk = 2, 8, 16, 5, 2, 256, 128
    W = Q + (S - 1) * d
    x = _rand(rng, (N, C, W), jnp.float32)
    g = _rand(rng, (N, K, Q), jnp.float32)
    got = k.conv1d_bwd_weight(x, g, S=S, dilation=d, wblk=wblk, interpret=True)
    want = ref.conv1d_bwd_weight_ref(x, g, dilation=d)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_bwd_data_ref_is_transpose():
    """conv1d_bwd_data_ref must equal the true VJP of conv1d_ref."""
    rng = np.random.default_rng(5)
    N, C, K, S, d, Q = 1, 4, 6, 3, 4, 64
    W = Q + (S - 1) * d
    x = _rand(rng, (N, C, W), jnp.float32)
    w = _rand(rng, (S, K, C), jnp.float32)
    g = _rand(rng, (N, K, Q), jnp.float32)
    _, vjp = jax.vjp(lambda x: ref.conv1d_ref(x, w, dilation=d), x)
    (want,) = vjp(g)
    got = ref.conv1d_bwd_data_ref(g, w, dilation=d)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# --- depthwise ---------------------------------------------------------------

DW_SWEEP = [
    (2, 16, 4, 1, 256, 128),
    (1, 64, 7, 2, 512, 256),
    (2, 128, 4, 1, 300, 128),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,C,S,d,Q,wblk", DW_SWEEP)
def test_depthwise_fwd(N, C, S, d, Q, wblk, dtype):
    rng = np.random.default_rng(6)
    W = Q + (S - 1) * d
    x = _rand(rng, (N, C, W), dtype)
    w = _rand(rng, (S, C), dtype)
    got = ops.depthwise_conv1d(x, w, dilation=d, padding="VALID", backend="pallas",
                               wblk=wblk, interpret=True)
    want = ref.depthwise_conv1d_ref(x, w, dilation=d)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("N,C,S,d,Q,wblk", DW_SWEEP[:2])
def test_depthwise_grad(N, C, S, d, Q, wblk):
    rng = np.random.default_rng(7)
    W = Q + (S - 1) * d
    x = _rand(rng, (N, C, W), jnp.float32)
    w = _rand(rng, (S, C), jnp.float32)
    cot = _rand(rng, (N, C, Q), jnp.float32)

    def f_pallas(x, w):
        return jnp.vdot(ops.depthwise_conv1d(x, w, dilation=d, padding="VALID",
                                             backend="pallas", wblk=wblk, interpret=True), cot)

    def f_ref(x, w):
        return jnp.vdot(ref.depthwise_conv1d_ref(x, w, dilation=d), cot)

    gx, gw = jax.grad(f_pallas, argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(f_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(gx, gx_r, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(gw, gw_r, rtol=1e-4, atol=1e-4)


def test_causal_depthwise_no_future_leak():
    """CAUSAL depthwise output at t must not depend on inputs > t."""
    rng = np.random.default_rng(8)
    N, C, S, W = 1, 8, 4, 128
    x = _rand(rng, (N, C, W), jnp.float32)
    w = _rand(rng, (S, C), jnp.float32)
    y0 = ops.depthwise_conv1d(x, w, padding="CAUSAL", backend="pallas", interpret=True)
    x2 = x.at[:, :, 64:].set(999.0)
    y1 = ops.depthwise_conv1d(x2, w, padding="CAUSAL", backend="pallas", interpret=True)
    np.testing.assert_allclose(y0[:, :, :64], y1[:, :, :64], rtol=1e-6, atol=1e-6)
