"""Fused conv epilogue (DESIGN.md §10): bias + activation + residual inside
the BRGEMM kernel.

Sweeps every epilogue combination (bias × {none, relu, gelu} × residual) in
fp32 and bf16 on the dense and depthwise paths, forward AND ``jax.grad``,
against the unfused composition through the readable oracle.  Plus: the
blocks.py rewrite (fused forward == pre-fusion baseline), the depthwise
bias+silu path used by Mamba2, the unified mixed-dtype policy, and the
tuner's epilogue-aware cache keys.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import epilogue as ep
from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)

COMBOS = [  # (has_bias, activation, has_residual) — the acceptance grid
    (hb, act, hr)
    for hb, act, hr in itertools.product(
        (False, True), ("none", "relu", "gelu"), (False, True))
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype, grad=False):
    if dtype == jnp.bfloat16:
        return dict(rtol=3e-2, atol=3e-2) if grad else dict(rtol=2e-2, atol=2e-2)
    return dict(rtol=2e-4, atol=2e-4) if grad else dict(rtol=2e-5, atol=2e-5)


def _dense_args(dtype, has_bias, has_residual, seed=0):
    rng = np.random.default_rng(seed)
    N, C, K, S, d, Q = 1, 4, 8, 3, 2, 128
    mk = lambda sh, scale=1.0: jnp.asarray(
        (scale * rng.standard_normal(sh)).astype(np.float32), dtype)
    x = mk((N, C, Q + (S - 1) * d))
    w = mk((S, K, C), 0.3)
    b = mk((K,), 0.2) if has_bias else None
    r = mk((N, K, Q)) if has_residual else None
    return x, w, b, r, d


def _dw_args(dtype, has_bias, has_residual, seed=1):
    rng = np.random.default_rng(seed)
    N, C, S, d, Q = 1, 8, 4, 1, 128
    mk = lambda sh, scale=1.0: jnp.asarray(
        (scale * rng.standard_normal(sh)).astype(np.float32), dtype)
    x = mk((N, C, Q + (S - 1) * d))
    w = mk((S, C), 0.3)
    b = mk((C,), 0.2) if has_bias else None
    r = mk((N, C, Q)) if has_residual else None
    return x, w, b, r, d


# ---------------------------------------------------------------------------
# Forward: every combination vs the fused oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("has_bias,act,has_residual", COMBOS)
def test_dense_fwd_all_combos(has_bias, act, has_residual, dtype):
    x, w, b, r, d = _dense_args(dtype, has_bias, has_residual)
    got = ops.conv1d(x, w, bias=b, activation=act, residual=r, dilation=d,
                     padding="VALID", backend="pallas", wblk=128, interpret=True)
    want = ref.conv1d_fused_ref(x, w, dilation=d, bias=b, activation=act,
                                residual=r)
    assert got.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("has_bias,act,has_residual", COMBOS)
def test_depthwise_fwd_all_combos(has_bias, act, has_residual, dtype):
    x, w, b, r, d = _dw_args(dtype, has_bias, has_residual)
    got = ops.depthwise_conv1d(x, w, bias=b, activation=act, residual=r,
                               dilation=d, padding="VALID", backend="pallas",
                               wblk=128, interpret=True)
    want = ref.depthwise_conv1d_fused_ref(x, w, dilation=d, bias=b,
                                          activation=act, residual=r)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# jax.grad: every combination vs autodiff through the oracle
# ---------------------------------------------------------------------------


def _grads(fn, args):
    diff = [a for a in args if a is not None]
    idx = [i for i, a in enumerate(args) if a is not None]

    def loss(*diff_args):
        full = list(args)
        for i, a in zip(idx, diff_args):
            full[i] = a
        return fn(*full)

    return jax.grad(loss, argnums=tuple(range(len(diff))))(*diff)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("has_bias,act,has_residual", COMBOS)
def test_dense_grad_all_combos(has_bias, act, has_residual, dtype):
    x, w, b, r, d = _dense_args(dtype, has_bias, has_residual)
    Q = x.shape[-1] - (w.shape[0] - 1) * d
    cot = jnp.asarray(np.random.default_rng(2).standard_normal(
        (x.shape[0], w.shape[1], Q)).astype(np.float32), dtype)

    def f_pallas(x, w, b, r):
        y = ops.conv1d(x, w, bias=b, activation=act, residual=r, dilation=d,
                       padding="VALID", backend="pallas", wblk=128,
                       interpret=True)
        return jnp.vdot(y.astype(jnp.float32), cot.astype(jnp.float32))

    def f_ref(x, w, b, r):
        y = ref.conv1d_fused_ref(x, w, dilation=d, bias=b, activation=act,
                                 residual=r)
        return jnp.vdot(y.astype(jnp.float32), cot.astype(jnp.float32))

    for g, g_r, name in zip(_grads(f_pallas, (x, w, b, r)),
                            _grads(f_ref, (x, w, b, r)), "xwbr"):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(g_r, np.float32),
                                   err_msg=f"d{name}", **_tol(dtype, grad=True))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("has_bias,act,has_residual", COMBOS)
def test_depthwise_grad_all_combos(has_bias, act, has_residual, dtype):
    x, w, b, r, d = _dw_args(dtype, has_bias, has_residual)
    cot = jnp.asarray(np.random.default_rng(3).standard_normal(
        (x.shape[0], x.shape[1], 128)).astype(np.float32), dtype)

    def f_pallas(x, w, b, r):
        y = ops.depthwise_conv1d(x, w, bias=b, activation=act, residual=r,
                                 dilation=d, padding="VALID",
                                 backend="pallas", wblk=128, interpret=True)
        return jnp.vdot(y.astype(jnp.float32), cot.astype(jnp.float32))

    def f_ref(x, w, b, r):
        y = ref.depthwise_conv1d_fused_ref(x, w, dilation=d, bias=b,
                                           activation=act, residual=r)
        return jnp.vdot(y.astype(jnp.float32), cot.astype(jnp.float32))

    for g, g_r, name in zip(_grads(f_pallas, (x, w, b, r)),
                            _grads(f_ref, (x, w, b, r)), "xwbr"):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(g_r, np.float32),
                                   err_msg=f"d{name}", **_tol(dtype, grad=True))


# ---------------------------------------------------------------------------
# The Mamba2/Zamba2 depthwise path: fused bias + SiLU
# ---------------------------------------------------------------------------


def test_depthwise_bias_silu_matches_unfused_composition():
    x, w, b, _, d = _dw_args(jnp.float32, True, False, seed=4)
    got = ops.depthwise_conv1d(x, w, bias=b, activation="silu", dilation=d,
                               padding="CAUSAL", backend="pallas",
                               interpret=True, out_dtype=jnp.float32)
    y = ref.depthwise_conv1d_ref(
        jnp.pad(x, ((0, 0), (0, 0), ((w.shape[0] - 1) * d, 0))), w, dilation=d)
    want = jax.nn.silu((y + b[None, :, None]).astype(jnp.float32))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# blocks.py rewrite: fused forward == pre-fusion baseline, fwd and grad
# ---------------------------------------------------------------------------


def test_blocks_fused_matches_unfused():
    from repro import configs
    from repro.core import blocks

    cfg = configs.get("atacworks")
    p = blocks.init_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 600), jnp.float32)
    sf, pf = blocks.forward(p, cfg, x, fused=True)
    su, pu = blocks.forward(p, cfg, x, fused=False)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(su),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(pf), np.asarray(pu),
                               rtol=1e-4, atol=1e-4)

    batch = {"noisy": x, "clean": x, "peaks": (x > 0).astype(jnp.float32)}
    gf = jax.grad(lambda p: blocks.loss_fn(p, cfg, batch, fused=True)[0])(p)
    gu = jax.grad(lambda p: blocks.loss_fn(p, cfg, batch, fused=False)[0])(p)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4), gf, gu)


# ---------------------------------------------------------------------------
# Unified dtype policy: bf16 activations + fp32 weights, one rule everywhere
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depthwise", [False, True])
def test_mixed_dtype_policy_consistent_across_backends(depthwise):
    """bf16 x + fp32 w: every backend computes in fp32 and returns x.dtype
    (the regression for the depthwise XLA path's old ad-hoc casting)."""
    rng = np.random.default_rng(5)
    N, C, K, S, d, Q = 1, 8, 8, 3, 1, 128
    x = jnp.asarray(rng.standard_normal((N, C, Q + S - 1)).astype(np.float32),
                    jnp.bfloat16)
    w_shape = (S, C) if depthwise else (S, K, C)
    w = jnp.asarray(0.3 * rng.standard_normal(w_shape).astype(np.float32))
    outs = {}
    for backend in ("pallas", "xla", "ref"):
        kw = dict(dilation=d, padding="VALID", backend=backend)
        if backend == "pallas":
            kw["interpret"] = True
        if depthwise:
            y = ops.depthwise_conv1d(x, w, **kw)
        else:
            y = ops.conv1d(x, w, **kw)
        assert y.dtype == x.dtype, (backend, y.dtype)
        outs[backend] = np.asarray(y, np.float32)
    np.testing.assert_allclose(outs["pallas"], outs["ref"], rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(outs["xla"], outs["ref"], rtol=2e-2, atol=2e-2)


def test_out_dtype_override():
    x, w, b, _, d = _dense_args(jnp.bfloat16, True, False)
    for backend in ("pallas", "xla", "ref"):
        y = ops.conv1d(x, w, bias=b, activation="relu", dilation=d,
                       padding="VALID", backend=backend, wblk=128,
                       interpret=True, out_dtype=jnp.float32)
        assert y.dtype == jnp.float32, backend


# ---------------------------------------------------------------------------
# Tuner: epilogue-aware cache keys
# ---------------------------------------------------------------------------


def test_signature_roundtrip():
    for hb, act, hr in COMBOS + [(True, "silu", False)]:
        sig = ep.signature(hb, act, hr)
        assert ep.parse(sig) == (hb, act, hr)
    assert ep.signature(False, None, False) == "none"
    with pytest.raises(ValueError):
        ep.canon("tanh")


def test_fused_cache_keys_distinct_and_legacy_compatible(tmp_path, monkeypatch):
    from repro import tune

    monkeypatch.setenv(tune.cache.ENV_CACHE_PATH, str(tmp_path / "c.json"))
    tune.reset_default_cache()
    try:
        prob = dict(device_kind="cpu", dtype="float32", N=1, C=4, K=8, S=3,
                    dilation=2, Q=128, padding="SAME")
        legacy = tune.cache_key(**prob)  # pre-epilogue key form
        assert tune.cache_key(**prob, epilogue="none") == legacy
        fused = tune.cache_key(**prob, epilogue="b+relu+r")
        assert fused == legacy + "|ep:b+relu+r"

        # a legacy (pre-PR) cache entry still resolves the unfused instance,
        # and the fused instance does NOT see it
        monkeypatch.setattr(tune, "device_kind", lambda: "cpu")
        tune.get_default_cache().put(legacy, {"backend": "xla", "wblk": None,
                                              "kblk": None, "source": "measured"})
        hit = tune.get_config(N=1, C=4, K=8, S=3, dilation=2, Q=128,
                              dtype=jnp.float32, padding="SAME",
                              allow_measure=False)
        assert hit.source == "cache" and hit.backend == "xla"
        miss = tune.get_config(N=1, C=4, K=8, S=3, dilation=2, Q=128,
                               dtype=jnp.float32, padding="SAME",
                               epilogue="b+relu+r", allow_measure=False)
        assert miss.source == "default"
    finally:
        tune.reset_default_cache()


def test_space_and_cost_accept_epilogue():
    from repro import tune
    from repro.tune import cost, space

    shape = dict(N=4, C=15, K=15, S=5, dilation=8, Q=5000, dtype="float32")
    plain_prob = tune.ConvProblem(**shape)
    fused_prob = tune.ConvProblem(**shape, epilogue="b+relu+r")
    plain = space.vmem_footprint_bytes(plain_prob, 256, 15)
    fused = space.vmem_footprint_bytes(fused_prob, 256, 15)
    assert fused == plain + 4 * (15 + 15 * 256)  # bias tile + residual tile

    cands = space.enumerate_candidates(fused_prob)
    assert any(c.backend == "pallas" for c in cands)
    est = cost.estimate_seconds(cands[0], fused_prob, device_kind="TPU v5 lite")
    est_plain = cost.estimate_seconds(cands[0], plain_prob,
                                      device_kind="TPU v5 lite")
    assert est >= est_plain  # residual read traffic never makes it cheaper
