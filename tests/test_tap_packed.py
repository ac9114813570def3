"""Tap-packed BRGEMM + batch folding (DESIGN.md §12).

Covers the second dense-kernel formulation end to end:

  * hypothesis property test: ``tap_loop`` ≡ ``tap_packed`` ≡ the XLA
    reference over random (N, C, K, S, dilation, padding, dtype) — fwd
    AND jax.grad — including non-divisible widths and nblk > 1;
  * spy test: ``backend='auto'`` dispatches exactly the alg/nblk the
    cache records, per pass;
  * shape rule: an untuned pass packs when its packed GEMM dimension is
    under one MXU tile and the packed operand fits VMEM, and a pinned or
    tuned alg wins over it;
  * candidate space: alg/nblk axes with per-pass legality + VMEM
    accounting (packed operand charged), constraint keys (``|alg:`` /
    ``|nblk:``) round-tripping while legacy entries stay readable;
  * cost model: MXU occupancy ranks tap_packed first for the paper's
    skinny AtacWorks shape on a TPU device kind, and keeps the copy-free
    tap loop for fat shapes;
  * the depthwise default-cblk fix (largest divisor ≤ 512 — C=768 used
    to trip the ``C % cblk == 0`` assert).
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.kernels import conv1d_brgemm as _kmod
from repro.kernels import ops, ref
from repro.tune import cost, space

jax.config.update("jax_enable_x64", False)

try:  # the hypothesis fuzz runs where dev deps are installed (CI); the
    # fixed-sample sweep below covers the invariant everywhere else
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    path = str(tmp_path / "tune_cache.json")
    monkeypatch.setenv(tune.cache.ENV_CACHE_PATH, path)
    monkeypatch.delenv(tune.ENV_TUNE, raising=False)
    tune.reset_default_cache()
    yield path
    tune.reset_default_cache()


def _tol(dtype, grad=False):
    if dtype == jnp.bfloat16:
        return dict(rtol=3e-2, atol=3e-2)
    return dict(rtol=2e-4, atol=2e-4) if grad else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Property: tap_loop ≡ tap_packed ≡ XLA reference, fwd + grad
# ---------------------------------------------------------------------------

# fixed-sample sweep (always runs): non-divisible widths, every padding
# mode, both dtypes, folds that do and don't divide N
SWEEP = [
    # (N, C, K, S, d, Q, padding, dtype, nblk)
    (1, 1, 1, 1, 1, 40, "VALID", "float32", 1),
    (2, 15, 15, 5, 8, 130, "SAME", "float32", 2),
    (4, 7, 9, 3, 2, 100, "CAUSAL", "float32", 4),
    (3, 8, 8, 9, 4, 150, "SAME", "bfloat16", 2),   # 2 ∤ 3 -> sanitized
    (2, 16, 4, 3, 1, 47, "VALID", "bfloat16", 1),
]


def _check_fwd(sh):
    n, c, k, s, d, q, padding, dtn, nblk = sh
    dt = jnp.dtype(dtn)
    kx, kw = jax.random.split(jax.random.key(q * s + d))
    w_in = q if padding != "VALID" else q + (s - 1) * d
    x = (jax.random.normal(kx, (n, c, w_in), jnp.float32)).astype(dt)
    w = (jax.random.normal(kw, (s, k, c), jnp.float32) * 0.3).astype(dt)

    def run(alg):
        return ops.conv1d(x, w, dilation=d, padding=padding,
                          backend="pallas", wblk=128, alg=alg, nblk=nblk,
                          interpret=True)

    y_loop, y_packed = run("tap_loop"), run("tap_packed")
    y_ref = ops.conv1d(x, w, dilation=d, padding=padding, backend="xla")
    np.testing.assert_allclose(np.asarray(y_packed, np.float32),
                               np.asarray(y_loop, np.float32), **_tol(dt))
    np.testing.assert_allclose(np.asarray(y_packed, np.float32),
                               np.asarray(y_ref, np.float32), **_tol(dt))


def _check_grads(sh):
    n, c, k, s, d, q, padding, dtn, nblk = sh
    dt = jnp.dtype(dtn)
    kx, kw = jax.random.split(jax.random.key(q + 7 * s))
    w_in = q if padding != "VALID" else q + (s - 1) * d
    x = (jax.random.normal(kx, (n, c, w_in), jnp.float32)).astype(dt)
    w = (jax.random.normal(kw, (s, k, c), jnp.float32) * 0.3).astype(dt)

    def grads(alg):
        cfg = ("pallas", 128, None, alg, nblk)
        return jax.grad(
            lambda x, w: ops.conv1d(
                x, w, dilation=d, padding=padding, backend="pallas",
                wblk=128, alg=alg, nblk=nblk, interpret=True,
                bwd_data_cfg=cfg, bwd_weight_cfg=cfg
            ).astype(jnp.float32).sum(), argnums=(0, 1))(x, w)

    gl, gp = grads("tap_loop"), grads("tap_packed")
    gr = jax.grad(lambda x, w: ops.conv1d(
        x, w, dilation=d, padding=padding,
        backend="xla").astype(jnp.float32).sum(), argnums=(0, 1))(x, w)
    for a, b, name in ((gp[0], gl[0], "dx"), (gp[1], gl[1], "dw")):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   err_msg=f"{name} packed-vs-loop",
                                   **_tol(dt, grad=True))
    for a, b, name in ((gp[0], gr[0], "dx"), (gp[1], gr[1], "dw")):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   err_msg=f"{name} packed-vs-xla",
                                   **_tol(dt, grad=True))


@pytest.mark.parametrize("sh", SWEEP)
def test_tap_packed_equals_tap_loop_and_xla(sh):
    _check_fwd(sh)


@pytest.mark.parametrize("sh", SWEEP[1:4])
def test_tap_packed_grads_equal_tap_loop_and_xla(sh):
    _check_grads(sh)


if HAVE_HYPOTHESIS:
    prop_shapes = st.tuples(
        st.integers(1, 4),                       # N
        st.integers(1, 9),                       # C
        st.integers(1, 9),                       # K
        st.sampled_from([1, 3, 5, 9]),           # S
        st.sampled_from([1, 2, 4]),              # d
        st.integers(40, 150),                    # Q (non-divisible widths)
        st.sampled_from(["SAME", "CAUSAL", "VALID"]),
        st.sampled_from(["float32", "bfloat16"]),
        st.sampled_from([1, 2, 3]),       # nblk (folds ∤ N sanitize to 1)
    )

    @settings(max_examples=20, deadline=None)
    @given(prop_shapes)
    def test_property_tap_packed_fwd(sh):
        _check_fwd(sh)

    @settings(max_examples=10, deadline=None)
    @given(prop_shapes)
    def test_property_tap_packed_grads(sh):
        _check_grads(sh)


def test_fused_epilogue_identical_across_algs():
    """bias+gelu+residual with save_preact composes with tap_packed and
    batch folding exactly as with the tap loop."""
    rng = np.random.default_rng(5)
    N, C, K, S, d, Q = 4, 15, 15, 5, 8, 300
    x = jnp.asarray(rng.standard_normal((N, C, Q)).astype(np.float32))
    w = jnp.asarray(0.1 * rng.standard_normal((S, K, C)).astype(np.float32))
    bias = jnp.asarray(0.1 * rng.standard_normal(K).astype(np.float32))
    res = jnp.asarray(0.1 * rng.standard_normal((N, K, Q)).astype(np.float32))

    def run(alg, nblk):
        return ops.conv1d(x, w, bias=bias, activation="gelu", residual=res,
                          dilation=d, padding="SAME", backend="pallas",
                          alg=alg, nblk=nblk, interpret=True)

    base = run("tap_loop", 1)
    for alg, nblk in (("tap_packed", 1), ("tap_packed", 2), ("tap_loop", 4)):
        np.testing.assert_allclose(np.asarray(run(alg, nblk)),
                                   np.asarray(base), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Spy: backend='auto' dispatches the alg/nblk recorded in the cache
# ---------------------------------------------------------------------------


def _spy(monkeypatch, name):
    calls = []
    orig = getattr(_kmod, name)

    @functools.wraps(orig)
    def wrapper(*a, **kw):
        calls.append(kw)
        return orig(*a, **kw)

    monkeypatch.setattr(_kmod, name, wrapper)
    return calls


def test_auto_dispatches_cached_alg_per_pass(tmp_cache, monkeypatch):
    """With alg/nblk recorded per pass in the cache, jax.grad of
    conv1d(backend='auto') runs each kernel under exactly that
    formulation — the tuner's choice, not a hardcoded one."""
    p = tune.ConvProblem(N=2, C=8, K=16, S=3, dilation=2, Q=256,
                         dtype="float32", padding="SAME")
    cache = tune.get_default_cache()
    dk = tune.device_kind()
    cache.put(p.key(dk), {"backend": "pallas", "wblk": 128, "kblk": 8,
                          "alg": "tap_packed", "nblk": 2})
    cache.put(p.with_pass("bwd_data").key(dk),
              {"backend": "pallas", "wblk": 128, "kblk": 8,
               "alg": "tap_loop", "nblk": 2})
    cache.put(p.with_pass("bwd_weight").key(dk),
              {"backend": "pallas", "wblk": 128, "kblk": None,
               "alg": "tap_packed", "nblk": 1})

    fwd_calls = _spy(monkeypatch, "conv1d_fwd")
    bwdw_calls = _spy(monkeypatch, "conv1d_bwd_weight")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((p.N, p.C, p.Q)).astype(np.float32))
    w = jnp.asarray(0.1 * rng.standard_normal((p.S, p.K, p.C)).astype(np.float32))
    jax.grad(lambda x, w: ops.conv1d(x, w, dilation=p.dilation,
                                     padding=p.padding,
                                     backend="auto").sum(),
             argnums=(0, 1))(x, w)

    assert len(fwd_calls) == 2           # Alg. 2 (fwd) + Alg. 3 (bwd-data)
    assert (fwd_calls[0]["alg"], fwd_calls[0]["nblk"]) == ("tap_packed", 2)
    assert (fwd_calls[1]["alg"], fwd_calls[1]["nblk"]) == ("tap_loop", 2)
    assert len(bwdw_calls) == 1
    assert (bwdw_calls[0]["alg"], bwdw_calls[0]["nblk"]) == ("tap_packed", 1)


def test_legacy_cache_entry_runs_historical_kernel(tmp_cache, monkeypatch):
    """An entry written before the alg/nblk axes existed (no such fields)
    dispatches the historical kernel: tap_loop, unfolded."""
    p = tune.ConvProblem(N=2, C=8, K=8, S=3, dilation=1, Q=128,
                         dtype="float32", padding="SAME")
    tune.get_default_cache().put(
        p.key(tune.device_kind()),
        {"backend": "pallas", "wblk": 128, "kblk": 8, "source": "measured"})
    fwd_calls = _spy(monkeypatch, "conv1d_fwd")
    x = jnp.ones((p.N, p.C, p.Q), jnp.float32)
    w = 0.1 * jnp.ones((p.S, p.K, p.C), jnp.float32)
    ops.conv1d(x, w, dilation=p.dilation, padding=p.padding, backend="auto")
    assert (fwd_calls[0]["alg"], fwd_calls[0]["nblk"]) == ("tap_loop", 1)


def test_nblk_not_dividing_batch_sanitizes_to_one(tmp_cache, monkeypatch):
    """A tuned nblk applied to a different batch at trace time falls back
    to the unfolded kernel instead of tripping the kernel assert."""
    p = tune.ConvProblem(N=3, C=8, K=8, S=3, dilation=1, Q=128,
                         dtype="float32", padding="SAME")
    tune.get_default_cache().put(
        p.key(tune.device_kind()),
        {"backend": "pallas", "wblk": 128, "kblk": 8,
         "alg": "tap_packed", "nblk": 2})   # 2 does not divide N=3
    fwd_calls = _spy(monkeypatch, "conv1d_fwd")
    x = jnp.ones((3, 8, 128), jnp.float32)
    w = 0.1 * jnp.ones((3, 8, 8), jnp.float32)
    y = ops.conv1d(x, w, dilation=1, padding="SAME", backend="auto")
    assert y.shape == (3, 8, 128)
    assert fwd_calls[0]["nblk"] == 1
    assert fwd_calls[0]["alg"] == "tap_packed"


# ---------------------------------------------------------------------------
# Shape rule: the untuned dense pass picks its formulation from its GEMM
# ---------------------------------------------------------------------------

F32, BF16 = jnp.float32, jnp.bfloat16
# (C, K, S, d, dtypes as _kernel_pass sees them: x then the weight)
ATAC = dict(S=51, d=8)
RULE_CASES = [
    # AtacWorks body: fp32 C=K=15, bf16 weights on fp32 activations C=K=16
    ("body-fp32", dict(C=15, K=15, **ATAC, dtypes=(F32, F32)),
     dict(fwd="tap_packed", bwd_data="tap_packed", bwd_weight="tap_packed")),
    ("body-bf16", dict(C=16, K=16, **ATAC, dtypes=(F32, BF16)),
     dict(fwd="tap_packed", bwd_data="tap_packed", bwd_weight="tap_packed")),
    ("stem", dict(C=1, K=15, **ATAC, dtypes=(F32, F32)),
     dict(fwd="tap_packed", bwd_data="tap_packed", bwd_weight="tap_packed")),
    # heads: bwd-weight streams K=1 (padded to 8) rows against 16 packed
    # columns a tap, where the tap loop is as fast (PERF.md §6)
    ("head", dict(C=15, K=1, **ATAC, dtypes=(F32, F32)),
     dict(fwd="tap_packed", bwd_data="tap_packed", bwd_weight="tap_loop")),
    # fat channels already fill an MXU tile: nothing to pack
    ("fat-C128", dict(C=128, K=128, S=5, d=1, dtypes=(F32, F32)),
     dict(fwd="tap_loop", bwd_data="tap_loop", bwd_weight="tap_loop")),
    # one tap: the packed GEMM is the tap loop
    ("S1", dict(C=15, K=15, S=1, d=1, dtypes=(F32, F32)),
     dict(fwd="tap_loop", bwd_data="tap_loop", bwd_weight="tap_loop")),
    # 64 taps of 64 channels: the packed operand alone is over budget
    ("vmem", dict(C=64, K=64, S=64, d=1, dtypes=(F32, F32)),
     dict(fwd="tap_loop", bwd_data="tap_loop", bwd_weight="tap_loop")),
]


@pytest.mark.parametrize("pass_", tune.PASSES)
@pytest.mark.parametrize("case,shape,want", RULE_CASES,
                         ids=[c[0] for c in RULE_CASES])
def test_untuned_pass_picks_alg_from_shape(case, shape, want, pass_):
    alg = ops.pick_alg(pass_, N=64, C=shape["C"], K=shape["K"], S=shape["S"],
                       dilation=shape["d"], Q=60_000, dtypes=shape["dtypes"],
                       wblk=512)
    assert alg == want[pass_]
    if case == "vmem":   # the loop is kept for the budget, not the width
        prob = tune.ConvProblem(N=64, C=64, K=64, S=64, dilation=1,
                                Q=60_000, dtype="float32", pass_=pass_)
        assert prob.contraction < cost.MXU_DIM
        assert space.vmem_footprint_bytes(
            prob, 512, None, "tap_packed") > space.VMEM_BUDGET_BYTES


def _pass_algs(monkeypatch, **conv_kw):
    """(fwd, bwd_data, bwd_weight) formulations jax.grad of one conv
    dispatches to the kernels."""
    fwd_calls = _spy(monkeypatch, "conv1d_fwd")
    bwdw_calls = _spy(monkeypatch, "conv1d_bwd_weight")
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 8, 256)).astype(np.float32))
    w = jnp.asarray(0.1 * rng.standard_normal((3, 16, 8)).astype(np.float32))
    jax.grad(lambda x, w: ops.conv1d(x, w, dilation=2, padding="SAME",
                                     **conv_kw).sum(),
             argnums=(0, 1))(x, w)
    return fwd_calls[0]["alg"], fwd_calls[1]["alg"], bwdw_calls[0]["alg"]


@pytest.mark.parametrize("how,want", [
    ("untuned", ("tap_packed",) * 3),
    ("pinned", ("tap_loop",) * 3),
    ("tuned", ("tap_loop", "tap_packed", "tap_loop")),
])
def test_pinned_and_tuned_alg_win_over_shape_rule(tmp_cache, monkeypatch,
                                                  how, want):
    """The shape rule fills only what nobody chose: an explicit ``alg``
    (and per-pass config) and a tuner cache entry win per pass."""
    if how == "untuned":
        kw = dict(backend="pallas")
    elif how == "pinned":
        cfg = ops.PassConfig("pallas", 128, None, "tap_loop")
        kw = dict(backend="pallas", alg="tap_loop", bwd_data_cfg=cfg,
                  bwd_weight_cfg=cfg)
    else:
        p = tune.ConvProblem(N=2, C=8, K=16, S=3, dilation=2, Q=256,
                             dtype="float32", padding="SAME")
        cache, dk = tune.get_default_cache(), tune.device_kind()
        for pass_, alg in zip(tune.PASSES, want):
            cache.put(p.with_pass(pass_).key(dk),
                      {"backend": "pallas", "wblk": 128, "kblk": None,
                       "alg": alg, "nblk": 1})
        kw = dict(backend="auto")
    assert _pass_algs(monkeypatch, **kw) == want


# ---------------------------------------------------------------------------
# Candidate space + constraint keys
# ---------------------------------------------------------------------------


def _prob(**kw):
    base = dict(N=4, C=15, K=15, S=51, dilation=8, Q=1000, dtype="float32",
                padding="SAME")
    base.update(kw)
    return tune.ConvProblem(**base)


def test_space_has_both_algs_and_legal_folds():
    cands = [c for c in space.enumerate_candidates(_prob())
             if c.backend == "pallas"]
    assert {c.alg for c in cands} == {"tap_loop", "tap_packed"}
    assert all(4 % c.nblk == 0 for c in cands)        # nblk divides N
    assert {c.nblk for c in cands} == {1, 2, 4}
    # every packed/folded candidate fits the VMEM budget it was charged
    for c in cands:
        assert space.vmem_footprint_bytes(
            _prob(), c.wblk, c.kblk, c.alg, c.nblk) <= space.VMEM_BUDGET_BYTES


def test_space_constraints_pin_one_axis():
    cands = [c for c in space.enumerate_candidates(_prob(alg="tap_packed",
                                                        nblk=2))
             if c.backend == "pallas"]
    assert cands and all(c.alg == "tap_packed" and c.nblk == 2
                         for c in cands)


def test_space_s1_and_depthwise_have_no_packed():
    for prob in (_prob(S=1, dilation=1), _prob(C=32, K=32, depthwise=True)):
        cands = [c for c in space.enumerate_candidates(prob)
                 if c.backend == "pallas"]
        assert cands and all(c.alg in (None, "tap_loop") for c in cands), prob


def test_backends_restriction_excludes_library():
    cands = space.enumerate_candidates(_prob(), backends=("pallas",))
    assert cands and all(c.backend == "pallas" for c in cands)


def test_constraint_key_tags_roundtrip(tmp_cache):
    free = _prob()
    pinned = _prob(alg="tap_packed", nblk=2)
    assert free.key("cpu").endswith("|SAME|dense")      # legacy untagged
    assert pinned.key("cpu").endswith("|alg:tap_packed|nblk:2")
    # the tags compose with the pass tag
    assert pinned.with_pass("bwd_data").key("cpu").endswith(
        "|pass:bwd_data|alg:tap_packed|nblk:2")
    cache = tune.TuneCache(tmp_cache)
    cache.put(pinned.key("cpu"), {"backend": "pallas", "wblk": 512,
                                  "alg": "tap_packed", "nblk": 2})
    cache.put(free.key("cpu"), {"backend": "pallas", "wblk": 256})
    reloaded = tune.TuneCache(tmp_cache)
    assert reloaded.get(pinned.key("cpu"))["wblk"] == 512
    assert reloaded.get(free.key("cpu"))["wblk"] == 256   # no collision


def test_invalid_constraints_rejected():
    with pytest.raises(ValueError):
        _prob(alg="img2col")
    with pytest.raises(ValueError):
        _prob(nblk=3)            # does not divide N=4


def test_tune_records_alg_and_nblk(tmp_cache):
    cfg = tune.tune(N=2, C=8, K=8, S=3, dilation=2, Q=128,
                    dtype=jnp.float32, iters=1, warmup=1, top_k=2)
    entry = tune.get_default_cache().get(
        tune.ConvProblem(N=2, C=8, K=8, S=3, dilation=2, Q=128,
                         dtype="float32").key(tune.device_kind()))
    assert "alg" in entry and "nblk" in entry
    hit = tune.get_config(N=2, C=8, K=8, S=3, dilation=2, Q=128,
                          dtype=jnp.float32)
    assert hit.source == "cache"
    assert (hit.alg, hit.nblk) == (cfg.alg, cfg.nblk)


# ---------------------------------------------------------------------------
# Cost model: occupancy ranks the formulations per shape on TPU
# ---------------------------------------------------------------------------


def test_cost_prefers_packed_for_skinny_shapes_on_tpu():
    """The AtacWorks shape (C=K=15, S=51): each tap GEMM occupies ~1% of
    the MXU, packing lifts the contraction to 765 — the model must rank
    tap_packed first on a TPU device kind."""
    prob = _prob(Q=5000)
    cands = [c for c in space.enumerate_candidates(prob)
             if c.backend == "pallas"]
    best = cost.rank(cands, prob, device_kind="TPU v5 lite")[0]
    assert best.alg == "tap_packed"


def test_cost_keeps_tap_loop_for_fat_shapes_on_tpu():
    """C=K=256: the tap GEMM already fills the MXU — the packed copy
    buys nothing, the model must keep the copy-free tap loop."""
    prob = _prob(C=256, K=256, S=5, dilation=1, Q=5000)
    cands = [c for c in space.enumerate_candidates(prob)
             if c.backend == "pallas"]
    best = cost.rank(cands, prob, device_kind="TPU v5 lite")[0]
    assert best.alg == "tap_loop"


def test_mxu_occupancy_matches_issue_arithmetic():
    # (15, 15)×(15, WBLK): ~1.4% of the 128×128 MXU, the paper's pain
    occ_loop = cost.mxu_occupancy(15, 15, 512)
    occ_packed = cost.mxu_occupancy(15, 51 * 15, 512)
    assert occ_loop == pytest.approx((15 / 128) ** 2)
    assert occ_packed == pytest.approx(15 / 128)        # contraction full
    assert occ_packed / occ_loop == pytest.approx(128 / 15)


# ---------------------------------------------------------------------------
# Depthwise default-cblk fix (satellite)
# ---------------------------------------------------------------------------


def test_default_cblk_is_largest_divisor():
    assert _kmod.default_cblk(512) == 512
    assert _kmod.default_cblk(768) == 384    # min(C, 512) would assert
    assert _kmod.default_cblk(1024) == 512
    assert _kmod.default_cblk(7) == 7
    assert _kmod.default_cblk(1021) == 1     # prime > cap
    for C in (768, 1021):
        assert C % _kmod.default_cblk(C) == 0


def test_depthwise_c768_runs_with_default_cblk():
    """C=768 used to trip ``C % cblk == 0`` (cblk defaulted to 512)."""
    rng = np.random.default_rng(11)
    N, C, S, d, Q = 1, 768, 4, 1, 128
    x = jnp.asarray(rng.standard_normal((N, C, Q)).astype(np.float32))
    w = jnp.asarray(0.2 * rng.standard_normal((S, C)).astype(np.float32))
    got = ops.depthwise_conv1d(x, w, dilation=d, padding="CAUSAL",
                               backend="pallas", interpret=True)
    want = ops.depthwise_conv1d(x, w, dilation=d, padding="CAUSAL",
                                backend="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # the gradient path shares the same default
    gw = jax.grad(lambda w: ops.depthwise_conv1d(
        x, w, dilation=d, padding="CAUSAL", backend="pallas",
        interpret=True).sum())(w)
    gw_ref = jax.grad(lambda w: ops.depthwise_conv1d(
        x, w, dilation=d, padding="CAUSAL", backend="ref").sum())(w)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                               rtol=2e-4, atol=2e-4)


def test_space_depthwise_c768_candidates_legal():
    prob = tune.ConvProblem(N=1, C=768, K=768, S=4, dilation=1, Q=1024,
                            dtype="float32", padding="CAUSAL",
                            depthwise=True)
    pallas = [c for c in space.enumerate_candidates(prob)
              if c.backend == "pallas"]
    assert pallas and all(768 % c.kblk == 0 for c in pallas)
