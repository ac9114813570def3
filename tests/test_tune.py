"""Tuning subsystem (repro.tune): cache round-trip without re-measurement,
backend='auto' numerical equivalence vs the readable oracle, cost-model
sanity, and the scripts/tune.py cache pre-population contract."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.kernels import ops, ref
from repro.tune import cost, measure, space

jax.config.update("jax_enable_x64", False)

TINY = dict(N=1, C=4, K=8, S=3, dilation=2, Q=128)


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    """Point the default cache at a fresh file for the duration of a test."""
    path = str(tmp_path / "tune_cache.json")
    monkeypatch.setenv(tune.cache.ENV_CACHE_PATH, path)
    tune.reset_default_cache()
    yield path
    tune.reset_default_cache()


def _no_measure(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("time_candidate ran — a cached/miss path re-measured")
    monkeypatch.setattr(measure, "time_candidate", boom)


# ---------------------------------------------------------------------------
# Cache round-trip
# ---------------------------------------------------------------------------


def test_cache_roundtrip_hit_without_remeasure(tmp_cache, monkeypatch):
    cfg = tune.tune(**TINY, dtype=jnp.float32, iters=1, warmup=1, top_k=2)
    assert cfg.source == "measured" and cfg.sec is not None
    assert os.path.exists(tmp_cache)

    # fresh cache object over the same file (a new process would see this)
    reloaded = tune.TuneCache(tmp_cache)
    _no_measure(monkeypatch)  # any measurement from here on is a failure
    monkeypatch.setenv(tune.ENV_TUNE, "1")  # even with tuning enabled
    hit = tune.get_config(**TINY, dtype=jnp.float32, cache=reloaded)
    assert hit.source == "cache"
    assert (hit.backend, hit.wblk, hit.kblk) == (cfg.backend, cfg.wblk, cfg.kblk)


def test_cache_miss_falls_back_to_ladder_without_measuring(tmp_cache, monkeypatch):
    monkeypatch.delenv(tune.ENV_TUNE, raising=False)
    _no_measure(monkeypatch)
    cfg = tune.get_config(**TINY, dtype=jnp.float32)
    assert cfg.source == "default"
    assert cfg.wblk == ops.pick_wblk(TINY["Q"], TINY["S"], TINY["dilation"])
    assert len(tune.get_default_cache()) == 0  # miss must not pollute the cache


def test_cache_atomic_write_and_mtime_reload(tmp_cache):
    c1 = tune.TuneCache(tmp_cache)
    c1.put("k1", {"backend": "xla"})
    c2 = tune.TuneCache(tmp_cache)
    assert c2.get("k1") == {"backend": "xla"}
    c2.put("k2", {"backend": "pallas", "wblk": 128})
    assert set(json.load(open(tmp_cache))) == {"k1", "k2"}


# ---------------------------------------------------------------------------
# backend='auto' numerical equivalence vs the oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_auto_matches_ref_from_cached_entry(tmp_cache, monkeypatch, dtype):
    """A populated cache entry drives backend='auto' (no measurement) and
    the result is allclose to the readable oracle."""
    N, C, K, S, d, Q = 2, 8, 16, 5, 2, 200
    key = tune.cache_key(device_kind=tune.device_kind(),
                         dtype=str(jnp.dtype(dtype)), N=N, C=C, K=K, S=S,
                         dilation=d, Q=Q, padding="SAME", depthwise=False)
    tune.get_default_cache().put(
        key, {"backend": "pallas", "wblk": 128, "kblk": 8, "source": "measured"})
    _no_measure(monkeypatch)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((N, C, Q)).astype(np.float32), dtype)
    w = jnp.asarray(0.1 * rng.standard_normal((S, K, C)).astype(np.float32), dtype)
    got = ops.conv1d(x, w, dilation=d, padding="SAME", backend="auto")
    want = ops.conv1d(x, w, dilation=d, padding="SAME", backend="ref")
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_auto_env_var_spelling(tmp_cache, monkeypatch):
    """REPRO_CONV_BACKEND=auto routes through the tuner like backend='auto'."""
    monkeypatch.setenv("REPRO_CONV_BACKEND", "auto")
    monkeypatch.delenv(tune.ENV_TUNE, raising=False)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, 4, 96)).astype(np.float32))
    w = jnp.asarray(0.1 * rng.standard_normal((3, 4, 4)).astype(np.float32))
    got = ops.conv1d(x, w, dilation=2, padding="CAUSAL")
    want = ops.conv1d(x, w, dilation=2, padding="CAUSAL", backend="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_auto_depthwise_matches_ref(tmp_cache, monkeypatch):
    N, C, S, d, Q = 1, 16, 4, 1, 160
    key = tune.cache_key(device_kind=tune.device_kind(), dtype="float32",
                         N=N, C=C, K=C, S=S, dilation=d, Q=Q,
                         padding="CAUSAL", depthwise=True)
    tune.get_default_cache().put(
        key, {"backend": "pallas", "wblk": 128, "kblk": 16, "source": "measured"})
    _no_measure(monkeypatch)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((N, C, Q)).astype(np.float32))
    w = jnp.asarray(0.2 * rng.standard_normal((S, C)).astype(np.float32))
    got = ops.depthwise_conv1d(x, w, dilation=d, padding="CAUSAL", backend="auto")
    want = ops.depthwise_conv1d(x, w, dilation=d, padding="CAUSAL", backend="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Space + cost model sanity
# ---------------------------------------------------------------------------


def _prob(**kw):
    base = dict(N=4, dtype="float32", padding="VALID")
    base.update(kw)
    return tune.ConvProblem(**base)


def test_space_legality():
    prob = _prob(C=15, K=15, S=5, dilation=8, Q=5000)
    cands = space.enumerate_candidates(prob)
    assert any(c.backend == "xla" for c in cands)
    for c in cands:
        if c.backend != "pallas":
            continue
        assert c.wblk % space.LANE == 0
        assert 15 % c.kblk == 0
        assert space.vmem_footprint_bytes(
            prob, c.wblk, c.kblk) <= space.VMEM_BUDGET_BYTES


def test_cost_model_wblk_never_shrinks_with_q():
    """Under the TPU device model (where the Pallas tiles actually run), a
    larger Q never prefers a smaller legal wblk than a smaller Q did, and
    the choice is never below the static pick_wblk ladder.

    Pinned to the historical kernel (tap_loop, unfolded, synchronous):
    the ladder invariant is a property of the pure tile axis.  The other
    axes legitimately trade tile size away — a batch fold reaches the
    same GEMM width with a smaller tile and fewer weight restages
    (DESIGN.md §12), and a pipelined candidate may prefer a smaller tile
    to have a second tile to overlap with (§15)."""
    for C, K, S, d in ((15, 15, 5, 8), (64, 64, 25, 1), (32, 32, 51, 4)):
        prev = 0
        for Q in (128, 256, 512, 1000, 5000, 20000, 60000):
            prob = _prob(C=C, K=K, S=S, dilation=d, Q=Q,
                         alg="tap_loop", nblk=1, pipe=0)
            cands = [c for c in space.enumerate_candidates(prob)
                     if c.backend == "pallas"]
            best = cost.rank(cands, prob, device_kind="TPU v5 lite")[0]
            assert best.wblk >= prev, (C, K, S, d, Q, best)
            assert best.wblk >= ops.pick_wblk(Q, S, d), (C, K, S, d, Q, best)
            prev = best.wblk


def test_cost_model_never_picks_interpret_pallas_on_cpu():
    for Q in (128, 5000, 60000):
        for pass_ in tune.PASSES:
            prob = _prob(C=64, K=64, S=25, dilation=1, Q=Q, pass_=pass_)
            best = cost.rank(space.enumerate_candidates(prob), prob,
                             device_kind="cpu")[0]
            assert best.backend == "xla", (Q, pass_)


# ---------------------------------------------------------------------------
# scripts/tune.py pre-population contract
# ---------------------------------------------------------------------------


def test_tune_script_covers_fig4(tmp_cache):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "tune_script", os.path.join(os.path.dirname(__file__), "..",
                                    "scripts", "tune.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.main(["--figset", "fig4", "--cache", tmp_cache])

    entries = json.load(open(tmp_cache))
    shapes = list(tune.presets.figset_shapes("fig4"))
    assert len(shapes) == 9
    for prob in shapes:
        key = tune.cache_key(device_kind=tune.device_kind(),
                             dtype=prob["dtype"], N=prob["N"], C=prob["C"],
                             K=prob["K"], S=prob["S"], dilation=prob["dilation"],
                             Q=prob["Q"], padding=prob["padding"],
                             depthwise=False)
        assert key in entries, key
        assert entries[key]["backend"] in ("pallas", "xla")
