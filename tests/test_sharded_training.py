"""Data-parallel (shard_map) conv1d training — DESIGN.md §13.

Two tiers:

  * in-process tests on the 1-device host mesh: the sharded wrappers'
    contract (shapes, error cases, gradient parity with the plain ops —
    the psum machinery runs, over an axis of size 1);
  * ONE subprocess on 8 virtual CPU devices
    (``--xla_force_host_platform_device_count=8``) running the real
    multi-shard checks: sharded-vs-single-device gradient equivalence for
    dense + depthwise × fp32/bf16, tuned-vs-default gradient equivalence
    under shard_map (per-shard plans resolved from a pre-populated
    cache), the local-N cache-key regression (per-shard lookups must key
    on N/dp, never global N), and one-step train equivalence of
    ``make_train_step(mesh=...)`` on the AtacWorks smoke config.

The subprocess pattern mirrors test_dryrun_machinery.py: XLA_FLAGS must
be set before jax initialises, and the tier-1 process must keep seeing
1 device.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.sharded import sharded_conv1d, sharded_depthwise_conv1d
from repro.launch.mesh import dp_axis_names, make_host_mesh


# ---------------------------------------------------------------------------
# In-process: wrapper contract on the host mesh (1 device)
# ---------------------------------------------------------------------------


def _operands(seed=0, N=4, C=8, K=4, S=3, W=64):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((N, C, W)), jnp.float32)
    w = jnp.asarray(0.1 * rng.standard_normal((S, K, C)), jnp.float32)
    b = jnp.asarray(0.1 * rng.standard_normal((K,)), jnp.float32)
    return x, w, b


@pytest.mark.parametrize("backend", ["xla", "pallas", "ref"])
def test_sharded_conv1d_matches_plain(backend):
    mesh = make_host_mesh()
    x, w, b = _operands()
    ys = sharded_conv1d(x, w, mesh=mesh, bias=b, activation="relu",
                        dilation=2, padding="SAME", backend=backend)
    y1 = ops.conv1d(x, w, bias=b, activation="relu", dilation=2,
                    padding="SAME", backend=backend)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(y1),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_sharded_conv1d_grads_match_plain(backend):
    mesh = make_host_mesh()
    x, w, b = _operands()

    def loss(w, b, fn, **kw):
        return (fn(x, w, bias=b, activation="relu", dilation=2,
                   padding="SAME", backend=backend, **kw) ** 2).sum()

    gs = jax.grad(lambda w, b: loss(w, b, sharded_conv1d, mesh=mesh),
                  argnums=(0, 1))(w, b)
    g1 = jax.grad(lambda w, b: loss(w, b, ops.conv1d), argnums=(0, 1))(w, b)
    for a, c in zip(gs, g1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-4, atol=1e-4)


def test_sharded_depthwise_matches_plain():
    mesh = make_host_mesh()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4, 8, 64)), jnp.float32)
    w = jnp.asarray(0.1 * rng.standard_normal((4, 8)), jnp.float32)
    ys = sharded_depthwise_conv1d(x, w, mesh=mesh, activation="silu",
                                  backend="pallas")
    y1 = ops.depthwise_conv1d(x, w, activation="silu", backend="pallas")
    np.testing.assert_allclose(np.asarray(ys), np.asarray(y1),
                               rtol=1e-5, atol=1e-5)


def test_sharded_rejects_meshes_without_data_axis():
    devs = np.array(jax.devices()[:1])
    mesh = jax.sharding.Mesh(devs, ("model",))
    x, w, _ = _operands()
    with pytest.raises(ValueError, match="no data axis"):
        sharded_conv1d(x, w, mesh=mesh)


def test_grad_reduce_axes_in_body_matches_plain():
    """The train path's shape: value_and_grad INSIDE a shard_map body with
    grad_reduce_axes threaded — the fused psum is then the only reduction
    (on a 1-axis mesh of size 1 it must be an exact no-op)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_host_mesh()
    axes = dp_axis_names(mesh)
    x, w, b = _operands()

    def local(x, w, b):
        def loss(wb):
            w_, b_ = wb
            y = ops.conv1d(x, w_, bias=b_, activation="relu", dilation=2,
                           padding="SAME", backend="pallas",
                           grad_reduce_axes=axes)
            return (y ** 2).sum()
        return jax.grad(loss)((w, b))

    sm = shard_map(local, mesh=mesh, in_specs=(P(axes), P(), P()),
                   out_specs=(P(), P()), check_vma=False)
    gs = sm(x, w, b)
    g1 = jax.grad(lambda wb: (ops.conv1d(
        x, wb[0], bias=wb[1], activation="relu", dilation=2, padding="SAME",
        backend="pallas") ** 2).sum())((w, b))
    for a, c in zip(gs, g1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-5, atol=1e-5)


def test_localized_problem_keys_use_local_batch():
    from repro.tune import ConvProblem

    prob = ConvProblem(N=8, C=8, K=8, S=3, dilation=2, Q=128,
                       dtype="float32")
    local = prob.localized(4)
    assert local.N == 2
    assert "|N2|" in local.key("cpu")
    with pytest.raises(ValueError, match="divide"):
        prob.localized(3)
    # an nblk constraint must stay legal at the LOCAL batch
    with pytest.raises(ValueError):
        ConvProblem(N=8, C=8, K=8, S=3, dilation=2, Q=128,
                    dtype="float32", nblk=4).localized(4)


# ---------------------------------------------------------------------------
# Subprocess: the real 8-shard checks
# ---------------------------------------------------------------------------

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["REPRO_TUNE_CACHE"] = %(cache)r
os.environ.pop("REPRO_TUNE", None)
import json
import jax, jax.numpy as jnp
import numpy as np
from repro import tune
from repro.kernels import ops
from repro.kernels.sharded import sharded_conv1d, sharded_depthwise_conv1d
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh()
out = {"n_devices": len(jax.devices())}

def maxdiff(a, b):
    # relative to the reference magnitude: bf16 grads are exact up to ulp
    # rounding of differently-ordered sums
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-6))

N, C, K, S, d, W = 8, 8, 8, 5, 2, 256
rng = np.random.default_rng(0)

# --- sharded vs single-device grads, dense + depthwise x fp32/bf16 --------
for dtype_name, dtype in [("float32", jnp.float32), ("bfloat16", jnp.bfloat16)]:
    x = jnp.asarray(rng.standard_normal((N, C, W)).astype(np.float32), dtype)
    w = jnp.asarray(0.1 * rng.standard_normal((S, K, C)).astype(np.float32), dtype)
    b = jnp.asarray(0.1 * rng.standard_normal(K).astype(np.float32), dtype)

    def loss(wb, fn, **kw):
        y = fn(x, wb[0], bias=wb[1], activation="relu", dilation=d,
               padding="SAME", backend="pallas", **kw)
        return (y.astype(jnp.float32) ** 2).sum()

    gs = jax.grad(lambda wb: loss(wb, sharded_conv1d, mesh=mesh))((w, b))
    g1 = jax.grad(lambda wb: loss(wb, ops.conv1d))((w, b))
    out[f"dense_{dtype_name}"] = [maxdiff(a, c) for a, c in zip(gs, g1)]

    wd = jnp.asarray(0.1 * rng.standard_normal((S, C)).astype(np.float32), dtype)
    bd = jnp.asarray(0.1 * rng.standard_normal(C).astype(np.float32), dtype)

    def dloss(wb, fn, **kw):
        y = fn(x, wb[0], bias=wb[1], activation="silu", backend="pallas", **kw)
        return (y.astype(jnp.float32) ** 2).sum()

    gs = jax.grad(lambda wb: dloss(wb, sharded_depthwise_conv1d, mesh=mesh))((wd, bd))
    g1 = jax.grad(lambda wb: dloss(wb, ops.depthwise_conv1d))((wd, bd))
    out[f"dw_{dtype_name}"] = [maxdiff(a, c) for a, c in zip(gs, g1)]

# --- per-shard tuner plans resolve from LOCAL-N keys ----------------------
# pre-populate the cache for the LOCAL problem (N/8) only; spy get_config
local_prob = tune.ConvProblem(N=N, C=C, K=K, S=S, dilation=d, Q=W,
                              dtype="float32", padding="SAME",
                              epilogue="b+relu").localized(8)
cache = tune.get_default_cache()
for p in tune.PASSES:
    q = local_prob.with_pass(p)
    cache.put(q.key(tune.device_kind()),
              {"backend": "pallas", "wblk": 128,
               "kblk": 8 if q.blk2_dim else None})

seen_N, seen_sources = [], []
orig = tune.get_config_for
def spy(prob, **kw):
    cfg = orig(prob, **kw)
    seen_N.append(prob.N)
    seen_sources.append(cfg.source)
    return cfg
tune.get_config_for = spy

xf = jnp.asarray(rng.standard_normal((N, C, W)).astype(np.float32))
wf = jnp.asarray(0.1 * rng.standard_normal((S, K, C)).astype(np.float32))
bf = jnp.asarray(0.1 * rng.standard_normal(K).astype(np.float32))

def auto_loss(wb):
    y = sharded_conv1d(xf, wb[0], mesh=mesh, bias=wb[1], activation="relu",
                       dilation=d, padding="SAME", backend="auto")
    return (y ** 2).sum()

g_auto = jax.grad(auto_loss)((wf, bf))
tune.get_config_for = orig
out["auto_seen_N"] = sorted(set(seen_N))
out["auto_sources"] = sorted(set(seen_sources))

g_def = jax.grad(lambda wb: (ops.conv1d(
    xf, wb[0], bias=wb[1], activation="relu", dilation=d, padding="SAME",
    backend="pallas") ** 2).sum())((wf, bf))
out["tuned_vs_default"] = [maxdiff(a, c) for a, c in zip(g_auto, g_def)]

# --- e2e: make_train_step(mesh=...) one-step equivalence ------------------
from repro import configs
from repro.data.synthetic import make_batch
from repro.models import get_model
from repro.train.train_step import init_state, make_train_step

cfg = configs.get("atacworks")
model = get_model(cfg)
params = model.init_params(jax.random.key(0), cfg)
batch = make_batch(cfg, 8, 512, seed=0)
s1, m1 = jax.jit(make_train_step(cfg, total_steps=10))(init_state(params), batch)
ss, ms = jax.jit(make_train_step(cfg, total_steps=10, mesh=mesh))(
    init_state(params), batch)
out["e2e_loss"] = [float(m1["loss"]), float(ms["loss"])]
out["e2e_param_diff"] = max(jax.tree.leaves(jax.tree.map(maxdiff,
                                                         s1.params, ss.params)))
print("JSON:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def shard8(tmp_path_factory):
    cache = str(tmp_path_factory.mktemp("tune") / "cache.json")
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD % {"cache": cache}],
        env=env, capture_output=True, text=True, timeout=560,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(l for l in proc.stdout.splitlines() if l.startswith("JSON:"))
    return json.loads(line[5:])


def test_8dev_grad_equivalence(shard8):
    assert shard8["n_devices"] == 8
    for key, tol in [("dense_float32", 1e-5), ("dw_float32", 1e-5),
                     ("dense_bfloat16", 3e-2), ("dw_bfloat16", 3e-2)]:
        assert max(shard8[key]) < tol, (key, shard8[key])


def test_8dev_local_shape_tuner_keys(shard8):
    """Every per-shard backend='auto' resolution keyed on the LOCAL batch
    (N/8 = 1) — a global-N key leaking into a shard lookup would change
    the legal candidate space — and hit the pre-populated local cache."""
    assert shard8["auto_seen_N"] == [1]
    assert shard8["auto_sources"] == ["cache"]
    assert max(shard8["tuned_vs_default"]) < 1e-4


def test_8dev_train_step_equivalence(shard8):
    l1, ls = shard8["e2e_loss"]
    assert abs(l1 - ls) < 1e-3 * max(1.0, abs(l1))
    assert shard8["e2e_param_diff"] < 1e-5
