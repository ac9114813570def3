"""Model-axis (tensor-parallel) conv1d — DESIGN.md §17.

Two tiers, mirroring test_sharded_training.py:

  * in-process tests on 1 device: the model-sharded wrappers' contract
    (parity with the plain ops over a size-1 model axis, depthwise
    ``model_reduce_axes`` rejection, local-K/local-C tuner problem keys,
    the preset generator, launcher device-divisibility validation);
  * ONE subprocess on 8 virtual CPU devices running the real
    multi-shard checks: K-sharded forward/grad equivalence vs single
    device (fp32 **bitwise** on the pallas path — K-sharding only
    selects filter rows, per-row tap order is preserved; documented
    tolerances for xla, whose contraction order may differ, and for the
    dx model psum, a genuine re-ordering of the K contraction),
    chunked-vs-single model-psum bitwise equivalence, local-K cache-key
    resolution under ``backend='auto'``, the launcher/grad-fn
    channel-divisibility errors (AtacWorks C=15 cannot split over
    mp=2), and one-step ``make_train_step`` parity on a (4, 2) mesh —
    including a ``model_reduce_chunks`` arm — with the ``train.mesh`` /
    ``conv.psum.model`` telemetry records checked from the same run.
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
from repro.kernels.sharded import (model_sharded_conv1d,
                                   model_sharded_depthwise_conv1d)
from repro.launch.mesh import make_host_mesh


# ---------------------------------------------------------------------------
# In-process: wrapper contract over a size-1 model axis (1 device)
# ---------------------------------------------------------------------------


def _operands(seed=0, N=4, C=8, K=8, S=3, W=64):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((N, C, W)), jnp.float32)
    w = jnp.asarray(0.1 * rng.standard_normal((S, K, C)), jnp.float32)
    b = jnp.asarray(0.1 * rng.standard_normal((K,)), jnp.float32)
    return x, w, b


@pytest.mark.parametrize("backend", ["xla", "pallas", "ref"])
def test_model_sharded_conv1d_matches_plain(backend):
    mesh = make_host_mesh(model=1)
    x, w, b = _operands()
    ys = model_sharded_conv1d(x, w, mesh=mesh, bias=b, activation="relu",
                              dilation=2, padding="SAME", backend=backend)
    y1 = ops.conv1d(x, w, bias=b, activation="relu", dilation=2,
                    padding="SAME", backend=backend)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(y1),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_model_sharded_grads_match_plain(backend):
    """Grads THROUGH the wrapper: shard_map's transpose supplies the dx
    model-psum and the dw/dbias data-psums (size-1 axes here — exact)."""
    mesh = make_host_mesh(model=1)
    x, w, b = _operands()

    def loss(xwb, fn, **kw):
        y = fn(xwb[0], xwb[1], bias=xwb[2], activation="relu", dilation=2,
               padding="SAME", backend=backend, **kw)
        return (y ** 2).sum()

    gs = jax.grad(lambda a: loss(a, model_sharded_conv1d, mesh=mesh))(
        (x, w, b))
    g1 = jax.grad(lambda a: loss(a, ops.conv1d))((x, w, b))
    for a, c in zip(gs, g1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-4, atol=1e-4)


def test_model_sharded_depthwise_matches_plain():
    mesh = make_host_mesh(model=1)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4, 8, 64)), jnp.float32)
    w = jnp.asarray(0.1 * rng.standard_normal((4, 8)), jnp.float32)
    ys = model_sharded_depthwise_conv1d(x, w, mesh=mesh, activation="silu",
                                        backend="pallas")
    y1 = ops.depthwise_conv1d(x, w, activation="silu", backend="pallas")
    np.testing.assert_allclose(np.asarray(ys), np.asarray(y1),
                               rtol=1e-5, atol=1e-5)


def test_model_sharded_rejects_meshes_without_model_axis():
    devs = np.array(jax.devices()[:1])
    mesh = jax.sharding.Mesh(devs, ("data",))
    x, w, _ = _operands()
    with pytest.raises(ValueError, match="no 'model' axis"):
        model_sharded_conv1d(x, w, mesh=mesh)


def test_depthwise_model_reduce_axes_rejected():
    """Channel-group sharding has no model-axis contraction: every output
    channel reads only its own input channel, so asking for a dx model
    psum is a spec error, not a silent no-op."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 8, 32)), jnp.float32)
    w = jnp.asarray(0.1 * rng.standard_normal((3, 8)), jnp.float32)
    with pytest.raises(ValueError, match="no model-axis contraction"):
        ops.depthwise_conv1d(x, w, model_reduce_axes=("model",))


def test_localized_problem_keys_use_local_filters():
    from repro.tune import ConvProblem

    prob = ConvProblem(N=8, C=8, K=8, S=3, dilation=2, Q=128,
                       dtype="float32")
    local = prob.localized(model_shards=2)
    assert (local.N, local.C, local.K) == (8, 8, 4)  # dense: C stays full
    assert "|K4|" in local.key("cpu")
    both = prob.localized(4, model_shards=2)  # composes with data shards
    assert (both.N, both.K) == (2, 4)
    with pytest.raises(ValueError, match="filters"):
        ConvProblem(N=8, C=15, K=15, S=3, dilation=2, Q=128,
                    dtype="float32").localized(model_shards=2)
    with pytest.raises(ValueError, match="model_shards"):
        prob.localized(model_shards=0)
    # depthwise channel groups split C (and the K == C that rides with it)
    dw = ConvProblem(N=8, C=8, K=8, S=3, dilation=2, Q=128,
                     dtype="float32", depthwise=True).localized(model_shards=4)
    assert (dw.C, dw.K) == (2, 2)
    with pytest.raises(ValueError, match="channel groups"):
        ConvProblem(N=8, C=6, K=6, S=3, dilation=2, Q=128, dtype="float32",
                    depthwise=True).localized(model_shards=4)


def test_model_sharded_preset_views():
    from repro.tune.presets import model_sharded_shapes

    cells = [dict(N=4, C=8, K=8, S=3, dilation=2, Q=128),
             dict(N=4, C=15, K=15, S=51, dilation=8, Q=1000)]
    views = list(model_sharded_shapes(cells, 2))
    # divisible cell -> both views at local shapes; C=K=15 -> neither
    assert [(v, p["C"], p["K"]) for v, p in views] == [
        ("local-K", 8, 4), ("local-C", 4, 8)]


def test_launcher_rejects_indivisible_device_count():
    """Regression: validation must cover the device grid, not just the
    batch — 1 host device cannot form (data, model) rows of width 3."""
    from repro.launch import train as launch_train

    with pytest.raises(SystemExit, match="does not divide the"):
        launch_train.main(["--arch", "atacworks", "--smoke",
                           "--model-parallel", "3"])


def test_tune_entrypoints_thread_model_shards(tmp_path):
    from repro import tune

    cache = tune.TuneCache(str(tmp_path / "cache.json"))
    cfg = tune.tune(N=4, C=8, K=8, S=3, dilation=2, Q=128, dtype="float32",
                    model_shards=2, cache=cache, measure=False)
    assert cfg.backend in ("pallas", "xla")
    assert any("|K4|" in k for k in cache.keys())
    plan = tune.get_plan(N=4, C=8, K=8, S=3, dilation=2, Q=128,
                         dtype="float32", model_shards=2, cache=cache)
    assert sorted(plan) == ["bwd_data", "bwd_weight", "fwd"]


# ---------------------------------------------------------------------------
# Subprocess: the real multi-shard checks (8 virtual devices)
# ---------------------------------------------------------------------------

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["REPRO_TUNE_CACHE"] = %(cache)r
os.environ.pop("REPRO_TUNE", None)
import json
import jax, jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro import tune
from repro.kernels import ops
from repro.kernels.sharded import (model_sharded_conv1d,
                                   model_sharded_depthwise_conv1d)
from repro.launch.mesh import make_grid_mesh

out = {"n_devices": len(jax.devices())}

def maxdiff(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-6))

def bitdiff(a, b):
    return float(np.abs(np.asarray(a, np.float32)
                        - np.asarray(b, np.float32)).max())

N, C, K, S, d, W = 8, 8, 8, 5, 2, 256
rng = np.random.default_rng(0)
mesh12 = make_grid_mesh(1, 2)  # dp=1: every data-axis psum is an identity

# --- K-sharded fwd + grads vs single device ------------------------------
# dense x {fused, plain} x {tap_loop, tap_packed} x {fp32, bf16}; fp32
# pallas is BITWISE (K-sharding selects filter rows, per-row tap order is
# unchanged); dx tolerances are real summation-order changes (the K
# contraction splits in two and psums)
for dtype_name, dtype in [("float32", jnp.float32), ("bfloat16", jnp.bfloat16)]:
    x = jnp.asarray(rng.standard_normal((N, C, W)).astype(np.float32), dtype)
    w = jnp.asarray(0.1 * rng.standard_normal((S, K, C)).astype(np.float32), dtype)
    b = jnp.asarray(0.1 * rng.standard_normal(K).astype(np.float32), dtype)
    for fused in (True, False):
        for alg in ("tap_loop", "tap_packed"):
            kw = dict(dilation=d, padding="SAME", backend="pallas", alg=alg)
            fkw = dict(kw, bias=b, activation="relu") if fused else kw
            tag = f"{dtype_name}_{'fused' if fused else 'plain'}_{alg}"
            ys = model_sharded_conv1d(x, w, mesh=mesh12, **fkw)
            y1 = ops.conv1d(x, w, **fkw)
            out[f"fwd_{tag}"] = bitdiff(ys, y1) if dtype == jnp.float32 \
                else maxdiff(ys, y1)

            def loss(a, fn, **k):
                fk = dict(kw, **k)
                if fused:
                    fk.update(bias=a[2], activation="relu")
                return (fn(a[0], a[1], **fk).astype(jnp.float32) ** 2).sum()
            gs = jax.grad(lambda a: loss(a, model_sharded_conv1d,
                                         mesh=mesh12))((x, w, b))
            g1 = jax.grad(lambda a: loss(a, ops.conv1d))((x, w, b))
            if dtype == jnp.float32:
                # dw/db: local per K-slice, data psum over dp=1 -> bitwise
                out[f"dw_{tag}"] = bitdiff(gs[1], g1[1])
                if fused:
                    out[f"db_{tag}"] = bitdiff(gs[2], g1[2])
                out[f"dx_{tag}"] = maxdiff(gs[0], g1[0])
            else:
                out[f"grad_{tag}"] = max(maxdiff(a, c)
                                         for a, c in zip(gs, g1))

# xla backend: contraction order is XLA's choice -> documented tolerance
xf = jnp.asarray(rng.standard_normal((N, C, W)).astype(np.float32))
wf = jnp.asarray(0.1 * rng.standard_normal((S, K, C)).astype(np.float32))
out["fwd_xla"] = maxdiff(
    model_sharded_conv1d(xf, wf, mesh=mesh12, dilation=d, padding="SAME",
                         backend="xla"),
    ops.conv1d(xf, wf, dilation=d, padding="SAME", backend="xla"))

# depthwise channel groups: no model collective on any pass -> bitwise
wd = jnp.asarray(0.1 * rng.standard_normal((S, C)).astype(np.float32))
bd = jnp.asarray(0.1 * rng.standard_normal(C).astype(np.float32))
def dwloss(a, fn, **k):
    return (fn(a[0], a[1], bias=a[2], activation="silu", dilation=d,
               backend="pallas", **k).astype(jnp.float32) ** 2).sum()
out["dw_fwd"] = bitdiff(
    model_sharded_depthwise_conv1d(xf, wd, mesh=mesh12, bias=bd,
                                   activation="silu", dilation=d,
                                   backend="pallas"),
    ops.depthwise_conv1d(xf, wd, bias=bd, activation="silu", dilation=d,
                         backend="pallas"))
gs = jax.grad(lambda a: dwloss(a, model_sharded_depthwise_conv1d,
                               mesh=mesh12))((xf, wd, bd))
g1 = jax.grad(lambda a: dwloss(a, ops.depthwise_conv1d))((xf, wd, bd))
out["dw_grads"] = max(bitdiff(a, c) for a, c in zip(gs, g1))

# --- chunked vs single bwd-data model psum: BITWISE ----------------------
# grads-inside spelling (the training path): w K-sharded in the body, dx
# finished by the in-VJP model psum; chunk boundaries are tile-aligned
# and columns disjoint, so 4-chunk and 1-chunk reductions are identical
def dx_psum(chunks):
    def local(x, w):
        def loss(xl):
            y = ops.conv1d(xl, w, dilation=d, padding="SAME",
                           backend="pallas", wblk=64,
                           model_reduce_axes=("model",),
                           model_reduce_chunks=chunks)
            return (y.astype(jnp.float32) ** 2).sum()
        return jax.grad(loss)(x)
    return shard_map(local, mesh=mesh12,
                     in_specs=(P(), P(None, "model", None)),
                     out_specs=P(), check_vma=False)(xf, wf)
out["chunked_vs_single_psum"] = bitdiff(dx_psum(4), dx_psum(1))

# --- per-shard tuner plans resolve from LOCAL-K keys ---------------------
local_prob = tune.ConvProblem(N=N, C=C, K=K, S=S, dilation=d, Q=W,
                              dtype="float32", padding="SAME",
                              epilogue="b+relu").localized(model_shards=2)
cache = tune.get_default_cache()
for p in tune.PASSES:
    q = local_prob.with_pass(p)
    cache.put(q.key(tune.device_kind()),
              {"backend": "pallas", "wblk": 128,
               "kblk": 4 if q.blk2_dim else None})
seen_K, seen_sources = [], []
orig = tune.get_config_for
def spy(prob, **kw):
    cfg = orig(prob, **kw)
    seen_K.append(prob.K)
    seen_sources.append(cfg.source)
    return cfg
tune.get_config_for = spy
bf = jnp.asarray(0.1 * rng.standard_normal(K).astype(np.float32))
g_auto = jax.grad(lambda a: (model_sharded_conv1d(
    xf, a[0], mesh=mesh12, bias=a[1], activation="relu", dilation=d,
    padding="SAME", backend="auto") ** 2).sum())((wf, bf))
tune.get_config_for = orig
out["auto_seen_K"] = sorted(set(seen_K))
out["auto_sources"] = sorted(set(seen_sources))

# --- channel-divisibility validation (AtacWorks C=15, mp=2) --------------
from repro import configs
from repro.train.data_parallel import make_sharded_grad_fn
grid = make_grid_mesh(4, 2)
try:
    make_sharded_grad_fn(configs.get("atacworks"), grid)
    out["gradfn_c15_error"] = ""
except ValueError as e:
    out["gradfn_c15_error"] = str(e)
from repro.launch import train as launch_train
try:
    launch_train.main(["--arch", "atacworks", "--model-parallel", "2"])
    out["launch_c15_error"] = ""
except SystemExit as e:
    out["launch_c15_error"] = str(e)

# --- e2e: make_train_step on the (4, 2) mesh, one-step parity ------------
from repro import obs
from repro.configs.base import reduced
from repro.data.synthetic import make_batch
from repro.models import get_model
from repro.train.train_step import init_state, make_train_step

cfg = reduced(configs.get("atacworks"))  # C=8: divides over mp=2
model = get_model(cfg)
params = model.init_params(jax.random.key(0), cfg)
batch = make_batch(cfg, 8, 512, seed=0)
s1, m1 = jax.jit(make_train_step(cfg, total_steps=10))(init_state(params),
                                                       batch)
ss, ms = jax.jit(make_train_step(cfg, total_steps=10, mesh=grid))(
    init_state(params), batch)
# the chunked-model-psum arm runs under telemetry so the same step also
# provides the train.mesh / conv.psum.model records
tele = os.path.join(os.path.dirname(%(cache)r), "tele.jsonl")
obs.enable(tele)
sc, mc = jax.jit(make_train_step(cfg, total_steps=10, mesh=grid,
                                 model_reduce_chunks=2))(init_state(params),
                                                         batch)
obs.disable()
out["e2e_loss"] = [float(m1["loss"]), float(ms["loss"]), float(mc["loss"])]
out["e2e_param_diff"] = max(jax.tree.leaves(jax.tree.map(
    maxdiff, s1.params, ss.params)))
out["e2e_chunked_param_diff"] = max(jax.tree.leaves(jax.tree.map(
    maxdiff, s1.params, sc.params)))

evs = obs.read_events(tele)
psums = [r for r in evs if r["name"] == "conv.psum.model"]
out["psum_events"] = len(psums)
out["psum_bytes_min"] = min((int(r["attrs"].get("bytes", 0))
                             for r in psums), default=0)
out["psum_mp"] = sorted({int(r["attrs"].get("mp", 0)) for r in psums})
out["mesh_events"] = [r["attrs"] for r in evs if r["name"] == "train.mesh"]
print("JSON:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def mp8(tmp_path_factory):
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


def test_8dev_ksharded_fwd_bitwise_fp32(mp8):
    """K-sharding only selects filter rows: the fp32 pallas forward is
    BITWISE equal to single-device across fused/plain x both algs."""
    assert mp8["n_devices"] == 8
    for fused in ("fused", "plain"):
        for alg in ("tap_loop", "tap_packed"):
            assert mp8[f"fwd_float32_{fused}_{alg}"] == 0.0
    assert mp8["fwd_xla"] < 1e-5  # xla picks its own contraction order
    for key in [k for k in mp8 if k.startswith("fwd_bfloat16_")]:
        assert mp8[key] < 3e-2, (key, mp8[key])


def test_8dev_ksharded_grads(mp8):
    """dw/dbias stay local to the K slice (data psum over dp=1 is an
    identity) -> bitwise; dx re-orders the K contraction -> allclose."""
    for fused in ("fused", "plain"):
        for alg in ("tap_loop", "tap_packed"):
            tag = f"float32_{fused}_{alg}"
            assert mp8[f"dw_{tag}"] == 0.0, (tag, mp8[f"dw_{tag}"])
            assert mp8[f"dx_{tag}"] < 1e-5, (tag, mp8[f"dx_{tag}"])
            if fused == "fused":
                assert mp8[f"db_{tag}"] == 0.0
    for key in [k for k in mp8 if k.startswith("grad_bfloat16_")]:
        assert mp8[key] < 3e-2, (key, mp8[key])


def test_8dev_depthwise_channel_groups_bitwise(mp8):
    """Channel-group sharding has no model collective on any pass — every
    pass is channel-local, so even the grads are bitwise in fp32."""
    assert mp8["dw_fwd"] == 0.0
    assert mp8["dw_grads"] == 0.0


def test_8dev_chunked_model_psum_bitwise(mp8):
    """Chunk boundaries are bd-wblk tile multiples and the chunks cover
    disjoint dx columns, so chunked and single psums are IDENTICAL."""
    assert mp8["chunked_vs_single_psum"] == 0.0


def test_8dev_local_filter_tuner_keys(mp8):
    """Every per-shard backend='auto' resolution keyed on the LOCAL
    filter count (K/2 = 4) and hit the pre-populated local-K cache."""
    assert mp8["auto_seen_K"] == [4]
    assert mp8["auto_sources"] == ["cache"]


def test_8dev_channel_divisibility_errors(mp8):
    """AtacWorks C=15 cannot split over mp=2: both the sharded grad fn
    and the launcher must say so in terms of conv_channels."""
    assert "conv_channels=15" in mp8["gradfn_c15_error"]
    assert "conv_channels=15" in mp8["launch_c15_error"]


def test_8dev_train_step_equivalence(mp8):
    l1, ls, lc = mp8["e2e_loss"]
    assert abs(l1 - ls) < 1e-3 * max(1.0, abs(l1))
    assert abs(l1 - lc) < 1e-3 * max(1.0, abs(l1))
    assert mp8["e2e_param_diff"] < 1e-5
    assert mp8["e2e_chunked_param_diff"] < 1e-5


def test_8dev_model_psum_telemetry(mp8):
    """The chunked (4, 2) train step must trace its bwd-data model-axis
    all-reduces (nonzero staged bytes, mp=2) and record the 2D mesh."""
    assert mp8["psum_events"] > 0
    assert mp8["psum_bytes_min"] > 0
    assert mp8["psum_mp"] == [2]
    assert any(int(m.get("mp", 0)) == 2 and int(m.get("dp", 0)) == 4
               for m in mp8["mesh_events"])
