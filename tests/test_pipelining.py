"""Software-pipelined BRGEMM kernels (DESIGN.md §15, docs/pipelining.md).

Four contracts:

  * **bit-equivalence**: the pipelined kernel bodies rotate staged
    operand tiles through extra VMEM slots but keep the same tap order
    and fp32 accumulation, so ``pipe >= 2`` must be *bitwise* equal to
    the synchronous kernel — forward and both backward passes, fp32 and
    bf16, dense tap_loop/tap_packed and depthwise, plain and fused
    epilogue, and under ``REPRO_PIPE_FORCE_ASYNC=1`` (the real async-copy
    schedule executed in interpret mode, not the synchronous fallback);
  * **cache schema**: ``|pipe:`` tags constrained problem keys (pipe=0 is
    a constraint, distinct from the untagged free problem), entries
    round-trip the pipe field, and legacy entries with no pipe field
    resolve to the synchronous kernel;
  * **VMEM budget**: the candidate space charges the (pipe-1) extra
    in-flight buffers, so too-deep pipelines are pruned exactly when
    their rotation blows the budget;
  * **chunked gradient psum** (8-virtual-device subprocess, the
    test_sharded_training.py harness): splitting the fused
    ``grad_reduce_axes`` all-reduce across bwd-weight width chunks
    returns the same gradients as the PR 5 single psum.
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

from repro import tune
from repro.kernels import conv1d_brgemm as k
from repro.kernels import ops
from repro.tune import space

jax.config.update("jax_enable_x64", False)


# ---------------------------------------------------------------------------
# Pipelined == synchronous, bitwise
# ---------------------------------------------------------------------------


def _operands(dtype, depthwise, N=2, C=8, K=8, S=3, W=520):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((N, C, W)).astype(np.float32), dtype)
    wshape = (S, C) if depthwise else (S, K, C)
    w = jnp.asarray(0.1 * rng.standard_normal(wshape).astype(np.float32),
                    dtype)
    nf = C if depthwise else K
    b = jnp.asarray(0.1 * rng.standard_normal(nf).astype(np.float32), dtype)
    r = jnp.asarray(0.1 * rng.standard_normal((N, nf, W)).astype(np.float32),
                    dtype)
    return x, w, b, r


def _run_all_passes(conv, x, w, b, r, *, pipe, fused, alg=None, nblk=None):
    """(y, dx, dw[, db]) with every pass pinned to the given pipe depth.
    wblk=128 over W=520 -> 5 width tiles (ragged tail included)."""
    cfg = ("pallas", 128, None, alg, nblk, pipe)
    kw = dict(dilation=2, padding="SAME", backend="pallas", wblk=128,
              pipe=pipe, bwd_data_cfg=cfg, bwd_weight_cfg=cfg)
    if alg is not None:
        kw.update(alg=alg, nblk=nblk)
    if fused:
        kw.update(activation="gelu", residual=r)

    def loss(x, w, b):
        y = conv(x, w, bias=b if fused else None, **kw)
        return (y.astype(jnp.float32) ** 2).sum()

    y = conv(x, w, bias=b if fused else None, **kw)
    grads = jax.grad(loss, argnums=(0, 1, 2) if fused else (0, 1))(x, w, b)
    return (y, *grads)


DENSE_KINDS = [("tap_loop", 1, 2), ("tap_packed", 2, 2)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
@pytest.mark.parametrize("alg,nblk,pipe", DENSE_KINDS,
                         ids=["tap_loop", "tap_packed-fold2"])
def test_dense_pipelined_bitwise_equals_sync(dtype, fused, alg, nblk, pipe):
    x, w, b, r = _operands(dtype, depthwise=False)
    sync = _run_all_passes(ops.conv1d, x, w, b, r, pipe=0, fused=fused,
                           alg=alg, nblk=nblk)
    piped = _run_all_passes(ops.conv1d, x, w, b, r, pipe=pipe, fused=fused,
                            alg=alg, nblk=nblk)
    for a, c in zip(sync, piped):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
def test_depthwise_pipelined_bitwise_equals_sync(dtype, fused):
    x, w, b, r = _operands(dtype, depthwise=True)
    sync = _run_all_passes(ops.depthwise_conv1d, x, w, b, r, pipe=0,
                           fused=fused)
    piped = _run_all_passes(ops.depthwise_conv1d, x, w, b, r, pipe=3,
                            fused=fused)
    for a, c in zip(sync, piped):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_forced_async_schedule_bitwise_equals_sync(monkeypatch):
    """REPRO_PIPE_FORCE_ASYNC=1 runs the real double-buffered DMA schedule
    (warmup prefetch, rotation, streamed store) in interpret mode rather
    than the synchronous fallback — still bit-identical."""
    x, w, b, r = _operands(jnp.float32, depthwise=False)
    sync = _run_all_passes(ops.conv1d, x, w, b, r, pipe=0, fused=True,
                           alg="tap_loop", nblk=1)
    monkeypatch.setenv(k.ENV_FORCE_ASYNC, "1")
    piped = _run_all_passes(ops.conv1d, x, w, b, r, pipe=3, fused=True,
                            alg="tap_loop", nblk=1)
    for a, c in zip(sync, piped):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_canon_pipe():
    """A 1-deep 'pipeline' has no lookahead — it IS the synchronous
    kernel; None/0 likewise."""
    assert k.canon_pipe(None) == 0
    assert k.canon_pipe(0) == 0
    assert k.canon_pipe(1) == 0
    assert k.canon_pipe(2) == 2
    assert k.canon_pipe(3) == 3


# ---------------------------------------------------------------------------
# Cache schema: |pipe: tag round-trip + legacy fallback
# ---------------------------------------------------------------------------


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    path = str(tmp_path / "tune_cache.json")
    monkeypatch.setenv(tune.cache.ENV_CACHE_PATH, path)
    tune.reset_default_cache()
    yield path
    tune.reset_default_cache()


def _prob(**kw):
    base = dict(N=2, C=8, K=8, S=3, dilation=2, Q=512, dtype="float32",
                padding="SAME")
    base.update(kw)
    return tune.ConvProblem(**base)


def test_pipe_key_tagging():
    """pipe=None is the free problem (untagged — legacy keys keep
    resolving); pipe=0 pins the synchronous kernel and IS tagged, so the
    race arms get distinct cache rows."""
    assert "|pipe:" not in _prob().key("cpu")
    assert _prob(pipe=0).key("cpu").endswith("|pipe:0")
    assert _prob(pipe=2).key("cpu").endswith("|pipe:2")
    with pytest.raises(ValueError):
        _prob(pipe=1)  # not a pipeline: canon would silently un-pin it
    with pytest.raises(ValueError):
        _prob(pipe=-2)


def test_pipe_cache_roundtrip(tmp_cache):
    cfg = tune.tune(N=2, C=8, K=8, S=3, dilation=2, Q=512, dtype="float32",
                    padding="SAME", pipe=2, measure=False,
                    backends=("pallas",))
    assert cfg.pipe == 2
    hit = tune.get_config(N=2, C=8, K=8, S=3, dilation=2, Q=512,
                          dtype="float32", padding="SAME", pipe=2)
    assert hit.source == "cache" and hit.pipe == 2
    assert any(key.endswith("|pipe:2")
               for key in json.load(open(tmp_cache)))


def test_legacy_entry_resolves_synchronous(tmp_cache):
    """A pre-§15 cache entry has no pipe field: it must read back as the
    synchronous kernel (pipe None -> canon 0), not re-measure."""
    prob = _prob()
    tune.get_default_cache().put(
        prob.key(tune.device_kind()),
        {"backend": "pallas", "wblk": 128, "kblk": 8, "source": "measured",
         "sec": 1e-5})
    hit = tune.get_config_for(prob, allow_measure=False)
    assert hit.source == "cache"
    assert hit.pipe is None
    assert k.canon_pipe(hit.pipe) == 0


# ---------------------------------------------------------------------------
# VMEM budget: deep rotations are charged and pruned
# ---------------------------------------------------------------------------


def test_vmem_budget_rejects_too_deep_pipelines():
    prob = _prob(N=4, C=384, K=384, S=3, dilation=1, Q=8192,
                 padding="VALID")
    cands = [c for c in space.enumerate_candidates(prob)
             if c.backend == "pallas"]
    assert any(c.pipe >= 2 for c in cands), "no pipelined candidate at all"
    # every surviving candidate fits with its in-flight buffers charged
    for c in cands:
        assert space.vmem_footprint_bytes(
            prob, c.wblk, c.kblk, c.alg or "tap_loop", c.nblk or 1,
            c.pipe or 0) <= space.VMEM_BUDGET_BYTES, c
    # at this shape some tile legal synchronously must lose its pipelined
    # variants, and only ever because the rotation blew the budget
    sync = {(c.wblk, c.kblk, c.alg, c.nblk) for c in cands if not c.pipe}
    pruned_any = False
    for depth in (2, 3):
        piped = {(c.wblk, c.kblk, c.alg, c.nblk)
                 for c in cands if c.pipe == depth}
        for wblk, kblk, alg, nblk in sync - piped:
            pruned_any = True
            assert space.vmem_footprint_bytes(
                prob, wblk, kblk, alg or "tap_loop", nblk or 1,
                depth) > space.VMEM_BUDGET_BYTES, (wblk, kblk, alg, nblk,
                                                   depth)
    assert pruned_any, "budget never pruned a pipelined candidate here"


def test_single_tile_has_no_pipelined_candidates():
    """One width tile leaves nothing to double-buffer: the axis collapses
    to the synchronous kernel (this is why SMOKE_PIPE exists)."""
    cands = space.enumerate_candidates(_prob(Q=128))
    assert any(c.backend == "pallas" for c in cands)
    assert all(not c.pipe for c in cands if c.backend == "pallas")


# ---------------------------------------------------------------------------
# Chunked gradient psum == single psum (8 virtual devices, subprocess)
# ---------------------------------------------------------------------------

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.kernels import ops
from repro.launch.mesh import make_host_mesh, dp_axis_names

mesh = make_host_mesh()
axes = dp_axis_names(mesh)
out = {"n_devices": len(jax.devices())}

def maxdiff(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-6))

N, C, K, S, d, W = 8, 8, 8, 5, 2, 512
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((N, C, W)), jnp.float32)
w = jnp.asarray(0.1 * rng.standard_normal((S, K, C)), jnp.float32)
b = jnp.asarray(0.1 * rng.standard_normal(K), jnp.float32)
wd = jnp.asarray(0.1 * rng.standard_normal((S, C)), jnp.float32)

def sharded_grads(conv, weights, chunks):
    def body(x, *ws):
        def loss(ws):
            y = conv(x, ws[0], bias=ws[1], activation="relu",
                     dilation=d, padding="SAME", backend="pallas",
                     grad_reduce_axes=axes, grad_reduce_chunks=chunks)
            return (y ** 2).sum()
        return jax.grad(loss)(ws)
    f = shard_map(body, mesh=mesh,
                  in_specs=(P(axes),) + (P(),) * len(weights),
                  out_specs=(P(),) * len(weights), check_vma=False)
    return jax.jit(f)(x, *weights)

# dense + depthwise, fused bias epilogue: chunked psum (4-way over the
# bwd-weight width partials) vs the PR 5 single fused psum
for name, conv, weights in [("dense", ops.conv1d, (w, b)),
                            ("dw", ops.depthwise_conv1d, (wd, b))]:
    g1 = sharded_grads(conv, weights, 1)
    g4 = sharded_grads(conv, weights, 4)
    out[name] = [maxdiff(a, c) for a, c in zip(g1, g4)]

print("JSON:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def chunk8():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD],
        env=env, capture_output=True, text=True, timeout=560,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(l for l in proc.stdout.splitlines() if l.startswith("JSON:"))
    return json.loads(line[5:])


def test_8dev_chunked_psum_matches_single(chunk8):
    assert chunk8["n_devices"] == 8
    # same fp32 summands, regrouped: agreement to summation-order ulp
    assert max(chunk8["dense"]) < 1e-6, chunk8["dense"]
    assert max(chunk8["dw"]) < 1e-6, chunk8["dw"]


def test_chunking_threads_through_training_stack():
    """core.blocks -> train.losses -> data_parallel accept and thread
    grad_reduce_chunks; on the 1-device host mesh the chunked grads equal
    the plain ones (the psum machinery runs over an axis of size 1)."""
    from repro import configs
    from repro.data.synthetic import make_batch
    from repro.launch.mesh import make_host_mesh
    from repro.models import get_model
    from repro.train.data_parallel import make_sharded_grad_fn

    cfg = configs.get("atacworks")
    model = get_model(cfg)
    params = model.init_params(jax.random.key(0), cfg)
    batch = make_batch(cfg, 2, 256, seed=0)
    mesh = make_host_mesh()
    (l1, _), g1 = jax.jit(make_sharded_grad_fn(cfg, mesh))(params, batch)
    (l4, _), g4 = jax.jit(make_sharded_grad_fn(
        cfg, mesh, grad_reduce_chunks=4))(params, batch)
    np.testing.assert_allclose(float(l1), float(l4), rtol=1e-6)
    for a, c in zip(jax.tree.leaves(g1), jax.tree.leaves(g4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-5, atol=1e-6)
