"""Ahead-of-time compiles of the BRGEMM kernels for a TPU v5e, without one.

Each case jits ``value_and_grad`` of a loss over one Pallas conv call at
the AtacWorks shape (width 60,000, S=51, d=8), so the fwd, bwd_data and
bwd_weight kernels all go through Mosaic together, against a ``v5e:2x2``
topology that is only described.  What interpret mode cannot show is
caught here: block tiling, lane alignment of the staged footprint and the
kernels' VMEM use.  Nothing runs, so nothing is timed or compared.

The topology is described inside a module-scoped fixture (never while a
module is imported): only the test worker given this file loads the TPU
compiler library.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

N, W, S, D = 8, 60_000, 51, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_grad(conv, one_chip, *, C, dtype, w_shape, bias,
                  x_dtype=None, residual=True, **kw):
    """Compile value_and_grad over (x, w, b) of one fused conv layer with
    every pass pinned to the Pallas kernels, compiled (not interpreted)."""
    def loss(x, w, b):
        y = conv(x, w, bias=b, activation="relu",
                 residual=x if residual else None, dilation=D,
                 padding="SAME", backend="pallas", interpret=False, **kw)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
              for s, dt in (((N, C, W), x_dtype or dtype), (w_shape, dtype),
                            (bias, dtype))]
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        *shapes).compile()


def _pallas_kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("C,dtype", [(15, jnp.float32), (16, jnp.bfloat16)],
                         ids=["fp32-C15", "bf16-C16"])
@pytest.mark.parametrize("pipe", [0, 2], ids=["pipe0", "pipe2"])
@pytest.mark.parametrize("alg", ["tap_loop", "tap_packed"])
def test_dense_conv_grad_compiles_for_v5e(one_chip, alg, pipe, C, dtype):
    cfg = ("pallas", 512, None, alg, 1, pipe)
    compiled = _compile_grad(
        ops.conv1d, one_chip, C=C, dtype=dtype, w_shape=(S, C, C),
        bias=(C,), wblk=512, alg=alg, pipe=pipe, bwd_data_cfg=cfg,
        bwd_weight_cfg=cfg)
    # fwd + bwd_data + bwd_weight, each a Mosaic kernel
    assert _pallas_kernels(compiled) >= 3


@pytest.mark.parametrize("C,dtype", [(15, jnp.float32), (16, jnp.bfloat16)],
                         ids=["fp32-C15", "bf16-C16"])
def test_untuned_dense_conv_grad_compiles_for_v5e(one_chip, C, dtype):
    """The path the training step takes: no alg and no pass configs, so
    each pass picks its formulation from its shape; at the AtacWorks body
    shape all three pick tap_packed.  The bf16 model's activations stay
    float32, as in training."""
    from repro import obs

    obs.reset_counters()
    compiled = _compile_grad(ops.conv1d, one_chip, C=C, dtype=dtype,
                             x_dtype=jnp.float32, w_shape=(S, C, C),
                             bias=(C,))
    counts = obs.counters()
    obs.reset_counters()
    assert counts["kernels.build"] == counts["kernels.build_packed"] == 3
    assert _pallas_kernels(compiled) >= 3


@pytest.mark.parametrize("C,dtype,pipe", [(15, jnp.float32, 0),
                                          (16, jnp.bfloat16, 2)],
                         ids=["fp32-C15-pipe0", "bf16-C16-pipe2"])
def test_depthwise_conv_grad_compiles_for_v5e(one_chip, C, dtype, pipe):
    cfg = ("pallas", 512, None, None, None, pipe)
    compiled = _compile_grad(
        ops.depthwise_conv1d, one_chip, C=C, dtype=dtype, w_shape=(S, C),
        bias=(C,), wblk=512, pipe=pipe, bwd_data_cfg=cfg,
        bwd_weight_cfg=cfg)
    assert _pallas_kernels(compiled) >= 3


@pytest.mark.parametrize("C,K,x_dtype,dtype", [
    (1, 16, jnp.float32, jnp.bfloat16),   # bf16 model's stem: fp32 track in
    (15, 1, jnp.float32, jnp.float32),    # fp32 model's heads: K=1
], ids=["stem-bf16", "head-fp32"])
def test_atacworks_edge_layers_compile_for_v5e(one_chip, C, K, x_dtype,
                                               dtype):
    compiled = _compile_grad(
        ops.conv1d, one_chip, C=C, dtype=dtype, x_dtype=x_dtype,
        w_shape=(S, K, C), bias=(K,), residual=False, out_dtype=jnp.float32)
    assert _pallas_kernels(compiled) >= 2
