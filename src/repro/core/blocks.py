"""AtacWorks-style 1D dilated-conv ResNet (paper §4.2) built on the
DilatedConv1D layer — the paper's end-to-end training workload.

25 conv layers: stem (1->C), 11 residual blocks of 2 convs each (C->C),
and two 1-channel heads (denoised signal regression + peak-call logits).
Most layers: C=K=15 (16 for bf16), S=51, dilation=8 — the paper's stated
AtacWorks configuration.

Each residual block is exactly **two fused kernel calls** (DESIGN.md §10):

    r = relu(conv1(h) + b1)            # bias+relu epilogue
    h = relu(conv2(r) + b2 + h)        # bias+residual+relu epilogue

so the bias-add, the fp32 activation, and the residual-add all happen on
the kernel's fp32 accumulator — no per-layer ``astype(float32)``
round-trips through HBM.  ``forward_unfused`` keeps the pre-fusion
composition (conv → bias → fp32 relu → residual as four XLA ops) as the
benchmark baseline; ``REPRO_FUSED_EPILOGUE=0`` routes ``forward`` to it.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.core.conv1d import DilatedConv1D
from repro.models import common as cm

N_RES_BLOCKS = 11  # 1 stem + 11*2 res + 2 heads = 25 conv layers

# Layer scopes (``jax.named_scope``): every op a layer adds, forward and
# backward, carries its scope in its HLO ``op_name`` and so in the device
# trace.  Metadata only: the compiled program is the same without them.
STEM = "layer.stem"
HEAD_SIGNAL = "layer.head_signal"
HEAD_PEAK = "layer.head_peak"


def res_scope(block: int, conv: int) -> str:
    """Scope of conv ``conv`` (1 or 2) of residual block ``block``."""
    return f"layer.res{block}.conv{conv}"


def init_params(key, cfg):
    dtype = jnp.dtype(cfg.dtype)
    C, S = cfg.conv_channels, cfg.conv_filter
    ks = cm.split(key, 2 * N_RES_BLOCKS + 3)
    mk = lambda k, cin, cout: DilatedConv1D.init(k, cin, cout, S, dtype=dtype)
    params = {
        "stem": mk(ks[0], 1, C),
        "res": [
            {"conv1": mk(ks[1 + 2 * i], C, C), "conv2": mk(ks[2 + 2 * i], C, C)}
            for i in range(N_RES_BLOCKS)
        ],
        "head_signal": mk(ks[-2], C, 1),
        "head_peak": mk(ks[-1], C, 1),
    }
    return params


def _fused_default() -> bool:
    return os.environ.get("REPRO_FUSED_EPILOGUE", "1") != "0"


def forward(params, cfg, x, *, backend=None, fused=None, grad_reduce_axes=None,
            grad_reduce_chunks=None, padding="SAME", model_axis=None,
            model_parallel=1, model_reduce_chunks=None):
    """x: (B, W) noisy coverage track -> (signal (B, W), peak_logits (B, W)).

    ``grad_reduce_axes``: mesh axes the batch shards over when this runs
    inside a data-parallel ``shard_map`` body — every layer's weight/bias
    gradient then all-reduces over them, fused per layer after its
    bwd-weight pass (DESIGN.md §13).  ``grad_reduce_chunks`` > 1 further
    chunks each layer's psum across its bwd-weight width partials
    (DESIGN.md §15).  ``padding="CAUSAL"`` is the streaming-servable
    variant (every layer looks back only) — it is the one-shot reference
    the chunked ``core.streaming`` path matches bitwise (DESIGN.md §16).

    ``model_axis``/``model_parallel`` additionally K-shard every
    shardable conv layer over that mesh axis (tensor parallelism,
    DESIGN.md §17) — params stay replicated, each layer slices its own
    filter block (``kernels.sharded.shard_param``), computes at local K,
    and reassembles via ``model_concat``; ``model_reduce_chunks`` chunks
    each layer's bwd-data model psum.  Requires the fused path and
    C % model_parallel == 0 (the heads' K=1 layers run replicated —
    their gradients are identical on every model shard, since shards
    along 'model' see the same data shard)."""
    if fused is None:
        fused = _fused_default()
    mp = int(model_parallel) if model_axis is not None else 1
    if mp > 1:
        if not fused:
            raise ValueError(
                "model-parallel forward requires the fused path "
                "(REPRO_FUSED_EPILOGUE=0 / fused=False is the pre-fusion "
                "benchmark baseline only)")
        return _forward_model_sharded(
            params, cfg, x, backend=backend, padding=padding,
            grad_reduce_axes=grad_reduce_axes,
            grad_reduce_chunks=grad_reduce_chunks, model_axis=model_axis,
            mp=mp, model_reduce_chunks=model_reduce_chunks)
    if not fused:
        return forward_unfused(params, cfg, x, backend=backend,
                               grad_reduce_axes=grad_reduce_axes,
                               padding=padding)
    d = cfg.conv_dilation
    gra = grad_reduce_axes
    grc = grad_reduce_chunks
    with jax.named_scope(STEM):
        h = x[:, None, :]  # (B, 1, W)
        h = DilatedConv1D.apply(params["stem"], h, dilation=d,
                                backend=backend, padding=padding,
                                activation="relu", grad_reduce_axes=gra,
                                grad_reduce_chunks=grc)
    for i, blk in enumerate(params["res"]):
        with jax.named_scope(res_scope(i, 1)):
            r = DilatedConv1D.apply(blk["conv1"], h, dilation=d,
                                    backend=backend, padding=padding,
                                    activation="relu", grad_reduce_axes=gra,
                                    grad_reduce_chunks=grc)
        with jax.named_scope(res_scope(i, 2)):
            h = DilatedConv1D.apply(blk["conv2"], r, dilation=d,
                                    backend=backend, padding=padding,
                                    activation="relu", residual=h,
                                    grad_reduce_axes=gra,
                                    grad_reduce_chunks=grc)
    with jax.named_scope(HEAD_SIGNAL):
        signal = DilatedConv1D.apply(params["head_signal"], h, dilation=d,
                                     backend=backend, activation="relu",
                                     padding=padding,
                                     out_dtype=jnp.float32,
                                     grad_reduce_axes=gra,
                                     grad_reduce_chunks=grc)[:, 0, :]
    with jax.named_scope(HEAD_PEAK):
        peak = DilatedConv1D.apply(params["head_peak"], h, dilation=d,
                                   backend=backend, out_dtype=jnp.float32,
                                   padding=padding,
                                   grad_reduce_axes=gra,
                                   grad_reduce_chunks=grc)[:, 0, :]
    return signal, peak


def _mp_apply(p, h, *, cfg, backend, padding, mp, axis, gra, grc, mrc,
              activation=None, residual=None, out_dtype=None,
              input_grad=True):
    """Apply one conv layer K-sharded over the model axis (inside a
    shard_map body, DESIGN.md §17).

    Shardable layers (K % mp == 0): slice this shard's filter block from
    the replicated params (``shard_param`` — its VJP zero-pads + psums the
    block gradients back to a full replicated dw/dbias), slice the
    residual activation with a plain ``shard_block`` (its cotangent stays
    shard-local), run the conv at local K with the dx model-psum fused
    into its VJP (``model_reduce_axes``, chunked by ``mrc``), and
    reassemble with ``model_concat`` (gather whose VJP takes this shard's
    block, pairing with the in-VJP psum).  ``input_grad=False`` skips the
    dx psum for layers whose input cotangent is never consumed (the
    stem — x is data, not a function of params).

    Unshardable layers (the heads' K=1 < mp) run replicated: every model
    shard computes the identical layer on the identical (data-sharded)
    input, so the data-axis grad reduction alone already yields the same
    full gradient on every shard."""
    from repro.kernels import sharded as sh

    K = p["w"].shape[1]
    if mp == 1 or K % mp:
        return DilatedConv1D.apply(
            p, h, dilation=cfg.conv_dilation, backend=backend,
            padding=padding, activation=activation, residual=residual,
            out_dtype=out_dtype, grad_reduce_axes=gra,
            grad_reduce_chunks=grc)
    local = {"w": sh.shard_param(p["w"], 1, mp, axis)}
    if "b" in p:
        local["b"] = sh.shard_param(p["b"], 0, mp, axis)
    res_l = (sh.shard_block(residual, 1, mp, axis)
             if residual is not None else None)
    y = DilatedConv1D.apply(
        local, h, dilation=cfg.conv_dilation, backend=backend,
        padding=padding, activation=activation, residual=res_l,
        out_dtype=out_dtype, grad_reduce_axes=gra, grad_reduce_chunks=grc,
        model_reduce_axes=(axis,) if input_grad else None,
        model_reduce_chunks=mrc)
    return sh.model_concat(y, 1, mp, axis)


def _forward_model_sharded(params, cfg, x, *, backend, padding,
                           grad_reduce_axes, grad_reduce_chunks, model_axis,
                           mp, model_reduce_chunks):
    """The fused forward with every shardable layer K-sharded over
    ``model_axis`` (see ``forward``; same layer graph, same math)."""
    kw = dict(cfg=cfg, backend=backend, padding=padding, mp=mp,
              axis=model_axis, gra=grad_reduce_axes,
              grc=grad_reduce_chunks, mrc=model_reduce_chunks)
    # stem: x is training data — nothing upstream needs dx, skip its psum
    with jax.named_scope(STEM):
        h = x[:, None, :]  # (B, 1, W)
        h = _mp_apply(params["stem"], h, activation="relu",
                      input_grad=False, **kw)
    for i, blk in enumerate(params["res"]):
        with jax.named_scope(res_scope(i, 1)):
            r = _mp_apply(blk["conv1"], h, activation="relu", **kw)
        with jax.named_scope(res_scope(i, 2)):
            h = _mp_apply(blk["conv2"], r, activation="relu", residual=h,
                          **kw)
    with jax.named_scope(HEAD_SIGNAL):
        signal = _mp_apply(params["head_signal"], h, activation="relu",
                           out_dtype=jnp.float32, **kw)[:, 0, :]
    with jax.named_scope(HEAD_PEAK):
        peak = _mp_apply(params["head_peak"], h, out_dtype=jnp.float32,
                         **kw)[:, 0, :]
    return signal, peak


def forward_unfused(params, cfg, x, *, backend=None, grad_reduce_axes=None,
                    padding="SAME"):
    """Pre-fusion baseline: conv, bias-add, fp32 relu round-trip, and
    residual-add as four separate XLA ops per layer.  Kept only as the
    fused-vs-unfused comparison arm of ``bench_atacworks_e2e`` — the model
    itself always trains through ``forward``."""
    from repro.kernels.ops import _axes_tuple, _psum_cotangent

    axes = _axes_tuple(grad_reduce_axes)

    def conv_bias(p, h):
        y = DilatedConv1D.apply({"w": p["w"]}, h, dilation=cfg.conv_dilation,
                                padding=padding,
                                backend=backend, grad_reduce_axes=axes)
        b = p["b"]
        if axes:  # bias-add is outside the kernel here
            b = _psum_cotangent(axes, b)
        return y + b[None, :, None].astype(y.dtype)

    with jax.named_scope(STEM):
        h = x[:, None, :]  # (B, 1, W)
        h = jax.nn.relu(conv_bias(params["stem"], h).astype(
            jnp.float32)).astype(h.dtype)
    for i, blk in enumerate(params["res"]):
        with jax.named_scope(res_scope(i, 1)):
            r = jax.nn.relu(conv_bias(blk["conv1"], h).astype(
                jnp.float32)).astype(h.dtype)
        with jax.named_scope(res_scope(i, 2)):
            r = conv_bias(blk["conv2"], r)
            h = jax.nn.relu((h + r).astype(jnp.float32)).astype(h.dtype)
    with jax.named_scope(HEAD_SIGNAL):
        signal = jax.nn.relu(
            conv_bias(params["head_signal"], h)[:, 0, :].astype(jnp.float32))
    with jax.named_scope(HEAD_PEAK):
        peak = conv_bias(params["head_peak"], h)[:, 0, :].astype(jnp.float32)
    return signal, peak


def loss_fn(params, cfg, batch, *, backend=None, peak_weight: float = 1.0,
            fused=None, grad_reduce_axes=None, grad_reduce_chunks=None,
            model_axis=None, model_parallel=1, model_reduce_chunks=None):
    """AtacWorks loss: MSE(denoised signal) + BCE(peak calls)."""
    signal, peak_logits = forward(params, cfg, batch["noisy"], backend=backend,
                                  fused=fused,
                                  grad_reduce_axes=grad_reduce_axes,
                                  grad_reduce_chunks=grad_reduce_chunks,
                                  model_axis=model_axis,
                                  model_parallel=model_parallel,
                                  model_reduce_chunks=model_reduce_chunks)
    with jax.named_scope("step.loss"):
        mse = jnp.mean((signal - batch["clean"].astype(jnp.float32)) ** 2)
        labels = batch["peaks"].astype(jnp.float32)
        bce = jnp.mean(
            jnp.maximum(peak_logits, 0) - peak_logits * labels
            + jnp.log1p(jnp.exp(-jnp.abs(peak_logits))))
        return mse + peak_weight * bce, {"mse": mse, "bce": bce}


def init_stream_state(cfg, batch, dtype=jnp.float32):
    """Streaming-serving state for the conv family — the causal-conv
    analogue of ``init_cache`` on the decoder families (per-layer
    ring buffers of the last ``(S-1)*dilation`` input columns).  The
    streaming step itself lives in ``repro.core.streaming`` (DESIGN.md
    §16); this re-export gives ``get_model(cfg)`` a uniform serving
    surface."""
    from repro.core import streaming
    return streaming.init_stream_state(cfg, batch, dtype)


def stream_step(params, cfg, state, chunk, **kw):
    """One chunked streaming step; see ``repro.core.streaming.stream_step``."""
    from repro.core import streaming
    return streaming.stream_step(params, cfg, state, chunk, **kw)


def prefill(params, cfg, history, **kw):
    """Fused prefill; see ``repro.core.streaming.prefill``."""
    from repro.core import streaming
    return streaming.prefill(params, cfg, history, **kw)
