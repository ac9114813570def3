"""Pure-jnp oracles for every kernel in this package.

These are the ground truth the Pallas kernels are tested against
(``tests/test_kernels_conv1d.py`` sweeps shapes/dtypes and asserts allclose).

The einsums run at ``Precision.HIGHEST``: on a TPU the default matmul
precision takes bf16 passes, which would make the oracle too loose to
check an fp32 kernel against.

Conventions (paper layout, Section 2):
  x      : (N, C, W)   input,  N batch, C channels, W width
  w      : (S, K, C)   weights in the paper's *forward* layout (Alg. 1/2)
  out    : (N, K, Q)   Q = W - (S - 1) * dilation   (VALID on pre-padded input)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import epilogue as _ep


def _conv1d_f32(x: jax.Array, w: jax.Array, dilation: int) -> jax.Array:
    """Alg. 1 body in fp32 (no output cast) — shared by the plain and the
    fused oracle so the fused path sees the un-rounded accumulator, exactly
    as the kernel's epilogue does."""
    S, K, C = w.shape
    N, Cx, W = x.shape
    assert C == Cx, (C, Cx)
    Q = W - (S - 1) * dilation
    assert Q > 0, f"width {W} too small for S={S}, d={dilation}"
    out = jnp.zeros((N, K, Q), dtype=jnp.promote_types(x.dtype, jnp.float32))
    for s in range(S):
        xs = jax.lax.dynamic_slice_in_dim(x, s * dilation, Q, axis=2)
        out = out + jnp.einsum(
            "kc,ncq->nkq", w[s].astype(jnp.float32), xs.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
    return out


def conv1d_ref(x: jax.Array, w: jax.Array, *, dilation: int = 1) -> jax.Array:
    """Direct evaluation of eq. (2): Out[k,q] = sum_{c,s} In[c, q+d*s] W[s,k,c].

    Implemented exactly as the paper's Algorithm 1 — a series of S GEMMs over
    width-shifted slices of the input — so it doubles as the readable spec of
    the BRGEMM formulation.
    """
    return _conv1d_f32(x, w, dilation).astype(x.dtype)


def conv1d_fused_ref(x: jax.Array, w: jax.Array, *, dilation: int = 1,
                     bias: jax.Array | None = None,
                     activation: str | None = None,
                     residual: jax.Array | None = None,
                     out_dtype=None) -> jax.Array:
    """Oracle for the fused-epilogue forward: act(conv + bias + residual),
    all epilogue math on the fp32 accumulator (DESIGN.md §10)."""
    u = _ep.apply_ref(_conv1d_f32(x, w, dilation), bias=bias,
                      residual=residual, activation=activation)
    return u.astype(out_dtype or x.dtype)


def conv1d_bwd_data_ref(
    gout: jax.Array, w: jax.Array, *, dilation: int = 1
) -> jax.Array:
    """Alg. 3: data gradient w.r.t. the (padded) input of conv1d_ref.

    gout: (N, K, Q) -> (N, C, W) with W = Q + (S-1)*dilation.
    """
    S, K, C = w.shape
    pad = (S - 1) * dilation
    g = jnp.pad(gout, ((0, 0), (0, 0), (pad, pad)))
    # flipped taps + transposed (K, C) -> exactly the paper's (S, C, K) layout
    w_flip = w[::-1].transpose(0, 2, 1)  # (S, C, K)
    return conv1d_ref(g, w_flip, dilation=dilation)


def conv1d_bwd_weight_ref(
    x: jax.Array, gout: jax.Array, *, dilation: int = 1
) -> jax.Array:
    """Alg. 4: dW[s,k,c] = sum_{n,q} gout[n,k,q] * x[n,c,q + s*d]."""
    N, K, Q = gout.shape
    N2, C, W = x.shape
    S = (W - Q) // dilation + 1
    g32 = gout.astype(jnp.float32)
    taps = []
    for s in range(S):
        xs = jax.lax.dynamic_slice_in_dim(x, s * dilation, Q, axis=2)
        taps.append(jnp.einsum("nkq,ncq->kc", g32, xs.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    return jnp.stack(taps, axis=0)  # (S, K, C) fp32


def depthwise_conv1d_bwd_weight_ref(
    x: jax.Array, gout: jax.Array, *, dilation: int = 1
) -> jax.Array:
    """Depthwise Alg. 4: dW[s,c] = sum_{n,q} gout[n,c,q] * x[n,c,q + s*d].

    x: (N, C, W), gout: (N, C, Q) -> (S, C) fp32.
    """
    N, C, Q = gout.shape
    N2, C2, W = x.shape
    S = (W - Q) // dilation + 1
    g32 = gout.astype(jnp.float32)
    taps = []
    for s in range(S):
        xs = jax.lax.dynamic_slice_in_dim(x, s * dilation, Q, axis=2)
        taps.append(jnp.sum(g32 * xs.astype(jnp.float32), axis=(0, 2)))
    return jnp.stack(taps, axis=0)  # (S, C) fp32


def _depthwise_conv1d_f32(x: jax.Array, w: jax.Array, dilation: int) -> jax.Array:
    S, C = w.shape
    N, Cx, W = x.shape
    assert C == Cx
    Q = W - (S - 1) * dilation
    out = jnp.zeros((N, C, Q), jnp.float32)
    for s in range(S):
        xs = jax.lax.dynamic_slice_in_dim(x, s * dilation, Q, axis=2)
        out = out + w[s].astype(jnp.float32)[None, :, None] * xs.astype(jnp.float32)
    return out


def depthwise_conv1d_ref(
    x: jax.Array, w: jax.Array, *, dilation: int = 1
) -> jax.Array:
    """Grouped (depthwise) variant: Out[c,q] = sum_s In[c, q+d*s] * W[s,c].

    This is the paper's kernel with groups == C == K (the Mamba2 causal-conv
    case).  x: (N, C, W), w: (S, C) -> (N, C, Q).
    """
    return _depthwise_conv1d_f32(x, w, dilation).astype(x.dtype)


def depthwise_conv1d_fused_ref(x: jax.Array, w: jax.Array, *,
                               dilation: int = 1,
                               bias: jax.Array | None = None,
                               activation: str | None = None,
                               residual: jax.Array | None = None,
                               out_dtype=None) -> jax.Array:
    """Fused-epilogue oracle for the depthwise variant."""
    u = _ep.apply_ref(_depthwise_conv1d_f32(x, w, dilation), bias=bias,
                      residual=residual, activation=activation)
    return u.astype(out_dtype or x.dtype)


def _xla_conv1d_f32(x: jax.Array, w: jax.Array, dilation: int) -> jax.Array:
    S, K, C = w.shape
    # lax wants (N, C, W) x (K, C, S) with NCW/OIW numbers; fp32 math so the
    # AD transpose sees consistent dtypes under bf16 params.
    w_oiw = w.transpose(1, 2, 0).astype(jnp.float32)  # (K, C, S)
    return jax.lax.conv_general_dilated(
        x.astype(jnp.float32),
        w_oiw,
        window_strides=(1,),
        padding="VALID",
        rhs_dilation=(dilation,),
        dimension_numbers=("NCW", "OIW", "NCW"),
    )


def xla_conv1d(x: jax.Array, w: jax.Array, *, dilation: int = 1) -> jax.Array:
    """The vendor-library general convolution (XLA's built-in conv).

    Plays the role oneDNN plays in the paper: the generic library baseline the
    BRGEMM formulation is compared against.  Same (VALID, pre-padded) contract
    as conv1d_ref.  Dtype policy (shared with the depthwise variant below):
    compute in fp32, return x.dtype regardless of the weight dtype.
    """
    return _xla_conv1d_f32(x, w, dilation).astype(x.dtype)


def _xla_depthwise_conv1d_f32(x: jax.Array, w: jax.Array,
                              dilation: int) -> jax.Array:
    S, C = w.shape
    # grouped conv via feature_group_count; same fp32-compute rule as the
    # dense vendor path so the AD transpose sees consistent dtypes.
    w_oiw = w.T[:, None, :].astype(jnp.float32)  # (C, 1, S)
    return jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w_oiw, (1,), "VALID",
        rhs_dilation=(dilation,),
        dimension_numbers=("NCW", "OIW", "NCW"),
        feature_group_count=C,
    )


def xla_depthwise_conv1d(x: jax.Array, w: jax.Array, *,
                         dilation: int = 1) -> jax.Array:
    """Vendor-library depthwise conv, same dtype policy as ``xla_conv1d``:
    fp32 compute, output in x.dtype whatever the weight dtype."""
    return _xla_depthwise_conv1d_f32(x, w, dilation).astype(x.dtype)
