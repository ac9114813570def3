"""Batch-sharded (data-parallel) spellings of the conv1d ops.

The paper's headline end-to-end result is *distributed*: 16-socket
data-parallel AtacWorks training, gradients all-reduced with MPI.  The
mesh-native analogue (DESIGN.md §13) is ``shard_map`` over the mesh's
data axes:

  * the batch dimension of ``x`` (and ``residual``) shards over
    ``('pod','data')``; weights/bias are replicated;
  * the per-shard body is the ordinary ``ops.conv1d`` /
    ``ops.depthwise_conv1d`` — the same fused kernels, custom VJPs and
    tuner dispatch as single-device code.  Because ``shard_map`` traces
    the body at **local** shapes, a ``backend='auto'`` call resolves its
    tuner plan against the *local* ``ConvProblem`` (N_local = N / dp):
    local N changes the legal ``nblk`` folds and the candidate space, so
    global-shape cache keys must never leak into per-shard lookups — here
    they cannot, by construction;
  * under ``jax.grad``, the weight/bias gradients all-reduce over the
    sharded axes.  WHERE the reduce happens depends on where the grad is
    taken: differentiating *through* these wrappers, ``shard_map``'s own
    transpose inserts the psum for the replicated (``P()``) operands — the
    body must NOT set ``grad_reduce_axes`` or every weight gradient
    double-counts by dp (verified by test).  Taking the grad *inside* a
    shard_map body — the training path, ``train/data_parallel.py`` —
    nothing reduces for you: there ``grad_reduce_axes`` fuses the psum
    directly after the bwd-weight pass in the custom VJP.  ``dx`` stays
    local either way.

**Model-axis (tensor-parallel) spellings** (DESIGN.md §17) compose with
the above on a 2D ``(data, model)`` mesh:

  * ``model_sharded_conv1d`` K-shards the dense filter dimension: ``w``
    partitions its K axis (``P(None, 'model', None)``), ``x`` replicates
    across 'model', and the output is a **psum-free concat** along K —
    each shard computes its own filter slice.  Differentiating through
    it, shard_map's transpose inserts exactly the right collectives: dx
    psums over 'model' (x was replicated there), dw/dbias psum over the
    data axes only (w was replicated there) and stay K-local.
  * ``model_sharded_depthwise_conv1d`` channel-group-shards: x and w both
    partition C over 'model'; **no** model-axis collective exists on any
    pass (each output channel reads only its own input channel).
  * grads taken *inside* a shard body (the training path) get no help
    from shard_map: compose ``shard_param`` (slice a replicated weight to
    this shard's block; its VJP zero-pads and psums the block gradients
    back to a full replicated gradient), ``shard_block`` (plain slice for
    activations whose cotangent must stay shard-local, e.g. the
    residual), ``ops.conv1d(model_reduce_axes=...)`` (fuses the dx psum —
    chunked via ``model_reduce_chunks``), and ``model_concat`` (tiled
    all_gather whose VJP takes this shard's block *without* a psum — see
    its docstring for why jax's default reduce-scatter transpose would
    double-count here).

``shard_map`` is used with ``check_vma=False``: the psums inside the
custom VJPs establish replication, which the varying-axes check does not
follow through a custom_vjp.

Example (single host; any device count divides the batch)::

    >>> import jax, jax.numpy as jnp
    >>> from repro.kernels.sharded import sharded_conv1d
    >>> from repro.launch.mesh import make_host_mesh
    >>> mesh = make_host_mesh()
    >>> x = jnp.ones((4, 8, 64))
    >>> w = jnp.ones((3, 4, 8))
    >>> sharded_conv1d(x, w, mesh=mesh, dilation=2, padding="SAME").shape
    (4, 4, 64)
    >>> from repro.kernels.sharded import model_sharded_conv1d
    >>> model_sharded_conv1d(x, w, mesh=mesh, dilation=2,
    ...                      padding="SAME").shape
    (4, 4, 64)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import MP_AXIS, dp_axis_names, dp_size

from . import ops


def _check_batch(N: int, mesh) -> tuple[str, ...]:
    axes = dp_axis_names(mesh)
    if not axes:
        raise ValueError(
            f"mesh {tuple(mesh.axis_names)} has no data axis to shard the "
            "batch over (expected 'data' and/or 'pod')")
    dp = dp_size(mesh)
    if N % dp:
        raise ValueError(
            f"batch {N} does not divide over {dp} data-parallel shards "
            f"(mesh axes {axes}); pad or re-batch the input")
    return axes


def _sharded_call(fn, mesh, x, w, bias, residual, kwargs):
    """shard_map ``fn`` with x/residual batch-sharded, w/bias replicated.

    Optional operands can't ride as ``None`` leaves through shard_map
    in_specs, so the arg list is built dynamically."""
    axes = _check_batch(x.shape[0], mesh)
    batch = P(axes)
    args, specs = [x, w], [batch, P()]
    has_bias, has_res = bias is not None, residual is not None
    if has_bias:
        args.append(bias)
        specs.append(P())
    if has_res:
        args.append(residual)
        specs.append(batch)

    def body(*a):
        it = iter(a[2:])
        b = next(it) if has_bias else None
        r = next(it) if has_res else None
        # no grad_reduce_axes here: shard_map's transpose reduces the
        # replicated operands' cotangents itself (see module docstring)
        return fn(a[0], a[1], bias=b, residual=r, **kwargs)

    return shard_map(body, mesh=mesh, in_specs=tuple(specs),
                     out_specs=batch, check_vma=False)(*args)


def sharded_conv1d(x, w, *, mesh, bias=None, residual=None, **kwargs):
    """Data-parallel ``ops.conv1d``: batch-shards ``x``/``residual`` over
    the mesh's data axes and replicates ``w``/``bias``.  Differentiating
    *through* this wrapper is correct as-is — the weight/bias gradient
    all-reduce comes from shard_map's transpose (do NOT also pass
    ``grad_reduce_axes``: that is for grads taken *inside* a shard body,
    see the module docstring, and would double-count here).  All
    ``conv1d`` keyword arguments (activation, dilation, padding, backend,
    tiles, ``alg``/``nblk``, per-pass configs, ``out_dtype``) pass through
    to the per-shard body unchanged — ``backend='auto'`` resolves
    per-shard plans from local-shape keys."""
    return _sharded_call(ops.conv1d, mesh, x, w, bias, residual, kwargs)


def sharded_depthwise_conv1d(x, w, *, mesh, bias=None, residual=None,
                             **kwargs):
    """Data-parallel ``ops.depthwise_conv1d`` (same contract as
    ``sharded_conv1d``)."""
    return _sharded_call(ops.depthwise_conv1d, mesh, x, w, bias, residual,
                         kwargs)


# ---------------------------------------------------------------------------
# Model-axis (tensor-parallel) sharding — DESIGN.md §17
# ---------------------------------------------------------------------------


def _check_model(mesh, *, K=None, C=None, depthwise=False) -> int:
    """Validate the mesh has a 'model' axis and the sharded dimension
    divides over it; returns mp (the model-axis size, possibly 1)."""
    if MP_AXIS not in mesh.axis_names:
        raise ValueError(
            f"mesh {tuple(mesh.axis_names)} has no '{MP_AXIS}' axis to "
            "shard filters/channels over (build one with "
            "make_host_mesh(model=...) or runtime.elastic.plan_mesh)")
    mp = mesh.shape[MP_AXIS]
    if depthwise:
        if C % mp:
            raise ValueError(
                f"channel count C={C} does not divide over mp={mp} model "
                "shards (depthwise channel groups must split evenly); "
                "pick C % mp == 0 or lower the model axis")
    elif K % mp:
        raise ValueError(
            f"filter count K={K} does not divide over mp={mp} model "
            "shards; pick K % mp == 0 or lower the model axis")
    return mp


def _shard_slice(a, dim: int, mp: int, axis: str):
    """This shard's contiguous block of ``a`` along ``dim`` (size/mp)."""
    size = a.shape[dim] // mp
    i = jax.lax.axis_index(axis)
    return jax.lax.dynamic_slice_in_dim(a, i * size, size, dim)


def shard_block(a, dim: int, mp: int, axis: str):
    """Slice a *sharded-activation* operand (e.g. the residual feeding a
    K-sharded conv) to this shard's block.  Plain autodiff is already
    right: the transpose zero-pads the block cotangent back — NO psum,
    because each shard's block cotangent is a distinct piece of the full
    activation's gradient, not a partial sum of it."""
    return _shard_slice(a, dim, mp, axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def shard_param(a, dim: int, mp: int, axis: str):
    """Slice a **replicated parameter** to this shard's block along
    ``dim``.  The custom VJP zero-pads the block gradient into the full
    shape and psums over the model axis, so every shard ends the backward
    pass with the identical *full* parameter gradient — the optimizer
    state stays mesh-agnostic (unsharded), exactly as in the
    data-parallel path.  (Plain autodiff would stop at the local zero-pad
    and leave each shard a different, mostly-zero gradient.)"""
    return _shard_slice(a, dim, mp, axis)


def _shard_param_fwd(a, dim, mp, axis):
    return _shard_slice(a, dim, mp, axis), None


def _shard_param_bwd(dim, mp, axis, _, g):
    full = jnp.zeros(g.shape[:dim] + (g.shape[dim] * mp,) + g.shape[dim + 1:],
                     g.dtype)
    i = jax.lax.axis_index(axis)
    full = jax.lax.dynamic_update_slice_in_dim(full, g, i * g.shape[dim], dim)
    return (jax.lax.psum(full, axis),)


shard_param.defvjp(_shard_param_fwd, _shard_param_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def model_concat(y, dim: int, mp: int, axis: str):
    """Reassemble a K-sharded layer output: tiled ``all_gather`` along
    ``dim`` (the psum-free concat — forward needs no reduction, each shard
    owns its filter rows).

    The custom VJP slices this shard's own block of the cotangent,
    **without** a psum.  jax's default transpose of a tiled all_gather is
    a reduce-scatter (psum_scatter) — correct when the per-shard
    cotangents are arbitrary partial sums, but in this stack the conv
    VJP's ``model_reduce_axes`` psum has *already* all-reduced the
    gathered activation's gradient (it is replicated across model shards,
    plus this shard's local residual-block cotangent); re-reducing would
    multiply the replicated part by mp.  Pairing gather-bwd=own-slice
    with the in-VJP chunked model psum is what lets the dx all-reduce
    overlap the bwd-data contraction instead of serialising at the
    gather."""
    return jax.lax.all_gather(y, axis, axis=dim, tiled=True)


def _model_concat_fwd(y, dim, mp, axis):
    return jax.lax.all_gather(y, axis, axis=dim, tiled=True), None


def _model_concat_bwd(dim, mp, axis, _, g):
    return (_shard_slice(g, dim, mp, axis),)


model_concat.defvjp(_model_concat_fwd, _model_concat_bwd)


def _model_sharded_call(fn, mesh, x, w, bias, residual, kwargs, *,
                        depthwise: bool):
    """shard_map ``fn`` on a 2D (data, model) mesh: batch over the data
    axes; filters (dense) or channel groups (depthwise) over 'model'.

    Dense: x replicates across 'model', w/bias/output partition K — the
    forward is a psum-free concat along K and shard_map's transpose
    supplies the dx model-psum and the dw/dbias data-psums (the body must
    set NO reduce axes; see the data-parallel note in ``_sharded_call``).
    Depthwise: x, w, bias and output all partition C."""
    dp_axes = _check_batch(x.shape[0], mesh)
    if depthwise:
        _check_model(mesh, C=w.shape[1], depthwise=True)
        xspec = P(dp_axes, MP_AXIS, None)
        wspec = P(None, MP_AXIS)
    else:
        _check_model(mesh, K=w.shape[1])
        xspec = P(dp_axes)
        wspec = P(None, MP_AXIS, None)
    out = P(dp_axes, MP_AXIS, None)
    args, specs = [x, w], [xspec, wspec]
    has_bias, has_res = bias is not None, residual is not None
    if has_bias:
        args.append(bias)
        specs.append(P(MP_AXIS))
    if has_res:
        args.append(residual)
        specs.append(out)

    def body(*a):
        it = iter(a[2:])
        b = next(it) if has_bias else None
        r = next(it) if has_res else None
        return fn(a[0], a[1], bias=b, residual=r, **kwargs)

    return shard_map(body, mesh=mesh, in_specs=tuple(specs),
                     out_specs=out, check_vma=False)(*args)


def model_sharded_conv1d(x, w, *, mesh, bias=None, residual=None, **kwargs):
    """Tensor-parallel ``ops.conv1d`` on a (data, model) mesh: the batch
    shards over the data axes AND the filter dimension K shards over
    'model' — each device computes its own filter slice at local shapes
    (``backend='auto'`` resolves plans from local-K cache keys, see
    ``ConvProblem.localized(model_shards=...)``).  The forward output is
    a psum-free concat along K; differentiating *through* the wrapper,
    shard_map's transpose inserts the dx model-psum and the dw/dbias
    data-psums (do NOT pass ``grad_reduce_axes``/``model_reduce_axes``
    here — those are for grads taken *inside* a shard body).  Requires
    K % mp == 0 and batch % dp == 0."""
    return _model_sharded_call(ops.conv1d, mesh, x, w, bias, residual,
                               kwargs, depthwise=False)


def model_sharded_depthwise_conv1d(x, w, *, mesh, bias=None, residual=None,
                                   **kwargs):
    """Tensor-parallel ``ops.depthwise_conv1d``: channel groups shard over
    'model' (x and w both partition C), the batch over the data axes.  No
    model-axis collective exists on any pass — forward, bwd-data and
    bwd-weight are all channel-local (DESIGN.md §17).  Requires
    C % mp == 0 and batch % dp == 0."""
    return _model_sharded_call(ops.depthwise_conv1d, mesh, x, w, bias,
                               residual, kwargs, depthwise=True)
