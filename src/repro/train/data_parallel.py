"""Data-parallel gradient computation over a mesh — the paper's 16-socket
MPI training loop, mesh-native (DESIGN.md §13).

``make_sharded_grad_fn`` returns a drop-in replacement for
``jax.value_and_grad(loss_fn, has_aux=True)`` that runs the loss/grad
*per batch shard* inside a ``shard_map`` over the mesh's data axes:

  * params replicated (``P()``), batch sharded on dim 0 (``P(dp_axes)``);
  * each shard traces the model at its **local** batch size, so every
    ``backend='auto'`` conv resolves its tuner plan from the local-shape
    ``ConvProblem`` key (N_local = N / dp) — global-shape keys cannot
    leak into per-shard lookups;
  * the conv family threads ``grad_reduce_axes`` into its fused custom
    VJPs, so each layer's (dw, dbias) psum fires directly after that
    layer's bwd-weight kernel — the all-reduce of layer *l* overlaps the
    backward compute of layers < l, which is what made the paper's
    MPI_Allreduce-per-gradient-as-ready scaling work.  For families whose
    parameter gradients don't all flow through the conv VJPs, the whole
    gradient tree is psummed at the end of the shard body instead
    (correct, just not overlapped);
  * the per-shard loss is scaled by 1/dp before differentiation, so the
    psummed gradients ARE the gradients of the global mean loss — no
    post-hoc rescale, bitwise-comparable to the single-device step up to
    summation order;
  * loss/aux metrics are psummed to their global means, so the returned
    values match the single-device semantics exactly.

Gradients come back replicated (identical on every shard after the psum);
the optimizer update downstream of this function is unchanged.

On a 2D ``(data, model)`` mesh with mp > 1 (conv family only,
DESIGN.md §17), the same shard body additionally K-shards every conv
layer over the 'model' axis: params and grads stay replicated
(``shard_param``'s VJP reassembles full gradients), the batch keeps
sharding over the data axes only — devices along 'model' see the same
data shard — and each layer's bwd-data dx psum fuses (and optionally
chunks) inside its custom VJP.
"""
from __future__ import annotations

import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.launch.mesh import dp_axis_names, dp_size, mp_axis_name, mp_size
from repro.train.losses import make_loss_fn


def make_sharded_grad_fn(cfg, mesh, *, loss_fn=None, grad_reduce_chunks=None,
                         model_reduce_chunks=None):
    """value_and_grad(loss, has_aux=True) over a data-parallel mesh.

    ``loss_fn(params, batch) -> (loss, aux)`` defaults to the family loss
    from ``make_loss_fn`` with ``grad_reduce_axes`` threaded for the conv
    family.  The returned function has the same call signature and return
    structure as ``jax.value_and_grad(loss_fn, has_aux=True)``; batches
    must have their leading (batch) dim divisible by the mesh's dp size.

    ``grad_reduce_chunks`` > 1 (conv family, default loss only) breaks
    each layer's fused gradient psum into that many width chunks, psummed
    as the bwd-weight partials complete (DESIGN.md §15): chunk i's
    all-reduce has no data dependency on chunk i+1's contraction, so
    XLA's async collectives overlap them — on top of the per-layer
    overlap the fused reduction already gives.  Same gradients up to fp32
    summation order.

    A mesh with a 'model' axis of size mp > 1 turns on tensor parallelism
    (conv family, default loss only): every shardable conv layer computes
    its own K/mp filter slice, with ``model_reduce_chunks`` chunking each
    layer's bwd-data model-axis psum (DESIGN.md §17).  Requires
    cfg.conv_channels % mp == 0.
    """
    axes = dp_axis_names(mesh)
    if not axes:
        raise ValueError(
            f"mesh {tuple(mesh.axis_names)} has no data axis "
            "(expected 'data' and/or 'pod')")
    dp = dp_size(mesh)
    mp = mp_size(mesh)
    fused_reduce = cfg.family == "conv"
    if mp > 1:
        if not fused_reduce:
            raise ValueError(
                f"model-parallel grad fn supports the conv family only "
                f"(cfg family is {cfg.family!r}); other families shard "
                "through the GSPMD rules in models/sharding.py")
        if loss_fn is None and cfg.conv_channels % mp:
            raise ValueError(
                f"conv_channels={cfg.conv_channels} does not divide over "
                f"mp={mp} model shards: every body layer has "
                f"K=C={cfg.conv_channels} filters, so C % mp must be 0 — "
                "pick a divisible channel count or lower the model axis "
                "(DESIGN.md §17)")
    if loss_fn is None:
        loss_fn = make_loss_fn(
            cfg, grad_reduce_axes=axes if fused_reduce else None,
            grad_reduce_chunks=grad_reduce_chunks if fused_reduce else None,
            model_axis=mp_axis_name(mesh) if mp > 1 else None,
            model_parallel=mp,
            model_reduce_chunks=model_reduce_chunks if mp > 1 else None)
    # host-side mesh-shape event: the report's mp=… column reads this (the
    # shard body itself traces under jit, where no span can be timed)
    obs.event("train.mesh", dp=dp, mp=mp,
              axes=",".join(mesh.axis_names))

    def local_grad(params, batch):
        def scaled_loss(p, b):
            loss, aux = loss_fn(p, b)
            # 1/dp here makes Σ_shards(local grad) the global-mean grad,
            # so the in-VJP psums need no downstream rescale
            return loss / dp, aux

        (loss, aux), grads = jax.value_and_grad(
            scaled_loss, has_aux=True)(params, batch)
        if not fused_reduce:
            grads = jax.lax.psum(grads, axes)
        loss = jax.lax.psum(loss, axes)
        aux = jax.tree.map(lambda a: jax.lax.psum(a / dp, axes), aux)
        return (loss, aux), grads

    # replicate params, shard every batch leaf on its leading dim; grads/
    # metrics come out replicated (identical post-psum on every shard).
    # check_vma=False: replication is established by the psums above and
    # inside the conv custom VJPs, which the varying-axes check does not
    # follow.
    return shard_map(local_grad, mesh=mesh,
                     in_specs=(P(), P(axes)),
                     out_specs=((P(), P()), P()),
                     check_vma=False)
