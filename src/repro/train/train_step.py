"""Train step factory: loss -> grad (with microbatch gradient accumulation
via ``lax.scan``) -> NaN/inf health guard -> AdamW update.

The returned ``train_step(state, batch)`` is the function the launcher
jits/lowers for the dry-run.  Gradient accumulation keeps peak activation
memory ~ microbatch-sized, which is what lets the 671B×(256×4096) train
cells fit per-chip HBM (DESIGN.md §4).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.optim import adamw, schedule
from repro.train.losses import make_loss_fn


class TrainState(NamedTuple):
    params: Any
    opt: adamw.AdamWState
    step: jax.Array
    ef: Any = None  # fp32 error-feedback buffers (grad compression only)


def init_state(params, *, grad_compression: bool = False) -> TrainState:
    from repro.optim import compression
    return TrainState(params=params, opt=adamw.init(params),
                      step=jnp.zeros((), jnp.int32),
                      ef=(compression.init_error_feedback(params)
                          if grad_compression else None))


def _split_microbatches(batch, accum: int):
    def r(x):
        return x.reshape(accum, x.shape[0] // accum, *x.shape[1:])
    return jax.tree.map(r, batch)


def _mesh_dp(mesh) -> int:
    from repro.launch.mesh import dp_size
    return dp_size(mesh)


def _mesh_mp(mesh) -> int:
    from repro.launch.mesh import mp_size
    return mp_size(mesh)


def _make_grad_fn(cfg, mesh=None, model_reduce_chunks=None):
    """The step's gradient engine — ``value_and_grad(loss, has_aux=True)``
    semantics, routed through the explicit shard_map path when the mesh
    has >1 data shard OR >1 model shard (tensor parallelism, §17).
    Shared by ``make_train_step`` and the telemetry phase probes
    (``make_phase_probes``) so both time/run the identical computation."""
    if mesh is not None and (_mesh_dp(mesh) > 1 or _mesh_mp(mesh) > 1):
        from repro.train.data_parallel import make_sharded_grad_fn
        return make_sharded_grad_fn(cfg, mesh,
                                    model_reduce_chunks=model_reduce_chunks)
    return jax.value_and_grad(make_loss_fn(cfg), has_aux=True)


def make_phase_probes(cfg, *, mesh=None, lr: float = 1e-4,
                      grad_clip: float = 1.0, weight_decay: float = 0.1):
    """Build the per-phase step-time probes behind telemetry's
    ``train.phase.*`` spans (DESIGN.md §14).

    A jitted train step is one fused program — its phases cannot be timed
    from inside without changing what is compiled.  Instead the probe jits
    each *prefix* of the step separately and times them differentially
    with the same harness the tuner uses (``tune.measure.median_time``):

      forward    = t(loss only)
      backward   = t(value_and_grad) − t(loss only)
      optimizer  = t(adamw.update on the step's real gradient tree)
      psum       = t(shard_map all-reduce of a grads-shaped tree over the
                     mesh's data axes)           (only when dp > 1)

    Returns ``probe(state, batch, iters=..., warmup=...) -> {phase: sec}``.
    Costs a few extra compiles — the launcher runs it once, after warmup,
    only when telemetry is enabled.
    """
    from repro.tune.measure import median_time

    loss_fn = make_loss_fn(cfg)
    grad_fn = _make_grad_fn(cfg, mesh)
    fwd_jit = jax.jit(lambda p, b: loss_fn(p, b)[0])
    grad_jit = jax.jit(grad_fn)
    opt_jit = jax.jit(functools.partial(
        adamw.update, lr=lr, weight_decay=weight_decay,
        grad_clip=grad_clip))

    psum_jit = None
    if mesh is not None and _mesh_dp(mesh) > 1:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import dp_axis_names
        axes = dp_axis_names(mesh)

        def _psum_tree(tree):
            return jax.tree.map(lambda g: jax.lax.psum(g, axes), tree)

        psum_jit = jax.jit(shard_map(
            _psum_tree, mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False))

    def probe(state, batch, *, iters: int = 3, warmup: int = 1):
        t_fwd = median_time(fwd_jit, state.params, batch,
                            iters=iters, warmup=warmup)
        t_grad = median_time(grad_jit, state.params, batch,
                             iters=iters, warmup=warmup)
        (_, _), grads = grad_jit(state.params, batch)
        jax.block_until_ready(grads)
        t_opt = median_time(opt_jit, grads, state.opt, state.params,
                            iters=iters, warmup=warmup)
        phases = {"forward": t_fwd,
                  "backward": max(0.0, t_grad - t_fwd),
                  "optimizer": t_opt}
        if psum_jit is not None:
            phases["psum"] = median_time(psum_jit, grads,
                                         iters=iters, warmup=warmup)
        return phases

    return probe


def make_train_step(cfg, *, accum_steps: int = 1, peak_lr: float = 3e-4,
                    warmup_steps: int = 100, total_steps: int = 10_000,
                    grad_clip: float = 1.0, weight_decay: float = 0.1,
                    skip_nonfinite: bool = True, unroll_accum: bool = False,
                    grad_compression: bool = False,
                    constrain_grads: bool = False, mesh=None,
                    model_reduce_chunks: int | None = None):
    """``unroll_accum`` replaces the microbatch ``lax.scan`` with a python
    loop — used by the roofline probes only (HloCostAnalysis counts a while
    body once; see roofline/analysis.py).

    ``grad_compression`` quantises the accumulated gradient to bf16 with an
    fp32 error-feedback buffer carried in TrainState (optim/compression.py)
    — the cast sits upstream of the GSPMD-inserted gradient reduction, so
    the cross-device reduce moves half the bytes; the EF residual re-enters
    next step, keeping the optimizer trajectory asymptotically exact.

    ``mesh`` switches gradient computation to the explicit ``shard_map``
    data-parallel path (``train/data_parallel.py``, DESIGN.md §13): the
    loss/grad runs per batch shard at local shapes (local-shape tuner
    keys), with the conv family's weight-gradient all-reduces fused into
    the custom VJPs.  The optimizer update is unchanged — it consumes the
    already-reduced (replicated) gradients.  With ``mesh=None`` (or a
    1-device mesh) the historical single-program path runs; microbatch
    accumulation composes with either (each microbatch's grad is a
    shard_map call inside the scan).  A mesh with a 'model' axis > 1
    additionally K-shards the conv layers (tensor parallelism,
    DESIGN.md §17); ``model_reduce_chunks`` chunks each layer's bwd-data
    model-axis psum."""
    from repro.optim import compression
    grad_fn = _make_grad_fn(cfg, mesh, model_reduce_chunks)

    def train_step(state: TrainState, batch):
        if accum_steps > 1:
            micro = _split_microbatches(batch, accum_steps)

            def accum_body(carry, mb):
                gsum, lsum = carry
                (loss, _), g = grad_fn(state.params, mb)
                if constrain_grads:  # pin to param layout (§Perf)
                    from repro.models.sharding import constrain_like_params
                    g = constrain_like_params(g)
                gsum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), gsum, g)
                return (gsum, lsum + loss), None

            gzero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 state.params)
            carry = (gzero, 0.0)
            if unroll_accum:
                for i in range(accum_steps):
                    mb = jax.tree.map(lambda x: x[i], micro)
                    carry, _ = accum_body(carry, mb)
                gsum, lsum = carry
            else:
                (gsum, lsum), _ = jax.lax.scan(accum_body, carry, micro)
            grads = jax.tree.map(lambda g: g / accum_steps, gsum)
            loss = lsum / accum_steps
        else:
            (loss, _), grads = grad_fn(state.params, batch)
            if constrain_grads:
                from repro.models.sharding import constrain_like_params
                grads = constrain_like_params(grads)

        new_ef = state.ef
        if grad_compression:
            q, new_ef = compression.compress(grads, state.ef)
            grads = compression.decompress(q)

        # --- health guard: skip the update if any grad is non-finite -------
        lr = schedule.cosine_with_warmup(
            state.step, peak_lr=peak_lr, warmup_steps=warmup_steps,
            total_steps=total_steps)
        new_params, new_opt, metrics = adamw.update(
            grads, state.opt, state.params, lr=lr,
            weight_decay=weight_decay, grad_clip=grad_clip)
        if skip_nonfinite:
            with jax.named_scope("step.guard"):
                finite = (jnp.isfinite(metrics["grad_norm"])
                          & jnp.isfinite(loss))
                new_params = jax.tree.map(
                    lambda n, o: jnp.where(finite, n, o), new_params,
                    state.params)
                new_opt = jax.tree.map(
                    lambda n, o: jnp.where(finite, n, o), new_opt, state.opt)
                metrics["skipped"] = (~finite).astype(jnp.float32)
        new_state = TrainState(new_params, new_opt, state.step + 1, new_ef)
        metrics.update(loss=loss, lr=lr)
        return new_state, metrics

    return train_step
