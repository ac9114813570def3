"""AdamW with fp32 first/second moments (params may be bf16).

Plain-pytree implementation (no optax dependency): ``init`` builds the
state, ``update`` is jit/pjit friendly and preserves param shardings (the
moments inherit each param's PartitionSpec because they are elementwise
images of the params).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    m: object
    v: object
    count: jax.Array


def init(params, *, moment_dtype=jnp.float32) -> AdamWState:
    zeros = lambda p: jnp.zeros(p.shape, moment_dtype)
    return AdamWState(
        m=jax.tree.map(zeros, params),
        v=jax.tree.map(zeros, params),
        count=jnp.zeros((), jnp.int32),
    )


def update(grads, state: AdamWState, params, *, lr, b1=0.9, b2=0.95,
           eps=1e-8, weight_decay=0.1, grad_clip=1.0):
    """Returns (new_params, new_state, metrics); every op it adds carries
    the ``step.optimizer`` scope in its HLO ``op_name``."""
    with jax.named_scope("step.optimizer"):
        g32 = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        gnorm = jnp.sqrt(sum(jnp.vdot(g, g) for g in jax.tree.leaves(g32)))
        scale = (jnp.where(gnorm > grad_clip, grad_clip / (gnorm + 1e-9), 1.0)
                 if grad_clip else jnp.float32(1.0))
        g32 = jax.tree.map(lambda g: g * scale, g32)

        count = state.count + 1
        b1c = 1 - b1 ** count.astype(jnp.float32)
        b2c = 1 - b2 ** count.astype(jnp.float32)

        new_m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state.m, g32)
        new_v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * (g * g),
                             state.v, g32)

        def step(p, m, v):
            upd = (m / b1c) / (jnp.sqrt(v / b2c) + eps)
            if p.ndim >= 2:  # decay matrices only (standard practice)
                upd = upd + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * upd).astype(p.dtype)

        new_params = jax.tree.map(step, params, new_m, new_v)
        return (new_params, AdamWState(new_m, new_v, count),
                {"grad_norm": gnorm})
