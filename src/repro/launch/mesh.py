"""Production mesh construction.

Functions (not module-level constants) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and smoke tests/benches must keep seeing 1 device.

Mesh axes:
  single-pod : (data=16, model=16)            — 256 chips (one v5e pod)
  multi-pod  : (pod=2, data=16, model=16)     — 512 chips (2 pods)

Batch shards on ('pod','data'); tensor/expert-parallel dims on 'model';
parameters are additionally sharded on 'data' (FSDP/ZeRO-style 2D
sharding).  Scaling to 1000+ nodes grows 'pod'/'data' only — all sharding
rules (models/sharding.py) are axis-NAME based, never size based, so the
same rules lower unchanged on any mesh that keeps these names.
"""
from __future__ import annotations

import jax


def compat_make_mesh(shape, axis_names):
    """``jax.make_mesh`` with every axis typed Auto (GSPMD propagation), the
    sharding mode all of this repo's rules are written for."""
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat_make_mesh(shape, axes)


def make_host_mesh(*, model: int = 1):
    """A mesh over whatever devices exist (CPU smoke / single host)."""
    n = len(jax.devices())
    assert n % model == 0
    return compat_make_mesh((n // model, model), ("data", "model"))


def make_data_mesh(n_data: int):
    """A pure data-parallel ('data',) mesh over the FIRST ``n_data`` host
    devices — what the scaling benchmark uses to race 1/2/4/8-device
    sharded training inside one virtual-device process
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
    ``jax.make_mesh`` always consumes all devices, hence the explicit
    ``Mesh`` over a device subset here."""
    import numpy as np

    devs = jax.devices()
    if n_data > len(devs):
        raise ValueError(f"asked for {n_data} devices, have {len(devs)}")
    return jax.sharding.Mesh(np.array(devs[:n_data]), ("data",))


def make_grid_mesh(n_data: int, n_model: int = 1):
    """A 2D ('data', 'model') mesh over the FIRST ``n_data * n_model`` host
    devices — the (dp, mp) layout grid of the scaling benchmark, which
    races several layouts inside one virtual-device process (same explicit
    device-subset ``Mesh`` trick as ``make_data_mesh``)."""
    import numpy as np

    devs = jax.devices()
    need = n_data * n_model
    if need > len(devs):
        raise ValueError(
            f"asked for a {n_data}x{n_model} mesh ({need} devices), "
            f"have {len(devs)}")
    return jax.sharding.Mesh(
        np.array(devs[:need]).reshape(n_data, n_model), ("data", "model"))


def make_elastic_mesh(shape, axis_names, devices=None):
    """A mesh of ``shape`` over an EXPLICIT device list — the elastic
    supervisor's mesh constructor (DESIGN.md §18): after a device loss it
    re-plans the layout with ``runtime.elastic.make_plan`` and rebuilds
    the mesh over the *surviving* devices only, so the lost ids never
    appear in any sharding.  Uses the first ``prod(shape)`` survivors
    (the plan may round the data axis down further to keep the global
    batch divisible)."""
    import numpy as np

    devs = list(devices) if devices is not None else jax.devices()
    need = int(np.prod(shape))
    if need > len(devs):
        raise ValueError(
            f"mesh shape {tuple(shape)} needs {need} devices, only "
            f"{len(devs)} healthy")
    return jax.sharding.Mesh(
        np.array(devs[:need]).reshape(tuple(shape)), tuple(axis_names))


def dp_size(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


def dp_axis_names(mesh) -> tuple[str, ...]:
    """The mesh axes the batch shards over, in mesh order — what
    ``shard_map`` in_specs and the fused gradient ``psum``
    (``ops.conv1d(grad_reduce_axes=...)``) both name."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def mp_size(mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1


MP_AXIS = "model"


def mp_axis_name(mesh) -> str | None:
    """The tensor-parallel axis name ('model') when the mesh has one, else
    None.  Size-1 model axes still count — the model-sharded wrappers and
    grad fns degenerate correctly (psum over a size-1 axis is identity),
    which is what lets single-device tests exercise the sharded path."""
    return MP_AXIS if MP_AXIS in mesh.axis_names else None
