"""Production mesh construction.

Single pod: 16x16 = 256 v5e chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model") — "pod"
carries data parallelism across the pod boundary (DCN-ish links), so only
gradient/all-reduce traffic crosses pods; "model" stays intra-pod.

A FUNCTION, not a module constant: importing this module must never touch
jax device state (device count is locked at first backend init; see
launch/dryrun.py which force-creates 512 host devices *before* any import).

Every axis is ``Auto``: the model code places arrays with sharding
constraints and GSPMD propagation, which ``Explicit`` axes (the
``jax.make_mesh`` default since jax 0.7) refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU integration tests (requires >= prod(shape) devices,
    e.g. via XLA_FLAGS=--xla_force_host_platform_device_count=4)."""
    return _auto_mesh(shape, axes)


def make_ep_mesh(ep_degree: int, *, data_degree: int = 1, devices=None):
    """``("data", "model")`` mesh for expert-parallel serving: the model
    axis spans ``ep_degree`` devices (each holding E/ep_degree experts),
    the data axis spans ``data_degree``.  ``data_degree=1`` (the default)
    is the 1×N layout serving parity tests pin — batch stays whole, only
    expert weights and the a2a dispatch shard.  Uses the first
    ``data_degree*ep_degree`` of the available devices, so it works on
    forced host devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)
    with any ep_degree dividing the forced count."""
    import numpy as np
    from jax.sharding import Mesh

    devs = list(devices) if devices is not None else jax.devices()
    need = data_degree * ep_degree
    if ep_degree < 1 or data_degree < 1:
        raise ValueError(f"degrees must be >= 1, got {data_degree}x{ep_degree}")
    if len(devs) < need:
        raise ValueError(
            f"mesh {data_degree}x{ep_degree} needs {need} devices, "
            f"have {len(devs)} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} before import)")
    return Mesh(np.asarray(devs[:need]).reshape(data_degree, ep_degree),
                ("data", "model"))


def data_axes(mesh) -> tuple:
    """The batch-parallel axes of a mesh: ("pod","data") or ("data",)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis_size(mesh) -> int:
    return mesh.shape["model"]


def data_axis_size(mesh) -> int:
    import math
    return math.prod(mesh.shape[a] for a in data_axes(mesh))
