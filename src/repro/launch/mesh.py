"""Production mesh construction.

Target: TPU v5e pods. Single pod = 256 chips as (16, 16) ("data",
"model"); multi-pod = 2 pods x 256 chips as (2, 16, 16) ("pod", "data",
"model") with batch data-parallel over "pod" (params replicated per pod,
FSDP inside a pod over "data", tensor/expert parallel over "model").

A FUNCTION, not a module constant — importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first init).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devs)} — the dry-run "
            "must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import/init")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devs[:n])


def make_host_mesh(model_parallel: int = 1, devices=None):
    """Mesh over the local devices (every one unless ``devices`` narrows
    it): the trainer's data axis, and the CPU tests and examples.

    Both meshes are ``Auto`` on every axis: the round's ``vmap`` mixes
    replicated state with data-sharded batches, which an ``Explicit``
    mesh (``jax.make_mesh``'s default) rejects at trace time.
    """
    devs = jax.devices() if devices is None else list(devices)
    dp = len(devs) // model_parallel
    return jax.make_mesh((dp, model_parallel), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=devs[: dp * model_parallel])


def data_axes(mesh) -> tuple:
    """The batch-parallel axes of a mesh (everything but 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")
