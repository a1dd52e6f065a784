"""Production mesh factory.

Importing this module never touches jax device state; meshes are built on
demand (so tests see 1 CPU device unless the dry-run set XLA_FLAGS first).
"""
from __future__ import annotations

import jax
import numpy as np


def _auto(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; multi_pod adds the 2-pod "pod" axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_debug_mesh(shape=None, axes=("data", "model")):
    """Small mesh for CPU multi-device tests.

    The default shape is derived from ``jax.device_count()`` — the largest
    ``(n // 2, 2)`` grid that fits, falling back to ``(1, 1)`` on
    single-device hosts — so construction never raises on a plain CPU dev
    box that didn't set ``--xla_force_host_platform_device_count``.
    """
    if shape is None:
        n = jax.device_count()
        shape = (n // 2, 2) if n >= 2 else (1,) * len(axes)
        shape = shape[: len(axes)] + (1,) * (len(axes) - len(shape))
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_serving_mesh(tp: int = 1, dp: int = 1):
    """``(dp, tp)`` serving mesh over the first ``dp*tp`` host devices.

    Axis names follow the training convention: replicas over ``"data"``,
    absorbed attention heads over ``"model"``.  The router slices this into
    per-replica submeshes with :func:`replica_meshes`.
    """
    devices = jax.devices()
    need = dp * tp
    if need > len(devices):
        raise ValueError(
            f"serving mesh needs {need} devices (tp={tp} x dp={dp}) but only "
            f"{len(devices)} are visible; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} on CPU")
    arr = np.array(devices[:need]).reshape(dp, tp)
    return jax.sharding.Mesh(arr, ("data", "model"))


def replica_meshes(mesh):
    """Split a ``("data", "model")`` serving mesh into one independent
    ``("model",)`` submesh per data-parallel replica.

    Each replica's scheduler runs its pool and shard_map collectives on a
    disjoint device slice, so replicas never synchronize with each other.
    """
    devs = np.asarray(mesh.devices)          # [dp, tp]
    return [jax.sharding.Mesh(devs[i], ("model",)) for i in range(devs.shape[0])]
