"""Mesh factories.

Importing this module never touches jax device state; meshes are built
lazily inside the functions so that ``XLA_FLAGS=--xla_force_host_platform_
device_count=...`` set by a launcher is respected.
"""
from __future__ import annotations

import jax


def make_device_mesh(*, pp: int = 1, tp: int = 1, devices=None):
    """("data", "model") mesh over every device that is present.

    ``model = pp * tp``; the data axis takes the rest, so
    data × pp × tp equals the device count.  ``devices`` defaults to
    ``jax.devices()`` (a described topology's devices may be passed to
    compile for a chip that is not attached).
    """
    devices = list(jax.devices() if devices is None else devices)
    model = pp * tp
    if len(devices) % model:
        raise ValueError(f"pp*tp={model} does not divide the "
                         f"{len(devices)} devices present")
    return jax.make_mesh((len(devices) // model, model), ("data", "model"),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """The target deployment mesh, for the dry-run tools only.

    Single pod: 16x16 = 256 chips, axes ("data", "model").
    Multi pod:  2x16x16 = 512 chips, axes ("pod", "data", "model").
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(*, data: int = 1, model: int = 1, pod: int | None = None):
    """Small mesh for CPU-host testing (device count set via XLA_FLAGS)."""
    if pod is not None:
        return jax.make_mesh((pod, data, model), ("pod", "data", "model"))
    return jax.make_mesh((data, model), ("data", "model"))
