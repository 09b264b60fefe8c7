"""Mesh construction for the distributed modules."""
from __future__ import annotations

import jax
import numpy as np


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axis types (sharding propagates through
    the jitted programs as in the single-device code they grew from).

    ``devices``: explicit device sequence to build the mesh over — the
    multi-controller path passes ``DistributedContext.global_devices`` so
    mesh axes span EVERY host's devices, never just the local ones."""
    kwargs = {}
    if devices is not None:
        need = int(np.prod(shape))
        if len(devices) < need:
            raise ValueError(
                f"mesh shape {tuple(shape)} needs {need} devices but the "
                f"context sees only {len(devices)}"
            )
        kwargs["devices"] = tuple(devices)[:need]
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes), **kwargs
    )


def local_device_mesh(n: int, axis_name: str = "data"):
    """A 1-D mesh over the FIRST ``n`` local devices. ``jax.make_mesh``
    insists on consuming every device; evaluation sharding wants a subset
    (e.g. 4 eval shards under ``--xla_force_host_platform_device_count=8``),
    so this builds the Mesh directly — the plain constructor defaults to
    Auto axis types."""
    devs = jax.devices()
    if n > len(devs):
        raise ValueError(
            f"need {n} devices for a {n}-way mesh but only {len(devs)} are "
            "visible — lower n_shards or force more simulated devices "
            "(XLA_FLAGS=--xla_force_host_platform_device_count=N)"
        )
    return jax.sharding.Mesh(np.asarray(devs[:n]), (axis_name,))
