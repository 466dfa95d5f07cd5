"""Mesh construction (functions, not module-level constants, so importing
this module never touches jax device state).

Every mesh of the repo is built by :func:`make_mesh`, with ``Auto`` axes:
the sharding rules (``repro.distributed.sharding``) constrain activations
with ``with_sharding_constraint`` and leave propagation to GSPMD, which
only ``Auto`` axes allow (``jax.make_mesh`` defaults to ``Explicit``).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (see the module doc)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """Whatever devices this process actually has, on the data axis."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))


def make_cnn_serve_mesh(n_data: int):
    """CNN serving mesh for the halo-exchange sharded conv engine:
    spatial H shards over ``data`` (rule ``"cnn_h"``), channels could
    ride ``model`` (kept 1 — trunk weights live whole in ROM macros).

    Takes the first ``n_data`` devices (so it composes with the dry-run's
    512 forced host devices) and raises when the process has fewer."""
    devices = jax.devices()
    if not 1 <= n_data <= len(devices):
        raise ValueError(
            f"make_cnn_serve_mesh(n_data={n_data}) needs {n_data} devices; "
            f"this process has {len(devices)} "
            f"({devices[0].platform}: {devices[0].device_kind})")
    return make_mesh((n_data, 1), ("data", "model"),
                     devices=devices[:n_data])
