"""Production meshes.

``make_production_mesh()`` is a FUNCTION (importing this module never
touches jax device state):
  single-pod:  (16, 16)      axes ('data', 'model')   — 256 chips
  multi-pod:   (2, 16, 16)   axes ('pod', 'data', 'model') — 512 chips

Design: TP/EP inside the 'model' axis (highest-bandwidth ICI dimension),
FSDP over 'data' (intra-pod ICI), pure DP over 'pod' (inter-pod DCN —
only gradient all-reduces cross it).
"""
from __future__ import annotations

from typing import Sequence

import jax
import numpy as np


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              **kwargs) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto (jax's default is Explicit):
    the sharding rules and activation constraints assume propagation."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names), **kwargs)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Whatever this host actually has (tests / examples): (n_dev, 1)."""
    n = jax.device_count()
    return make_mesh((n, 1), ("data", "model"))


def make_data_mesh(world: int | None = None) -> jax.sharding.Mesh:
    """A 1-D pure-DP ``('data',)`` mesh over the first ``world`` local
    devices — the elastic trainer's mesh (``Trainer.fit_elastic``).

    ``world`` may be *smaller* than the host's device count: an elastic
    resize that drops workers keeps running on the surviving device prefix
    (the extra devices just idle), which is how the chaos tests model a
    W=4 → W=2 shrink inside one host.  Built directly from a device subset
    rather than ``make_mesh`` (``jax.make_mesh`` always spans every
    addressable device)."""
    devices = jax.devices()
    world = len(devices) if world is None else int(world)
    if not 1 <= world <= len(devices):
        raise ValueError(f'world must be in [1, {len(devices)}] '
                         f'(local devices), got {world}')
    return jax.sharding.Mesh(np.asarray(devices[:world]), ("data",))
