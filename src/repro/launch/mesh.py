"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before any jax
initialization, and smoke tests must keep seeing the single real CPU device.

Every mesh has ``Auto`` axes: the model code places activations with
``with_sharding_constraint`` by logical axis names
(`repro.distributed.sharding`), which is the Auto-mode API.
``jax.make_mesh`` otherwise makes ``Explicit`` axes, under which every
ambiguous gather and contraction would have to state its output sharding.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (elastic resizing, tests)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def local_mesh():
    """Whatever devices exist locally, as a 1-D (data,) mesh."""
    n = len(jax.devices())
    return make_mesh((n,), ("data",))
