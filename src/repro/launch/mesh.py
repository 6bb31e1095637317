"""Production mesh factory.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device count is locked at first jax init, and the dry-run
must set XLA_FLAGS before that).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Optional[Tuple[str, ...]] = None):
    """Arbitrary mesh (tests, elastic reconfiguration).  Slices the device
    list so a 16x16 mesh also works in the 512-fake-device dry-run process."""
    if axes is None:
        axes = ("pod", "data", "model")[-len(shape):]
    n = int(np.prod(shape))
    return jax.make_mesh(shape, axes, (jax.sharding.AxisType.Auto,) * len(axes),
                         devices=jax.devices()[:n])


def parse_mesh(spec: str):
    """'16x16' -> (data, model); '2x16x16' -> (pod, data, model)."""
    dims = tuple(int(x) for x in spec.lower().split("x"))
    return make_mesh(dims)
