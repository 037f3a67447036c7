"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") -- the
"pod" axis is an outer data-parallel axis whose gradient all-reduce
crosses the inter-pod links once per step.

Defined as functions (never module-level constants) so importing this
module does not touch jax device state.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_debug_mesh(n_devices: int = 1, *,
                    axes: Sequence[str] = ("data", "model"),
                    shape: Optional[Tuple[int, ...]] = None):
    """A small mesh over the real local devices (tests).

    ``axes`` names the mesh axes; ``shape`` optionally fixes the extent
    per axis (must multiply to ``n_devices``).  Defaults keep the
    historical model-major layout -- all devices along the LAST axis,
    e.g. ``(1, n)`` over ("data", "model") -- while
    ``make_debug_mesh(8, axes=("data",))`` builds the data-parallel
    ``(8,)`` mesh the retrieval fan-out tests place shards on.
    """
    devs = jax.devices()[:n_devices]
    if shape is None:
        shape = (1,) * (len(axes) - 1) + (len(devs),)
    if int(np.prod(shape)) != len(devs):
        raise ValueError(f"mesh shape {shape} needs {int(np.prod(shape))} "
                         f"devices, have {len(devs)}")
    return jax.sharding.Mesh(np.array(devs).reshape(shape), tuple(axes))
