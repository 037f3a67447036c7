"""Debug meshes over the real local devices.

Built by a function (never a module-level constant) so importing this
module does not touch jax device state.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np


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
