"""The ambient mesh and the retrieval mesh's shard placement rule.

``set_mesh`` makes a mesh current for the calling thread and
``current_mesh`` reads it; ``place_shards`` falls back to it when no
mesh is passed.

``place_shards`` maps S ``.idx`` shards onto the D devices of the mesh's
``"data"`` axis round-robin (shard s -> device s mod D).  The mapping
depends only on the shard's position, so growing the tail of the shard
list (a live append or spill) never moves an already-placed shard -- the
property ``ShardedIndex.refresh`` relies on to keep unchanged shards'
device-resident corpora warm.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

_STATE = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_STATE, "mesh", None)


@contextlib.contextmanager
def set_mesh(mesh: Optional[Mesh]):
    prev = current_mesh()
    _STATE.mesh = mesh
    try:
        yield
    finally:
        _STATE.mesh = prev


# ---------------------------------------------------------------------------
# Shard placement (the retrieval mesh)
# ---------------------------------------------------------------------------

def data_axis_devices(mesh: Mesh, axis: str = "data"
                      ) -> Tuple[jax.Device, ...]:
    """The device per position along one named mesh axis.

    Collapses every other axis to its first coordinate, so a 2-D
    ``("data", "model")`` mesh yields one representative device per
    data-parallel rank -- the device set the retrieval fan-out places
    shards on.
    """
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no {axis!r} axis")
    i = mesh.axis_names.index(axis)
    devs = np.moveaxis(np.asarray(mesh.devices), i, 0)
    return tuple(devs.reshape(devs.shape[0], -1)[:, 0])


def place_shards(n_shards: int, mesh: Optional[Mesh] = None, *,
                 axis: str = "data") -> Optional[Tuple[jax.Device, ...]]:
    """Round-robin shard -> device placement along the ``"data"`` axis.

    Shard s lands on device ``s % D`` (D = the axis extent).  Returns
    one device per shard, or None with no mesh (single-device serving,
    no placement).  Because the mapping is a pure function of the shard
    position, appending or spilling NEW shards at the tail never
    relocates an existing shard -- ``refresh()`` after a tail-only
    mutation keeps every unchanged shard on its device.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        return None
    devs = data_axis_devices(mesh, axis)
    return tuple(devs[s % len(devs)] for s in range(n_shards))
