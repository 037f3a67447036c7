"""repro.sharding.rules: the ambient mesh and the retrieval mesh's shard
placement rule."""

import jax
import numpy as np
import pytest

from repro.launch.mesh import make_debug_mesh
from repro.sharding.rules import data_axis_devices, place_shards, set_mesh


def test_data_axis_devices_orders_and_validates():
    devs = jax.devices()
    n = len(devs)
    mesh = make_debug_mesh(n, axes=("data",))
    assert data_axis_devices(mesh) == tuple(devs[:n])
    # multi-axis mesh: one representative device per data rank
    if n >= 2:
        half = n // 2
        mesh2 = make_debug_mesh(2 * half, axes=("data", "model"),
                                shape=(half, 2))
        picked = data_axis_devices(mesh2)
        assert len(picked) == half
        assert picked == tuple(np.asarray(mesh2.devices)[:, 0])
    with pytest.raises(ValueError, match="no 'data' axis"):
        data_axis_devices(make_debug_mesh(1, axes=("model",)))


def test_place_shards_round_robin_and_tail_stable():
    devs = jax.devices()
    D = len(devs)
    mesh = make_debug_mesh(D, axes=("data",))
    for s_count in (1, D, D + 3, 3 * D):
        placed = place_shards(s_count, mesh)
        assert len(placed) == s_count
        assert all(placed[s] == devs[s % D] for s in range(s_count))
        # tail growth never relocates an existing shard -- the property
        # ShardedIndex.refresh relies on after a spill-append
        assert place_shards(s_count + 1, mesh)[:s_count] == placed
    with pytest.raises(ValueError, match="n_shards"):
        place_shards(0, mesh)


def test_place_shards_uses_ambient_mesh_or_none():
    assert place_shards(3) is None               # no mesh anywhere
    mesh = make_debug_mesh(1, axes=("data",))
    with set_mesh(mesh):
        placed = place_shards(3)
    assert placed == (jax.devices()[0],) * 3


def test_make_debug_mesh_axes_and_shape_validation():
    # legacy default: model-major (1, n) over ("data", "model")
    n = min(2, len(jax.devices()))
    legacy = make_debug_mesh(n)
    assert legacy.axis_names == ("data", "model")
    assert legacy.shape["data"] == 1 and legacy.shape["model"] == n
    # the retrieval fan-out's data-major form
    data = make_debug_mesh(n, axes=("data",))
    assert data.axis_names == ("data",) and data.shape["data"] == n
    with pytest.raises(ValueError, match="devices"):
        make_debug_mesh(n, axes=("data", "model"), shape=(n, 7))
