"""Property tests for b-bit packing / expansion / elastic.

Seeded parametrized sweeps (numpy RNG) instead of hypothesis: the same
invariants, exercised over deterministic grids of (k, b) covering the
word boundaries hypothesis used to hunt for.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bbit import (expand_onehot, expand_tokens, lowest_bits,
                             pack_signatures, raw_storage_bits, storage_bits,
                             unpack_signatures, vw_storage_bits)


@pytest.mark.parametrize("b", [1, 2, 4, 8, 16])
@pytest.mark.parametrize("k", [1, 7, 16, 31, 32, 33])
def test_pack_unpack_roundtrip(k, b):
    rng = np.random.default_rng(k * 37 + b)
    sig = jnp.asarray(rng.integers(0, 1 << b, (3, k)), jnp.uint32)
    packed = pack_signatures(sig, b)
    got = unpack_signatures(packed, b, k)
    assert np.array_equal(np.asarray(got), np.asarray(sig))
    # storage really is ceil(k*b/32) words
    assert packed.shape[1] == -(-k * b // 32)


@pytest.mark.parametrize("b", [1, 2, 3, 8, 12])
@pytest.mark.parametrize("k", [2, 5, 16])
def test_expansion_has_exactly_k_ones(b, k):
    rng = np.random.default_rng(b * 100 + k)
    sig = jnp.asarray(rng.integers(0, 1 << b, (2, k)), jnp.uint32)
    oh = np.asarray(expand_onehot(sig, b))
    assert oh.shape == (2, k * (1 << b))
    assert (oh.sum(axis=1) == k).all()
    # inner product == match count (Eq. 5)
    matches = int((np.asarray(sig[0]) == np.asarray(sig[1])).sum())
    assert int(oh[0] @ oh[1]) == matches


@pytest.mark.parametrize("b", [1, 2, 8, 16])
@pytest.mark.parametrize("k", [1, 7, 33, 64])
def test_tokens_are_block_disjoint(b, k):
    rng = np.random.default_rng(k)
    sig = jnp.asarray(rng.integers(0, 1 << b, (1, k)), jnp.uint32)
    tok = np.asarray(expand_tokens(sig, b))[0]
    blocks = tok >> b
    assert np.array_equal(blocks, np.arange(k))


def test_lowest_bits_range():
    sig = jnp.asarray([[0xFFFFFFFF, 0, 12345]], jnp.uint32)
    for b in (1, 4, 8, 31):
        out = np.asarray(lowest_bits(sig, b))
        assert out.max() < (1 << b)


def test_storage_model_ordering():
    """b-bit storage << raw and << VW-at-parity (paper Figs 10-12)."""
    bbit = storage_bits(k=500, b=8)                  # 4,000 bits/example
    assert bbit < raw_storage_bits(avg_nnz=12062) / 90
    assert bbit < vw_storage_bits(m_bins=16384) / 100


def test_elastic_reshard_changes_mesh(tmp_path):
    """Checkpoint saved unsharded restores under a 2-device mesh (elastic
    scale-up) and values survive."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.train import checkpoint
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    state = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    d = str(tmp_path / "ck")
    checkpoint.save(d, 1, state)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("data", "model"))

    shardings = {"w": NamedSharding(mesh, P("data", None))}
    restored, step = checkpoint.restore(d, state, shardings=shardings)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(state["w"]))
    assert len(restored["w"].sharding.device_set) == 2
