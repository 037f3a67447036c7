"""LSH dedup application + distributed preprocessing driver."""

import numpy as np
import jax
import pytest

from repro.core import Hash2U, lowest_bits, minhash_signatures
from repro.core.bbit import unpack_signatures
from repro.core.lsh import (LSHConfig, band_keys, candidate_pairs, dedup,
                            match_probability)
from repro.data import word_pair_sets
from repro.data.pipeline import make_sharded_dataset
from repro.data.preprocess import preprocess_shards, read_signature_shard
from repro.data.sparse import from_lists
from repro.data.synthetic import TINY


def _docs_with_duplicates(D=2**18, seed=0):
    """6 docs: (0,1) near-dups R~0.9, (2,3) R~0.5, others unrelated."""
    rng = np.random.default_rng(seed)
    s0, s1 = word_pair_sets(D, 800, 820, 0.9, seed=1)
    s2, s3 = word_pair_sets(D, 500, 520, 0.5, seed=2)
    s4 = np.sort(rng.choice(D, 600, replace=False))
    s5 = np.sort(rng.choice(D, 700, replace=False))
    return [s0, s1, s2, s3, s4, s5], D


def test_lsh_finds_near_duplicates():
    docs, D = _docs_with_duplicates()
    cfg = LSHConfig(n_bands=16, rows_per_band=4, b=8)
    fam = Hash2U.create(jax.random.PRNGKey(0), cfg.k, 18)
    batch = from_lists(docs)
    sig = lowest_bits(minhash_signatures(batch.indices, batch.mask, fam),
                      cfg.b)
    found = dedup(sig, [len(d) for d in docs], D, cfg, threshold=0.8)
    pairs = [(i, j) for i, j, _ in found]
    assert (0, 1) in pairs, found
    # unrelated docs never pass verification
    assert all({i, j} <= {0, 1, 2, 3} for i, j in pairs), found


def test_lsh_s_curve_is_monotone_and_selective():
    cfg = LSHConfig(n_bands=16, rows_per_band=4, b=8)
    p_low = match_probability(0.2, 800, 800, 2**18, cfg)
    p_mid = match_probability(0.6, 800, 800, 2**18, cfg)
    p_high = match_probability(0.95, 800, 800, 2**18, cfg)
    assert p_low < p_mid < p_high
    assert p_high > 0.95 and p_low < 0.5


def test_band_keys_roundtrip_and_candidates():
    cfg = LSHConfig(n_bands=4, rows_per_band=3, b=4)
    rng = np.random.default_rng(1)
    sig = jax.numpy.asarray(rng.integers(0, 16, (5, cfg.k)),
                            jax.numpy.uint32)
    keys = np.asarray(band_keys(sig, cfg))
    assert keys.shape == (5, 4)
    # identical signatures -> candidates in every band
    sig2 = sig.at[1].set(sig[0])
    keys2 = np.asarray(band_keys(sig2, cfg))
    assert (0, 1) in candidate_pairs(keys2)


def test_preprocess_pipeline_roundtrip(tmp_path):
    paths = make_sharded_dataset(TINY, str(tmp_path / "raw"), n_shards=2,
                                 n=120)
    fam = Hash2U.create(jax.random.PRNGKey(3), 64, 16)
    out = str(tmp_path / "sig")
    stats = preprocess_shards(paths, out, fam, b=8, chunk_size=48)
    assert stats.examples == 96          # 80% train split of 120
    assert stats.kernel_s > 0 and stats.load_s > 0 and stats.store_s > 0
    assert stats.reduction() > 2.0       # the paper's size reduction

    # signatures on disk decode to exactly the direct computation
    import os
    shard0 = sorted(os.listdir(out))[0]
    packed, labels, k, b = read_signature_shard(os.path.join(out, shard0))
    assert (k, b) == (64, 8)
    from repro.data.pipeline import read_shard_binary
    sets = [r for p in paths for r in read_shard_binary(p)[0]][:48]
    chunk = from_lists(sets)
    direct = lowest_bits(
        minhash_signatures(chunk.indices, chunk.mask, fam), 8)
    got = unpack_signatures(jax.numpy.asarray(packed), 8, 64)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(direct))


def test_preprocess_rejects_permutations(tmp_path):
    from repro.core import PermutationFamily
    fam = PermutationFamily.create(jax.random.PRNGKey(0), 8, 2**10)
    with pytest.raises(TypeError):
        preprocess_shards([], str(tmp_path), fam)
