"""One Permutation Hashing: kernel parity, estimator statistics, invariants.

Three layers, mirroring what the subsystem promises:

  * Pallas-kernel-vs-jnp-reference bit-exactness across the full
    (b, family, densification, k) grid (interpret mode),
  * statistical tests that OPH resemblance estimates are unbiased within
    tolerance on synthetic pairs of known Jaccard similarity,
  * seeded property-style tests (numpy RNG + parametrize, no hypothesis)
    for the bin-split and densification invariants, checked against
    brute-force python references.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.estimator import estimate_resemblance_oph
from repro.core.hashing import Hash2U, Hash4U, PermutationFamily, \
    family_storage_bytes
from repro.core.oph import (EMPTY, OPH, densify_fast, densify_optimal,
                            densify_rotation, hash_evaluations,
                            oph_match_fraction, oph_signatures, split_hash)
from repro.data import word_pair_sets
from repro.data.sparse import from_lists
from repro.kernels import batch_signatures, oph2u, oph4u

RNG = np.random.default_rng(11)
_E = np.uint32(0xFFFFFFFF)


def _random_batch(n, max_set, s, seed, max_nnz=256):
    """Fixed max_nnz so every case shares one padded shape (jit cache)."""
    rng = np.random.default_rng(seed)
    sets = [rng.choice(1 << s, rng.integers(1, max_set + 1), replace=False)
            for _ in range(n)]
    return from_lists(sets, max_nnz=max_nnz)


@pytest.fixture(scope="module")
def batch16():
    return _random_batch(5, 250, 16, seed=101)


@pytest.fixture(scope="module")
def batch18():
    return _random_batch(3, 137, 18, seed=77)


# ---------------------------------------------------------------------------
# Kernel vs jnp reference: bit-exact across the acceptance grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b", [1, 2, 4, 8])
@pytest.mark.parametrize("densify", ["sentinel", "rotation"])
@pytest.mark.parametrize("family", ["2u", "4u"])
def test_oph_kernel_bit_exact(b, densify, family, batch16):
    s, k = 16, 128
    batch = batch16
    oph = OPH.create(jax.random.PRNGKey(b), k, s, family, densify)
    want = oph_signatures(batch.indices, batch.mask, oph, b=b)
    got = batch_signatures(batch, oph, b=b)
    assert got.dtype == jnp.uint32
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("k,family", [
    (16, "2u"), (64, "4u"), (512, "2u"),
    pytest.param(64, "2u", marks=pytest.mark.slow),
    pytest.param(128, "4u", marks=pytest.mark.slow),
    pytest.param(512, "4u", marks=pytest.mark.slow),
])
def test_oph_kernel_bit_exact_k_sweep(k, family, batch18):
    """k below / at / above the lane block; odd nnz counts per row."""
    s = 18
    batch = batch18
    oph = OPH.create(jax.random.PRNGKey(k), k, s, family, "rotation")
    want = oph_signatures(batch.indices, batch.mask, oph, b=0)
    got = batch_signatures(batch, oph, b=0)
    assert got.shape == (3, k)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("family,b", [
    ("2u", 0), ("2u", 4),
    pytest.param("4u", 8, marks=pytest.mark.slow),
    pytest.param("4u", 1, marks=pytest.mark.slow),
])
def test_oph_optimal_densify_kernel_parity(family, b, batch16):
    """Shrivastava-2017 optimal densification: engine epilogue == reference."""
    s, k = 16, 128
    oph = OPH.create(jax.random.PRNGKey(b + 17), k, s, family, "optimal")
    want = oph_signatures(batch16.indices, batch16.mask, oph, b=b)
    got = batch_signatures(batch16, oph, b=b)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("family,b", [
    ("2u", 0), ("2u", 8),
    pytest.param("4u", 4, marks=pytest.mark.slow),
    pytest.param("4u", 1, marks=pytest.mark.slow),
])
def test_oph_fast_densify_kernel_parity(family, b, batch16):
    """Mai-et-al fast densification: engine epilogue == reference."""
    s, k = 16, 128
    oph = OPH.create(jax.random.PRNGKey(b + 29), k, s, family, "fast")
    want = oph_signatures(batch16.indices, batch16.mask, oph, b=b)
    got = batch_signatures(batch16, oph, b=b)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_densify_fast_properties():
    """Genuine bins untouched; empty bins copy a genuine same-row donor;
    all-empty rows stay EMPTY; matched holes receive matched donors
    (the probe walk depends only on (bin, round, k))."""
    s, k = 12, 64
    oph = OPH.create(jax.random.PRNGKey(5), k, s, "2u", "sentinel")
    batch = _random_batch(6, 40, s, seed=9)      # sparse: many empty bins
    sent = np.asarray(oph_signatures(batch.indices, batch.mask, oph))
    dense = np.asarray(densify_fast(jnp.asarray(sent)))
    holes = sent == _E
    assert holes.any() and not (dense == _E).any()
    assert np.array_equal(dense[~holes], sent[~holes])
    for i in range(sent.shape[0]):
        genuine = set(sent[i][~holes[i]].tolist())
        assert all(v in genuine for v in dense[i][holes[i]].tolist())
    all_empty = np.full((2, k), _E, np.uint32)
    assert (np.asarray(densify_fast(jnp.asarray(all_empty))) == _E).all()
    # two rows with identical occupancy patterns walk identical donors
    row = sent[0:1]
    twin = np.concatenate([row, row])
    out = np.asarray(densify_fast(jnp.asarray(twin)))
    assert np.array_equal(out[0], out[1])


def test_densify_optimal_properties():
    """Genuine bins untouched; empty bins copy a genuine same-row donor;
    all-empty rows stay EMPTY."""
    s, k = 12, 64
    oph = OPH.create(jax.random.PRNGKey(5), k, s, "2u", "sentinel")
    batch = _random_batch(6, 40, s, seed=9)      # sparse: many empty bins
    sent = np.asarray(oph_signatures(batch.indices, batch.mask, oph))
    dense = np.asarray(densify_optimal(jnp.asarray(sent)))
    holes = sent == _E
    assert holes.any() and not (dense == _E).any()
    assert np.array_equal(dense[~holes], sent[~holes])
    for i in range(sent.shape[0]):
        genuine = set(sent[i][~holes[i]].tolist())
        assert all(v in genuine for v in dense[i][holes[i]].tolist())
    all_empty = np.full((2, k), _E, np.uint32)
    assert (np.asarray(densify_optimal(jnp.asarray(all_empty))) == _E).all()


def test_oph_kernel_multi_lane_block(batch18):
    """k spanning several BLK_K blocks (forces the j-grid loop)."""
    s, k = 18, 512
    batch = batch18
    oph = OPH.create(jax.random.PRNGKey(7), k, s, "2u", "sentinel")
    counts = jnp.sum(batch.mask.astype(jnp.int32), axis=1)
    got = oph2u(batch.indices, counts, oph.base.a1, oph.base.a2, s=s, k=k,
                densify="sentinel", blk_k=128)
    want = oph_signatures(batch.indices, batch.mask, oph)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_oph_pallas_matches_ref_path(batch16):
    """use_pallas=True == use_pallas=False (the kernels/ref.py oracle)."""
    s, k = 16, 256
    batch = batch16
    counts = jnp.sum(batch.mask.astype(jnp.int32), axis=1)
    o2 = OPH.create(jax.random.PRNGKey(1), k, s, "2u", "sentinel")
    a = oph2u(batch.indices, counts, o2.base.a1, o2.base.a2, s=s, k=k,
              densify="sentinel", use_pallas=True)
    b = oph2u(batch.indices, counts, o2.base.a1, o2.base.a2, s=s, k=k,
              densify="sentinel", use_pallas=False)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    o4 = OPH.create(jax.random.PRNGKey(2), k, s, "4u", "rotation")
    a = oph4u(batch.indices, counts, o4.base.a, s=s, k=k, b=4,
              use_pallas=True)
    b = oph4u(batch.indices, counts, o4.base.a, s=s, k=k, b=4,
              use_pallas=False)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_oph_padding_invariance():
    """Extra padding lanes must not change OPH signatures."""
    s = 16
    s1, _ = word_pair_sets(1 << s, 400, 400, 0.5, seed=3)
    oph = OPH.create(jax.random.PRNGKey(0), 128, s, "2u", "rotation")
    small = from_lists([s1], lane_multiple=128)
    big = from_lists([s1], max_nnz=2048, lane_multiple=128)
    sig_small = batch_signatures(small, oph)
    sig_big = batch_signatures(big, oph)
    assert np.array_equal(np.asarray(sig_small), np.asarray(sig_big))


# ---------------------------------------------------------------------------
# Brute-force semantic references (seeded property-style)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,family", [
    (0, "2u"), (1, "4u"), (2, "perm"),
    pytest.param(1, "2u", marks=pytest.mark.slow),
    pytest.param(2, "4u", marks=pytest.mark.slow),
    pytest.param(0, "perm", marks=pytest.mark.slow),
])
def test_oph_sentinel_matches_bruteforce(seed, family):
    """Sentinel signatures == per-bin minima computed by a python loop."""
    s, k = 10, 16
    oph = OPH.create(jax.random.PRNGKey(seed), k, s, family, "sentinel")
    rng = np.random.default_rng(seed)
    sets = [rng.choice(1 << s, rng.integers(1, 60), replace=False)
            for _ in range(3)]
    batch = from_lists(sets, lane_multiple=8)
    got = np.asarray(oph_signatures(batch.indices, batch.mask, oph))
    h_all = np.asarray(oph.base(batch.indices))[..., 0]
    bw = oph.bin_width
    for i, st in enumerate(sets):
        want = np.full(k, _E, np.uint32)
        for j, t in enumerate(st):
            h = int(h_all[i, j])
            bin_id, off = h // bw, h % bw
            want[bin_id] = min(want[bin_id], np.uint32(off))
        assert np.array_equal(got[i], want), (i, family)


@pytest.mark.parametrize("seed,k", [
    (0, 8), (1, 32), (2, 128),
    pytest.param(3, 8, marks=pytest.mark.slow),
    pytest.param(4, 32, marks=pytest.mark.slow),
    pytest.param(3, 128, marks=pytest.mark.slow),
])
def test_densify_rotation_matches_bruteforce(seed, k):
    """Rotation == nearest-right-donor python loop on random holes."""
    rng = np.random.default_rng(seed)
    bin_width = 1 << 10
    n = 4
    sig = rng.integers(0, bin_width, (n, k)).astype(np.uint32)
    holes = rng.random((n, k)) < rng.uniform(0.1, 0.9)
    sig[holes] = _E
    sig[2, :] = _E                         # one all-empty row
    got = np.asarray(densify_rotation(jnp.asarray(sig), bin_width))
    C = bin_width + 1
    for i in range(n):
        if (sig[i] == _E).all():
            assert (got[i] == _E).all()
            continue
        for j in range(k):
            if sig[i, j] != _E:
                assert got[i, j] == sig[i, j]
                continue
            d = next(t for t in range(1, k + 1) if sig[i, (j + t) % k] != _E)
            want = np.uint32(int(sig[i, (j + d) % k]) + C * d)
            assert got[i, j] == want, (i, j)


def test_rotation_borrows_never_collide_with_genuine():
    """Borrowed values live above bin_width, so a borrowed bin can only
    match another bin that borrowed the same value over the same distance
    -- the densification paper's collision-preserving property."""
    s, k = 12, 64
    oph = OPH.create(jax.random.PRNGKey(5), k, s, "2u", "sentinel")
    batch = _random_batch(6, 40, s, seed=9)      # sparse: many empty bins
    sent = oph_signatures(batch.indices, batch.mask, oph)
    dense = densify_rotation(sent, oph.bin_width)
    borrowed = (np.asarray(sent) == _E) & (np.asarray(dense) != _E)
    assert borrowed.any()                        # the test is non-vacuous
    assert (np.asarray(dense)[borrowed] >= oph.bin_width).all()
    genuine = np.asarray(sent) != _E
    assert (np.asarray(dense)[genuine] < oph.bin_width).all()


def test_oph_split_hash_partition():
    """(bin << off_bits) | offset reconstructs the hash: a true partition."""
    s, k = 16, 32
    h = jnp.asarray(RNG.integers(0, 1 << s, 500), jnp.uint32)
    bins, offs = split_hash(h, s, 5)
    assert int(jnp.max(bins)) < k
    assert int(jnp.max(offs)) < (1 << (s - 5))
    recon = (bins.astype(jnp.uint32) << (s - 5)) | offs
    assert np.array_equal(np.asarray(recon), np.asarray(h))


def test_oph_bbit_preserves_sentinel():
    s, k, b = 14, 64, 4
    oph = OPH.create(jax.random.PRNGKey(1), k, s, "2u", "sentinel")
    batch = _random_batch(4, 30, s, seed=2)      # sparse -> empty bins
    sig = np.asarray(oph_signatures(batch.indices, batch.mask, oph, b=b))
    assert (sig == _E).any()
    nonempty = sig != _E
    assert sig[nonempty].max() < (1 << b)


def test_oph_empty_set_stays_empty():
    oph = OPH.create(jax.random.PRNGKey(0), 32, 12, "2u", "rotation")
    batch = from_lists([np.array([], np.int64)], lane_multiple=8)
    sig = oph_signatures(batch.indices, batch.mask, oph)
    assert (np.asarray(sig) == _E).all()
    # with b > 0 the rotation path folds EMPTY to the all-ones code (the
    # minhash path's empty-set value), so bit-packing never sees EMPTY
    sig_b = oph_signatures(batch.indices, batch.mask, oph, b=4)
    assert (np.asarray(sig_b) == 15).all()
    got = batch_signatures(batch, oph, b=4)
    assert (np.asarray(got) == 15).all()


def test_oph_create_validation():
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError):
        OPH.create(key, 48, 16)                  # k not a power of two
    with pytest.raises(ValueError):
        OPH.create(key, 1 << 17, 16)             # k > D
    with pytest.raises(ValueError):
        OPH(base=Hash2U.create(key, 4, 16), k=16)   # base.k != 1
    with pytest.raises(ValueError):
        OPH.create(key, 16, 16, densify="bogus")
    assert OPH.create(key, 16, 16, densify="fast").densify == "fast"


def test_oph_storage_and_cost_accounting():
    """Issue 3 taken to its extreme: ONE function's coefficients, and the
    analytic hash-evaluation model shows exactly the k x reduction."""
    oph2 = OPH.create(jax.random.PRNGKey(0), 512, 16, "2u")
    oph4 = OPH.create(jax.random.PRNGKey(0), 512, 16, "4u")
    assert family_storage_bytes(oph2) == 2 * 4
    assert family_storage_bytes(oph4) == 4 * 4
    assert family_storage_bytes(Hash2U.create(jax.random.PRNGKey(0), 512, 16)) \
        == 512 * family_storage_bytes(oph2)
    k = 512
    ratio = (hash_evaluations(100, 256, k, "minhash")
             / hash_evaluations(100, 256, k, "oph"))
    assert ratio == k


# ---------------------------------------------------------------------------
# Statistical correctness: unbiased resemblance estimates
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("densify,R", [
    ("sentinel", 0.2), ("rotation", 0.7), ("optimal", 0.2), ("fast", 0.7),
    pytest.param("sentinel", 0.7, marks=pytest.mark.slow),
    pytest.param("rotation", 0.2, marks=pytest.mark.slow),
    pytest.param("optimal", 0.7, marks=pytest.mark.slow),
    pytest.param("fast", 0.2, marks=pytest.mark.slow),
])
def test_oph_estimator_unbiased(densify, R):
    """Mean OPH estimate over seeds within 4 s.e. of the true Jaccard.

    One jit of the whole per-seed pipeline (fresh single hash function ->
    bins -> densify -> estimate) keeps 24 replications cheap.
    """
    s, k, n_seeds = 14, 256, 24
    s1, s2 = word_pair_sets(1 << s, 500, 550, R, seed=17)
    true_r = len(np.intersect1d(s1, s2)) / len(np.union1d(s1, s2))
    batch = from_lists([s1, s2])

    @jax.jit
    def one_seed(key):
        oph = OPH.create(key, k, s, "2u", densify)
        sig = oph_signatures(batch.indices, batch.mask, oph)
        return oph_match_fraction(sig[0], sig[1])

    ests = [float(one_seed(jax.random.PRNGKey(seed)))
            for seed in range(n_seeds)]
    se = np.sqrt(true_r * (1 - true_r) / (k * n_seeds))
    assert abs(np.mean(ests) - true_r) < 4 * se + 0.015, \
        (np.mean(ests), true_r)


@pytest.mark.slow
def test_oph_matches_minwise_estimates():
    """OPH and k-pass minwise hashing agree at the estimator level."""
    from repro.core import Hash2U as H2, minhash_signatures, signature_matches
    s, k = 14, 512
    s1, s2 = word_pair_sets(1 << s, 600, 620, 0.8, seed=23)
    batch = from_lists([s1, s2])
    fam = H2.create(jax.random.PRNGKey(1), k, s)
    sig_mh = minhash_signatures(batch.indices, batch.mask, fam)
    r_mh = float(signature_matches(sig_mh[0], sig_mh[1]))
    oph = OPH.create(jax.random.PRNGKey(2), k, s, "2u", "rotation")
    sig_oph = oph_signatures(batch.indices, batch.mask, oph)
    r_oph = float(oph_match_fraction(sig_oph[0], sig_oph[1]))
    assert abs(r_mh - r_oph) < 0.08, (r_mh, r_oph)


def test_oph_bbit_theorem1_estimate():
    """b-bit OPH signatures + Theorem-1 debiasing recover R."""
    s, b, k = 14, 4, 512
    D = 1 << s
    s1, s2 = word_pair_sets(D, 500, 520, 0.6, seed=31)
    true_r = len(np.intersect1d(s1, s2)) / len(np.union1d(s1, s2))
    batch = from_lists([s1, s2])

    @jax.jit
    def one_seed(key):
        oph = OPH.create(key, k, s, "2u", "sentinel")
        sig = oph_signatures(batch.indices, batch.mask, oph, b=b)
        return estimate_resemblance_oph(sig[0], sig[1], len(s1), len(s2),
                                        D, b)

    ests = [float(one_seed(jax.random.PRNGKey(seed))) for seed in range(8)]
    assert abs(np.mean(ests) - true_r) < 0.05, (np.mean(ests), true_r)


@pytest.mark.slow
def test_oph_identical_and_disjoint_sets():
    s, k = 14, 128
    rng = np.random.default_rng(0)
    univ = rng.choice(1 << s, 800, replace=False)
    a, bdis = univ[:400], univ[400:]
    batch = from_lists([a, a, bdis])
    oph = OPH.create(jax.random.PRNGKey(0), k, s, "4u", "rotation")
    sig = oph_signatures(batch.indices, batch.mask, oph)
    assert float(oph_match_fraction(sig[0], sig[1])) == 1.0
    assert float(oph_match_fraction(sig[0], sig[2])) < 0.1


# ---------------------------------------------------------------------------
# Pipeline integration
# ---------------------------------------------------------------------------

def test_oph_preprocess_shards_roundtrip(tmp_path):
    from repro.core.bbit import unpack_signatures
    from repro.data.pipeline import make_sharded_dataset
    from repro.data.preprocess import preprocess_shards, read_signature_shard
    from repro.data.synthetic import DatasetSpec
    spec = DatasetSpec("ophpre", n=96, D=2**14, avg_nnz=40, n_prototypes=2,
                       overlap=0.5, seed=0)
    paths = make_sharded_dataset(spec, str(tmp_path / "raw"), n_shards=2)
    from repro.data.pipeline import read_shard_binary
    n_total = sum(len(read_shard_binary(p)[1]) for p in paths)
    oph = OPH.create(jax.random.PRNGKey(0), 128, 14, "2u", "rotation")
    stats = preprocess_shards(paths, str(tmp_path / "sig"), oph, b=8,
                              chunk_size=64)
    assert stats.examples == n_total >= 64
    packed, labels, k, b = read_signature_shard(
        str(tmp_path / "sig" / "sig_00000.sig"))
    assert (k, b) == (128, 8)
    sig = np.asarray(unpack_signatures(jnp.asarray(packed), b, k))
    assert sig.shape == (64, 128) and sig.max() < 256

    # sentinel OPH now packs too: (b+1)-bit codes, EMPTY stored as 2^b
    from repro.data.sigshard import read_sig_shard
    sent = OPH.create(jax.random.PRNGKey(0), 128, 14, "2u", "sentinel")
    preprocess_shards(paths, str(tmp_path / "sig_sent"), sent, b=8,
                      chunk_size=64)
    words, _, meta = read_sig_shard(str(tmp_path / "sig_sent" /
                                        "sig_00000.sig"))
    assert meta.sentinel and meta.code_bits == 9
    assert meta.words == (128 * 9 + 31) // 32          # k*(b+1) bits/example
    from repro.core.bbit import unpack_codes
    codes = np.asarray(unpack_codes(jnp.asarray(words), 9, 128))
    assert codes.max() <= 256                          # values + EMPTY code
    with pytest.raises(ValueError):                    # legacy 4-tuple reader
        read_signature_shard(str(tmp_path / "sig_sent" /  # refuses (b+1)-bit
                                 "sig_00000.sig"))        # codes

    with pytest.raises(TypeError):
        preprocess_shards(paths, str(tmp_path / "bad2"),
                          OPH.create(jax.random.PRNGKey(0), 32, 10, "perm"))


def test_oph_signature_stream(tmp_path):
    from repro.data.pipeline import SignatureStream, make_sharded_dataset
    from repro.data.synthetic import DatasetSpec
    spec = DatasetSpec("ophstream", n=64, D=2**12, avg_nnz=30,
                       n_prototypes=2, overlap=0.5, seed=1)
    paths = make_sharded_dataset(spec, str(tmp_path / "raw"), n_shards=2)
    from repro.data.pipeline import read_shard_binary
    n_total = sum(len(read_shard_binary(p)[1]) for p in paths)
    oph = OPH.create(jax.random.PRNGKey(0), 64, 12, "2u", "rotation")
    stream = SignatureStream(paths, oph, b=4, chunk_size=32)
    chunks = list(stream)
    assert stream.examples == n_total > 0
    assert sum(sig.shape[0] for sig, _ in chunks) == n_total
    assert all(sig.shape[1] == 64 for sig, _ in chunks)
    assert all(int(jnp.max(sig)) < 16 for sig, _ in chunks)
    assert stream.kernel_seconds > 0
