"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hashing import Hash2U, Hash4U
from repro.kernels import batch_signatures, minhash2u, minhash4u, sigbag
from repro.kernels import ref as kref
from repro.data.sparse import from_lists

RNG = np.random.default_rng(7)


def _case(n, nnz, k, s):
    indices = jnp.asarray(RNG.integers(0, 2**s, (n, nnz)), jnp.int32)
    counts = jnp.asarray(RNG.integers(1, nnz + 1, (n,)), jnp.int32)
    return indices, counts


# full (shape x s) product in the slow tier; fast tier keeps the s=24 row
# (all padding paths) plus the aligned shape at the s extremes
_2U_CASES = [
    pytest.param(n, nnz, k, s,
                 marks=[] if (s == 24 or (n, nnz, k) == (8, 128, 128))
                 else [pytest.mark.slow])
    for n, nnz, k in [(3, 100, 20), (8, 128, 128), (17, 300, 70),
                      (5, 513, 33)]
    for s in (12, 24, 32)]


@pytest.mark.parametrize("n,nnz,k,s", _2U_CASES)
def test_minhash2u_kernel_matches_ref(n, nnz, k, s):
    indices, counts = _case(n, nnz, k, s)
    fam = Hash2U.create(jax.random.PRNGKey(n * 1000 + k), k, s)
    got = minhash2u(indices, counts, fam.a1, fam.a2, s=s)
    want = kref.minhash2u_ref(indices, counts.reshape(-1, 1), fam.a1, fam.a2,
                              s=s)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b", [1, 2, 4, 8, 12])
def test_minhash2u_fused_bbit(b):
    indices, counts = _case(6, 200, 50, 20)
    fam = Hash2U.create(jax.random.PRNGKey(b), 50, 20)
    got = minhash2u(indices, counts, fam.a1, fam.a2, s=20, b=b)
    full = kref.minhash2u_ref(indices, counts.reshape(-1, 1), fam.a1, fam.a2,
                              s=20)
    assert np.array_equal(np.asarray(got),
                          np.asarray(full) & ((1 << b) - 1))
    assert int(jnp.max(got)) < (1 << b)


@pytest.mark.parametrize("n,nnz,k,s", [
    (4, 100, 16, 16),
    pytest.param(9, 257, 40, 24, marks=pytest.mark.slow),
    (8, 128, 128, 30)])
def test_minhash4u_kernel_matches_ref(n, nnz, k, s):
    indices, counts = _case(n, nnz, k, s)
    fam = Hash4U.create(jax.random.PRNGKey(k), k, s)
    got = minhash4u(indices, counts, fam.a, s=s)
    want = kref.minhash4u_ref(indices, counts.reshape(-1, 1), fam.a, s=s)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_kernel_vs_minhash_module():
    """Pallas path == the core library path on a real SparseBatch."""
    from repro.core.minhash import minhash_signatures
    from repro.data import word_pair_sets
    D = 2**20
    s1, s2 = word_pair_sets(D, 700, 600, 0.4, seed=2)
    batch = from_lists([s1, s2])
    fam = Hash2U.create(jax.random.PRNGKey(0), 64, 20)
    via_kernel = batch_signatures(batch, fam)
    via_module = minhash_signatures(batch.indices, batch.mask, fam)
    assert np.array_equal(np.asarray(via_kernel), np.asarray(via_module))


# fast tier: fp32 small + one bf16 case; the rest of the product is slow
_SIGBAG_FAST = {(jnp.float32, 10, 16, 4, 8), (jnp.float32, 64, 500, 8, 1),
                (jnp.bfloat16, 130, 32, 6, 32)}
_SIGBAG_CASES = [
    pytest.param(dtype, n, k, b, d,
                 marks=[] if (dtype, n, k, b, d) in _SIGBAG_FAST
                 else [pytest.mark.slow])
    for dtype in (jnp.float32, jnp.bfloat16)
    for n, k, b, d in ((10, 16, 4, 8), (130, 32, 6, 32), (64, 500, 8, 1))]


@pytest.mark.parametrize("dtype,n,k,b,d", _SIGBAG_CASES)
def test_sigbag_kernel_matches_ref(dtype, n, k, b, d):
    tok = jnp.asarray(RNG.integers(0, 2**b, (n, k)), jnp.int32)
    table = jnp.asarray(RNG.normal(size=(k, 2**b, d)), dtype)
    got = sigbag(tok, table)
    want = kref.sigbag_ref(tok, table)
    rtol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=rtol,
                               atol=1e-4 if dtype == jnp.float32 else 0.3)


def test_sigbag_is_eq5_inner_product():
    """sigbag with d=1 equals the Eq.(5) one-hot expansion dot product."""
    from repro.core.bbit import expand_onehot
    k, b, n = 24, 3, 12
    tok = jnp.asarray(RNG.integers(0, 2**b, (n, k)), jnp.int32)
    w = jnp.asarray(RNG.normal(size=(k * 2**b,)), jnp.float32)
    via_kernel = np.asarray(sigbag(tok, w.reshape(k, 2**b, 1)))[:, 0]
    oh = expand_onehot(tok.astype(jnp.uint32), b)
    via_onehot = np.asarray(oh @ w)
    np.testing.assert_allclose(via_kernel, via_onehot, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Layout regressions: k=512, fused pack, ragged rows, straddling codes
# ---------------------------------------------------------------------------

def _ragged_batch(n, max_set, s, seed):
    rng = np.random.default_rng(seed)
    sets = [rng.choice(1 << s, rng.integers(1, max_set + 1), replace=False)
            for _ in range(n)]
    return from_lists(sets)


@pytest.mark.parametrize("family,b", [("2u", 1), ("2u", 2), ("2u", 4),
                                      ("2u", 8), ("2u", 16), ("4u", 8)])
def test_fused_pack_k512_ragged_rows_matches_ref(family, b):
    """k=512 over two k-blocks, 37 rows (not a multiple of the 128-row
    block) and a ragged nnz: the in-kernel pack emits the ref's words."""
    from repro.core.bbit import pack_codes
    from repro.kernels import SignatureEngine
    from repro.kernels.pack import can_pack_in_kernel
    k, s, blocks = 512, 20, {"blk_n": 128, "blk_t": 128, "blk_k": 256}
    assert can_pack_in_kernel(k, k, b, blocks["blk_k"])
    batch = _ragged_batch(37, 300, s, seed=b)
    key = jax.random.PRNGKey(b)
    fam = Hash2U.create(key, k, s) if family == "2u" else \
        Hash4U.create(key, k, s)
    got = SignatureEngine(fam, b=b, packed=True, backend="interpret",
                          blocks=blocks).packed_signatures(batch)
    counts = jnp.sum(batch.mask.astype(jnp.int32), axis=1)[:, None]
    want = (kref.minhash2u_ref(batch.indices, counts, fam.a1, fam.a2, s=s,
                               b=b) if family == "2u" else
            kref.minhash4u_ref(batch.indices, counts, fam.a, s=s, b=b))
    assert np.array_equal(np.asarray(got.data),
                          np.asarray(pack_codes(want, b)))


@pytest.mark.parametrize("k,b,sentinel", [(512, 8, False), (512, 8, True),
                                          (100, 2, True), (77, 16, False)])
def test_packed_match_layouts_match_ref(k, b, sentinel):
    """Word-aligned and word-straddling codes (9- and 3-bit sentinel
    wires), k not a multiple of 32, ragged Q/N, garbage past code k."""
    from repro.kernels import PackSpec, packed_match
    spec = PackSpec(k, b, sentinel)
    rng = np.random.default_rng(k + b)
    q = rng.integers(0, 2**32, (5, spec.words), dtype=np.uint64) \
        .astype(np.uint32)
    # corpus rows: the queries with a few words re-drawn, so most codes
    # match and some do not
    c = np.repeat(q, 40, axis=0)[:197]
    flip = rng.random(c.shape) < 0.3
    c[flip] = rng.integers(0, 2**32, int(flip.sum()), dtype=np.uint64)
    got = packed_match(q, c, spec, backend="interpret")
    want = kref.packed_match_ref(jnp.asarray(q), jnp.asarray(c), k=k,
                                 code_bits=spec.code_bits, sentinel=sentinel)
    got, want = (got, want) if sentinel else ((got,), (want,))
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    assert np.asarray(want[0]).max() > 0
