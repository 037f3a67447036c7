"""Property tests for the universal-hash building blocks.

Seeded parametrized sweeps (numpy RNG) instead of hypothesis: each case
draws a large batch of random operands -- including the adversarial
boundary values hypothesis would shrink to -- and checks the exact
arithmetic invariant against 64-bit numpy.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hashing import (Hash2U, Hash4U, MERSENNE_P, add64,
                                fold_words31, hash2u_apply, hash4u_apply,
                                hash4u_limbs, limbs16, mod_mersenne31,
                                mod_mersenne31_2p, mul31_words,
                                mulmod_mersenne31, umul32_wide,
                                word16_mersenne31, PermutationFamily,
                                family_storage_bytes)

# boundary values every sweep mixes in (what hypothesis would find)
_EDGES_U32 = np.array([0, 1, 2, 0xFFFF, 0x10000, 0x7FFFFFFF, 0x80000000,
                       0xFFFFFFFE, 0xFFFFFFFF], np.uint32)
_EDGES_U31 = np.array([0, 1, 2, 0xFFFF, 0x10000, 2**31 - 2, 2**31 - 1],
                      np.uint32)


def _draw(rng, size, hi, edges):
    vals = rng.integers(0, hi, size, dtype=np.uint64).astype(np.uint32)
    vals[: len(edges)] = edges
    return rng.permutation(vals)


@pytest.mark.parametrize("seed", range(3))
def test_umul32_wide_matches_uint64(seed):
    rng = np.random.default_rng(seed)
    a = _draw(rng, 500, 2**32, _EDGES_U32)
    b = _draw(rng, 500, 2**32, _EDGES_U32)
    hi, lo = umul32_wide(jnp.asarray(a), jnp.asarray(b))
    prod = a.astype(np.uint64) * b.astype(np.uint64)
    assert np.array_equal(np.asarray(hi), (prod >> 32).astype(np.uint32))
    assert np.array_equal(np.asarray(lo), (prod & 0xFFFFFFFF).astype(np.uint32))


@pytest.mark.parametrize("seed", range(3))
def test_mod_mersenne31_matches_modulo(seed):
    rng = np.random.default_rng(100 + seed)
    a = _draw(rng, 500, 2**31, _EDGES_U31)
    b = _draw(rng, 500, 2**31, _EDGES_U31)
    got = np.asarray(mulmod_mersenne31(jnp.asarray(a), jnp.asarray(b)))
    want = ((a.astype(np.uint64) * b.astype(np.uint64))
            % np.uint64(2**31 - 1)).astype(np.uint32)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("seed", range(3))
def test_add64_carry(seed):
    rng = np.random.default_rng(200 + seed)
    his = _draw(rng, 200, 2**31, _EDGES_U31)
    los = _draw(rng, 200, 2**32, _EDGES_U32)
    cs = _draw(rng, 200, 2**32, _EDGES_U32)
    h, l = add64(jnp.asarray(his), jnp.asarray(los), jnp.asarray(cs))
    total = (his.astype(object) * 2**32 + los.astype(object)
             + cs.astype(object))
    got = np.asarray(h).astype(object) * 2**32 + np.asarray(l).astype(object)
    assert (got == total).all()


@pytest.mark.parametrize("s", [8, 16, 24, 30])
def test_4u_polynomial_vs_bigint(s):
    key = jax.random.PRNGKey(1)
    h4 = Hash4U.create(key, k=5, s=s)
    rng = np.random.default_rng(0)
    t = rng.integers(0, 2**s, 64, dtype=np.int64)
    out = np.asarray(h4(jnp.asarray(t)))
    A = np.asarray(h4.a).astype(object)
    p = 2**31 - 1
    for i in range(len(t)):
        for j in range(5):
            ti = int(t[i])
            val = (int(A[0, j]) + int(A[1, j]) * ti + int(A[2, j]) * ti**2
                   + int(A[3, j]) * ti**3) % p % (2**s)
            assert out[i, j] == val


# the 4U kernel's arithmetic, checked against Python integers: ids at the
# limb and Mersenne edges, coefficients at theirs
_P = 2**31 - 1
_IDS_4U = np.array([0, 1, 2**15 - 1, 2**15, 2**16 - 1, 2**16, 2**30 - 1,
                    2**30, _P - 1, _P], np.uint32)
_COEFS_4U = np.array([0, 1, 2**16 - 1, _P - 1], np.uint32)


def _ints(x):
    return np.asarray(x).astype(object)


@pytest.mark.parametrize("seed", range(3))
def test_mul31_words_exact(seed):
    rng = np.random.default_rng(300 + seed)
    ids = _draw(rng, 300, 2**31, _IDS_4U)
    coefs = _draw(rng, 40, _P, _COEFS_4U)
    a, y = (v.ravel() for v in np.meshgrid(coefs, ids))
    al, ah = limbs16(jnp.asarray(a))
    w32, w16, w0 = map(_ints, mul31_words(al, ah, *limbs16(jnp.asarray(y))))
    assert (w32 * 2**32 + w16 * 2**16 + w0 == _ints(a) * _ints(y)).all()
    assert (w32 < 2**30).all() and (w16 < 2**16).all() and (w0 < 2**16).all()


@pytest.mark.parametrize("seed", range(3))
def test_fold_words31_bound_and_residue(seed):
    rng = np.random.default_rng(400 + seed)
    w32 = _draw(rng, 500, 2**32, _EDGES_U32)
    w16 = _draw(rng, 500, 2**32, _EDGES_U32)
    w0 = _draw(rng, 500, 2**30, np.array([0, 1, 2**30 - 1], np.uint32))
    x = _ints(fold_words31(*map(jnp.asarray, (w32, w16, w0))))
    assert (x < 2**31 + 2**30 + 2**17).all()
    want = (_ints(w32) * 2**32 + _ints(w16) * 2**16 + _ints(w0)) % _P
    assert (x % _P == want).all()


@pytest.mark.parametrize("mask", [_P, 2**30 - 1, 0xFF])
def test_mod_mersenne31_2p(mask):
    x = np.array([0, 1, _P - 1, _P, _P + 1, 2**31, 2**31 + 2**30,
                  2 * _P - 1], np.uint32)
    got = _ints(mod_mersenne31_2p(jnp.asarray(x), mask))
    assert (got == [v % _P & mask for v in _ints(x)]).all()


@pytest.mark.parametrize("s", [30, 31])
def test_hash4u_limbs_matches_bigint(s):
    """The kernel's hash of x from the limbs of x, x^2 and x^3 mod p and
    the coefficients' limbs: the unreduced sum stays below 2p and has
    the polynomial's residue, and the hash is the polynomial mod p, mod
    2^s, for every coefficient edge in every position."""
    rng = np.random.default_rng(s)
    x = _draw(rng, 200, 2**31, _IDS_4U)
    xi = _ints(x)
    powers = [xi, xi * xi % _P, xi * xi * xi % _P]
    y_limbs = [limbs16(jnp.asarray(np.array(v, np.uint32))) for v in powers]
    edges = [[c] * 4 for c in _COEFS_4U] + [
        np.roll([c, 0, 1, _P - 1], r) for c in _COEFS_4U for r in range(4)]
    rand = rng.integers(0, _P, (8, 4))
    for a in [np.array(v, np.uint32) for v in [*edges, *rand]]:
        a_limbs = [limbs16(jnp.uint32(c)) for c in a[1:]]
        words = [mul31_words(al, ah, yl, yh)
                 for (al, ah), (yl, yh) in zip(a_limbs, y_limbs)]
        w32, w16, w0 = (sum(w[i] for w in words) for i in range(3))
        w16 = w16 + word16_mersenne31(jnp.uint32(a[0]))
        x_sum = _ints(fold_words31(w32, w16, w0))
        poly = (int(a[0]) + sum(int(c) * y for c, y in zip(a[1:], powers)))
        assert (x_sum < 2 * _P).all()
        assert (x_sum % _P == poly % _P).all()
        got = _ints(hash4u_limbs(word16_mersenne31(jnp.uint32(a[0])),
                                 a_limbs, y_limbs, (1 << s) - 1))
        assert (got == poly % _P % 2**s).all(), a


@pytest.mark.parametrize("variant", ["high", "low"])
def test_2u_matches_formula(variant):
    key = jax.random.PRNGKey(2)
    f = Hash2U.create(key, k=7, s=20, variant=variant)
    rng = np.random.default_rng(1)
    t = rng.integers(0, 2**20, 100, dtype=np.int64)
    out = np.asarray(f(jnp.asarray(t)))
    a1 = np.asarray(f.a1).astype(np.uint64)
    a2 = np.asarray(f.a2).astype(np.uint64)
    v = (a1[None, :] + a2[None, :] * t[:, None].astype(np.uint64)) % 2**32
    want = (v >> (32 - 20)) if variant == "high" else (v % 2**20)
    assert np.array_equal(out, want.astype(np.uint32))


def test_2u_output_range_and_determinism():
    f = Hash2U.create(jax.random.PRNGKey(0), k=16, s=10)
    t = jnp.arange(1000)
    o1, o2 = f(t), f(t)
    assert np.array_equal(np.asarray(o1), np.asarray(o2))
    assert int(jnp.max(o1)) < 2**10


def test_storage_accounting():
    key = jax.random.PRNGKey(0)
    D, k = 2**14, 100
    perm = PermutationFamily.create(key, k, D)
    h2 = Hash2U.create(key, k, 16)
    h4 = Hash4U.create(key, k, 16)
    assert family_storage_bytes(perm) == k * D * 4
    assert family_storage_bytes(h2) == 2 * k * 4
    assert family_storage_bytes(h4) == 4 * k * 4
    # the paper's Issue 3: permutations are >> hash coefficients
    assert family_storage_bytes(perm) > 1000 * family_storage_bytes(h4)
