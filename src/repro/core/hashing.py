"""Universal hash families used by (b-bit) minwise hashing.

This module implements the paper's three hashing schemes:

  * full random permutations (the "gold standard" -- storable only for
    small D; used to validate the simple hash families),
  * 2-universal (2U) multiply-shift hashing without modulo ops (Eq. 10),
  * 4-universal (4U) polynomial hashing over the Mersenne prime
    p = 2^31 - 1, with the modulo replaced by the paper's §3.4 ``BitMod``
    shift/mask/conditional-subtract sequence.

All arithmetic is 32-bit (TPU-native), the TPU adaptation of the paper's
"avoid modulo operations" GPU tricks.  The reference 4U path
(``hash4u_apply``) evaluates the polynomial by Horner's rule, each step a
64-bit product emulated with 16-bit-limb long multiplication
(``umul32_wide``) and reduced by ``BitMod`` (``mod_mersenne31``).

The 4U kernel evaluates the same polynomial as ``a1 + a2 x + a3 x^2 +
a4 x^3 (mod p)`` instead: x, x^2 mod p and x^3 mod p depend only on the
id, so their 16-bit limbs are computed once per tile, and each hash
function then costs three scalar-by-vector products.  A product is kept
as exact 16-bit-aligned words (``mul31_words``), never as a (hi, lo)
pair; the three products' words are summed word by word, and because
``2^31 = 1`` and ``2^32 = 2 (mod p)`` the sums reduce below 2p with
shifts and masks (``fold_words31``) and to [0, p) with one add-carry
step (``mod_mersenne31_2p``); ``hash4u_limbs`` is the whole hash.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

MERSENNE_P = np.uint32(2**31 - 1)  # p = 2^31 - 1, the paper's §3.4 prime
_U32 = jnp.uint32


# ---------------------------------------------------------------------------
# 32-bit building blocks (shared by jnp reference paths and Pallas kernels)
# ---------------------------------------------------------------------------

def umul32_wide(a: jax.Array, b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Full 32x32 -> 64 bit product as a ``(hi, lo)`` pair of uint32.

    Emulated with 16-bit limbs so it lowers to plain uint32 ops (TPU has no
    64-bit integer unit; this is the standard ``umulhi`` emulation).
    """
    a = a.astype(_U32)
    b = b.astype(_U32)
    mask16 = _U32(0xFFFF)
    a_lo, a_hi = a & mask16, a >> 16
    b_lo, b_hi = b & mask16, b >> 16
    ll = a_lo * b_lo
    lh = a_lo * b_hi
    hl = a_hi * b_lo
    hh = a_hi * b_hi
    mid1 = lh + (ll >> 16)          # <= 2^32 - 2^17 + 2^16, no overflow
    mid2 = hl + (mid1 & mask16)     # no overflow
    hi = hh + (mid1 >> 16) + (mid2 >> 16)
    lo = (mid2 << 16) | (ll & mask16)
    return hi, lo


def add64(hi: jax.Array, lo: jax.Array, c: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``(hi, lo) + c`` with carry, all uint32."""
    c = c.astype(_U32)
    new_lo = lo + c
    carry = (new_lo < c).astype(_U32)
    return hi + carry, new_lo


def mod_mersenne31(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """``(hi * 2^32 + lo) mod (2^31 - 1)`` for values < 2^62.

    Branch-free transliteration of the paper's §3.4 ``BitMod``:
    two fold steps ``v = (v >> 31) + (v & p)`` followed by one conditional
    subtract.  The first fold is done directly on the (hi, lo) pair:
    ``v >> 31 == (hi << 1) | (lo >> 31)`` and ``v & p == lo & p``.
    """
    p = _U32(MERSENNE_P)
    # fold 1: requires hi < 2^30, guaranteed for products of values < 2^31.
    v1 = ((hi << 1) | (lo >> 31)) + (lo & p)      # < 2^32
    # fold 2
    v2 = (v1 >> 31) + (v1 & p)                    # <= 2^31
    return jnp.where(v2 >= p, v2 - p, v2)


def mulmod_mersenne31(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a * b mod (2^31 - 1)`` for a, b < 2^31, all in uint32."""
    hi, lo = umul32_wide(a, b)
    return mod_mersenne31(hi, lo)


def mul31_words(al, ah, yl, yh):
    """``a * y`` as exact words ``(w32, w16, w0)``: ``a * y = w32 * 2^32 +
    w16 * 2^16 + w0`` with ``w16, w0 < 2^16`` and ``w32 < 2^30``.

    ``a = ah * 2^16 + al`` and ``y = yh * 2^16 + yl`` are below 2^31
    (16-bit low limbs, 15-bit high limbs); either side may be a scalar.
    The middle sum ``ah yl + al yh + (al yl >> 16)`` is below 2^32 - 2^17.
    """
    low = al * yl
    mid = ah * yl + al * yh + (low >> 16)
    return ah * yh + (mid >> 16), mid & _U32(0xFFFF), low & _U32(0xFFFF)


def fold_words31(w32, w16, w0):
    """A value ``x < 2^31 + 2^30 + 2^17`` (below 2p) with ``x = w32 * 2^32 +
    w16 * 2^16 + w0 (mod p)``, for any uint32 ``w32``, ``w16`` and
    ``w0 < 2^30``.

    ``2^32 = 2`` and ``2^31 = 1 (mod p)``: ``2 w32`` is its top two bits
    plus ``(w32 << 1) & p``, and ``2^16 w16`` is ``w16 >> 15`` plus
    ``(w16 << 16) & p``.  The two masked parts sum to at most
    ``2^32 - 2^16 - 2`` and fold once below 2^31; the rest is small.
    """
    p = _U32(MERSENNE_P)
    t = ((w32 << 1) & p) + ((w16 << 16) & p)
    f = (t >> 31) + (t & p)                                  # < 2^31
    return f + w0 + (w16 >> 15) + (w32 >> 30)


def mod_mersenne31_2p(x, mask=MERSENNE_P):
    """``(x mod p) & mask`` for ``x < 2p`` and a mask of low bits: adding
    the carry of ``x + 1`` past bit 31 subtracts p exactly when
    ``x >= p``, once bit 31 is masked off."""
    return (x + ((x + _U32(1)) >> 31)) & _U32(mask)


def limbs16(v):
    """``(v & 0xFFFF, v >> 16)``, the limbs ``mul31_words`` takes."""
    return v & _U32(0xFFFF), v >> 16


def word16_mersenne31(a):
    """A word ``c < 2^31`` with ``c * 2^16 = a (mod p)`` for ``a < 2^31``:
    its halves swapped, since ``2^16 * 2^15 = 2^31 = 1``."""
    return (a >> 16) | ((a & _U32(0xFFFF)) << 15)


def hash4u_limbs(a1_word16, a_limbs, y_limbs, mask):
    """``(a1 + a2 y2 + a3 y3 + a4 y4 mod p) & mask`` from limbs: the 4U
    hash when ``y`` is x, x^2 mod p, x^3 mod p.

    ``a_limbs`` holds the ``limbs16`` of a2, a3, a4 (< 2^31), ``y_limbs``
    those of the three ``y`` (< 2^31), ``a1_word16`` is
    ``word16_mersenne31(a1)``, and ``mask`` is ``2^s - 1`` for s <= 31.
    The word sums stay in uint32: ``w32 < 3 * 2^30``, ``w16 < 2^31 +
    3 * 2^16``, ``w0 < 3 * 2^16``.
    """
    w32 = w0 = None
    w16 = a1_word16
    for (al, ah), (yl, yh) in zip(a_limbs, y_limbs):
        h, m, lo = mul31_words(al, ah, yl, yh)
        w16 = w16 + m
        w32, w0 = (h, lo) if w32 is None else (w32 + h, w0 + lo)
    return mod_mersenne31_2p(fold_words31(w32, w16, w0), mask)


# ---------------------------------------------------------------------------
# Hash families
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Hash2U:
    """2-universal multiply-shift family (paper Eq. 10, Dietzfelbinger).

    ``h_j(t) = ((a1_j + a2_j * t) mod 2^32) >> (32 - s)`` with ``a2`` odd.

    We default to the *high-bits* variant (the form proven 2U in [14]);
    ``variant="low"`` gives the paper's literal ``mod 2^s`` form.  Output
    range is ``[0, 2^s) == [0, D)``.
    """

    a1: jax.Array   # (k,) uint32
    a2: jax.Array   # (k,) uint32, odd
    s: int          # D = 2^s
    variant: str = "high"

    @property
    def k(self) -> int:
        return self.a1.shape[0]

    @property
    def D(self) -> int:
        return 1 << self.s

    @staticmethod
    def create(key: jax.Array, k: int, s: int, variant: str = "high") -> "Hash2U":
        if not (1 <= s <= 32):
            raise ValueError(f"need 1 <= s <= 32, got {s}")
        k1, k2 = jax.random.split(key)
        a1 = jax.random.bits(k1, (k,), jnp.uint32)
        a2 = jax.random.bits(k2, (k,), jnp.uint32) | _U32(1)
        return Hash2U(a1=a1, a2=a2, s=s, variant=variant)

    def __call__(self, t: jax.Array) -> jax.Array:
        """Hash indices ``t`` (any shape, int) with all k functions.

        Returns shape ``t.shape + (k,)`` uint32 in ``[0, 2^s)``.
        """
        t = t.astype(_U32)[..., None]
        v = self.a1 + self.a2 * t           # wraps mod 2^32
        if self.variant == "high":
            return v >> _U32(32 - self.s) if self.s < 32 else v
        return v & _U32((1 << self.s) - 1) if self.s < 32 else v

    def apply_one(self, t: jax.Array, j_a1: jax.Array, j_a2: jax.Array) -> jax.Array:
        """Single-function form used inside kernels: coefficients passed in."""
        v = j_a1 + j_a2 * t.astype(_U32)
        if self.variant == "high":
            return v >> _U32(32 - self.s) if self.s < 32 else v
        return v & _U32((1 << self.s) - 1) if self.s < 32 else v


def hash2u_apply(t: jax.Array, a1: jax.Array, a2: jax.Array, s: int,
                 variant: str = "high") -> jax.Array:
    """Functional 2U hash: broadcast ``a1``/``a2`` against ``t``."""
    v = a1.astype(_U32) + a2.astype(_U32) * t.astype(_U32)
    if s >= 32:
        return v
    if variant == "high":
        return v >> _U32(32 - s)
    return v & _U32((1 << s) - 1)


@dataclasses.dataclass(frozen=True)
class Hash4U:
    """4-universal polynomial family over p = 2^31 - 1 (paper Eq. 9 + §3.4).

    ``h_j(t) = ((sum_i a_{i,j} t^{i-1}) mod p) mod D`` evaluated by Horner's
    rule; every ``mod p`` uses the Mersenne ``BitMod`` trick, and the final
    ``mod D`` is a mask when D is a power of two (``use_bitmod=True``), or a
    true modulo for the reference/validation path (``use_bitmod=False``,
    the paper's "4U (Mod)" row in Table 2).
    """

    a: jax.Array    # (4, k) uint32, coefficients < p
    s: int          # D = 2^s, s <= 31
    use_bitmod: bool = True

    @property
    def k(self) -> int:
        return self.a.shape[1]

    @property
    def D(self) -> int:
        return 1 << self.s

    @staticmethod
    def create(key: jax.Array, k: int, s: int, use_bitmod: bool = True) -> "Hash4U":
        if not (1 <= s <= 31):
            raise ValueError(f"4U over p=2^31-1 needs s <= 31, got {s}")
        a = jax.random.bits(key, (4, k), jnp.uint32) % _U32(MERSENNE_P)
        return Hash4U(a=a, s=s, use_bitmod=use_bitmod)

    def __call__(self, t: jax.Array) -> jax.Array:
        """Hash indices ``t``; returns ``t.shape + (k,)`` uint32 in [0, 2^s)."""
        return hash4u_apply(t[..., None], self.a[0], self.a[1], self.a[2],
                            self.a[3], self.s, self.use_bitmod)


def hash4u_apply(t: jax.Array, a1: jax.Array, a2: jax.Array, a3: jax.Array,
                 a4: jax.Array, s: int, use_bitmod: bool = True) -> jax.Array:
    """Horner evaluation of the 4U polynomial, all uint32.

    ``h = ((a4 t^3 + a3 t^2 + a2 t + a1) mod p) mod 2^s``.
    Inputs must satisfy ``t < 2^31`` and coefficients ``< p``.
    """
    t = t.astype(_U32)
    acc = jnp.broadcast_to(a4.astype(_U32), jnp.broadcast_shapes(t.shape, a4.shape))
    for coef in (a3, a2, a1):
        hi, lo = umul32_wide(acc, t)             # acc * t < 2^62
        hi, lo = add64(hi, lo, coef.astype(_U32))
        if use_bitmod:
            acc = mod_mersenne31(hi, lo)
        else:
            # Reference "Mod" path: same mathematical value, computed with
            # the double-fold as well (there is no 64-bit % on TPU); kept
            # separate so benchmarks can cost the two variants differently.
            acc = _slow_mod_mersenne31(hi, lo)
    mask = _U32((1 << s) - 1) if s < 31 else _U32(MERSENNE_P)
    return acc & mask if s < 31 else acc % _U32(MERSENNE_P)


def _slow_mod_mersenne31(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """Generic (hi,lo) mod p via remainder chains -- the 'Mod' baseline.

    Emulates a true 64-bit modulo using 32-bit ops only:
    v mod p = ((hi mod p) * (2^32 mod p) + lo mod p) mod p.
    """
    p = _U32(MERSENNE_P)
    two32_mod_p = _U32((2**32) % int(MERSENNE_P))  # == 2
    hi_m = hi % p
    term = mulmod_mersenne31(hi_m, two32_mod_p)
    lo_m = lo % p
    v = term + lo_m                     # < 2p < 2^32
    return jnp.where(v >= p, v - p, v)


# ---------------------------------------------------------------------------
# Full random permutations (gold standard, small D only)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PermutationFamily:
    """k independent uniformly random permutations of [0, D).

    Storage is O(k * D) -- exactly the paper's Issue 3.  Only usable for
    small D (tests / the webspam-scale validation of §4).
    """

    perms: jax.Array   # (k, D) int32; perms[j, t] = pi_j(t)

    @property
    def k(self) -> int:
        return self.perms.shape[0]

    @property
    def D(self) -> int:
        return self.perms.shape[1]

    @staticmethod
    def create(key: jax.Array, k: int, D: int) -> "PermutationFamily":
        keys = jax.random.split(key, k)
        perms = jax.vmap(lambda kk: jax.random.permutation(kk, D))(keys)
        return PermutationFamily(perms=perms.astype(jnp.int32))

    def __call__(self, t: jax.Array) -> jax.Array:
        """Returns ``t.shape + (k,)`` permuted values."""
        # perms: (k, D); t: (...,) -> out (..., k)
        out = self.perms[:, t]                       # (k, ...)
        return jnp.moveaxis(out, 0, -1).astype(jnp.uint32)

    def storage_bytes(self) -> int:
        return int(self.k) * int(self.D) * 4


def family_storage_bytes(family) -> int:
    """Coefficient storage -- the paper's Issue-3 comparison."""
    if isinstance(family, PermutationFamily):
        return family.storage_bytes()
    if isinstance(family, Hash2U):
        return 2 * family.k * 4
    if isinstance(family, Hash4U):
        return 4 * family.k * 4
    base = getattr(family, "base", None)   # OPH: ONE function's coefficients
    if base is not None:
        return family_storage_bytes(base)
    raise TypeError(type(family))
