"""Packed b-bit wire format for signatures: spec + device-side epilogues.

The paper's §6/Table-2 systems claim is that b-bit hashing shrinks what
*moves*: k·b bits per example on the wire and on disk, not k uint32
lanes.  This module defines that wire format once so the kernels, the
engine, the cache shards and the learning layer all agree:

  * ``PackSpec``        -- (k, b, sentinel) -> code width and word count.
                           Plain signatures pack b-bit codes; sentinel
                           OPH packs (b+1)-bit codes with EMPTY stored as
                           the value 2^b (no aliasing with genuine b-bit
                           values, no unpacked escape hatch).
  * ``encode_sentinel`` / ``decode_sentinel`` -- EMPTY <-> 2^b mapping.
  * ``pack_device`` / ``unpack_device`` -- jnp pack/unpack epilogues,
    meant to be traced *inside* the same jit as the kernel (pack) or the
    SGD step (unpack) so only packed words ever cross the host boundary.
  * ``pack_row`` / ``pack_block`` -- the in-kernel packing epilogue of
    ``kernels/minhash.py``: the kernel stores code j of a k-block in row
    ``pack_row(j)`` of a (BLK_K, rows) tile, and ``pack_block`` turns that
    tile into (BLK_K*b/32, rows) words with whole-tile shifts only.

Bit layout (shared with ``repro.core.bbit.pack_codes``): code j occupies
bits [j*code_bits, (j+1)*code_bits) of the row's little-endian bitstream.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.bbit import pack_codes, packed_words, unpack_codes
from repro.core.oph import EMPTY


@dataclasses.dataclass(frozen=True)
class PackSpec:
    """Static description of one packed-signature wire format."""

    k: int                 # signature length (values per example)
    b: int                 # b-bit width of genuine values
    sentinel: bool = False  # True: OPH sentinel scheme, EMPTY coded as 2^b

    def __post_init__(self):
        if not 1 <= self.b <= 16:
            raise ValueError(f"packed wire format needs 1 <= b <= 16, "
                             f"got b={self.b}")

    @property
    def code_bits(self) -> int:
        return self.b + 1 if self.sentinel else self.b

    @property
    def words(self) -> int:
        return packed_words(self.k, self.code_bits)

    @property
    def empty_code(self) -> int:
        return 1 << self.b

    def bytes_per_example(self) -> int:
        return 4 * self.words


def encode_sentinel(sig: jax.Array, b: int) -> jax.Array:
    """b-bit values with EMPTY markers -> (b+1)-bit codes (EMPTY = 2^b)."""
    mask_b = jnp.uint32((1 << b) - 1)
    return jnp.where(sig == EMPTY, jnp.uint32(1 << b),
                     sig.astype(jnp.uint32) & mask_b)


def decode_sentinel(codes: jax.Array, b: int) -> jax.Array:
    """(b+1)-bit codes -> b-bit values with EMPTY restored."""
    return jnp.where(codes == jnp.uint32(1 << b), EMPTY,
                     codes.astype(jnp.uint32))


def pack_device(sig: jax.Array, spec: PackSpec) -> jax.Array:
    """(n, k) signature values -> (n, spec.words) uint32 words.

    ``sig`` carries b-bit values (sentinel schemes: b-bit values + EMPTY
    markers).  Trace this inside the kernel wrapper's jit so the packed
    words are what leaves the device.
    """
    if sig.shape[-1] != spec.k:
        raise ValueError(f"sig has k={sig.shape[-1]}, spec has k={spec.k}")
    codes = encode_sentinel(sig, spec.b) if spec.sentinel else sig
    return pack_codes(codes, spec.code_bits)


def unpack_device(packed: jax.Array, spec: PackSpec) -> jax.Array:
    """(n, spec.words) uint32 words -> (n, k) values, EMPTY restored."""
    codes = unpack_codes(packed, spec.code_bits, spec.k)
    return decode_sentinel(codes, spec.b) if spec.sentinel else codes


def can_pack_in_kernel(k_pad: int, k: int, b: int, blk_k: int) -> bool:
    """True when the kernel's final grid step can emit packed words
    directly: lane-aligned codes (b | 32), no sliced padding codes, and a
    packed block of whole words whose height is a multiple of the 8-row
    sublane tile or the whole packed signature."""
    if not (0 < b <= 16 and 32 % b == 0 and k_pad == k
            and (blk_k * b) % 32 == 0):
        return False
    return (blk_k * b // 32) % 8 == 0 or blk_k == k


def pack_row(j, blk_k: int, b: int):
    """Tile row that holds code j (0 <= j < blk_k) of a k-block.

    Code j belongs to word j // (32/b) at field j % (32/b).  Storing it
    at row ``field * words + word`` makes every field a contiguous run of
    ``words`` rows, so ``pack_block`` needs no strided or lane access.
    Works on Python ints and traced int32 scalars alike.
    """
    per_word = 32 // b
    return (j % per_word) * (blk_k // per_word) + j // per_word


def pack_block(tile: jax.Array, b: int) -> jax.Array:
    """In-kernel epilogue: (BLK_K, rows) b-bit tile in ``pack_row`` order
    -> (BLK_K*b/32, rows) uint32 words, word-major per row.

    Requires ``can_pack_in_kernel``.  Word w of a row is
    ``sum_f code[w*(32/b) + f] << (f*b)``, the ``repro.core.bbit.pack_codes``
    bitstream for b | 32, so host-side unpacking is one shared code path
    regardless of where the packing ran.
    """
    per_word = 32 // b
    words = tile.shape[0] // per_word
    out = tile[:words]
    for f in range(1, per_word):
        out = out | (tile[f * words:(f + 1) * words] << jnp.uint32(f * b))
    return out
