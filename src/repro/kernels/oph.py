"""Pallas TPU kernels for One Permutation Hashing signatures.

Same transposed layout and running-min reduction as ``kernels/minhash.py``
-- indices arrive as (nnz, n) with examples on lanes, grid (n/BLK_N,
k/BLK_K, nnz/BLK_T), the last axis accumulating into a (BLK_K, BLK_N)
scratch tile with one row per bin -- but the hash work per nonzero
collapses from k evaluations to ONE: a single 2U/4U function is evaluated
on the (BLK_T, BLK_N) index tile, split into (bin, offset) bit-fields, and
each bin row takes the minimum offset over the nonzeros that fell into it
(a compare against the bin number instead of k - 1 extra hash
evaluations).

Hash evaluations per nonzero = ceil(k / BLK_K): with the default BLK_K
covering all k bins at once (k <= 512 fits one block), that is literally
one pass, versus k passes for the minhash kernels -- the paper's §3
preprocessing cost divided by k.

Empty bins come out as the 0xFFFFFFFF sentinel; densification (and b-bit
extraction, which must not destroy the sentinel before densification
reads it) happens in the thin jnp epilogue in ``kernels/engine.py``,
shared bit-for-bit with the ``core/oph.py`` reference.  For the packed
*sentinel* wire format, ``code_b > 0`` moves that b-bit step into the
kernel's final grid iteration: genuine minima are masked to b bits and
EMPTY becomes the (b+1)-bit code 2^b (``repro.kernels.pack.PackSpec``),
so the epilogue only has to bitstream-pack the codes.

Paper mapping:
  * §3.2-§3.3 (the GPU chunk kernel, re-derived for TPU): grid layout,
    VMEM tiling, running-min accumulation over the nnz axis,
  * Eq. (10) / §3.4: the in-kernel 2U multiply-shift and 4U Horner +
    Mersenne ``BitMod`` -- identical arithmetic to ``kernels/minhash.py``,
    evaluated ONCE per nonzero,
  * arXiv:1208.1259 §3: the bin/offset bit-split (``_oph_kernel``), high
    bits select the bin, low bits compete in the running min.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import hash2u_apply
from repro.kernels.minhash import (_IMAX, _biased, _compiler_params,
                                   _hash4u, _init_acc, _min_into,
                                   _row_major_specs, _unbiased, _valid_rows)

_U32 = jnp.uint32
_EMPTY = np.uint32(0xFFFFFFFF)


def _oph_kernel(counts_ref, idx_ref, coef_ref, out_ref, acc_ref, bins_ref,
                offs_ref, *, hash_fn, s: int, bin_bits: int, blk_t: int,
                blk_k: int, code_b: int):
    _init_acc(acc_ref)
    j0 = pl.program_id(1) * blk_k

    # ONE hash evaluation for the whole tile (scalar coefficients)
    t = idx_ref[...].astype(_U32)                         # (BLK_T, BLK_N)
    h = hash_fn(t, *[coef_ref[c, 0] for c in range(coef_ref.shape[0])])
    off_bits = s - bin_bits
    bins = ((h >> _U32(off_bits)).astype(jnp.int32) if bin_bits > 0
            else jnp.zeros(h.shape, jnp.int32))
    valid = _valid_rows(counts_ref, t.shape, pl.program_id(2) * blk_t)
    bins_ref[...] = jnp.where(valid, bins, -1)
    offs_ref[...] = _biased(h & _U32((1 << off_bits) - 1))

    # row j of this block owns global bin j0 + j; rows past the real bins
    # are never written and come out EMPTY
    def one_bin(j, carry):
        hit = bins_ref[...] == j0 + j
        _min_into(acc_ref, j, jnp.where(hit, offs_ref[...], _IMAX))
        return carry

    jax.lax.fori_loop(0, min(blk_k, 1 << bin_bits), one_bin, 0)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _emit():
        v = _unbiased(acc_ref[...])
        if code_b > 0:      # b-bit values + EMPTY -> (b+1)-bit codes
            v = jnp.where(v == _EMPTY, _U32(1 << code_b),
                          v & _U32((1 << code_b) - 1))
        out_ref[...] = v


def _oph_call(indices, counts, coef, hash_fn, *, s, bin_bits, blk_n, blk_t,
              blk_k, code_b, interpret):
    n, nnz = indices.shape
    k_lanes = blk_k * max(1, (1 << bin_bits) // blk_k)
    grid, counts_spec, idx_spec, out_spec = _row_major_specs(
        n, nnz, k_lanes, blk_n, blk_t, blk_k)
    kern = functools.partial(_oph_kernel, hash_fn=hash_fn, s=s,
                             bin_bits=bin_bits, blk_t=blk_t, blk_k=blk_k,
                             code_b=code_b)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[counts_spec, idx_spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((k_lanes, n), _U32),
        scratch_shapes=[pltpu.VMEM((blk_k, blk_n), jnp.int32),
                        pltpu.VMEM((blk_t, blk_n), jnp.int32),
                        pltpu.VMEM((blk_t, blk_n), jnp.int32)],
        interpret=interpret,
        **_compiler_params("parallel", "parallel", "arbitrary"),
    )(counts.reshape(1, n), indices.T, coef)
    return out.T


def oph2u_pallas(indices: jax.Array, counts: jax.Array, a1: jax.Array,
                 a2: jax.Array, *, s: int, bin_bits: int,
                 blk_n: int = 128, blk_t: int = 128, blk_k: int = 128,
                 variant: str = "high", code_b: int = 0,
                 interpret: bool) -> jax.Array:
    """2U OPH: (n, nnz) indices -> (n, k_lanes) sentinel-coded bin minima.

    Args:
      indices:  (n, max_nnz) int32, padded; n, nnz, k_lanes must tile.
      counts:   (n, 1) int32 valid-slot counts per example.
      a1, a2:   (1,) uint32 -- the ONE multiply-shift function (a2 odd).
      s:        D = 2^s.
      bin_bits: log2(number of real bins); bins >= 2^bin_bits never match
                and come out EMPTY (callers slice them off).
      code_b:   if > 0, the final grid step emits (code_b+1)-bit sentinel
                codes (EMPTY -> 2^code_b) instead of raw minima -- the
                packed-wire-format epilogue fused into the kernel.
      interpret: run the Pallas interpreter (CPU) instead of Mosaic.
    """
    hash_fn = functools.partial(hash2u_apply, s=s, variant=variant)
    coef = jnp.stack([a1.reshape(1), a2.reshape(1)])
    return _oph_call(indices, counts, coef, hash_fn, s=s, bin_bits=bin_bits,
                     blk_n=blk_n, blk_t=blk_t, blk_k=blk_k, code_b=code_b,
                     interpret=interpret)


def oph4u_pallas(indices: jax.Array, counts: jax.Array, a: jax.Array, *,
                 s: int, bin_bits: int, blk_n: int = 128, blk_t: int = 128,
                 blk_k: int = 128, code_b: int = 0,
                 interpret: bool) -> jax.Array:
    """4U OPH with in-kernel Mersenne BitMod; a: (4, 1) uint32."""
    hash_fn = functools.partial(_hash4u, s=s)
    return _oph_call(indices, counts, a.reshape(4, 1), hash_fn, s=s,
                     bin_bits=bin_bits, blk_n=blk_n, blk_t=blk_t,
                     blk_k=blk_k, code_b=code_b, interpret=interpret)
