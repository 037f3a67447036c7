"""Pallas TPU kernels for minwise-hash signature computation.

This is the TPU adaptation of the paper's §3 GPU preprocessing kernel.

Mapping of the paper's GPU design onto TPU v5e:

  paper (CUDA, Tesla C2050)            this kernel (Pallas, TPU)
  -----------------------------------  -----------------------------------
  chunk of 10K sets copied to GPU mem  (BLK_T, BLK_N) index tiles DMA'd
                                       HBM -> VMEM via BlockSpec
  SIMD threads over (element, hash j)  VPU lanes over BLK_N examples,
                                       sublanes over BLK_T nonzeros; one
                                       hash function j at a time, its
                                       coefficients scalars in SMEM
  per-set running minima in registers  running-min accumulator in a VMEM
                                       scratch tile, one row per hash
                                       function (grid's innermost
                                       "arbitrary" dim iterates nnz chunks)
  avoid % via 2^32 overflow (Eq. 10)   identical uint32 wraparound +
                                       multiply-shift
  avoid % via BitMod, p = 2^31-1       4U kernel: the limbs of x, x^2 mod p
                                       and x^3 mod p made once per tile;
                                       per hash three scalar-limb x vector-
                                       limb products kept as 16-bit-aligned
                                       words, summed, folded with 2^31 = 1
                                       and 2^32 = 2 (mod p), one add-carry
                                       step to [0, p): no 64-bit product is
                                       built (TPU has no 64-bit integer
                                       unit; ``core.hashing.hash4u_limbs``)

Layout.  The wrapper hands the kernel the indices transposed, (nnz, n):
examples on the 128-lane axis, nonzeros on sublanes.  For hash j the
(BLK_T, BLK_N) tile hashes in full vregs and its minimum over sublanes is
one (1, BLK_N) row -- no lane-to-sublane relayout.  Outputs come out as
(k, n) and the wrapper transposes them back.

Grid = (n/BLK_N, k/BLK_K, nnz/BLK_T); the last axis accumulates
("parallel", "parallel", "arbitrary").  Mosaic has no unsigned min, so the
running min is kept in int32 after the order-preserving bias
``x ^ 0x80000000``.

Padding is communicated via per-example nonzero counts: sublane t of
example i is valid iff ``t < counts[i]``; invalid sublanes hash to
0xFFFFFFFF so they never win the min.  The 4U kernel's hashes are below
2^31, so it keeps them unbiased, ORs a per-tile pad word (0x7FFFFFFF on
invalid sublanes) into them, and hands out 0xFFFFFFFF where no slot was
valid.  If ``b > 0`` the lowest-b-bit extraction (the *b-bit* step) is
fused into the final grid iteration; with ``pack=True`` that step instead
emits the bit-packed words (``repro.kernels.pack.pack_block``), so
signatures leave the kernel in the paper's k*b-bit wire format.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import (add64, hash2u_apply, hash4u_limbs, limbs16,
                                mod_mersenne31, mulmod_mersenne31,
                                umul32_wide, word16_mersenne31)
from repro.kernels.pack import pack_block, pack_row

_U32 = jnp.uint32
# numpy scalars (not traced jax arrays) so kernels don't capture constants
_BIAS = np.uint32(0x80000000)
_IMAX = np.int32(0x7FFFFFFF)          # biased 0xFFFFFFFF: never wins a min
# rows of a tile the 4U body hashes at once: on a v5e the k=512 kernel took
# 0.30 s a 40,960 x 1,280 chunk in 32-row strips, 0.41 s over whole tiles
_STRIP = 32


def _biased(h: jax.Array) -> jax.Array:
    """uint32 -> int32 with the same order (Mosaic has no unsigned min)."""
    return jax.lax.bitcast_convert_type(h ^ _BIAS, jnp.int32)


def _unbiased(v: jax.Array) -> jax.Array:
    """Inverse of ``biased``."""
    return jax.lax.bitcast_convert_type(v, _U32) ^ _BIAS


def _valid_rows(counts_ref, shape, t0) -> jax.Array:
    """(BLK_T, BLK_N) mask: nonzero slot t0 + t of example i is real."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0) + t0 < counts_ref[...]


def _init_acc(acc_ref) -> None:
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.full(acc_ref.shape, _IMAX, jnp.int32)


def _min_into(acc_ref, row, vals) -> None:
    """acc[row] = min(acc[row], min over sublanes of ``vals``)."""
    m = jnp.min(vals, axis=0, keepdims=True)
    acc_ref[pl.ds(row, 1), :] = jnp.minimum(acc_ref[pl.ds(row, 1), :], m)


# ---------------------------------------------------------------------------
# 4U hash of one function on a (BLK_T, BLK_N) tile by Horner's rule, with
# scalar coefficients: the OPH kernel's (the k-pass 4U body below hoists
# the per-id work; 2U is ``repro.core.hashing.hash2u_apply`` as is)
# ---------------------------------------------------------------------------

def _hash4u(t, a1, a2, a3, a4, *, s: int):
    # Horner: acc = ((a4 t + a3) t + a2) t + a1, each step mod p via BitMod.
    acc = jnp.full(t.shape, a4, _U32)
    for coef in (a3, a2, a1):
        hi, lo = umul32_wide(acc, t)                      # acc*t < 2^62
        hi, lo = add64(hi, lo, jnp.full(lo.shape, coef, _U32))
        acc = mod_mersenne31(hi, lo)
    return acc & _U32((1 << s) - 1) if s < 31 else acc


# ---------------------------------------------------------------------------
# Kernel body
# ---------------------------------------------------------------------------

def _minhash_kernel(counts_ref, idx_ref, coef_ref, out_ref, acc_ref, *,
                    hash_fn, b: int, blk_t: int, blk_k: int, pack: bool):
    _init_acc(acc_ref)
    # grid indices are read outside the loop body: the interpreter only
    # binds them at the kernel's top level
    j0 = pl.program_id(1) * blk_k
    t0 = pl.program_id(2) * blk_t
    n_coef = coef_ref.shape[0]

    def one_hash(j, carry):
        t = idx_ref[...].astype(_U32)                     # (BLK_T, BLK_N)
        h = hash_fn(t, *[coef_ref[c, j0 + j] for c in range(n_coef)])
        valid = _valid_rows(counts_ref, t.shape, t0)
        row = pack_row(j, blk_k, b) if pack else j
        _min_into(acc_ref, row, jnp.where(valid, _biased(h), _IMAX))
        return carry

    jax.lax.fori_loop(0, blk_k, one_hash, 0)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _emit():
        sig = _unbiased(acc_ref[...])
        if b > 0:
            sig = sig & _U32((1 << b) - 1)
        out_ref[...] = pack_block(sig, b) if pack else sig


def _minhash4u_kernel(counts_ref, idx_ref, coef_ref, out_ref, acc_ref,
                      pad_ref, *limb_refs, s: int, b: int, blk_t: int,
                      blk_k: int, pack: bool):
    """The 4U k-pass body.  Once per grid step it writes the per-id work
    to VMEM: the pad word and the 16-bit limbs of x, x^2 mod p and x^3
    mod p.  Each hash j then reads them strip by strip (``hash4u_limbs``:
    three products, no 64-bit pair) and keeps its hashes, all below
    2^31, unbiased in ``acc_ref``."""
    _init_acc(acc_ref)
    j0 = pl.program_id(1) * blk_k
    t0 = pl.program_id(2) * blk_t
    limb_pairs = list(zip(limb_refs[::2], limb_refs[1::2]))
    x = idx_ref[...].astype(_U32)
    x2 = mulmod_mersenne31(x, x)
    for y, (lo_ref, hi_ref) in zip((x, x2, mulmod_mersenne31(x2, x)),
                                   limb_pairs):
        lo_ref[...], hi_ref[...] = limbs16(y)
    pad_ref[...] = jnp.where(_valid_rows(counts_ref, x.shape, t0), _U32(0),
                             _U32(_IMAX))
    mask = (1 << min(s, 31)) - 1
    strip = _STRIP if blk_t % _STRIP == 0 else blk_t

    def one_hash(j, carry):
        a1w = word16_mersenne31(coef_ref[0, j0 + j])
        a_limbs = [limbs16(coef_ref[c, j0 + j]) for c in (1, 2, 3)]
        m = None
        for r in range(0, blk_t, strip):
            rows = pl.ds(r, strip)
            y_limbs = [(lo[rows, :], hi[rows, :]) for lo, hi in limb_pairs]
            h = hash4u_limbs(a1w, a_limbs, y_limbs, mask) | pad_ref[rows, :]
            h = jax.lax.bitcast_convert_type(h, jnp.int32)
            m = h if m is None else jnp.minimum(m, h)
        row = pack_row(j, blk_k, b) if pack else j
        _min_into(acc_ref, row, m)
        return carry

    jax.lax.fori_loop(0, blk_k, one_hash, 0)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _emit():
        acc = acc_ref[...]
        sig = jnp.where(acc == _IMAX, _U32(0xFFFFFFFF),
                        jax.lax.bitcast_convert_type(acc, _U32))
        if b > 0:
            sig = sig & _U32((1 << b) - 1)
        out_ref[...] = pack_block(sig, b) if pack else sig


# ---------------------------------------------------------------------------
# pallas_call builders
# ---------------------------------------------------------------------------

def _compiler_params(*semantics: str) -> dict:
    """TPU compiler params declaring which grid axes are reductions."""
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics)}


def _row_major_specs(n, nnz, k, blk_n, blk_t, blk_k):
    """Grid and the (counts, indices, out) specs of the transposed layout:
    indices (nnz, n), counts (1, n), out (k, n)."""
    if n % blk_n or nnz % blk_t or k % blk_k:
        raise ValueError(
            f"shapes must tile: n={n}%{blk_n}, nnz={nnz}%{blk_t}, k={k}%{blk_k}")
    grid = (n // blk_n, k // blk_k, nnz // blk_t)
    counts_spec = pl.BlockSpec((1, blk_n), lambda i, j, t: (0, i))
    idx_spec = pl.BlockSpec((blk_t, blk_n), lambda i, j, t: (t, i))
    out_spec = pl.BlockSpec((blk_k, blk_n), lambda i, j, t: (j, i))
    return grid, counts_spec, idx_spec, out_spec


def _minhash_call(indices, counts, coef, body, *, b, blk_n, blk_t, blk_k,
                  pack, interpret, tiles: int = 0):
    """``body``: a kernel body with its hash bound; ``tiles`` extra
    (blk_t, blk_n) VMEM scratch tiles it takes after the accumulator."""
    n, nnz = indices.shape
    k = coef.shape[1]
    grid, counts_spec, idx_spec, out_spec = _row_major_specs(
        n, nnz, k, blk_n, blk_t, blk_k)
    out_rows = k
    if pack:
        if b <= 0 or 32 % b or (blk_k * b) % 32:
            raise ValueError(f"fused pack needs b | 32 and blk_k*b % 32 == 0, "
                             f"got b={b}, blk_k={blk_k}")
        out_rows = k * b // 32
        out_spec = pl.BlockSpec((blk_k * b // 32, blk_n),
                                lambda i, j, t: (j, i))
    kern = functools.partial(body, b=b, blk_t=blk_t, blk_k=blk_k, pack=pack)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[counts_spec, idx_spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((out_rows, n), _U32),
        scratch_shapes=[pltpu.VMEM((blk_k, blk_n), jnp.int32)]
        + [pltpu.VMEM((blk_t, blk_n), _U32)] * tiles,
        interpret=interpret,
        **_compiler_params("parallel", "parallel", "arbitrary"),
    )(counts.reshape(1, n), indices.T, coef)
    return out.T


def minhash2u_pallas(indices: jax.Array, counts: jax.Array, a1: jax.Array,
                     a2: jax.Array, *, s: int, b: int = 0,
                     blk_n: int = 128, blk_t: int = 128, blk_k: int = 128,
                     variant: str = "high", pack: bool = False,
                     interpret: bool):
    """2U minhash signatures: (n, nnz) indices -> (n, k) uint32 minima.

    Args:
      indices: (n, max_nnz) int32, padded; n, nnz and k must tile.
      counts:  (n, 1) int32 valid-slot counts per example.
      a1, a2:  (k,) uint32 multiply-shift coefficients (a2 odd).
      s:       D = 2^s.
      b:       if > 0, fuse lowest-b-bit extraction into the last step.
      pack:    emit the bit-packed (n, k*b/32) words from the final grid
               step instead of the (n, k) signatures.
      interpret: run the Pallas interpreter (CPU) instead of Mosaic.
    """
    hash_fn = functools.partial(hash2u_apply, s=s, variant=variant)
    body = functools.partial(_minhash_kernel, hash_fn=hash_fn)
    return _minhash_call(indices, counts, jnp.stack([a1, a2]), body, b=b,
                         blk_n=blk_n, blk_t=blk_t, blk_k=blk_k, pack=pack,
                         interpret=interpret)


def minhash4u_pallas(indices: jax.Array, counts: jax.Array, a: jax.Array, *,
                     s: int, b: int = 0, blk_n: int = 128, blk_t: int = 128,
                     blk_k: int = 128, pack: bool = False,
                     interpret: bool):
    """4U minhash signatures with in-kernel Mersenne BitMod (§3.4);
    a: (4, k) uint32 coefficients below 2^31, ids below 2^31, s <= 31.
    Same layout and options as ``minhash2u_pallas``."""
    body = functools.partial(_minhash4u_kernel, s=s)
    return _minhash_call(indices, counts, a, body, b=b, blk_n=blk_n,
                         blk_t=blk_t, blk_k=blk_k, pack=pack,
                         interpret=interpret, tiles=7)
