"""Pallas TPU kernel for packed-signature match counting (retrieval).

The search workload (paper §1's dedup/crawling pipeline; Li-Owen-Zhang,
arXiv:1208.1259, "...for Efficient Search and Learning") scores a batch
of query signatures against a corpus block: for every (query, doc) pair,
how many of the k b-bit codes agree?  That count is the collision
fraction P̂_b behind the Theorem-1 resemblance estimate, so this kernel
is the entire scoring hot path of ``repro.index``.

Both operands arrive in the packed wire format (``kernels/pack.py``:
k codes of ``code_bits`` each, little-endian bitstream in uint32 words
-- (b+1)-bit codes with EMPTY = 2^b for sentinel OPH).  The kernel never
unpacks: it XORs each query's packed row against a (BLK_N, W) corpus
tile -- documents on sublanes, the whole packed row of W words on lanes
-- and a code matches iff its bit-field of the XOR is zero.  The fields
are read with per-word shifts from a small (S, W) table of code offsets
(``_code_slots``), so there is no lane gather; a code that straddles two
words (code widths that do not divide 32, e.g. 9-bit sentinel codes)
takes its high bits from the next word via a one-lane rotate.  The
per-word zero counts are summed across lanes on the MXU (a ones-vector
matmul), which lands each query's counts as one (1, BLK_N) row of the
(BLK_Q, BLK_N) output block.

Grid = (N/BLK_N, Q/BLK_Q), both parallel; the corpus tile stays in VMEM
while every query block is scored against it, so each corpus word is read
from HBM once per call.

For sentinel OPH the kernel also counts jointly-EMPTY positions, so the
caller can apply the Li-Owen-Zhang normalization
N_match / (k - N_jointly_empty) without ever unpacking.

Backend selection / block sizes come from the ``SignatureEngine``
registry (``repro.kernels.engine``): the public wrapper ``packed_match``
resolves a Backend (interpret / tpu run this kernel; ref runs the
``kernels/ref.py`` oracle) and looks up ``TuningTable`` entries under
scheme ``"hamming"`` keyed on the packed word count.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.bbit import packed_words
from repro.kernels.minhash import _compiler_params
from repro.kernels.pack import PackSpec

_U32 = jnp.uint32


def _code_slots(k: int, code_bits: int) -> np.ndarray:
    """(S, W) int32: bit offset in word w of the s-th code starting there.

    S = ceil(32 / code_bits) is the most codes that can start in one
    word; -1 marks an empty slot (including everything past code k).
    Same bitstream geometry as ``repro.core.bbit.pack_codes``.
    """
    n_words = packed_words(k, code_bits)
    slots = np.full((-(-32 // code_bits), n_words), -1, np.int32)
    bit0 = np.arange(k, dtype=np.int64) * code_bits
    word, off = bit0 >> 5, bit0 & 31
    first = np.searchsorted(word, np.arange(n_words))
    slots[np.arange(k) - first[word], word] = off
    return slots


def _hamming_kernel(slots_ref, q_ref, c_ref, match_ref, *empty_refs,
                    code_bits: int, sentinel: bool, blk_q: int):
    straddle = 32 % code_bits != 0
    mask = _U32((1 << code_bits) - 1)
    slots = slots_ref[...]                                 # (S, W)
    n_words = slots.shape[1]
    ones = jnp.ones((8, n_words), jnp.bfloat16)

    def fields(x):
        """Per slot: (code field of every word of ``x``, slot is real)."""
        nxt = jnp.roll(x, -1, axis=1) if straddle else None
        for sl in range(slots.shape[0]):
            off = slots[sl:sl + 1, :]
            sh = jnp.maximum(off, 0).astype(_U32)
            f = x >> sh
            if straddle:        # high bits from the next word; no shift by 32
                f = f | ((nxt << (_U32(31) - sh)) << _U32(1))
            yield f & mask, off >= 0

    def row_sum(z):
        """(BLK_N, W) small counts -> (1, BLK_N) int32 sums over words."""
        r = jax.lax.dot_general(ones, z.astype(jnp.bfloat16),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return r[0:1].astype(jnp.int32)

    def one_query(i, carry):
        q = q_ref[pl.ds(i, 1), :]                          # (1, W)
        x = c_ref[...] ^ q                                 # (BLK_N, W)
        q_empty = ([f == _U32(1 << (code_bits - 1)) for f, _ in fields(q)]
                   if sentinel else None)
        match = both = 0
        for sl, (f, real) in enumerate(fields(x)):
            z = (f == 0) & real
            if sentinel:
                both = both + (z & q_empty[sl]).astype(jnp.int32)
                z = z & ~q_empty[sl]
            match = match + z.astype(jnp.int32)
        match_ref[pl.ds(i, 1), :] = row_sum(match)
        if sentinel:
            empty_refs[0][pl.ds(i, 1), :] = row_sum(both)
        return carry

    jax.lax.fori_loop(0, blk_q, one_query, 0)


def packed_match_pallas(qwords: jax.Array, cwords: jax.Array, *, k: int,
                        code_bits: int, sentinel: bool = False,
                        blk_q: int = 8, blk_n: int = 128, interpret: bool):
    """Match counts between packed query and corpus signatures.

    Args:
      qwords: (Q, W) uint32 packed query signatures.
      cwords: (N, W) uint32 packed corpus signatures (same wire format).
      k, code_bits, sentinel: the wire format (``PackSpec``).
      blk_q, blk_n: output tile (the word axis is always whole rows).
      interpret: run the Pallas interpreter (CPU) instead of Mosaic.

    Q and N must tile (pad in the caller: padded *rows* produce garbage
    counts the caller slices off; bits past code k never count).

    Returns (Q, N) int32 match counts; for ``sentinel=True`` a tuple
    ``(matches, both_empty)`` where matches already excludes jointly-EMPTY
    positions (the Li-Owen-Zhang numerator) and both_empty counts them
    (the denominator correction).
    """
    q, w = qwords.shape
    n, wc = cwords.shape
    if wc != w or w != packed_words(k, code_bits):
        raise ValueError(f"query words {w} / corpus words {wc} != "
                         f"{packed_words(k, code_bits)} for k={k}, "
                         f"code_bits={code_bits}")
    if q % blk_q or n % blk_n:
        raise ValueError(f"shapes must tile: Q={q}%{blk_q}, N={n}%{blk_n}")
    slots = jnp.asarray(_code_slots(k, code_bits))
    out_spec = pl.BlockSpec((blk_q, blk_n), lambda j, i: (i, j))
    out_shape = jax.ShapeDtypeStruct((q, n), jnp.int32)
    kern = functools.partial(_hamming_kernel, code_bits=code_bits,
                             sentinel=sentinel, blk_q=blk_q)
    return pl.pallas_call(
        kern,
        grid=(n // blk_n, q // blk_q),
        in_specs=[pl.BlockSpec(slots.shape, lambda j, i: (0, 0)),
                  pl.BlockSpec((blk_q, w), lambda j, i: (i, 0)),
                  pl.BlockSpec((blk_n, w), lambda j, i: (j, 0))],
        out_specs=[out_spec, out_spec] if sentinel else out_spec,
        out_shape=[out_shape, out_shape] if sentinel else out_shape,
        interpret=interpret,
        **_compiler_params("parallel", "parallel"),
    )(slots, qwords, cwords)


@functools.partial(jax.jit, static_argnames=("k", "code_bits", "sentinel",
                                             "backend", "blk_q", "blk_n"))
def _packed_match_run(qwords, cwords, *, k, code_bits, sentinel, backend,
                      blk_q, blk_n):
    from repro.kernels import ref as kref
    from repro.kernels.engine import BACKENDS, _pad_axis
    q, n = qwords.shape[0], cwords.shape[0]
    be = BACKENDS[backend]
    if not be.use_pallas:
        return kref.packed_match_ref(qwords, cwords, k=k,
                                     code_bits=code_bits, sentinel=sentinel)
    qp = _pad_axis(qwords, blk_q, 0)
    cp = _pad_axis(cwords, blk_n, 0)
    out = packed_match_pallas(qp, cp, k=k, code_bits=code_bits,
                              sentinel=sentinel, blk_q=blk_q, blk_n=blk_n,
                              interpret=be.interpret)
    if sentinel:
        return out[0][:q, :n], out[1][:q, :n]
    return out[:q, :n]


def packed_match(qwords: jax.Array, cwords: jax.Array, spec: PackSpec, *,
                 backend: Optional[str] = None, blocks: Optional[dict] = None,
                 tuning=None) -> Union[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Match counts between packed signature batches (the query hot path).

    ``spec`` is the shared wire format; ``backend`` resolves through the
    ``SignatureEngine`` registry ("auto" per hardware; interpret/tpu run
    the Pallas kernel, ref the jnp oracle).  Block sizes come from
    explicit ``blocks`` > ``TuningTable`` entry (scheme ``"hamming"``,
    keyed on the packed word count) > ``HAMMING_BLOCKS`` defaults.

    Returns (Q, N) int32 matches, or ``(matches, both_empty)`` for
    sentinel wires (see ``packed_match_pallas``).
    """
    from repro.kernels.engine import (HAMMING_BLOCKS, default_tuning_table,
                                      resolve_backend)
    words = packed_words(spec.k, spec.code_bits)
    if qwords.shape[-1] != words or cwords.shape[-1] != words:
        raise ValueError(
            f"packed operands have {qwords.shape[-1]}/{cwords.shape[-1]} "
            f"words, spec (k={spec.k}, code_bits={spec.code_bits}) "
            f"needs {words}")
    be = resolve_backend(backend)
    if not blocks:
        table = tuning or default_tuning_table()
        blocks = (table.lookup(be.name, "hamming", spec.k, words)
                  or dict(HAMMING_BLOCKS))
    return _packed_match_run(qwords, cwords, k=spec.k,
                             code_bits=spec.code_bits, sentinel=spec.sentinel,
                             backend=be.name, blk_q=blocks["blk_q"],
                             blk_n=blocks["blk_n"])
