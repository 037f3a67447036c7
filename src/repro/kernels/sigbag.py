"""Pallas TPU kernel for the signature embedding-bag (Eq. 5 forward).

The paper's learning construction expands k b-bit signatures into a
``2^b * k`` one-hot vector and feeds it to a linear model (Eq. 5).  The
inner product with the weight vector is

    f(x) = sum_j  W[j, z_j]            (W reshaped to (k, 2^b, d))

i.e., a k-way embedding-bag over per-slot tables.  With d = 1 this *is*
the paper's linear SVM / logistic forward; d > 1 gives a d-wide hashed
embedding per example.

TPU design: the per-slot gather is expressed as a one-hot (2^b, BLK_N)
matrix multiplied into a (d, 2^b) table slice so it runs on the MXU (the
canonical TPU small-vocab gather).  Tokens arrive transposed, (k, n):
examples on lanes, slots on sublanes, so one slot's tokens are a (1,
BLK_N) row and its one-hot is a compare against a sublane iota.  Grid =
(n/BLK_N, k/8): each step takes 8 slots and their (8, d, 2^b) table
slices, and the j axis accumulates into the (d, BLK_N) output block
(revisited), so the kernel streams the table through VMEM instead of
holding all k*2^b rows.  The wrapper pads k to a multiple of 8 with
all-zero table slices and transposes the result back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.minhash import _compiler_params

_SLOTS = 8          # slots per grid step: one sublane tile of tokens


def _sigbag_kernel(tok_ref, table_ref, out_ref, *, two_b: int):
    # out_ref is a float32 accumulator regardless of table dtype (the
    # standard MXU practice: bf16 operands, fp32 accumulation).
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = out_ref[...]                                    # (d, BLK_N)
    for sl in range(_SLOTS):
        tok = tok_ref[sl:sl + 1, :]                       # (1, BLK_N) int32
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (two_b, tok.shape[1]),
                                           0) == tok).astype(table_ref.dtype)
        acc = acc + jnp.dot(table_ref[sl], onehot,        # (d, 2^b) @ ...
                            preferred_element_type=jnp.float32)
    out_ref[...] = acc


def sigbag_pallas(tokens: jax.Array, table: jax.Array, *, blk_n: int = 128,
                  interpret: bool) -> jax.Array:
    """Sum-of-rows lookup: out[i] = sum_j table[j, tokens[i, j]].

    Args:
      tokens: (n, k) int32 b-bit signature values in [0, 2^b); n must
        tile by ``blk_n``.
      table:  (k, 2^b, d) float weights.
      interpret: run the Pallas interpreter (CPU) instead of Mosaic.

    Returns:
      (n, d) float.
    """
    n, k = tokens.shape
    k_t, two_b, d = table.shape
    if k_t != k:
        raise ValueError(f"table k={k_t} != tokens k={k}")
    if n % blk_n:
        raise ValueError(f"n={n} must tile by blk_n={blk_n}")
    k_pad = -(-k // _SLOTS) * _SLOTS
    tok_t = jnp.pad(tokens.astype(jnp.int32).T, ((0, k_pad - k), (0, 0)))
    tbl_t = jnp.pad(jnp.swapaxes(table, 1, 2),
                    ((0, k_pad - k), (0, 0), (0, 0)))     # (k_pad, d, 2^b)
    out = pl.pallas_call(
        functools.partial(_sigbag_kernel, two_b=two_b),
        grid=(n // blk_n, k_pad // _SLOTS),
        in_specs=[
            pl.BlockSpec((_SLOTS, blk_n), lambda i, j: (j, i)),
            pl.BlockSpec((_SLOTS, d, two_b), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((d, blk_n), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((d, n), jnp.float32),
        interpret=interpret,
        **_compiler_params("parallel", "arbitrary"),
    )(tok_t, tbl_t)
    return out.T.astype(table.dtype)
