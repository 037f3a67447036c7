"""Padded-CSR sparse batch layout.

Binary feature *sets* are stored as ``indices (n, max_nnz) int32`` plus a
validity ``mask (n, max_nnz) bool``.  This is the TPU-friendly ragged
layout: fixed shape, 128-lane alignable, maskable.  It is the on-device
analogue of the paper's "chunks of 10K sets" (each chunk is one
SparseBatch).
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparseBatch:
    """A batch of binary sets in padded-CSR form."""

    indices: jax.Array          # (n, max_nnz) int32, ids in [0, D)
    mask: jax.Array             # (n, max_nnz) bool
    labels: Optional[jax.Array] = None   # (n,) float32 in {-1, +1} or None

    @property
    def n(self) -> int:
        return self.indices.shape[0]

    @property
    def max_nnz(self) -> int:
        return self.indices.shape[1]

    def nnz_per_row(self) -> jax.Array:
        return jnp.sum(self.mask.astype(jnp.int32), axis=1)

    def nbytes(self) -> int:
        b = self.indices.size * 4 + self.mask.size
        if self.labels is not None:
            b += self.labels.size * 4
        return b


def pad_to_multiple(x: np.ndarray, multiple: int, axis: int, value=0) -> np.ndarray:
    size = x.shape[axis]
    target = ((size + multiple - 1) // multiple) * multiple
    if target == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return np.pad(x, pad, constant_values=value)


def pad_lists(sets: Sequence[np.ndarray], max_nnz: Optional[int] = None,
              lane_multiple: int = 128) -> Tuple[np.ndarray, np.ndarray]:
    """The padded host arrays of ``from_lists``: indices (n, width) int32
    and mask (n, width) bool, width ``max_nnz`` (default: the longest
    set) rounded up to ``lane_multiple``; longer sets are truncated."""
    n = len(sets)
    if max_nnz is None:
        max_nnz = max((len(s) for s in sets), default=1) or 1
    max_nnz = ((max_nnz + lane_multiple - 1) // lane_multiple) * lane_multiple
    idx = np.zeros((n, max_nnz), np.int32)
    msk = np.zeros((n, max_nnz), bool)
    for i, s in enumerate(sets):
        m = min(len(s), max_nnz)
        idx[i, :m] = np.asarray(s[:m], np.int32)
        msk[i, :m] = True
    return idx, msk


def from_lists(sets: Sequence[np.ndarray], labels: Optional[np.ndarray] = None,
               max_nnz: Optional[int] = None, lane_multiple: int = 128) -> SparseBatch:
    """Build a SparseBatch from a list of index arrays (CPU-side)."""
    idx, msk = pad_lists(sets, max_nnz, lane_multiple)
    lab = None if labels is None else jnp.asarray(labels, jnp.float32)
    return SparseBatch(indices=jnp.asarray(idx), mask=jnp.asarray(msk), labels=lab)


def to_dense(batch: SparseBatch, D: int) -> jax.Array:
    """Dense 0/1 matrix (n, D).  Tests/small-D only."""
    n, nnz = batch.indices.shape
    row = jnp.broadcast_to(jnp.arange(n)[:, None], (n, nnz))
    flat = row * D + batch.indices
    vals = batch.mask.astype(jnp.float32).reshape(-1)
    out = jnp.zeros((n * D,), jnp.float32).at[flat.reshape(-1)].add(vals, mode="drop")
    return jnp.minimum(out.reshape(n, D), 1.0)


def slice_batch(batch: SparseBatch, start: int, size: int) -> SparseBatch:
    return SparseBatch(
        indices=jax.lax.dynamic_slice_in_dim(batch.indices, start, size, 0),
        mask=jax.lax.dynamic_slice_in_dim(batch.mask, start, size, 0),
        labels=None if batch.labels is None
        else jax.lax.dynamic_slice_in_dim(batch.labels, start, size, 0),
    )
