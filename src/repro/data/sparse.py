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


def _pad_rows(rows: Iterable[np.ndarray], lens: np.ndarray,
              max_nnz: Optional[int],
              lane_multiple: int) -> Tuple[np.ndarray, np.ndarray]:
    """The one padding rule: ``rows`` (1-D arrays, in order, of lengths
    ``lens``) into indices (n, width) int32 and mask (n, width) bool,
    width ``max_nnz`` (default: the longest row) rounded up to
    ``lane_multiple``; longer rows are truncated.  Each id is copied
    once, cast to int32 in that copy, into a fresh buffer; the mask is
    one compare."""
    if max_nnz is None:
        max_nnz = int(lens.max(initial=0)) or 1
    width = ((max_nnz + lane_multiple - 1) // lane_multiple) * lane_multiple
    lens = np.minimum(lens, width).astype(np.int32)
    idx = np.zeros((lens.size, width), np.int32)
    for r, (row, m) in enumerate(zip(rows, lens.tolist())):
        idx[r, :m] = row[:m]
    return idx, np.arange(width, dtype=np.int32) < lens[:, None]


def pad_csr_parts(parts: Sequence[Tuple[np.ndarray, np.ndarray]],
                  max_nnz: Optional[int] = None,
                  lane_multiple: int = 128) -> Tuple[np.ndarray, np.ndarray]:
    """The padded host arrays straight from CSR pieces ``(flat,
    offsets)``: row ``i`` of a piece is ``flat[offsets[i]:offsets[i +
    1]]`` (``offsets`` need not start at 0), and the rows of the first
    piece come first, then those of the next, under one width (the rule
    of ``pad_lists``).  A chunk that spans two shards pads this way
    without joining them first."""
    offs = [np.asarray(o, np.int64) for _, o in parts]
    lens = np.concatenate([np.diff(o) for o in offs] or [[]]).astype(np.int64)
    rows = (flat[a:b] for (flat, _), o in zip(parts, offs)
            for a, b in zip(o[:-1].tolist(), o[1:].tolist()))
    return _pad_rows(rows, lens, max_nnz, lane_multiple)


def pad_lists(sets: Sequence[np.ndarray], max_nnz: Optional[int] = None,
              lane_multiple: int = 128) -> Tuple[np.ndarray, np.ndarray]:
    """The padded host arrays of ``from_lists``: indices (n, width) int32
    and mask (n, width) bool, width ``max_nnz`` (default: the longest
    set) rounded up to ``lane_multiple``; longer sets are truncated."""
    rows = [np.asarray(s).reshape(-1) for s in sets]
    return _pad_rows(rows, np.array([r.size for r in rows], np.int64),
                     max_nnz, lane_multiple)


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
