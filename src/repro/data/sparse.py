"""Sparse batch layouts: padded CSR and fixed-width segments.

``SparseBatch`` stores binary feature *sets* as ``indices (n, max_nnz)
int32`` plus a validity ``mask (n, max_nnz) bool``: fixed shape,
128-lane alignable, maskable, one padded row per set.

``SegmentedBatch`` is the layout the chunked loader hands the signature
engine (the on-device analogue of the paper's "chunks of 10K sets"):
each set's ids are cut into segments of a fixed width, and every
segment is one row of ``indices (S, W)`` with its own count of real
ids.  Minwise hashing is a minimum over the set, so it distributes over
any partition of the set: a set's minimum is the least of its segments'
minima.  A chunk of heavy-tailed rows then costs about its real ids in
slots, where padding every row to the longest would cost the longest
row times the chunk size.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence, Tuple

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


# A segment's width when a chunk's longest row exceeds it: ten 128-lane
# tiles, so that rows of 3,616-3,840 ids (webspam) fill three segments,
# the 3,840 slots that padding them to the longest row gave.  A chunk
# whose rows all fit in fewer slots uses its longest row rounded up to
# whole tiles, which is one segment per row.
SEGMENT_WIDTH = 1280
LANES = 128
SEGMENT_BUCKET = 4096


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["indices", "counts", "rows", "labels"],
                   meta_fields=["n"])
@dataclasses.dataclass(frozen=True)
class SegmentedBatch:
    """A batch of ``n`` binary sets as fixed-width segments.

    Segment ``i`` holds ``counts[i]`` real ids at the start of
    ``indices[i]`` and belongs to set ``rows[i]``; the segments of a set
    are consecutive and in set order, so ``rows`` is non-decreasing.
    ``rows`` is None where segment ``i`` is set ``i`` (every set fits one
    segment).  Segments past the real ones are padding: count 0, and
    ``rows`` gives them ``n``, which names no set.
    """

    indices: jax.Array          # (S, W) int32, ids in [0, D)
    counts: jax.Array           # (S,) int32 real ids per segment
    rows: Optional[jax.Array]   # (S,) int32 set of each segment, or None
    labels: Optional[jax.Array]  # (n,) float32 or None
    n: int

    def nbytes(self) -> int:
        b = (self.indices.size + self.counts.size) * 4
        for x in (self.rows, self.labels):
            if x is not None:
                b += x.size * 4
        return b


def _segment_bucket(n_segments: int) -> int:
    """The number of segment rows a chunk of ``n_segments`` is laid out
    in (see ``segment_csr_parts``)."""
    if n_segments <= SEGMENT_BUCKET:
        return max(LANES, 1 << max(0, n_segments - 1).bit_length())
    return -(-n_segments // SEGMENT_BUCKET) * SEGMENT_BUCKET


def _fresh_segments(shape: Tuple[int, int]) -> np.ndarray:
    return np.empty(shape, np.int32)


def segment_csr_parts(parts: Sequence[Tuple[np.ndarray, np.ndarray]],
                      buffer: Callable[[Tuple[int, int]], np.ndarray]
                      = _fresh_segments
                      ) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
    """The segmented host arrays of a chunk straight from CSR pieces
    ``(flat, offsets)``: row ``i`` of a piece is ``flat[offsets[i]:
    offsets[i + 1]]`` (``offsets`` need not start at 0), and the rows of
    the first piece come first, then those of the next.

    Returns ``indices (S, W) int32``, ``counts (S,) int32`` and ``rows``
    as ``SegmentedBatch`` holds them.  ``W`` is ``SEGMENT_WIDTH``, or the
    longest row rounded up to ``LANES`` where that is less; a row takes
    ``ceil(len / W)`` segments, at least one (an empty row is one empty
    segment); ``S`` is the total rounded up to a power of two from
    ``LANES`` up to ``SEGMENT_BUCKET`` and to a multiple of
    ``SEGMENT_BUCKET`` above it, so that a pass compiles few shapes.  A
    row's segments
    are consecutive rows of ``indices``, so its ids are one contiguous
    run of the flattened array: each id is copied once, cast to int32 in
    that copy, into ``buffer((S, W))``, an int32 array of that shape
    (default: a new one).  Whatever the buffer held, the slots the ids
    do not fill (the rest of each row's last segment, and the padding
    segments) are set to 0, so a reused buffer gives the same layout as
    a new one."""
    offs = [np.asarray(o, np.int64) for _, o in parts]
    lens = np.concatenate([np.diff(o) for o in offs] or [[]]).astype(np.int64)
    longest = int(lens.max(initial=0))
    width = (SEGMENT_WIDTH if longest > SEGMENT_WIDTH
             else max(LANES, -(-longest // LANES) * LANES))
    per_row = np.maximum(1, -(-lens // width))
    first = np.zeros(lens.size + 1, np.int64)
    np.cumsum(per_row, out=first[1:])
    real = int(first[-1])
    total = _segment_bucket(real)
    idx = buffer((total, width))
    out = idx.reshape(-1)
    bounds = (first * width).tolist()
    spans = zip(bounds[:-1], bounds[1:])
    for (flat, _), o in zip(parts, offs):
        for a, b in zip(o[:-1].tolist(), o[1:].tolist()):
            lo, hi = next(spans)
            out[lo:lo + b - a] = flat[a:b]
            out[lo + b - a:hi] = 0
    idx[real:] = 0
    seg_row = np.repeat(np.arange(lens.size, dtype=np.int32), per_row)
    within = np.arange(real, dtype=np.int64) - np.repeat(first[:-1], per_row)
    counts = np.zeros(total, np.int32)
    counts[:real] = np.clip(lens[seg_row] - within * width, 0, width)
    if real == lens.size:
        return idx, counts, None
    rows = np.full(total, lens.size, np.int32)
    rows[:real] = seg_row
    return idx, counts, rows


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
    rows = [np.asarray(s).reshape(-1) for s in sets]
    lens = np.array([r.size for r in rows], np.int64)
    if max_nnz is None:
        max_nnz = int(lens.max(initial=0)) or 1
    width = ((max_nnz + lane_multiple - 1) // lane_multiple) * lane_multiple
    lens = np.minimum(lens, width).astype(np.int32)
    idx = np.zeros((lens.size, width), np.int32)
    for r, (row, m) in enumerate(zip(rows, lens.tolist())):
        idx[r, :m] = row[:m]
    return idx, np.arange(width, dtype=np.int32) < lens[:, None]


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
