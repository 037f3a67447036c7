"""The segmented chunk layout: rows cut into fixed-width segments, hashed
segment by segment, and reduced to rows by their minimum.

  * the engine's signatures of a segmented batch equal those of the
    same rows padded (one segment per row), for 2U, 4U and OPH, packed
    and not, with rows shorter than a segment, exactly one segment,
    several segments long, and empty;
  * ``preprocess_shards`` over heavy-tailed shards writes ``.sig`` rows
    in file order, word for word equal to the jnp reference, and counts
    the real ids and the slots hashed (``PreprocessStats`` and the
    ``data_loader_*`` counters).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bbit import pack_codes
from repro.core.hashing import Hash2U, Hash4U
from repro.core.minhash import minhash_signatures
from repro.core.oph import OPH
from repro.data.pipeline import ChunkedLoader, write_shard_binary
from repro.data.preprocess import preprocess_shards
from repro.data.sigshard import read_sig_shard
from repro.data.sparse import (SEGMENT_WIDTH, SegmentedBatch, from_lists,
                               segment_csr_parts)
from repro.kernels import SignatureEngine

# shorter than a segment, empty, exactly one, just past one, several
LENS = [5, 0, SEGMENT_WIDTH, SEGMENT_WIDTH + 1, 3000, 2 * SEGMENT_WIDTH, 7]
S_BITS = 20


def _sets(lens, s=S_BITS, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 1 << s, n) for n in lens]


def _segmented(sets):
    offsets = np.zeros(len(sets) + 1, np.int64)
    np.cumsum([len(s) for s in sets], out=offsets[1:])
    flat = np.concatenate(sets)
    idx, counts, rows = segment_csr_parts([(flat, offsets)])
    return SegmentedBatch(jnp.asarray(idx), jnp.asarray(counts),
                          None if rows is None else jnp.asarray(rows),
                          None, len(sets))


FAMILIES = {
    "2u": lambda: Hash2U.create(jax.random.PRNGKey(0), 128, S_BITS),
    "4u": lambda: Hash4U.create(jax.random.PRNGKey(1), 128, S_BITS),
    "oph-2u-rotation": lambda: OPH.create(jax.random.PRNGKey(2), 64, S_BITS,
                                          "2u", "rotation"),
    "oph-4u-sentinel": lambda: OPH.create(jax.random.PRNGKey(3), 64, S_BITS,
                                          "4u", "sentinel"),
}


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_segmented_equals_padded(family, packed):
    sets = _sets(LENS)
    seg = _segmented(sets)
    assert seg.rows is not None and seg.indices.shape[1] == SEGMENT_WIDTH
    eng = SignatureEngine(FAMILIES[family](), b=8, packed=packed,
                          backend="interpret")
    got, want = eng(seg), eng(from_lists(sets))
    if packed:
        got, want = got.data, want.data
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _slots(lens):
    """Segments x width of one chunk, by the layout's rule: width 1,280
    (or the longest row in whole 128-lane tiles where that is less), at
    least one segment a row, the count padded to a power of two from 128
    up to 4,096 and to a multiple of 4,096 above."""
    longest = int(max(lens))
    width = (SEGMENT_WIDTH if longest > SEGMENT_WIDTH
             else max(128, -(-longest // 128) * 128))
    segs = sum(max(1, -(-int(n) // width)) for n in lens)
    total = (max(128, 1 << (segs - 1).bit_length()) if segs <= 4096
             else -(-segs // 4096) * 4096)
    return total * width


def _write(path, sets, labels):
    write_shard_binary(path, sets, labels)
    return path


def test_preprocess_heavy_tailed_file_order(tmp_path):
    """Rows of 0 to 6,000 ids in two shards, chunks straddling them: the
    ``.sig`` rows come out in file order, equal to the reference; the
    real ids and the slots hashed (segments x width, bucket padding
    included) are counted."""
    from repro.obs.metrics import get_registry
    rng = np.random.default_rng(7)
    lens = np.concatenate([[6000, 0, 1], rng.integers(1, 400, 40),
                           [SEGMENT_WIDTH, 2500]])
    sets = _sets(lens.tolist(), s=30, seed=8)
    labels = rng.choice([-1.0, 1.0], len(sets)).astype(np.float32)
    half = len(sets) // 2
    paths = [_write(str(tmp_path / "a.npz"), sets[:half], labels[:half]),
             _write(str(tmp_path / "b.npz"), sets[half:], labels[half:])]
    fam = Hash4U.create(jax.random.PRNGKey(4), 64, 30)
    chunk = 16
    stats = preprocess_shards(paths, str(tmp_path / "sig"), fam, b=8,
                              chunk_size=chunk, backend="interpret")
    files = sorted(os.listdir(tmp_path / "sig"))
    assert len(files) == -(-len(sets) // chunk)
    words = np.concatenate([read_sig_shard(str(tmp_path / "sig" / f))[0]
                            for f in files])
    got_labels = np.concatenate([read_sig_shard(str(tmp_path / "sig" / f))[1]
                                 for f in files])
    ref = from_lists(sets)
    want = pack_codes(minhash_signatures(ref.indices, ref.mask, fam)
                      & jnp.uint32(0xFF), 8)
    np.testing.assert_array_equal(words, np.asarray(want))
    np.testing.assert_array_equal(got_labels, labels)

    slots = sum(_slots(lens[lo:lo + chunk])
                for lo in range(0, len(sets), chunk))
    assert stats.nonzeros == int(lens.sum())
    assert stats.slots_hashed == slots
    loader = ChunkedLoader(paths, chunk_size=chunk)   # alive while read
    assert sum(c.indices.size for c in loader) == slots
    vals = get_registry().values()
    assert vals['data_loader_nonzeros_total{role="load"}'] == lens.sum()
    assert vals['data_loader_slots_total{role="load"}'] == slots


# rows of 3,616-3,840 ids, three segments each, and a bucket of padding
# segments (192 real of 256)
WEBSPAM_LENS = np.rint(3615.5 + 225 * (np.arange(64) + 0.5) / 64
                       ).astype(int).tolist()
# one row of 16 segments among rows of one: 79 segments of 128
HEAVY_LENS = [20000] + [40] * 62 + [0]
# one segment a row, and a width of one 128-lane tile
SHORT_LENS = [1 + i % 100 for i in range(64)]


def _csr(sets):
    offsets = np.zeros(len(sets) + 1, np.int64)
    np.cumsum([len(s) for s in sets], out=offsets[1:])
    return np.concatenate(sets), offsets


@pytest.mark.parametrize("lens", [WEBSPAM_LENS, HEAVY_LENS, SHORT_LENS],
                         ids=["webspam", "heavy-tailed", "short"])
def test_recycled_buffer_layout_equals_fresh(lens):
    """Laid out into a buffer that holds -1 everywhere, a chunk comes out
    bit for bit as in a zeroed new one: the rest of each row's last
    segment and the padding segments are zeros, the ids land in the
    buffer handed over, and it is asked for at the chunk's own shape."""
    flat, offsets = _csr(_sets(lens, s=24, seed=5))
    asked = []

    def dirty(shape):
        asked.append(shape)
        return np.full(shape, -1, np.int32)

    want = segment_csr_parts([(flat, offsets)],
                             lambda shape: np.zeros(shape, np.int32))
    got = segment_csr_parts([(flat, offsets)], dirty)
    assert asked == [want[0].shape]
    for g, w in zip(got, want):
        if w is None:
            assert g is None
            continue
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)
    np.testing.assert_array_equal(segment_csr_parts([(flat, offsets)])[0],
                                  want[0])


@pytest.mark.parametrize("prefetch", [0, 2])
def test_segment_buffers_reused_by_shape(tmp_path, prefetch):
    """A pass reuses a segment buffer only for a chunk of the same padded
    segment count and width: chunks shaped A, A, B (fewer segments), C
    (narrower), C, A lay out in 4 buffers, 2 of them reused, whether
    the ring holds one buffer (no prefetch) or two; every chunk comes
    out as its fresh layout."""
    kinds = {"A": WEBSPAM_LENS, "B": HEAVY_LENS, "C": SHORT_LENS}
    order = "AABCCA"
    sets, paths = [], []
    for i, kind in enumerate(order):
        rows = _sets(kinds[kind], s=24, seed=20 + i)
        paths.append(_write(str(tmp_path / f"s{i}.npz"), rows,
                            np.ones(len(rows), np.float32)))
        sets.append(rows)
    loader = ChunkedLoader(paths, chunk_size=64, prefetch=prefetch)
    chunks = list(loader)
    assert [c.indices.shape for c in chunks] == [
        {"A": (256, SEGMENT_WIDTH), "B": (128, SEGMENT_WIDTH),
         "C": (128, 128)}[k] for k in order]
    for chunk, rows in zip(chunks, sets):
        want = segment_csr_parts([_csr(rows)])
        for g, w in zip((chunk.indices, chunk.counts, chunk.rows), want):
            assert (g is None) == (w is None)
            if w is not None:
                np.testing.assert_array_equal(np.asarray(g), w)
    st = loader.stats
    assert (st.segment_buffers_reused, st.segment_buffers_fresh) == (2, 4)
