"""Data pipeline: shard formats, chunked iteration, prefetch, stragglers."""

import os
import random
import time
import types

import numpy as np
import pytest

from repro.data import TINY, generate
from repro.data.pipeline import (ChunkedLoader, LoaderStats,
                                 make_sharded_dataset, read_shard_binary,
                                 read_shard_csr, read_shard_csr_libsvm,
                                 read_shard_libsvm, read_with_retries,
                                 write_shard_binary, write_shard_libsvm,
                                 write_shards)
from repro.data.sparse import pad_lists, segment_csr_parts


def _toy_sets(n=50, seed=0):
    rng = np.random.default_rng(seed)
    sets = [np.sort(rng.choice(1000, size=rng.integers(3, 30), replace=False))
            for _ in range(n)]
    labels = rng.choice([-1.0, 1.0], n).astype(np.float32)
    return sets, labels


@pytest.mark.parametrize("fmt", ["binary", "libsvm"])
def test_shard_roundtrip(tmp_path, fmt):
    sets, labels = _toy_sets()
    path = str(tmp_path / ("s.npz" if fmt == "binary" else "s.txt"))
    writer = write_shard_binary if fmt == "binary" else write_shard_libsvm
    reader = read_shard_binary if fmt == "binary" else read_shard_libsvm
    writer(path, sets, labels)
    got_sets, got_labels = reader(path)
    np.testing.assert_array_equal(got_labels, labels)
    for a, b in zip(got_sets, sets):
        np.testing.assert_array_equal(np.asarray(a, np.int64), b)


@pytest.mark.parametrize("prefetch", [0, 2])
def test_chunked_iteration(tmp_path, prefetch):
    sets, labels = _toy_sets(101)
    paths = write_shards(sets, labels, str(tmp_path), n_shards=4)
    loader = ChunkedLoader(paths, chunk_size=25, prefetch=prefetch)
    chunks = list(loader)
    assert sum(c.n for c in chunks) == 101
    assert chunks[0].n == 25
    # labels preserved in order
    all_labels = np.concatenate([np.asarray(c.labels) for c in chunks])
    np.testing.assert_array_equal(all_labels, labels)
    assert loader.stats.chunks == len(chunks)
    assert loader.stats.load_seconds > 0


def test_straggler_detection_counters(tmp_path):
    sets, labels = _toy_sets(40)
    paths = write_shards(sets, labels, str(tmp_path), n_shards=2)
    # absurd deadline of 0 -> every read is a straggler, then reassigned
    loader = ChunkedLoader(paths, chunk_size=40, prefetch=0,
                           straggler_deadline_s=0.0, max_retries=1)
    chunks = list(loader)
    assert sum(c.n for c in chunks) == 40
    assert loader.stats.straggler_retries >= 2
    assert loader.stats.shard_reassignments == 2


def test_read_shard_oserror_accounted(tmp_path):
    """Flaky reads retry with accounting; exhausted retries raise."""
    sets, labels = _toy_sets(20)
    paths = write_shards(sets, labels, str(tmp_path), n_shards=1)
    loader = ChunkedLoader(paths, chunk_size=20, prefetch=0, max_retries=2)
    real_reader = loader._reader
    fails = {"n": 2}

    def flaky(path):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise OSError("transient read failure")
        return real_reader(path)

    loader._reader = flaky
    chunks = list(loader)
    assert sum(c.n for c in chunks) == 20
    assert loader.stats.io_errors == 2
    # the successful attempt is fully accounted (no silent re-read);
    # only the kept read counts as a mapped read
    assert loader.stats.load_seconds > 0 and loader.stats.bytes_read > 0
    assert (loader.stats.mapped_reads, loader.stats.decoded_reads) == (1, 0)

    # every attempt failing must surface the OSError, all attempts counted
    dead = ChunkedLoader(paths, chunk_size=20, prefetch=0, max_retries=1)

    def always_fails(path):
        raise OSError("gone")

    dead._reader = always_fails
    with pytest.raises(OSError):
        list(dead)
    assert dead.stats.io_errors == 2  # max_retries + 1 attempts
    assert dead.stats.bytes_read == 0


@pytest.mark.parametrize("prefetch", [0, 2])
@pytest.mark.parametrize("failures", [1, 2], ids=["retried", "exhausted"])
def test_loader_freed_after_read_errors(tmp_path, prefetch, failures):
    """A loader whose reads raised, whether a retry then succeeded or the
    error surfaced, is freed with its last reference, without the cycle
    collector: no exception's traceback keeps the frames that hold the
    loader (its stats, its buffers) alive, so its counters leave the
    registry at once."""
    import gc
    import weakref
    sets, labels = _toy_sets(20, seed=15)
    paths = write_shards(sets, labels, str(tmp_path), n_shards=1)
    loader = ChunkedLoader(paths, chunk_size=20, prefetch=prefetch,
                           max_retries=1, io_backoff_base_s=0.0)
    real_reader, fails = loader._reader, {"n": failures}

    def flaky(path):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise OSError("read failure")
        return real_reader(path)

    loader._reader = flaky
    gc.disable()
    try:
        if failures > loader.max_retries:
            with pytest.raises(OSError):
                list(loader)
        else:
            assert sum(c.n for c in loader) == 20
        assert loader.stats.io_errors == failures
        alive = weakref.ref(loader.stats)
        del loader, flaky
        assert alive() is None
    finally:
        gc.enable()


def test_io_backoff_schedule_pinned(tmp_path):
    """Fake-clock regression of the retry backoff: attempt ``i`` sleeps
    ``min(cap, base * 2**i)`` scaled by the rng's uniform [0.5, 1.0)
    jitter -- pinned against a replay of the same seeded rng.  No sleep
    after the final failed attempt, and none on the straggler path."""
    calls = {"n": 0}

    def flaky(path):
        calls["n"] += 1
        raise OSError("down")

    sleeps = []
    stats = LoaderStats()
    with pytest.raises(OSError):
        read_with_retries(flaky, "p", stats, deadline=30.0, max_retries=3,
                          backoff_base_s=0.05, backoff_cap_s=0.12,
                          rng=random.Random(7), sleep=sleeps.append)
    assert calls["n"] == 4 and stats.io_errors == 4
    replay = random.Random(7)
    want = [min(0.12, 0.05 * 2.0 ** i) * (0.5 + 0.5 * replay.random())
            for i in range(3)]             # one sleep per retry, capped,
    assert sleeps == want                  # none after the last failure

    # stragglers retry immediately: a 0-second deadline forces retries
    # on every (successful) read, and the sleep clock must never tick
    sleeps.clear()
    real = tmp_path / "shard"
    real.write_bytes(b"x" * 16)
    out = read_with_retries(lambda p: "ok", str(real), LoaderStats(),
                            deadline=0.0, max_retries=2,
                            backoff_base_s=0.05, backoff_cap_s=0.12,
                            rng=random.Random(7), sleep=sleeps.append)
    assert out == "ok" and sleeps == []


def test_loader_backoff_knobs_reach_reader(tmp_path):
    """ChunkedLoader threads its io_backoff_* knobs into the shared
    retry helper -- the sleeps a flaky shard sees follow the loader's
    configured base/cap, not the defaults."""
    sets, labels = _toy_sets(20)
    paths = write_shards(sets, labels, str(tmp_path), n_shards=1)
    loader = ChunkedLoader(paths, chunk_size=20, prefetch=0, max_retries=2,
                           io_backoff_base_s=1e-4,
                           io_backoff_cap_s=2e-4)
    real_reader = loader._reader
    fails = {"n": 2}

    def flaky(path):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise OSError("transient")
        return real_reader(path)

    loader._reader = flaky
    t0 = time.perf_counter()
    chunks = list(loader)
    dt = time.perf_counter() - t0
    assert sum(c.n for c in chunks) == 20
    assert loader.stats.io_errors == 2
    assert dt < 1.0                      # default base (50ms) not in play


def test_make_sharded_dataset(tmp_path):
    paths = make_sharded_dataset(TINY, str(tmp_path), n_shards=3, n=60)
    assert len(paths) == 3
    loader = ChunkedLoader(paths, chunk_size=16)
    total = sum(c.n for c in loader)
    assert total == 48  # 80% train split of 60


def test_binary_faster_than_text(tmp_path):
    """The paper's observation: binary loading beats LibSVM text."""
    import time
    sets, labels = _toy_sets(2000, seed=3)
    pb = write_shards(sets, labels, str(tmp_path / "b"), 1, fmt="binary")
    pt = write_shards(sets, labels, str(tmp_path / "t"), 1, fmt="libsvm")
    t0 = time.perf_counter(); read_shard_binary(pb[0]); tb = time.perf_counter() - t0
    t0 = time.perf_counter(); read_shard_libsvm(pt[0]); tt = time.perf_counter() - t0
    assert tb < tt  # text parsing is slower


def _shred(paths):
    """Overwrite each shard in place with zeros, then delete it: data
    still read lazily from the file (a map of it) would read zeros
    afterwards."""
    for p in paths:
        size = os.path.getsize(p)
        with open(p, "r+b") as f:
            f.write(bytes(size))
        os.remove(p)


def _chunk_rows(c):
    """The sets of a segmented chunk, read back from its segments in
    order (padding segments name no set)."""
    idx, counts = np.asarray(c.indices), np.asarray(c.counts)
    rows = (np.arange(len(counts)) if c.rows is None
            else np.asarray(c.rows))
    out = [[] for _ in range(c.n)]
    for seg, row in enumerate(rows.tolist()):
        if row < c.n:
            out[row].append(idx[seg, :counts[seg]])
        else:
            assert counts[seg] == 0
    return [np.concatenate(r) for r in out]


def _assert_rows(chunks, sets, labels):
    pos = 0
    for c in chunks:
        for row, got in enumerate(_chunk_rows(c)):
            np.testing.assert_array_equal(got, sets[pos + row])
        np.testing.assert_array_equal(np.asarray(c.labels),
                                      labels[pos:pos + c.n])
        pos += c.n
    assert pos == len(sets)


@pytest.mark.parametrize("n,chunk_size", [(101, 25), (96, 16), (30, 64),
                                          (101, 40)])
def test_chunk_contents_pinned(tmp_path, monkeypatch, n, chunk_size):
    """Chunk boundaries AND per-row set contents must equal slicing the
    concatenated shard stream (chunks straddle shard boundaries: 101
    rows are shards of 26, 26, 26 and 23), and no batch may view a
    shard's buffer or file: no host array handed to the device shares
    memory with a shard, and the files are shredded before the batches
    are read."""
    import jax.numpy as jnp
    from repro.data import pipeline
    sets, labels = _toy_sets(n, seed=3)
    paths = write_shards(sets, labels, str(tmp_path), n_shards=4)
    loader = ChunkedLoader(paths, chunk_size=chunk_size, prefetch=0)
    real_reader, shards, handed = loader._reader, [], []

    def keep(path):
        shards.append(real_reader(path))
        return shards[-1]

    def handing(upload):
        def put(x, *a, **kw):
            handed.append(x)
            return upload(x, *a, **kw)
        return put

    loader._reader = keep
    monkeypatch.setattr(pipeline, "jnp", types.SimpleNamespace(
        array=handing(jnp.array), asarray=handing(jnp.asarray)))
    chunks = list(loader)
    sizes = [c.n for c in chunks]
    assert sizes[:-1] == [chunk_size] * (len(chunks) - 1)
    assert sum(sizes) == n
    assert loader.stats.mapped_reads == 4 and loader.stats.decoded_reads == 0
    assert all(sh.mapped for sh in shards)
    assert len(handed) == 3 * len(chunks)
    for host in handed:
        for sh in shards:
            for view in (sh.flat, sh.offsets, sh.labels):
                assert not np.shares_memory(host, view)
    del shards[:], handed[:]
    _shred(paths)
    _assert_rows(chunks, sets, labels)


@pytest.mark.parametrize("n,chunk_size,offset", [
    (101, 25, 50),     # chunk-aligned, mid-shard
    (101, 16, 37),     # unaligned, mid-shard
    (101, 40, 78),     # on a shard boundary (shards of 26)
    (101, 25, 101),    # past the end
])
def test_resume_contents_pinned(tmp_path, n, chunk_size, offset):
    """``resume_point`` + ``iter_from`` (offset arithmetic on the CSR
    offsets) yield exactly the stream's rows from ``offset`` on, cut
    into ``chunk_size`` chunks from there; a chunk-aligned resume
    reproduces the full pass's remaining chunks."""
    sets, labels = _toy_sets(n, seed=4)
    paths = write_shards(sets, labels, str(tmp_path), n_shards=4)
    loader = ChunkedLoader(paths, chunk_size=chunk_size, prefetch=0)
    full = list(loader)
    start, skip = loader.resume_point(offset)
    tail = list(loader.iter_from(start, skip))
    sizes = [c.n for c in tail]
    assert sum(sizes) == n - offset
    assert sizes[:-1] == [chunk_size] * (len(tail) - 1)
    if offset % chunk_size == 0:
        assert len(tail) == len(full) - offset // chunk_size
        for a, b in zip(tail, full[offset // chunk_size:]):
            np.testing.assert_array_equal(np.asarray(a.indices),
                                          np.asarray(b.indices))
    _shred(paths)
    _assert_rows(tail, sets[offset:], labels[offset:])


# ---------------------------------------------------------------------------
# Flat-CSR shard reads and padding
# ---------------------------------------------------------------------------

def _csr(sets):
    offsets = np.zeros(len(sets) + 1, np.int64)
    np.cumsum([len(s) for s in sets], out=offsets[1:])
    return np.concatenate(sets), offsets


def _write_bench_style(path, sets, labels):
    """The benchmark's raw shard: ``np.savez`` of int32 ids, int64
    offsets, float32 labels."""
    flat, offsets = _csr(sets)
    np.savez(path, indices=flat.astype(np.int32), offsets=offsets,
             labels=labels)


def _write_compressed(path, sets, labels):
    flat, offsets = _csr(sets)
    np.savez_compressed(path, indices=flat, offsets=offsets, labels=labels)


@pytest.mark.parametrize("writer,mapped", [
    (_write_bench_style, True),
    (write_shard_binary, True),
    (_write_compressed, False),
], ids=["bench-int32", "write_shard_binary", "savez_compressed"])
def test_csr_reader_matches_read_shard_binary(tmp_path, writer, mapped):
    sets, labels = _toy_sets(60, seed=6)
    path = str(tmp_path / "s.npz")
    writer(path, sets, labels)
    want_sets, want_labels = read_shard_binary(path)
    from repro.obs.metrics import get_registry
    loader = ChunkedLoader([path], chunk_size=25, prefetch=0)
    _assert_rows(list(loader), sets, labels)
    assert (loader.stats.mapped_reads, loader.stats.decoded_reads) == (
        (1, 0) if mapped else (0, 1))
    vals = get_registry().values()
    assert vals['data_loader_mapped_reads_total{role="load"}'] == int(mapped)
    assert vals['data_loader_decoded_reads_total{role="load"}'] == int(
        not mapped)

    # every byte is read inside the call: shredding the file afterwards
    # changes nothing the reader returned
    got = read_shard_csr(path)
    _shred([path])
    assert got.mapped is mapped
    assert got.flat.dtype == want_sets[0].dtype
    assert len(got.offsets) == len(want_sets) + 1
    for i, want in enumerate(want_sets):
        np.testing.assert_array_equal(
            got.flat[got.offsets[i]:got.offsets[i + 1]], want)
    np.testing.assert_array_equal(got.labels, want_labels)
    assert got.labels.dtype == want_labels.dtype
    if mapped:   # the ids' view is aligned in the read buffer
        assert got.flat.flags.aligned and got.flat.ctypes.data % 64 == 0


def test_csr_reader_short_read_retried(tmp_path, monkeypatch):
    """A file that yields fewer bytes than its size raises ``OSError``
    inside the reader, so ``read_with_retries`` counts and retries it."""
    from repro.data import pipeline
    sets, labels = _toy_sets(40, seed=9)
    path = str(tmp_path / "s.npz")
    _write_bench_style(path, sets, labels)
    real_fstat, calls = os.fstat, []

    def fstat(fd):
        st = real_fstat(fd)
        calls.append(fd)
        if len(calls) > 1:
            return st
        return types.SimpleNamespace(st_size=st.st_size + 1)

    monkeypatch.setattr(pipeline.os, "fstat", fstat)
    with pytest.raises(OSError, match="short read"):
        read_shard_csr(path)
    calls.clear()
    loader = ChunkedLoader([path], chunk_size=16, prefetch=0,
                           io_backoff_base_s=0.0)
    chunks = list(loader)
    assert loader.stats.io_errors == 1
    assert (loader.stats.mapped_reads, loader.stats.decoded_reads) == (1, 0)
    _assert_rows(chunks, sets, labels)


@pytest.mark.parametrize("chunk_size,max_buffers", [
    (12, 1),    # one chunk per shard: every read reuses the first buffer
    (18, 2),    # chunks straddle two shards
    (30, 3),    # a chunk views three shards at once
])
def test_read_buffers_recycled(tmp_path, chunk_size, max_buffers):
    """A pass over equal-size shards reads into at most as many buffers
    as a pending chunk views at once, and the recycling changes no row."""
    rng = np.random.default_rng(10)
    lens = rng.integers(3, 30, size=12)
    sets, labels, paths = [], [], []
    for i in range(6):
        rows = [rng.choice(1000, size=n, replace=False) for n in lens]
        lab = rng.choice([-1.0, 1.0], 12).astype(np.float32)
        paths.append(str(tmp_path / f"s{i}.npz"))
        _write_bench_style(paths[-1], rows, lab)
        sets += rows
        labels.append(lab)
    labels = np.concatenate(labels)
    loader = ChunkedLoader(paths, chunk_size=chunk_size, prefetch=0)
    real_reader, buffers = loader._reader, set()

    def note(path):
        shard = real_reader(path)
        buffers.add(shard.buffer.ctypes.data)
        return shard

    loader._reader = note
    _assert_rows(list(loader), sets, labels)
    assert 1 <= len(buffers) <= max_buffers
    assert loader._spare == []


def _assert_fresh_layout(chunks, sets, labels):
    """Each chunk's segments, counts, row map and labels are those of a
    fresh layout of its rows (``_segment_rowwise``)."""
    pos = 0
    for c in chunks:
        want = _segment_rowwise(sets[pos:pos + c.n])
        for got, w in zip((c.indices, c.counts, c.rows), want):
            assert (got is None) == (w is None)
            if w is not None:
                np.testing.assert_array_equal(np.asarray(got), w)
        np.testing.assert_array_equal(np.asarray(c.labels),
                                      labels[pos:pos + c.n])
        pos += c.n
    assert pos == len(sets)


def test_held_batches_keep_their_layout(tmp_path):
    """A consumer that holds every batch of a prefetched pass to its end
    still reads each one as laid out, though later chunks were laid out
    in the same host buffers: no buffer is reused before its upload
    completes, and no upload aliases the buffer."""
    sets, labels = _toy_sets(96, seed=12)
    paths = write_shards(sets, labels, str(tmp_path), n_shards=4)
    loader = ChunkedLoader(paths, chunk_size=16, prefetch=2)
    held = list(loader)
    assert len(held) == 6
    assert loader.stats.segment_buffers_reused > 0
    _assert_fresh_layout(held, sets, labels)


@pytest.mark.parametrize("prefetch", [0, 2])
def test_segment_buffers_recycled(tmp_path, monkeypatch, prefetch):
    """A pass of equal-shape chunks lays them all out in one segment
    buffer, counts every chunk once as reused or fresh, exports both
    counts, and changes no row."""
    from repro.data import pipeline
    from repro.obs.metrics import get_registry
    sets, labels = _toy_sets(96, seed=13)
    paths = write_shards(sets, labels, str(tmp_path), n_shards=4)
    laid_out = []

    def keep(parts, buffer):
        out = segment_csr_parts(parts, buffer)
        laid_out.append(out[0])
        return out

    monkeypatch.setattr(pipeline, "segment_csr_parts", keep)
    loader = ChunkedLoader(paths, chunk_size=16, prefetch=prefetch)
    chunks = list(loader)
    st = loader.stats
    assert st.chunks == len(chunks) == 6
    assert len({a.ctypes.data for a in laid_out}) == 1
    assert (st.segment_buffers_reused, st.segment_buffers_fresh) == (5, 1)
    vals = get_registry().values()
    assert vals['data_loader_segment_buffers_reused_total{role="load"}'] == 5
    assert vals['data_loader_segment_buffers_fresh_total{role="load"}'] == 1
    _assert_fresh_layout(chunks, sets, labels)


def test_read_buffers_concurrent_passes(tmp_path):
    """Passes over one loader in several threads at once share its spare
    read buffers; every pass still yields exactly the stream's rows."""
    import sys
    import threading
    rng = np.random.default_rng(11)
    sets, labels, paths = [], [], []
    for i in range(6):
        rows = [rng.choice(1000, size=n, replace=False)
                for n in rng.integers(3, 30, size=12)]
        lab = rng.choice([-1.0, 1.0], 12).astype(np.float32)
        paths.append(str(tmp_path / f"s{i}.npz"))
        _write_bench_style(paths[-1], rows, lab)
        sets += rows
        labels.append(lab)
    labels = np.concatenate(labels)
    loader = ChunkedLoader(paths, chunk_size=18, prefetch=0)
    results, errors = {}, []

    def run(k):
        try:
            for _ in range(5):
                results[k] = [types.SimpleNamespace(
                    indices=np.asarray(c.indices),
                    counts=np.asarray(c.counts),
                    rows=None if c.rows is None else np.asarray(c.rows),
                    labels=np.asarray(c.labels), n=c.n) for c in loader]
                _assert_rows(results[k], sets, labels)
        except Exception as e:   # surfaced by the main thread
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=run, args=(k,))
                   for k in range(2 * (os.cpu_count() or 2))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert len(results) == len(threads)


def _write_odd_member(path, sets, labels, odd):
    flat, offsets = _csr(sets)
    if odd == "big-endian":
        flat = flat.astype(">i4")
    else:                       # a Fortran-order (2-D) label member
        labels = np.asfortranarray(np.stack([labels, labels], 1))
    np.savez(path, indices=flat, offsets=offsets, labels=labels)


@pytest.mark.parametrize("odd", ["big-endian", "fortran"])
def test_csr_reader_odd_member_decoded(tmp_path, odd):
    """A stored member the reader does not view in place (big-endian
    ids, a Fortran-order array) goes through ``np.load`` and reads the
    same as ``read_shard_binary``."""
    sets, labels = _toy_sets(20, seed=7)
    path = str(tmp_path / "odd.npz")
    _write_odd_member(path, sets, labels, odd)
    want_sets, want_labels = read_shard_binary(path)
    got = read_shard_csr(path)
    assert not got.mapped
    for i, want in enumerate(want_sets):
        np.testing.assert_array_equal(
            got.flat[got.offsets[i]:got.offsets[i + 1]], want)
    np.testing.assert_array_equal(got.labels, want_labels)
    if odd == "big-endian":
        loader = ChunkedLoader([path], chunk_size=8, prefetch=0)
        _assert_rows(list(loader), sets, labels)
        assert loader.stats.decoded_reads == 1


def test_csr_libsvm_reader_matches(tmp_path):
    sets, labels = _toy_sets(30, seed=8)
    path = str(tmp_path / "s.txt")
    write_shard_libsvm(path, sets, labels)
    want_sets, want_labels = read_shard_libsvm(path)
    got = read_shard_csr_libsvm(path)
    assert not got.mapped
    for i, want in enumerate(want_sets):
        np.testing.assert_array_equal(
            got.flat[got.offsets[i]:got.offsets[i + 1]], want)
    np.testing.assert_array_equal(got.labels, want_labels)


def _pad_rowwise(sets, max_nnz, lane_multiple):
    """The padding rule written out row by row (independent of
    ``pad_csr_parts``)."""
    if max_nnz is None:
        max_nnz = max((len(s) for s in sets), default=1) or 1
    width = -(-max_nnz // lane_multiple) * lane_multiple
    idx = np.zeros((len(sets), width), np.int32)
    msk = np.zeros((len(sets), width), bool)
    for i, s in enumerate(sets):
        m = min(len(s), width)
        idx[i, :m] = np.asarray(s[:m], np.int32)
        msk[i, :m] = True
    return idx, msk


def _rows(lens, dtype=np.int32, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 1 << 24, size=n).astype(dtype) for n in lens]


@pytest.mark.parametrize("sets,max_nnz,lane", [
    (_rows([0, 5, 0, 3]), None, 8),              # empty rows
    (_rows([0, 0]), None, 8),                    # only empty rows
    (_rows([40, 7, 64]), 16, 8),                 # truncation at max_nnz
    (_rows([128, 3]), None, 128),                # width on a lane multiple
    (_rows([129, 3]), None, 128),                # just past one
    (_rows([17, 9, 0, 33], np.int64), None, 8),  # int64 ids
    (_rows([17, 9, 33], np.int64), 20, 8),       # int64, truncated
], ids=["empty-rows", "all-empty", "truncate", "on-lane", "past-lane",
        "int64", "int64-truncate"])
def test_pad_lists_matches_rowwise(sets, max_nnz, lane):
    want = _pad_rowwise(sets, max_nnz, lane)
    for g, w in zip(pad_lists(sets, max_nnz, lane), want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


def _segment_rowwise(sets):
    """The segmented layout written out segment by segment (independent
    of ``segment_csr_parts``): width 1280, or the longest row rounded up
    to 128 where that is less; each row cut into width-long segments,
    an empty row one empty segment; the segment count padded to a power
    of two from 128 up to 4096, a multiple of 4096 above."""
    longest = max((len(s) for s in sets), default=0)
    width = 1280 if longest > 1280 else max(128, -(-longest // 128) * 128)
    segs, owner = [], []
    for r, s in enumerate(sets):
        for lo in range(0, max(len(s), 1), width):
            segs.append(np.asarray(s[lo:lo + width]))
            owner.append(r)
    total = len(segs)
    padded = (max(128, 1 << (total - 1).bit_length()) if total <= 4096
              else -(-total // 4096) * 4096)
    idx = np.zeros((padded, width), np.int32)
    counts = np.zeros(padded, np.int32)
    for i, seg in enumerate(segs):
        idx[i, :len(seg)] = seg
        counts[i] = len(seg)
    rows = None
    if total != len(sets):
        rows = np.full(padded, len(sets), np.int32)
        rows[:total] = owner
    return idx, counts, rows


@pytest.mark.parametrize("lens,dtype", [
    ([0, 5, 0, 3], np.int32),                    # short rows, empty rows
    ([0, 0], np.int32),                          # only empty rows
    ([1280, 7], np.int32),                       # a row exactly one segment
    ([1281, 0, 1280, 3000, 2560, 7], np.int32),  # rows several segments long
    ([128, 129, 3], np.int64),                   # int64 ids, one segment each
    ([5000] + [3] * 130, np.int64),              # 135 segments: bucket 256
], ids=["short", "all-empty", "exactly-w", "multi", "int64", "bucket"])
def test_segment_layout_matches_rowwise(lens, dtype):
    """``segment_csr_parts`` lays every row out as the segment-by-segment
    rule does, from one CSR piece and from a window of a larger array in
    two pieces (offsets not from 0)."""
    from repro.data.sparse import segment_csr_parts
    sets = _rows(lens, dtype)
    flat, offsets = _csr(sets)
    want = _segment_rowwise(sets)
    pre = np.arange(11, dtype=flat.dtype)
    mid = len(sets) // 2
    big = np.concatenate([pre, flat])
    for got in (segment_csr_parts([(flat, offsets)]),
                segment_csr_parts([(big, offsets[:mid + 1] + 11),
                                   (big, offsets[mid:] + 11)])):
        for g, w in zip(got, want):
            if w is None:
                assert g is None
                continue
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(g, w)


def test_segment_layout_webspam_width():
    """A webspam-shaped chunk (4,096 rows of 3,616-3,840 ids) is three
    1,280-wide segments a row: 3,840 slots a row, what padding each row
    to the chunk's longest gave."""
    from repro.data.sparse import segment_csr_parts
    lens = np.rint(3615.5 + 225 * (np.arange(4096) + 0.5) / 4096)
    offsets = np.zeros(4097, np.int64)
    np.cumsum(lens.astype(np.int64), out=offsets[1:])
    flat = np.arange(offsets[-1], dtype=np.int32)
    idx, counts, rows = segment_csr_parts([(flat, offsets)])
    assert idx.shape == (3 * 4096, 1280)
    assert idx.size == 4096 * 3840
    assert int(counts.sum()) == offsets[-1]
    np.testing.assert_array_equal(rows, np.repeat(np.arange(4096), 3))


# ---------------------------------------------------------------------------
# Spans of the preprocessing pipeline
# ---------------------------------------------------------------------------

def _preprocess(tmp_path, tracer, out="sig"):
    import jax
    from repro.core.hashing import Hash2U
    from repro.data.preprocess import preprocess_shards
    sets, labels = _toy_sets(64, seed=5)
    raw = tmp_path / "raw"
    if not raw.exists():
        write_shards(sets, labels, str(raw), n_shards=2)    # 32 rows each
    paths = sorted(str(p) for p in raw.iterdir())
    fam = Hash2U.create(jax.random.PRNGKey(0), 64, 10)
    return preprocess_shards(paths, str(tmp_path / out), fam, b=4,
                             chunk_size=32, tracer=tracer)


def test_preprocess_spans_one_set_per_chunk(tmp_path):
    """The loader thread reads, pads and uploads once per chunk (one
    shard here); the caller waits, hashes and stores once per chunk and
    waits once more for the end of the stream."""
    import threading
    from repro.obs.trace import Tracer
    tr = Tracer(enabled=True, jax_annotations=True)
    stats = _preprocess(tmp_path, tr)
    chunks = 2
    assert stats.examples == 64
    me = threading.get_ident()
    seen = {}
    for e in tr.events():
        seen.setdefault(e["name"], []).append(e["tid"])
    caller = {"prep.wait": chunks + 1, "prep.hash": chunks,
              "prep.store": chunks}
    loader = {"prep.read": chunks, "prep.pad": chunks,
              "prep.upload": chunks}
    assert {n: len(t) for n, t in seen.items()} == {**caller, **loader}
    assert all(set(seen[n]) == {me} for n in caller)
    loader_tids = {t for n in loader for t in seen[n]}
    assert len(loader_tids) == 1 and me not in loader_tids


def test_sig_output_identical_with_tracing_on_and_off(tmp_path):
    from repro.obs.trace import Tracer
    _preprocess(tmp_path, Tracer(enabled=True, jax_annotations=True), "on")
    _preprocess(tmp_path, Tracer(enabled=False), "off")
    on = sorted((tmp_path / "on").iterdir())
    off = sorted((tmp_path / "off").iterdir())
    assert [p.name for p in on] == [p.name for p in off] == [
        "sig_00000.sig", "sig_00001.sig"]
    for a, b in zip(on, off):
        assert a.read_bytes() == b.read_bytes()
