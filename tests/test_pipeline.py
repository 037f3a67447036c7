"""Data pipeline: shard formats, chunked iteration, prefetch, stragglers."""

import numpy as np
import pytest

import random
import time

from repro.data import TINY, generate
from repro.data.pipeline import (ChunkedLoader, LoaderStats,
                                 make_sharded_dataset, read_shard_binary,
                                 read_shard_libsvm, read_with_retries,
                                 write_shard_binary, write_shard_libsvm,
                                 write_shards)


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
    loader = ChunkedLoader(paths, chunk_size=25, prefetch=prefetch,
                           lane_multiple=8)
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
                           straggler_deadline_s=0.0, max_retries=1,
                           lane_multiple=8)
    chunks = list(loader)
    assert sum(c.n for c in chunks) == 40
    assert loader.stats.straggler_retries >= 2
    assert loader.stats.shard_reassignments == 2


def test_read_shard_oserror_accounted(tmp_path):
    """Flaky reads retry with accounting; exhausted retries raise."""
    sets, labels = _toy_sets(20)
    paths = write_shards(sets, labels, str(tmp_path), n_shards=1)
    loader = ChunkedLoader(paths, chunk_size=20, prefetch=0, max_retries=2,
                           lane_multiple=8)
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
    # the successful attempt is fully accounted (no silent re-read)
    assert loader.stats.load_seconds > 0 and loader.stats.bytes_read > 0

    # every attempt failing must surface the OSError, all attempts counted
    dead = ChunkedLoader(paths, chunk_size=20, prefetch=0, max_retries=1,
                         lane_multiple=8)

    def always_fails(path):
        raise OSError("gone")

    dead._reader = always_fails
    with pytest.raises(OSError):
        list(dead)
    assert dead.stats.io_errors == 2  # max_retries + 1 attempts
    assert dead.stats.bytes_read == 0


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
                           lane_multiple=8, io_backoff_base_s=1e-4,
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
    loader = ChunkedLoader(paths, chunk_size=16, lane_multiple=8)
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


@pytest.mark.parametrize("n,chunk_size", [(101, 25), (96, 16), (30, 64)])
def test_chunk_contents_pinned(tmp_path, n, chunk_size):
    """Chunk boundaries AND per-row set contents must equal slicing the
    concatenated shard stream -- pins that the O(n) moving-cursor chunk
    assembly (no per-chunk list re-copy) changed nothing observable."""
    sets, labels = _toy_sets(n, seed=3)
    paths = write_shards(sets, labels, str(tmp_path), n_shards=4)
    loader = ChunkedLoader(paths, chunk_size=chunk_size, prefetch=0,
                           lane_multiple=8)
    chunks = list(loader)
    sizes = [c.n for c in chunks]
    assert sizes[:-1] == [chunk_size] * (len(chunks) - 1)
    assert sum(sizes) == n
    pos = 0
    for c in chunks:
        idx = np.asarray(c.indices)
        mask = np.asarray(c.mask)
        for row in range(c.n):
            got = np.sort(idx[row][mask[row]])
            np.testing.assert_array_equal(got, np.sort(sets[pos + row]))
        np.testing.assert_array_equal(np.asarray(c.labels),
                                      labels[pos:pos + c.n])
        pos += c.n


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
                             chunk_size=32, tracer=tracer,
                             loader_kwargs={"lane_multiple": 8})


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
