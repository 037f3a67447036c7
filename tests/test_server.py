"""Continuous-batching SearchServer + lock-file coordination + live
appends under readers: the PR-6 serving-path promises.

  * micro-batched server results bit-identical to direct ``search()``
    (and the batch triggers: full, aged, deadline, drain),
  * ``FileLock`` mutual exclusion, reentrancy, timeout, stale break,
  * flush racing ``ShardedIndex.append``: every result consistent with
    the pre- OR post-append corpus, never a torn mix; a second router
    picks the append up via the manifest generation,
  * the serve CLI parses its index options and refuses the removed
    model options,
  * ``ZipfianTraffic`` determinism and shape.
"""

import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.core.oph import OPH
from repro.data.lockfile import FileLock, LockTimeout
from repro.data.pipeline import make_sharded_dataset
from repro.data.preprocess import preprocess_shards
from repro.data.synthetic import DatasetSpec
from repro.index import (IndexSearcher, build_index, build_sharded,
                         choose_band_config, load_index, load_sharded)
from repro.launch.serve import build_parser, serve_index
from repro.launch.server import (RequestShed, SearchServer, ServerStats,
                                 ZipfianTraffic)

K, S, B = 128, 16, 8


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """Synthetic corpus as .sig shards + one single-index searcher."""
    tmp = str(tmp_path_factory.mktemp("server_corpus"))
    spec = DatasetSpec("servertest", n=260, D=1 << S, avg_nnz=48,
                       n_prototypes=6, overlap=0.8, seed=4)
    raw = make_sharded_dataset(spec, os.path.join(tmp, "raw"), n_shards=4)
    fam = OPH.create(jax.random.PRNGKey(1), K, S, "2u", "rotation")
    preprocess_shards(raw, os.path.join(tmp, "sig"), fam, b=B,
                      chunk_size=64)
    sig_paths = sorted(glob.glob(os.path.join(tmp, "sig", "*.sig")))
    assert len(sig_paths) >= 4
    cfg = choose_band_config(K, B, threshold=0.5)
    idx_path = os.path.join(tmp, "single.idx")
    build_index(sig_paths, idx_path, cfg)
    return tmp, sig_paths, cfg, idx_path


@pytest.fixture(scope="module")
def searcher(corpus):
    _, _, _, idx_path = corpus
    return IndexSearcher(load_index(idx_path), backend="interpret",
                         corpus_block=128)


# ---------------------------------------------------------------------------
# FileLock
# ---------------------------------------------------------------------------

def test_filelock_mutual_exclusion_and_timeout(tmp_path):
    path = str(tmp_path / "x.lock")
    a = FileLock(path)
    b = FileLock(path, timeout_s=0.05, poll_s=0.005)
    with a:
        assert a.held and os.path.exists(path)
        with pytest.raises(LockTimeout):
            b.acquire()
    assert not os.path.exists(path)              # released -> removed
    with b:                                      # free again
        assert b.held


def test_filelock_reentrant(tmp_path):
    lock = FileLock(str(tmp_path / "r.lock"))
    with lock:
        with lock:                               # same instance re-enters
            assert lock.held
        assert lock.held                         # inner exit keeps it
    assert not lock.held


def test_filelock_breaks_stale(tmp_path):
    path = str(tmp_path / "dead.lock")
    with open(path, "w") as f:
        f.write("999999 0")                      # a crashed holder
    old = time.time() - 3600
    os.utime(path, (old, old))
    lock = FileLock(path, timeout_s=1.0, poll_s=0.01, stale_s=60.0)
    with lock:                                   # broke the stale file
        assert lock.held
    # without stale breaking the same file times out
    with open(path, "w") as f:
        f.write("999999 0")
    os.utime(path, (old, old))
    with pytest.raises(LockTimeout):
        FileLock(path, timeout_s=0.05, poll_s=0.005).acquire()


def test_filelock_released_on_generator_abandon(tmp_path, corpus):
    """Abandoning a SignatureCache populate pass mid-epoch must release
    the cache dir's lock (generator close runs the with-block exit)."""
    from repro.data.pipeline import SignatureStream
    from repro.train.online import SignatureCache, make_family
    fam = make_family(jax.random.PRNGKey(0), "oph", K, S)
    raw = sorted(glob.glob(os.path.join(corpus[0], "raw", "*")))
    cache_dir = str(tmp_path / "shared")
    cache = SignatureCache(SignatureStream(raw, fam, b=B, chunk_size=64),
                           cache_dir=cache_dir)
    it = iter(cache)
    next(it)                                     # lock held mid-pass
    assert os.path.exists(os.path.join(cache_dir, ".lock"))
    it.close()
    assert not os.path.exists(os.path.join(cache_dir, ".lock"))
    # a second trainer sharing the dir can now populate immediately
    other = SignatureCache(SignatureStream(raw, fam, b=B, chunk_size=64),
                           cache_dir=cache_dir, lock_timeout_s=1.0)
    assert len(list(other)) > 0 and other.populated


# ---------------------------------------------------------------------------
# SearchServer
# ---------------------------------------------------------------------------

def test_server_bit_identical_to_direct_search(searcher):
    """Micro-batched results == direct search(), row for row."""
    n = searcher.index.n
    picks = [0, 3, n // 2, n - 1, 7, n // 3]
    rows = [np.asarray(searcher.index.words_host[i]) for i in picks]
    direct = searcher.search(np.stack(rows), 5, mode="exact")
    with SearchServer(searcher, max_batch=4, max_delay_s=0.01,
                      topk=5) as srv:
        handles = [srv.submit(r) for r in rows]
        results = [h.result(timeout=60.0) for h in handles]
    for j, res in enumerate(results):
        assert np.array_equal(res.indices[0], direct.indices[j])
        assert np.array_equal(res.scores[0], direct.scores[j])
    assert srv.stats.requests == len(picks)
    assert srv.stats.batches >= 2                # max_batch=4 over 6 reqs


def test_server_full_batch_trigger(searcher):
    """With a huge delay window, only a full queue can flush."""
    rows = [np.asarray(searcher.index.words_host[i]) for i in range(4)]
    with SearchServer(searcher, max_batch=2, max_delay_s=30.0,
                      topk=3) as srv:
        handles = [srv.submit(r) for r in rows]
        t0 = time.monotonic()
        for h in handles:
            h.result(timeout=60.0)
        assert time.monotonic() - t0 < 25.0      # did not wait out the delay
    assert srv.stats.flush_full >= 1
    assert srv.stats.flush_aged == 0


def test_server_aged_trigger_flushes_partial_batch(searcher):
    """A lone request flushes after max_delay_s, not never."""
    row = np.asarray(searcher.index.words_host[1])
    with SearchServer(searcher, max_batch=64, max_delay_s=0.05,
                      topk=3) as srv:
        h = srv.submit(row)
        h.result(timeout=60.0)
    assert srv.stats.flush_aged == 1
    assert srv.stats.flush_full == 0
    assert h.queue_wait_s >= 0.04                # sat out the delay window


def test_server_deadline_trigger(searcher):
    """An explicit deadline flushes before the aging window would."""
    row = np.asarray(searcher.index.words_host[2])
    with SearchServer(searcher, max_batch=64, max_delay_s=30.0,
                      topk=3) as srv:
        t0 = time.monotonic()
        h = srv.submit(row, deadline_s=0.25)
        h.result(timeout=60.0)
        assert time.monotonic() - t0 < 25.0
    assert srv.stats.flush_deadline == 1


def test_server_drains_on_stop(searcher):
    """stop() flushes whatever is queued instead of dropping it."""
    rows = [np.asarray(searcher.index.words_host[i]) for i in (1, 2, 3)]
    srv = SearchServer(searcher, max_batch=64, max_delay_s=30.0,
                       topk=3).start()
    handles = [srv.submit(r) for r in rows]
    srv.stop()
    for h in handles:
        assert h.done()
        assert h.result(timeout=0).indices.shape == (1, 3)
    assert srv.stats.flush_drain >= 1
    with pytest.raises(RuntimeError):
        srv.submit(rows[0])                      # stopped server rejects


def test_server_bad_query_fails_only_itself(searcher):
    """A malformed row errors its own handle; co-batched queries still
    get bit-identical results."""
    good = np.asarray(searcher.index.words_host[5])
    direct = searcher.search(good[None, :], 3, mode="exact")
    with SearchServer(searcher, max_batch=2, max_delay_s=30.0,
                      topk=3) as srv:
        h_bad = srv.submit(np.zeros(3, np.uint32))   # wrong word count
        h_good = srv.submit(good)
        res = h_good.result(timeout=60.0)
        with pytest.raises(ValueError):
            h_bad.result(timeout=60.0)
    assert np.array_equal(res.indices, direct.indices)
    assert np.array_equal(res.scores, direct.scores)
    assert srv.stats.errors == 1


def test_server_requires_start():
    with pytest.raises(RuntimeError, match="not started"):
        SearchServer(object()).submit(np.zeros(1))


def test_server_stats_snapshot(searcher):
    rows = [np.asarray(searcher.index.words_host[i]) for i in range(3)]
    with SearchServer(searcher, max_batch=3, max_delay_s=0.01,
                      topk=3) as srv:
        for h in [srv.submit(r) for r in rows]:
            h.result(timeout=60.0)
    snap = srv.stats.snapshot()
    assert snap["requests"] == 3 and snap["errors"] == 0
    for key in ("latency_p50_ms", "latency_p99_ms", "queue_wait_p50_ms",
                "flush_p50_ms", "mean_batch"):
        assert np.isfinite(snap[key]), key
    assert snap["latency_p99_ms"] >= snap["latency_p50_ms"]
    assert len(srv.stats.queue_wait_s) == 3      # one sample per request


def test_server_stats_reservoir_bounded():
    stats = ServerStats(window=4)
    for i in range(10):
        stats.latency_s.append(float(i))
    assert list(stats.latency_s) == [6.0, 7.0, 8.0, 9.0]


# ---------------------------------------------------------------------------
# Multi-worker dispatch + admission control
# ---------------------------------------------------------------------------

def test_server_multiworker_bit_identical(searcher):
    """Four dispatch workers draining one queue: every request's row is
    still bit-identical to direct search(), no matter which worker's
    flush served it, and the per-worker histograms account for every
    batch."""
    n = searcher.index.n
    rng = np.random.default_rng(42)
    picks = rng.integers(0, n, size=24)
    rows = [np.asarray(searcher.index.words_host[i]) for i in picks]
    direct = searcher.search(np.stack(rows), 5, mode="exact")
    with SearchServer(searcher, max_batch=4, max_delay_s=0.005,
                      topk=5, num_workers=4) as srv:
        handles = [srv.submit(r) for r in rows]
        results = [h.result(timeout=60.0) for h in handles]
    for j, res in enumerate(results):
        assert np.array_equal(res.indices[0], direct.indices[j])
        assert np.array_equal(res.scores[0], direct.scores[j])
    snap = srv.stats.snapshot()
    assert snap["workers"] == 4
    assert snap["requests"] == len(rows) and snap["errors"] == 0
    assert sum(snap["worker_flushes"]) == snap["batches"]
    assert len(snap["worker_occupancy"]) == 4
    assert all(h.outcome == "served" for h in handles)


class _SlowSearcher:
    """Wraps a real searcher so every flush costs a fixed wall-clock
    delay -- a deterministic overload lever for the admission tests."""

    def __init__(self, inner, delay_s):
        self.inner = inner
        self.delay_s = delay_s

    @property
    def spec(self):
        return self.inner.spec

    def search(self, queries, topk=10, *, mode="exact", query_sizes=None):
        time.sleep(self.delay_s)
        return self.inner.search(queries, topk, mode=mode,
                                 query_sizes=query_sizes)


def test_server_overload_sheds_and_never_deadlocks(searcher):
    """Offered load >> capacity with a bounded queue: shed-oldest drops
    traffic instead of blowing the budget, every handle resolves (no
    deadlock), and the requests that WERE served met their deadline."""
    slow = _SlowSearcher(searcher, 0.05)
    rows = [np.asarray(searcher.index.words_host[i % searcher.index.n])
            for i in range(60)]
    with SearchServer(slow, max_batch=4, max_delay_s=0.002, topk=3,
                      admission="shed-oldest", max_queue=8) as srv:
        handles = [srv.submit(r, deadline_s=5.0) for r in rows]
        for h in handles:
            if h.outcome != "shed":
                h.result(timeout=60.0)
    assert all(h.done() for h in handles)            # nothing stranded
    stats = srv.stats
    assert stats.shed > 0                            # overload really shed
    assert stats.requests + stats.shed == len(rows)  # full accounting
    assert stats.deadline_misses == 0                # survivors on budget
    shed_handles = [h for h in handles if h.outcome == "shed"]
    assert len(shed_handles) == stats.shed
    with pytest.raises(RequestShed):
        shed_handles[0].result(timeout=0)
    snap = stats.snapshot()
    assert snap["shed_rate"] == pytest.approx(
        stats.shed / len(rows))


def test_server_admission_reject_is_immediate(searcher):
    """reject resolves the arriving request at submit time -- the
    caller learns within the submit call, not after a queue wait."""
    slow = _SlowSearcher(searcher, 0.05)
    rows = [np.asarray(searcher.index.words_host[i % searcher.index.n])
            for i in range(30)]
    with SearchServer(slow, max_batch=4, max_delay_s=0.002, topk=3,
                      admission="reject", max_queue=4) as srv:
        handles = [srv.submit(r, deadline_s=5.0) for r in rows]
        rejected = [h for h in handles if h.done() and h.outcome == "shed"]
        assert rejected                              # rejected at admission
        for h in handles:
            if h.outcome != "shed":
                h.result(timeout=60.0)
    assert srv.stats.shed == len([h for h in handles
                                  if h.outcome == "shed"])
    assert srv.stats.requests + srv.stats.shed == len(rows)
    assert srv.stats.deadline_misses == 0


def test_server_degrade_to_lsh(searcher):
    """Under a budget no exact flush can meet, degrade-to-lsh serves
    every request -- nothing shed -- through the LSH path, bit-identical
    to a direct mode='lsh' search."""
    n = searcher.index.n
    rows = [np.asarray(searcher.index.words_host[i])
            for i in (0, 3, n // 2, n - 1)]
    direct = searcher.search(np.stack(rows), 5, mode="lsh")
    with SearchServer(searcher, max_batch=4, max_delay_s=0.01, topk=5,
                      admission="degrade-to-lsh",
                      deadline_budget_s=1e-6) as srv:   # unmeetable budget
        handles = [srv.submit(r) for r in rows]
        results = [h.result(timeout=60.0) for h in handles]
    assert all(h.outcome == "degraded" for h in handles)
    for j, res in enumerate(results):
        assert np.array_equal(res.indices[0], direct.indices[j])
        assert np.array_equal(res.scores[0], direct.scores[j])
    assert srv.stats.shed == 0
    assert srv.stats.degraded == len(rows)
    assert srv.stats.snapshot()["degraded_rate"] == 1.0


def test_server_admission_validation(searcher):
    with pytest.raises(ValueError, match="admission"):
        SearchServer(searcher, admission="drop-everything")
    with pytest.raises(ValueError, match="degrade-to-lsh"):
        SearchServer(searcher, admission="degrade-to-lsh", mode="lsh")
    with pytest.raises(ValueError, match="max_queue"):
        SearchServer(searcher, admission="reject", max_queue=0)
    with pytest.raises(ValueError, match="num_workers"):
        SearchServer(searcher, num_workers=0)


def test_server_stats_concurrent_snapshot(searcher):
    """Seeded multi-thread submit storm while snapshot() runs hot:
    every snapshot is computed from a consistent copy (np.percentile
    over a mutating deque raises RuntimeError -- this pins the lock-
    copy), and the final counters account for every request."""
    n = searcher.index.n
    rng = np.random.default_rng(7)
    per_thread = 25
    picks = rng.integers(0, n, size=(4, per_thread))
    snap_errors, submit_errors = [], []
    with SearchServer(searcher, max_batch=8, max_delay_s=0.001,
                      topk=3, num_workers=2) as srv:
        def storm(t):
            try:
                hs = [srv.submit(
                    np.asarray(searcher.index.words_host[i]))
                    for i in picks[t]]
                for h in hs:
                    h.result(timeout=60.0)
            except Exception as e:               # pragma: no cover
                submit_errors.append(e)

        threads = [threading.Thread(target=storm, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        seen = 0
        while any(t.is_alive() for t in threads):
            try:
                snap = srv.stats.snapshot()
            except RuntimeError as e:            # pragma: no cover
                snap_errors.append(e)
                break
            assert snap["requests"] >= seen      # monotone, never torn
            seen = snap["requests"]
        for t in threads:
            t.join()
    assert not submit_errors and not snap_errors
    snap = srv.stats.snapshot()
    assert snap["requests"] == 4 * per_thread
    assert snap["errors"] == 0
    assert sum(snap["worker_flushes"]) == snap["batches"]


def test_server_worker_survives_flush_crash(searcher):
    """A worker whose flush blows up mid-storm is restarted: the dead
    batch's handles resolve as errors (never strand), later requests
    are served normally, and the restart is counted."""
    n = searcher.index.n
    rows = [np.asarray(searcher.index.words_host[i % n])
            for i in range(24)]
    with SearchServer(searcher, max_batch=4, max_delay_s=0.002,
                      topk=3, num_workers=2) as srv:
        real = srv._flush_batch
        crashes = [2]

        def flaky(batch, trigger, wi, handle):
            if crashes[0] > 0:
                crashes[0] -= 1
                raise RuntimeError("injected flush crash")
            return real(batch, trigger, wi, handle)

        srv._flush_batch = flaky
        handles = [srv.submit(r) for r in rows]
        outcomes = []
        for h in handles:
            try:
                res = h.result(timeout=60.0)
                assert res.indices.shape == (1, 3)   # never torn
                outcomes.append("served")
            except RuntimeError as e:
                assert "injected flush crash" in str(e)
                outcomes.append("error")
    assert all(h.done() for h in handles)            # nothing stranded
    assert crashes[0] == 0                           # both crashes fired
    assert outcomes.count("error") >= 1
    assert outcomes.count("served") >= 1             # server kept serving
    snap = srv.stats.snapshot()
    assert snap["worker_restarts"] == 2
    # full accounting: every row either served (counted) or errored
    assert snap["requests"] == outcomes.count("served")
    assert snap["requests"] + outcomes.count("error") == len(rows)
    assert srv.stats.errors >= 2


def test_zipfian_traffic_identical_across_worker_counts(searcher):
    """The load model is independent of the serving side: the same seed
    replays the same query ids and arrival times no matter how many
    workers serve it, and both servers return bit-identical results."""
    m = 16
    ids = {}
    results = {}
    for workers in (1, 3):
        traffic = ZipfianTraffic(searcher.index.n, alpha=1.1, seed=13)
        ids[workers] = traffic.ids(m)
        offs = traffic.arrival_offsets(m, rate_qps=5000.0)
        with SearchServer(searcher, max_batch=4, max_delay_s=0.002,
                          topk=5, num_workers=workers) as srv:
            handles = [srv.submit(
                np.asarray(searcher.index.words_host[i]))
                for i in ids[workers]]
            results[workers] = [h.result(timeout=60.0) for h in handles]
        ids[f"offs{workers}"] = offs
    np.testing.assert_array_equal(ids[1], ids[3])
    np.testing.assert_array_equal(ids["offs1"], ids["offs3"])
    for a, b in zip(results[1], results[3]):
        assert np.array_equal(a.indices, b.indices)
        assert np.array_equal(a.scores, b.scores)


# ---------------------------------------------------------------------------
# Live appends under readers
# ---------------------------------------------------------------------------

@pytest.fixture()
def growing_router(corpus, tmp_path):
    tmp, sig_paths, cfg, _ = corpus
    shard_dir = str(tmp_path / "growing")
    build_sharded(sig_paths[:3], shard_dir, cfg, n_shards=2)
    router = load_sharded(shard_dir, backend="interpret", corpus_block=64)
    return router, sig_paths[3:]


def test_search_racing_append_never_torn(growing_router):
    """Concurrent search() calls during append() return results equal to
    the pre-append OR the post-append corpus -- never a torn mix."""
    router, extra = growing_router
    n0 = router.n
    q = np.ascontiguousarray(
        router.searchers[0].index.words_host[[0, 3, 9, 17]])
    pre = router.search(q, 5, mode="exact")
    results, errors = [], []

    def reader():
        try:
            for _ in range(10):
                results.append(router.search(q, 5, mode="exact"))
        except Exception as e:               # pragma: no cover
            errors.append(e)

    t = threading.Thread(target=reader)
    t.start()
    time.sleep(0.02)
    router.append(extra)
    t.join()
    assert not errors
    assert router.n > n0
    post = router.search(q, 5, mode="exact")
    assert not (np.array_equal(pre.indices, post.indices)
                and np.array_equal(pre.scores, post.scores))
    for res in results:
        matches_pre = (np.array_equal(res.indices, pre.indices)
                       and np.array_equal(res.scores, pre.scores))
        matches_post = (np.array_equal(res.indices, post.indices)
                        and np.array_equal(res.scores, post.scores))
        assert matches_pre or matches_post


def test_server_flush_picks_up_append_via_refresh(growing_router):
    """Flushes before the append serve the old corpus, flushes after it
    serve the grown corpus -- the server's per-flush refresh() is the
    reader side of the generation-versioned manifest."""
    router, extra = growing_router
    q_rows = [np.asarray(router.searchers[0].index.words_host[i])
              for i in (1, 6, 11)]
    pre = router.search(np.stack(q_rows), 5, mode="exact")
    with SearchServer(router, max_batch=len(q_rows), max_delay_s=0.01,
                      topk=5) as srv:
        first = [srv.submit(r) for r in q_rows]
        first = [h.result(timeout=60.0) for h in first]
        gen0 = router.generation
        router.append(extra)
        assert router.generation == gen0 + 1
        second = [srv.submit(r) for r in q_rows]
        second = [h.result(timeout=60.0) for h in second]
    post = router.search(np.stack(q_rows), 5, mode="exact")
    for j, res in enumerate(first):
        assert np.array_equal(res.indices[0], pre.indices[j])
        assert np.array_equal(res.scores[0], pre.scores[j])
    for j, res in enumerate(second):
        assert np.array_equal(res.indices[0], post.indices[j])
        assert np.array_equal(res.scores[0], post.scores[j])


def test_second_router_picks_up_append(growing_router, tmp_path):
    """Two routers over one shard dir model two processes: an append in
    one is visible to the other after refresh(), via the generation."""
    router, extra = growing_router
    other = load_sharded(router.manifest_dir, backend="interpret",
                         corpus_block=64)
    assert other.generation == router.generation
    router.append(extra)
    assert other.n < router.n                    # not yet refreshed
    assert other.refresh() is True
    assert other.n == router.n
    assert other.generation == router.generation
    assert other.refresh() is False              # idempotent
    q = np.ascontiguousarray(
        router.searchers[0].index.words_host[[2, 5]])
    a = router.search(q, 5, mode="exact")
    b = other.search(q, 5, mode="exact")
    assert np.array_equal(a.indices, b.indices)
    assert np.array_equal(a.scores, b.scores)


# ---------------------------------------------------------------------------
# CLI + traffic model
# ---------------------------------------------------------------------------

def test_serve_refuses_mesh_larger_than_devices():
    """--mesh D with fewer than D devices fails instead of quietly
    serving on the devices that are there."""
    args = build_parser().parse_args(["--index", "--shards", "2",
                                      "--mesh", "4096"])
    with pytest.raises(SystemExit, match="--mesh 4096 needs 4096 devices"):
        serve_index(args)


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir_is_fixed(monkeypatch, tmp_path, env_dir):
    """Unset: the cache lives at <checkout>/.jax_cache.  Set: JAX's own
    JAX_COMPILATION_CACHE_DIR wins and nothing is changed."""
    from repro.launch.compile_cache import enable_compile_cache
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    try:
        got = enable_compile_cache()
        if env_dir is None:
            assert got == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_serve_cli_smoke_flag_both_ways():
    """The serve CLI serves the index only: --smoke/--no-smoke, --arch and
    --tokens are refused, --index is accepted but not required, and the
    index and --serve options parse."""
    ap = build_parser()
    for gone in (["--smoke"], ["--no-smoke"], ["--arch", "din"],
                 ["--tokens", "8"]):
        with pytest.raises(SystemExit):
            ap.parse_args(gone)
    assert ap.parse_args(["--index"]).index is True
    assert ap.parse_args([]).index is False
    assert ap.parse_args(["--requests", "3"]).requests == 3
    args = ap.parse_args(["--index", "--serve", "--rate", "123",
                          "--max-delay-ms", "2.5"])
    assert args.serve and args.rate == 123.0 and args.max_delay_ms == 2.5
    assert ap.parse_args([]).serve is False
    # multi-worker + admission knobs parse and default sanely
    args = ap.parse_args(["--index", "--serve", "--workers", "4",
                          "--admission", "shed-oldest",
                          "--max-queue", "64",
                          "--deadline-budget-ms", "20"])
    assert args.workers == 4 and args.admission == "shed-oldest"
    assert args.max_queue == 64 and args.deadline_budget_ms == 20.0
    defaults = ap.parse_args([])
    assert defaults.workers is None and defaults.admission == "none"
    with pytest.raises(SystemExit):
        ap.parse_args(["--admission", "drop-everything"])


def test_roofline_search_model():
    """The serving benchmark's analytic roofline terms: corpus-stream
    dominance, linear scaling, and the gap/bandwidth arithmetic."""
    from repro.roofline.search import exact_scan_cost, roofline_gap
    c1 = exact_scan_cost(10_000, 32, 8, topk=10)
    c2 = exact_scan_cost(20_000, 32, 8, topk=10)
    assert c2["corpus_bytes"] == 2 * c1["corpus_bytes"]
    assert c1["corpus_bytes"] == 10_000 * 32 * 4
    assert c2["bytes"] > c1["bytes"] and c2["flops"] == 2 * c1["flops"]
    # batching amortizes the corpus stream: bytes/query shrinks with q
    c_batched = exact_scan_cost(10_000, 32, 64, topk=10)
    assert c_batched["bytes_per_query"] < c1["bytes_per_query"]
    g = roofline_gap(819e9, 2.0, bw=819e9)     # 1s of traffic in 2s
    assert g["gap"] == pytest.approx(2.0)
    assert g["predicted_s"] == pytest.approx(1.0)
    assert g["achieved_gbps"] == pytest.approx(819e9 / 2.0 / 1e9)
    with pytest.raises(ValueError):
        exact_scan_cost(0, 32, 8)
    with pytest.raises(ValueError):
        roofline_gap(0.0, 1.0)


def test_zipfian_traffic_deterministic_and_skewed():
    a = ZipfianTraffic(500, alpha=1.2, seed=7)
    b = ZipfianTraffic(500, alpha=1.2, seed=7)
    ids_a, ids_b = a.ids(400), b.ids(400)
    np.testing.assert_array_equal(ids_a, ids_b)
    assert ids_a.min() >= 0 and ids_a.max() < 500
    # Zipf skew: the most popular id dwarfs the uniform expectation
    top = np.bincount(ids_a).max()
    assert top > 3 * (400 / 500)
    arr = a.arrival_offsets(100, rate_qps=1000.0)
    assert arr.shape == (100,) and np.all(np.diff(arr) > 0)
    assert 0.02 < arr[-1] < 1.0                  # ~100/1000 s, loose bounds
    with pytest.raises(ValueError):
        a.arrival_offsets(5, rate_qps=0.0)
    with pytest.raises(ValueError):
        ZipfianTraffic(0)
