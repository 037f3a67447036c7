"""Sharded-index router + incremental append: the scale-out promises.

  * router top-k merge bit-identical (ids AND scores) to a single-index
    search over the same corpus, exact and LSH, including the Theorem-1
    set-sizes rerank,
  * ``append_index`` produces byte-equivalent tables/payload to a full
    rebuild over old + new shards (and appending through the router
    keeps global ids stable),
  * ``build_sharded`` manifest round trip + error paths.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.oph import OPH
from repro.data.pipeline import make_sharded_dataset
from repro.data.preprocess import preprocess_shards
from repro.data.sigshard import write_sig_shard
from repro.data.sparse import from_lists
from repro.data.synthetic import DatasetSpec
from repro.index import (BandingConfig, IndexSearcher, ShardedIndex,
                         append_index, build_index, build_sharded,
                         choose_band_config, load_index, load_sharded,
                         merge_topk)
from repro.index.query import SearchResult
from repro.kernels import SignatureEngine

K, S, B = 128, 16, 8


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """Synthetic corpus as .sig shards + one reference .idx."""
    tmp = str(tmp_path_factory.mktemp("router_corpus"))
    spec = DatasetSpec("routertest", n=420, D=1 << S, avg_nnz=48,
                       n_prototypes=8, overlap=0.8, seed=11)
    raw = make_sharded_dataset(spec, os.path.join(tmp, "raw"), n_shards=5)
    fam = OPH.create(jax.random.PRNGKey(1), K, S, "2u", "rotation")
    preprocess_shards(raw, os.path.join(tmp, "sig"), fam, b=B,
                      chunk_size=64)
    sig_paths = sorted(glob.glob(os.path.join(tmp, "sig", "*.sig")))
    assert len(sig_paths) >= 4
    cfg = choose_band_config(K, B, threshold=0.5)
    idx_path = os.path.join(tmp, "single.idx")
    build_index(sig_paths, idx_path, cfg)
    return tmp, sig_paths, cfg, idx_path


@pytest.mark.parametrize("n_shards", [2, 3])
def test_router_topk_bit_identical_to_single_index(corpus, tmp_path,
                                                   n_shards):
    """Fan-out + merge == single-index search: same ids, same scores,
    exact and LSH, search() and submit()/flush()."""
    tmp, sig_paths, cfg, idx_path = corpus
    single = IndexSearcher(load_index(idx_path), backend="interpret",
                           corpus_block=128)
    shard_dir = str(tmp_path / f"shards{n_shards}")
    built = build_sharded(sig_paths, shard_dir, cfg, n_shards=n_shards)
    assert len(built) == n_shards
    router = load_sharded(shard_dir, backend="interpret", corpus_block=128)
    assert router.n == single.index.n
    n = single.index.n
    picks = [0, 7, n // 3, n // 2, n - 2, n - 1]
    q = jnp.asarray(np.ascontiguousarray(single.index.words_host[picks]))
    for mode in ("exact", "lsh"):
        want = single.search(q, 10, mode=mode)
        got = router.search(q, 10, mode=mode)
        assert np.array_equal(got.indices, want.indices), mode
        assert np.array_equal(got.scores, want.scores), mode
        if mode == "lsh":
            assert np.array_equal(got.n_candidates, want.n_candidates)
    # batched admission returns the same per-ticket rows
    rows = [np.asarray(single.index.words_host[i])
            for i in (3, n // 2 + 1, n - 5)]
    tickets = [router.submit(r) for r in rows]
    out = router.flush(5, mode="exact")
    want = single.search(jnp.asarray(np.stack(rows)), 5, mode="exact")
    for i, t in enumerate(tickets):
        assert np.array_equal(out[t].indices[0], want.indices[i])
        assert np.array_equal(out[t].scores[0], want.scores[i])
    assert router.flush() == {}


def test_router_with_set_sizes_rerank(tmp_path):
    """Theorem-1 rerank flows through the router: per-shard doc sizes,
    merged results equal the single index's."""
    rng = np.random.default_rng(9)
    sets = [rng.choice(1 << S, rng.integers(30, 90), replace=False)
            for _ in range(96)]
    batch = from_lists(sets, max_nnz=128)
    fam = OPH.create(jax.random.PRNGKey(2), K, S, "2u", "rotation")
    wire = SignatureEngine(fam, b=B, packed=True).packed_signatures(batch)
    sizes = np.array([len(s) for s in sets], np.uint32)
    paths = []
    for i in range(3):
        p = str(tmp_path / f"c{i}.sig")
        write_sig_shard(p, np.asarray(wire.data[i * 32:(i + 1) * 32]),
                        np.zeros(32, np.float32), k=K, b=B, code_bits=B)
        paths.append(p)
    cfg = BandingConfig(16, 2, B)
    build_index(paths, str(tmp_path / "one.idx"), cfg, set_sizes=sizes, s=S)
    build_sharded(paths, str(tmp_path / "sh"), cfg, n_shards=3,
                  set_sizes=sizes, s=S)
    single = IndexSearcher(load_index(str(tmp_path / "one.idx")),
                           backend="interpret", corpus_block=32)
    router = load_sharded(str(tmp_path / "sh"), backend="interpret",
                          corpus_block=32)
    want = single.search(wire[:5], 5, mode="exact", query_sizes=sizes[:5])
    got = router.search(wire[:5], 5, mode="exact", query_sizes=sizes[:5])
    assert np.array_equal(got.indices, want.indices)
    assert np.array_equal(got.scores, want.scores)
    with pytest.raises(ValueError):              # sizes still required
        router.search(wire[:5], 5, mode="exact")


def test_append_equals_full_rebuild(corpus, tmp_path):
    """append_index over the tail shards == build_index over everything:
    identical header, tables, labels, payload -- and identical queries."""
    tmp, sig_paths, cfg, idx_path = corpus
    full = load_index(idx_path)
    grown_path = str(tmp_path / "grown.idx")
    build_index(sig_paths[:2], grown_path, cfg)
    meta = append_index(grown_path, sig_paths[2:])
    grown = load_index(grown_path)
    assert meta == full.meta
    np.testing.assert_array_equal(grown.labels, full.labels)
    np.testing.assert_array_equal(grown.band_offsets, full.band_offsets)
    np.testing.assert_array_equal(grown.keys, full.keys)
    np.testing.assert_array_equal(grown.bucket_offsets, full.bucket_offsets)
    np.testing.assert_array_equal(grown.postings, full.postings)
    np.testing.assert_array_equal(grown.words_host, full.words_host)
    q = jnp.asarray(np.ascontiguousarray(full.words_host[50:60]))
    want = IndexSearcher(full, backend="interpret",
                         corpus_block=128).search(q, 10)
    got = IndexSearcher(grown, backend="interpret",
                        corpus_block=128).search(q, 10)
    assert np.array_equal(got.indices, want.indices)
    assert np.array_equal(got.scores, want.scores)


def test_append_wire_and_set_size_validation(corpus, tmp_path):
    tmp, sig_paths, cfg, idx_path = corpus
    target = str(tmp_path / "t.idx")
    build_index(sig_paths[:1], target, cfg)
    bad = str(tmp_path / "bad.sig")
    rng = np.random.default_rng(0)
    w4 = rng.integers(0, 2**32, (4, 16), dtype=np.uint64).astype(np.uint32)
    write_sig_shard(bad, w4, np.zeros(4, np.float32), k=64, b=B, code_bits=B)
    with pytest.raises(ValueError, match="wire format"):
        append_index(target, [bad])
    with pytest.raises(ValueError, match="no set sizes"):
        append_index(target, sig_paths[1:2],
                     set_sizes=np.ones(64, np.uint32))


def test_router_append_grows_last_shard(corpus, tmp_path):
    """ShardedIndex.append: existing global ids stay put, the grown
    router matches a single index over all shards."""
    tmp, sig_paths, cfg, idx_path = corpus
    shard_dir = str(tmp_path / "growing")
    build_sharded(sig_paths[:3], shard_dir, cfg, n_shards=2)
    router = load_sharded(shard_dir, backend="interpret", corpus_block=128)
    n_before = router.n
    router.append(sig_paths[3:])
    assert router.n > n_before
    assert router.n_shards == 2                  # grew in place
    full = IndexSearcher(load_index(idx_path), backend="interpret",
                         corpus_block=128)
    assert router.n == full.index.n
    q = jnp.asarray(np.ascontiguousarray(
        full.index.words_host[[1, n_before - 1, n_before, router.n - 1]]))
    want = full.search(q, 10, mode="exact")
    got = router.search(q, 10, mode="exact")
    assert np.array_equal(got.indices, want.indices)
    assert np.array_equal(got.scores, want.scores)
    # the updated manifest reloads to the same state
    reloaded = load_sharded(shard_dir, backend="interpret", corpus_block=128)
    got2 = reloaded.search(q, 10, mode="exact")
    assert np.array_equal(got2.indices, want.indices)


def test_build_sharded_manifest_and_errors(corpus, tmp_path):
    tmp, sig_paths, cfg, idx_path = corpus
    import json
    shard_dir = str(tmp_path / "m")
    built = build_sharded(sig_paths, shard_dir, cfg, n_shards=3)
    with open(os.path.join(shard_dir, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["version"] == 1 and len(manifest["shards"]) == 3
    counts = [m.n for _, m in built]
    assert manifest["n"] == sum(counts)
    assert manifest["offsets"] == [0, counts[0], counts[0] + counts[1]]
    assert all(c > 0 for c in counts)            # no empty shard
    with pytest.raises(ValueError, match="n_shards"):
        build_sharded(sig_paths, shard_dir, cfg,
                      n_shards=len(sig_paths) + 1)
    with pytest.raises(OSError):
        load_sharded(str(tmp_path))              # no manifest.json here


def test_merge_topk_tie_break_and_padding():
    """merge_topk reproduces lax.top_k's lowest-id tie rule across shard
    boundaries and pads short corpora like a single index does."""
    r0 = SearchResult(np.array([[1, 0, -1]]),
                      np.array([[0.5, 0.5, -np.inf]], np.float32))
    r1 = SearchResult(np.array([[0, 2, -1]]),
                      np.array([[0.7, 0.5, -np.inf]], np.float32))
    out = merge_topk([r0, r1], [0, 10], 3)
    # 0.7 first, then the tied 0.5s in ascending GLOBAL-id order (0 then
    # 1) -- even though shard 0 reported them in the opposite order: the
    # merge rule is a pure function of (score, global id), never of the
    # arrival position, so any partition / dispatch order converges
    np.testing.assert_array_equal(out.indices, [[10, 0, 1]])
    np.testing.assert_array_equal(out.scores,
                                  np.array([[0.7, 0.5, 0.5]], np.float32))
    out = merge_topk([r0], [0], 5)               # fewer docs than topk
    np.testing.assert_array_equal(out.indices, [[0, 1, -1, -1, -1]])
    with pytest.raises(ValueError):
        merge_topk([], [], 3)


def test_merge_topk_all_empty_shards():
    """Every shard empty (e.g. LSH with zero candidates anywhere): the
    merge yields pure padding, not garbage ids."""
    empty = SearchResult(np.full((2, 3), -1),
                         np.full((2, 3), -np.inf, np.float32))
    out = merge_topk([empty, empty, empty], [0, 10, 20], 3)
    np.testing.assert_array_equal(out.indices, np.full((2, 3), -1))
    assert np.all(np.isneginf(out.scores))


def test_merge_topk_topk_exceeds_total_docs():
    """topk larger than ALL shards' real docs combined: valid docs first
    (score order), then -1/-inf padding out to topk."""
    r0 = SearchResult(np.array([[1, 0, -1]]),
                      np.array([[0.9, 0.4, -np.inf]], np.float32))
    r1 = SearchResult(np.array([[0, -1, -1]]),
                      np.array([[0.6, -np.inf, -np.inf]], np.float32))
    out = merge_topk([r0, r1], [0, 10], 8)
    np.testing.assert_array_equal(out.indices,
                                  [[1, 10, 0, -1, -1, -1, -1, -1]])
    np.testing.assert_array_equal(
        out.scores[0, :3], np.array([0.9, 0.6, 0.4], np.float32))
    assert np.all(np.isneginf(out.scores[0, 3:]))


def test_merge_topk_tie_run_spans_three_shards():
    """A tie run crossing every shard boundary resolves in ascending
    global-id order -- lax.top_k's rule over the concatenated corpus."""
    tie = np.float32(0.5)
    r0 = SearchResult(np.array([[0, 2]]), np.array([[tie, tie]], np.float32))
    r1 = SearchResult(np.array([[1, 3]]), np.array([[tie, tie]], np.float32))
    r2 = SearchResult(np.array([[0, 4]]), np.array([[tie, tie]], np.float32))
    out = merge_topk([r0, r1, r2], [0, 10, 20], 6)
    # per-shard results keep ascending local id inside the tie run, so
    # the merge must produce ascending GLOBAL ids across all shards
    np.testing.assert_array_equal(out.indices, [[0, 2, 11, 13, 20, 24]])
    assert np.all(out.scores == tie)


@pytest.mark.parametrize("seed", range(6))
def test_merge_topk_any_partition_matches_lax_topk(seed):
    """Property test: partition a scored corpus into 1..8 shards at
    random cut points, run a real per-shard lax.top_k, merge in a
    SHUFFLED shard order -- ids and scores must be bit-identical to
    lax.top_k over the unpartitioned corpus.  Scores are quantized so
    duplicate values and cross-shard tie runs are everywhere."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(20, 200))
    topk = int(rng.integers(1, 13))
    nq = 3
    scores = (rng.integers(0, 6, (nq, n)) / 4.0).astype(np.float32)
    kk = min(topk, n)
    want_s, want_i = jax.lax.top_k(jnp.asarray(scores), kk)
    n_shards = int(rng.integers(1, 9))
    cuts = np.sort(rng.choice(np.arange(1, n),
                              size=min(n_shards - 1, n - 1),
                              replace=False)) if n_shards > 1 else []
    bounds = [0, *np.asarray(cuts, int).tolist(), n]
    results, offsets = [], []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        s_i, i_i = jax.lax.top_k(jnp.asarray(scores[:, lo:hi]),
                                 min(topk, hi - lo))
        results.append(SearchResult(np.asarray(i_i).astype(np.int64),
                                    np.asarray(s_i)))
        offsets.append(lo)
    perm = rng.permutation(len(results))         # arrival-order-blind
    out = merge_topk([results[p] for p in perm],
                     [offsets[p] for p in perm], topk)
    np.testing.assert_array_equal(out.indices[:, :kk], np.asarray(want_i))
    np.testing.assert_array_equal(out.scores[:, :kk], np.asarray(want_s))
    assert np.all(out.indices[:, kk:] == -1)     # padding past the corpus
    assert np.all(np.isneginf(out.scores[:, kk:]))


def test_router_append_spills_into_new_shards(corpus, tmp_path):
    """With a max_shard_docs budget, append extends the last shard only
    while it has headroom, then spills into NEW tail shards; global ids
    stay put and the grown router matches a single index over all docs.
    A second process (fresh load_sharded) picks the spill up via the
    manifest."""
    tmp, sig_paths, cfg, idx_path = corpus
    shard_dir = str(tmp_path / "spilling")
    build_sharded(sig_paths[:3], shard_dir, cfg, n_shards=2)
    router = load_sharded(shard_dir, backend="interpret", corpus_block=128,
                          max_shard_docs=1)      # every file spills
    n_before, shards_before = router.n, router.n_shards
    n_files = len(sig_paths) - 3
    touched = router.append(sig_paths[3:])
    # budget below every file size: each appended file becomes its own
    # NEW shard, the original shards never grow
    assert router.n_shards == shards_before + n_files
    assert all(os.path.basename(p).startswith("shard_")
               for p, _ in touched)
    assert [p for p, _ in touched] == list(router.paths[-n_files:])
    full = IndexSearcher(load_index(idx_path), backend="interpret",
                         corpus_block=128)
    assert router.n == full.index.n
    q = jnp.asarray(np.ascontiguousarray(
        full.index.words_host[[1, n_before - 1, n_before, router.n - 1]]))
    want = full.search(q, 10, mode="exact")
    got = router.search(q, 10, mode="exact")
    assert np.array_equal(got.indices, want.indices)
    assert np.array_equal(got.scores, want.scores)
    # reader-side pickup: an independently loaded router refreshes into
    # the spilled shard set
    reader = load_sharded(shard_dir, backend="interpret", corpus_block=128)
    assert reader.n_shards == router.n_shards
    got2 = reader.search(q, 10, mode="exact")
    assert np.array_equal(got2.indices, want.indices)
    assert np.array_equal(got2.scores, want.scores)


def test_router_append_spill_respects_budget_granularity(corpus, tmp_path):
    """Spill planning is at .sig-file granularity: a shard may overshoot
    the budget by at most one file, and each spilled shard is refilled
    up to the budget before the next one starts."""
    tmp, sig_paths, cfg, idx_path = corpus
    from repro.data.sigshard import read_sig_meta
    counts = [read_sig_meta(p).n for p in sig_paths]
    shard_dir = str(tmp_path / "granular")
    build_sharded(sig_paths[:2], shard_dir, cfg, n_shards=2)
    # budget below the last shard's size -> the append is a pure spill
    budget = min(counts) // 2
    router = load_sharded(shard_dir, backend="interpret", corpus_block=128,
                          max_shard_docs=budget)
    router.append(sig_paths[2:])
    # pure spill: the two original shards never grew
    from repro.index.builder import read_manifest
    man = read_manifest(shard_dir)
    assert man["offsets"][:2] == [0, counts[0]]
    assert router.n == sum(counts)
    # every spilled shard holds >= 1 file and started below the budget
    spilled = [b - a for a, b in zip(man["offsets"][2:],
                                     man["offsets"][3:] + [man["n"]])]
    assert spilled and all(s > 0 for s in spilled)
    assert len(spilled) == len(sig_paths) - 2    # budget < every file size


def test_router_append_spill_crash_before_manifest_is_invisible(
        corpus, tmp_path, monkeypatch):
    """Fault injection at the spill-append commit point: the new shard
    is fully written but the process dies BEFORE the manifest rewrite.
    Readers must stay on the old generation with no torn shard visible,
    and a clean retry + refresh() must converge."""
    tmp, sig_paths, cfg, idx_path = corpus
    import repro.index.router as router_mod
    shard_dir = str(tmp_path / "crashy")
    build_sharded(sig_paths[:3], shard_dir, cfg, n_shards=2)
    writer = load_sharded(shard_dir, backend="interpret", corpus_block=128,
                          max_shard_docs=1)      # pure spill, no grow
    reader = load_sharded(shard_dir, backend="interpret", corpus_block=128)
    gen0, n0, paths0 = reader.generation, reader.n, reader.paths
    q = jnp.asarray(np.ascontiguousarray(
        reader.searchers[0].index.words_host[[0, 3]]))
    want = reader.search(q, 5)

    def boom(*a, **kw):
        raise RuntimeError("injected crash before manifest publish")

    monkeypatch.setattr(router_mod, "write_manifest", boom)
    with pytest.raises(RuntimeError, match="injected crash"):
        writer.append(sig_paths[3:4])
    monkeypatch.undo()

    # reader side: manifest untouched -> refresh is a no-op, same corpus,
    # same results; no temp files leak, no lock is left held
    assert reader.refresh() is False
    assert reader.generation == gen0 and reader.n == n0
    assert reader.paths == paths0
    got = reader.search(q, 5)
    assert np.array_equal(got.indices, want.indices)
    assert np.array_equal(got.scores, want.scores)
    assert not [f for f in os.listdir(shard_dir) if ".tmp" in f]
    # ... and a fresh load (new process) sees only the old generation
    fresh = load_sharded(shard_dir, backend="interpret", corpus_block=128)
    assert fresh.generation == gen0 and fresh.n == n0

    # clean retry: the orphaned shard file from the crash is atomically
    # overwritten, the manifest lands, readers converge via refresh()
    writer2 = load_sharded(shard_dir, backend="interpret",
                           corpus_block=128, max_shard_docs=1)
    writer2.append(sig_paths[3:4])
    assert reader.refresh() is True
    assert reader.generation > gen0
    assert reader.n_shards == 3 and reader.n > n0
    full_idx = str(tmp_path / "full.idx")
    build_index(sig_paths[:4], full_idx, cfg)
    single = IndexSearcher(load_index(full_idx), backend="interpret",
                           corpus_block=128)
    want2 = single.search(q, 5)
    got2 = reader.search(q, 5)
    assert np.array_equal(got2.indices, want2.indices)
    assert np.array_equal(got2.scores, want2.scores)
