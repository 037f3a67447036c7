"""Similarity-search subsystem: kernel parity, LSH recall, .idx format.

Four layers, mirroring the subsystem's promises:

  * packed-Hamming kernel vs an unpacked numpy/jnp reference: match
    counts bit-exact across (scheme, b, densify) including sentinel-OPH
    EMPTY bins, and exact brute-force top-k identical to a full-matrix
    reference top-k (same scores, same tie-breaking),
  * LSH candidate generation + rerank: recall@10 >= 0.9 vs exact on a
    synthetic corpus with the S-curve-predicted band config,
  * index build -> mmap load -> query round trip with ZERO host-side
    unpacking of the corpus (guards on the unpack entry points),
  * the ``.idx`` header: version byte round trip + clear mismatch error,
    banding math, batched query admission.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hashing import Hash2U, Hash4U
from repro.core.oph import EMPTY, OPH
from repro.data.pipeline import make_sharded_dataset
from repro.data.preprocess import preprocess_shards
from repro.data.sparse import from_lists
from repro.data.synthetic import DatasetSpec
from repro.index import (BandingConfig, IndexSearcher, band_keys_from_codes,
                         band_keys_packed, build_band_tables, build_index,
                         choose_band_config, load_index, read_index_meta,
                         resemblance_scores, s_curve)
from repro.kernels import SignatureEngine, packed_match
from repro.kernels.pack import PackSpec

K, S = 128, 16
_E = np.uint32(0xFFFFFFFF)


def _batch(n=40, max_set=60, s=S, seed=5, max_nnz=128):
    rng = np.random.default_rng(seed)
    sets = [rng.choice(1 << s, rng.integers(1, max_set + 1), replace=False)
            for _ in range(n)]
    return from_lists(sets, max_nnz=max_nnz)


def _family(scheme, fam, densify, k=K, s=S):
    import zlib
    key = jax.random.PRNGKey(
        zlib.crc32(repr((scheme, fam, densify)).encode()) % (2**31))
    if scheme == "minhash":
        return (Hash2U.create(key, k, s) if fam == "2u"
                else Hash4U.create(key, k, s))
    return OPH.create(key, k, s, fam, densify)


def _ref_counts(sig_q: np.ndarray, sig_c: np.ndarray, sentinel: bool):
    """Unpacked reference: per-pair match counts (and joint-EMPTY)."""
    eq = sig_q[:, None, :] == sig_c[None, :, :]
    if sentinel:
        both = (sig_q == _E)[:, None, :] & (sig_c == _E)[None, :, :]
        return (eq & ~both).sum(-1), both.sum(-1)
    return eq.sum(-1), None


# ---------------------------------------------------------------------------
# Kernel vs unpacked reference: the acceptance grid
# ---------------------------------------------------------------------------

_GRID = [
    ("minhash", "2u", None, 8),
    ("oph", "2u", "sentinel", 8),        # EMPTY bins in play
    ("oph", "2u", "rotation", 4),
    ("oph", "2u", "fast", 8),
    pytest.param("oph", "2u", "optimal", 8, marks=pytest.mark.slow),
    pytest.param("minhash", "4u", None, 16, marks=pytest.mark.slow),
    pytest.param("oph", "4u", "sentinel", 1, marks=pytest.mark.slow),
]


@pytest.mark.parametrize("scheme,fam,densify,b", _GRID)
def test_packed_match_bit_exact_vs_unpacked_reference(scheme, fam, densify,
                                                      b):
    """Kernel match counts over packed wires == numpy counts over the
    unpacked signatures, EMPTY-aware for sentinel OPH."""
    family = _family(scheme, fam, densify)
    batch = _batch(seed=b)
    eng = SignatureEngine(family, b=b, packed=True)
    wire = eng.packed_signatures(batch)
    sig = np.asarray(wire.unpack())
    if densify == "sentinel":
        assert (sig == _E).any(), "grid case must exercise EMPTY bins"
    spec = wire.spec
    qwords, cwords = wire.data[:7], wire.data
    out = packed_match(qwords, cwords, spec, backend="interpret")
    want_m, want_e = _ref_counts(sig[:7], sig, spec.sentinel)
    if spec.sentinel:
        got_m, got_e = out
        assert np.array_equal(np.asarray(got_e), want_e)
    else:
        got_m = out
    assert np.array_equal(np.asarray(got_m), want_m)
    # the ref backend (jnp oracle) agrees too
    out_ref = packed_match(qwords, cwords, spec, backend="ref")
    ref_m = out_ref[0] if spec.sentinel else out_ref
    assert np.array_equal(np.asarray(ref_m), want_m)


@pytest.mark.parametrize("scheme,fam,densify,b", [
    ("oph", "2u", "sentinel", 8),
    ("oph", "2u", "rotation", 8),
    pytest.param("minhash", "2u", None, 8, marks=pytest.mark.slow),
])
def test_exact_topk_matches_full_matrix_reference(tmp_path, scheme, fam,
                                                  densify, b):
    """Blocked brute-force top-k == one-shot full-matrix reference top-k
    (identical scores AND indices, i.e. identical tie-breaking)."""
    family = _family(scheme, fam, densify)
    batch = _batch(n=90, seed=17)
    wire = SignatureEngine(family, b=b, packed=True).packed_signatures(batch)
    sig = np.asarray(wire.unpack())
    cfg = BandingConfig(16, 2, wire.spec.code_bits)
    from repro.data.sigshard import write_sig_shard
    path = str(tmp_path / "c.sig")
    write_sig_shard(path, np.asarray(wire.data),
                    np.zeros(len(sig), np.float32), k=K, b=b,
                    code_bits=wire.spec.code_bits,
                    sentinel=wire.spec.sentinel)
    build_index([path], str(tmp_path / "c.idx"), cfg)
    index = load_index(str(tmp_path / "c.idx"))
    # corpus_block smaller than n forces the running top-k merge
    searcher = IndexSearcher(index, backend="interpret", corpus_block=32)
    topk = 10
    res = searcher.search(wire[:6], topk, mode="exact")

    want_m, want_e = _ref_counts(sig[:6], sig, wire.spec.sentinel)
    want_sc = resemblance_scores(
        jnp.asarray(want_m),
        None if want_e is None else jnp.asarray(want_e), K, b)
    ref_s, ref_i = jax.lax.top_k(want_sc, topk)
    assert np.array_equal(res.indices, np.asarray(ref_i).astype(np.int64))
    assert np.array_equal(res.scores, np.asarray(ref_s))
    # self-queries rank themselves first with resemblance estimate 1
    assert np.array_equal(res.indices[:, 0], np.arange(6))
    np.testing.assert_allclose(res.scores[:, 0], 1.0, atol=1e-6)


# ---------------------------------------------------------------------------
# Build -> mmap load -> query: the subsystem round trip
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def corpus_idx(tmp_path_factory):
    """A .sig-sharded synthetic corpus built into a .idx (rotation OPH)."""
    tmp = str(tmp_path_factory.mktemp("corpus"))
    spec = DatasetSpec("idxtest", n=512, D=1 << S, avg_nnz=48,
                       n_prototypes=8, overlap=0.8, seed=2)
    raw = make_sharded_dataset(spec, os.path.join(tmp, "raw"), n_shards=3)
    fam = OPH.create(jax.random.PRNGKey(0), K, S, "2u", "rotation")
    preprocess_shards(raw, os.path.join(tmp, "sig"), fam, b=8,
                      chunk_size=128)
    sig_paths = sorted(glob.glob(os.path.join(tmp, "sig", "*.sig")))
    assert len(sig_paths) > 1
    cfg = choose_band_config(K, 8, threshold=0.5, target_recall=0.95)
    idx_path = os.path.join(tmp, "corpus.idx")
    meta = build_index(sig_paths, idx_path, cfg)
    return idx_path, meta, cfg


def test_index_roundtrip_zero_host_unpack(corpus_idx, monkeypatch):
    """mmap-load + both query paths while every unpack entry point is
    guarded against concrete host (numpy) corpus input."""
    idx_path, meta, cfg = corpus_idx

    def _guard(fn, what):
        def wrapped(arr, *a, **kw):
            assert not isinstance(arr, np.ndarray), \
                f"host-side {what} of packed data"
            return fn(arr, *a, **kw)
        return wrapped

    import repro.core.bbit as bbit
    import repro.index.banding as banding
    import repro.kernels.pack as pack
    monkeypatch.setattr(pack, "unpack_codes",
                        _guard(pack.unpack_codes, "unpack_codes"))
    monkeypatch.setattr(banding, "unpack_device",
                        _guard(banding.unpack_device, "unpack_device"))
    monkeypatch.setattr(bbit, "unpack_codes",
                        _guard(bbit.unpack_codes, "unpack_codes"))

    index = load_index(idx_path, mmap=True)
    assert isinstance(index.words_host, np.memmap)      # packed, off disk
    assert index.words_host.shape == (meta.n, meta.words)
    searcher = IndexSearcher(index, backend="interpret", corpus_block=128)
    q = jnp.asarray(np.ascontiguousarray(index.words_host[:5]))
    exact = searcher.search(q, 10, mode="exact")
    lsh = searcher.search(q, 10, mode="lsh")
    assert np.array_equal(exact.indices[:, 0], np.arange(5))
    assert np.array_equal(lsh.indices[:, 0], np.arange(5))
    # rebuild through the guarded entry points too: keys stay device-side
    build_index(sorted(glob.glob(os.path.join(
        os.path.dirname(idx_path), "sig", "*.sig"))),
        idx_path + ".re", cfg)
    assert read_index_meta(idx_path + ".re").n == meta.n


def test_lsh_recall_at_10(corpus_idx):
    """LSH candidates + kernel rerank reach recall@10 >= 0.9 vs exact
    with the S-curve-predicted band config."""
    idx_path, meta, cfg = corpus_idx
    # the chooser's own prediction clears the target at the threshold
    from repro.index.banding import sparse_collision_prob
    pb = sparse_collision_prob(0.5, 8)
    assert s_curve(pb, cfg.n_bands, cfg.rows_per_band) >= 0.95
    index = load_index(idx_path)
    searcher = IndexSearcher(index, backend="interpret", corpus_block=256)
    rng = np.random.default_rng(3)
    picks = rng.integers(0, meta.n, 16)
    q = jnp.asarray(np.ascontiguousarray(index.words_host[picks]))
    exact = searcher.search(q, 10, mode="exact")
    lsh = searcher.search(q, 10, mode="lsh")
    hits = [len(set(l.tolist()) & set(e.tolist())) / 10
            for l, e in zip(lsh.indices, exact.indices)]
    assert float(np.mean(hits)) >= 0.9, hits
    # candidate generation is genuinely selective, not a full scan
    assert float(np.mean(lsh.n_candidates)) < 0.5 * meta.n


def test_batched_admission_matches_search(corpus_idx):
    idx_path, meta, _ = corpus_idx
    index = load_index(idx_path)
    searcher = IndexSearcher(index, backend="interpret", corpus_block=128)
    rows = [np.ascontiguousarray(index.words_host[i]) for i in (3, 11, 40)]
    tickets = [searcher.submit(r) for r in rows]
    out = searcher.flush(5, mode="exact")
    batch = searcher.search(jnp.asarray(np.stack(rows)), 5, mode="exact")
    assert set(out) == set(tickets)
    for i, t in enumerate(tickets):
        assert np.array_equal(out[t].indices[0], batch.indices[i])
        assert np.array_equal(out[t].scores[0], batch.scores[i])
    assert searcher.flush() == {}                        # queue drained


def test_theorem1_rerank_with_set_sizes(tmp_path):
    """An index carrying set sizes + universe bits reranks with the exact
    Theorem-1 constants; self-queries still estimate R = 1."""
    rng = np.random.default_rng(4)
    sets = [rng.choice(1 << S, rng.integers(30, 90), replace=False)
            for _ in range(64)]
    batch = from_lists(sets, max_nnz=128)
    fam = _family("oph", "2u", "rotation")
    wire = SignatureEngine(fam, b=8, packed=True).packed_signatures(batch)
    from repro.data.sigshard import write_sig_shard
    path = str(tmp_path / "c.sig")
    write_sig_shard(path, np.asarray(wire.data),
                    np.zeros(len(sets), np.float32), k=K, b=8, code_bits=8)
    sizes = np.array([len(s) for s in sets], np.uint32)
    build_index([path], str(tmp_path / "c.idx"),
                BandingConfig(16, 2, 8), set_sizes=sizes, s=S)
    index = load_index(str(tmp_path / "c.idx"))
    assert index.meta.has_set_sizes and index.meta.s == S
    assert np.array_equal(index.set_sizes, sizes)
    searcher = IndexSearcher(index, backend="interpret", corpus_block=64)
    res = searcher.search(wire[:4], 5, mode="exact", query_sizes=sizes[:4])
    assert np.array_equal(res.indices[:, 0], np.arange(4))
    np.testing.assert_allclose(res.scores[:, 0], 1.0, atol=1e-5)
    with pytest.raises(ValueError):                      # sizes required
        searcher.search(wire[:4], 5, mode="exact")
    # batched admission carries per-ticket sizes through to the rerank
    t0 = searcher.submit(wire[0:1], query_size=int(sizes[0]))
    t1 = searcher.submit(wire[1:2], query_size=int(sizes[1]))
    out = searcher.flush(5, mode="exact")
    assert np.array_equal(out[t0].indices[0], res.indices[0])
    assert np.array_equal(out[t1].indices[0], res.indices[1])
    searcher.submit(wire[0:1], query_size=int(sizes[0]))
    searcher.submit(wire[1:2])                           # mixed sizes
    with pytest.raises(ValueError, match="every submitted query"):
        searcher.flush(5, mode="exact")


# ---------------------------------------------------------------------------
# .idx format: versioning + structure
# ---------------------------------------------------------------------------

def test_idx_version_byte_roundtrip_and_mismatch(corpus_idx, tmp_path):
    idx_path, meta, _ = corpus_idx
    assert read_index_meta(idx_path) == meta             # header round trip
    bad = str(tmp_path / "bad.idx")
    with open(idx_path, "rb") as f:
        blob = bytearray(f.read())
    blob[4] = 99                                         # bump version byte
    with open(bad, "wb") as f:
        f.write(blob)
    with pytest.raises(ValueError, match="version 99"):
        read_index_meta(bad)
    blob[:4] = b"NOPE"
    with open(bad, "wb") as f:
        f.write(blob)
    with pytest.raises(ValueError, match="bad magic"):
        read_index_meta(bad)


def test_build_index_rejects_mismatched_shards(tmp_path):
    from repro.data.sigshard import write_sig_shard
    rng = np.random.default_rng(0)
    w8 = rng.integers(0, 2**32, (4, 32), dtype=np.uint64).astype(np.uint32)
    write_sig_shard(str(tmp_path / "a.sig"), w8, np.zeros(4, np.float32),
                    k=128, b=8, code_bits=8)
    write_sig_shard(str(tmp_path / "b.sig"), w8[:, :16],
                    np.zeros(4, np.float32), k=128, b=4, code_bits=4)
    with pytest.raises(ValueError, match="wire format"):
        build_index([str(tmp_path / "a.sig"), str(tmp_path / "b.sig")],
                    str(tmp_path / "c.idx"), BandingConfig(16, 2, 8))
    with pytest.raises(ValueError):                      # cb mismatch
        build_index([str(tmp_path / "a.sig")], str(tmp_path / "c.idx"),
                    BandingConfig(16, 2, 9))


# ---------------------------------------------------------------------------
# Banding math
# ---------------------------------------------------------------------------

def test_band_keys_packed_matches_unpacked_keys():
    fam = _family("oph", "2u", "sentinel")
    wire = SignatureEngine(fam, b=8, packed=True).packed_signatures(_batch())
    cfg = BandingConfig(14, 3, 9)
    keys = np.asarray(band_keys_packed(wire.data, wire.spec, cfg))
    codes = np.asarray(wire.unpack())
    codes = np.where(codes == _E, np.uint32(1 << 8), codes)  # EMPTY -> 2^b
    want = np.asarray(band_keys_from_codes(jnp.asarray(codes), cfg))
    assert np.array_equal(keys, want)
    with pytest.raises(ValueError):                      # wire mismatch
        band_keys_packed(wire.data, wire.spec, BandingConfig(14, 3, 8))


def test_choose_band_config_s_curve():
    cfg = choose_band_config(128, 8, threshold=0.5, target_recall=0.95)
    assert cfg.k <= 128 and cfg.rows_per_band * cfg.code_bits <= 60
    from repro.index.banding import sparse_collision_prob
    pb = sparse_collision_prob(0.5, 8)
    assert s_curve(pb, cfg.n_bands, cfg.rows_per_band) >= 0.95
    # one row more per band would miss the target (maximally selective)
    r2 = cfg.rows_per_band + 1
    assert s_curve(pb, 128 // r2, r2) < 0.95
    with pytest.raises(ValueError):
        choose_band_config(4, 1, threshold=0.05, target_recall=0.999)


def test_build_band_tables_structure():
    keys = np.array([[1, 5], [1, 7], [2, 5], [1, 5]])
    band_offsets, skeys, bucket_offsets, postings = build_band_tables(keys)
    assert band_offsets.tolist() == [0, 2, 4]            # {1,2}, {5,7}
    assert skeys.tolist() == [1, 2, 5, 7]
    # bucket for band 0 key 1 -> docs 0,1,3 (ascending)
    assert postings[bucket_offsets[0]:bucket_offsets[1]].tolist() == [0, 1, 3]
    assert postings[bucket_offsets[2]:bucket_offsets[3]].tolist() == [0, 2, 3]


# ---------------------------------------------------------------------------
# Fused exact scan: one traced computation, bit-identical, out-of-core
# ---------------------------------------------------------------------------

def test_fused_scan_bit_identical_to_blockloop_reference(corpus_idx):
    """The fused in-jit scan returns exactly (ids AND scores) what the
    PR-4 per-block host loop returned."""
    idx_path, meta, _ = corpus_idx
    index = load_index(idx_path)
    q = jnp.asarray(np.ascontiguousarray(index.words_host[10:30]))
    fused = IndexSearcher(index, backend="interpret", corpus_block=128)
    ref = IndexSearcher(index, backend="interpret", corpus_block=128,
                        exact_impl="blockloop")
    r_f = fused.search(q, 10, mode="exact")
    r_b = ref.search(q, 10, mode="exact")
    assert np.array_equal(r_f.indices, r_b.indices)
    assert np.array_equal(r_f.scores, r_b.scores)
    with pytest.raises(ValueError, match="exact_impl"):
        IndexSearcher(index, exact_impl="nope")


def test_exact_flush_is_one_traced_computation(corpus_idx, monkeypatch):
    """flush() dispatches the fused scan exactly once, and a repeat flush
    with the same (batch, corpus, topk, block) is a jit-cache hit -- no
    per-block host round trips, no retrace."""
    import repro.index.query as query

    idx_path, meta, _ = corpus_idx
    index = load_index(idx_path)
    searcher = IndexSearcher(index, backend="interpret", corpus_block=64)
    assert meta.n // 64 > 2                      # genuinely multi-block
    calls = []
    real_scan = query._exact_scan

    def counting_scan(*args, **kwargs):
        calls.append(1)
        return real_scan(*args, **kwargs)

    monkeypatch.setattr(query, "_exact_scan", counting_scan)
    for i in (3, 4, 5):
        searcher.submit(np.asarray(index.words_host[i]))
    searcher.flush(10, mode="exact")
    assert len(calls) == 1                       # ONE dispatch per flush
    traces = query.TRACE_COUNTS["exact_scan"]
    for i in (6, 7, 8):
        searcher.submit(np.asarray(index.words_host[i]))
    searcher.flush(10, mode="exact")
    assert len(calls) == 2
    assert query.TRACE_COUNTS["exact_scan"] == traces   # cache hit


def test_streamed_out_of_core_bit_identical(corpus_idx):
    """A device window smaller than the corpus forces the mmap-window
    streaming path; results are bit-identical to the in-core scan."""
    idx_path, meta, _ = corpus_idx
    index = load_index(idx_path)
    q = jnp.asarray(np.ascontiguousarray(index.words_host[:12]))
    incore = IndexSearcher(index, backend="interpret", corpus_block=128)
    window = meta.payload_bytes // 3
    streamed = IndexSearcher(index, backend="interpret", corpus_block=128,
                             max_device_bytes=window)
    assert streamed.streamed and meta.payload_bytes > window
    assert not incore.streamed
    r_i = incore.search(q, 10, mode="exact")
    r_s = streamed.search(q, 10, mode="exact")
    assert np.array_equal(r_i.indices, r_s.indices)
    assert np.array_equal(r_i.scores, r_s.scores)
    # LSH on a streamed searcher gathers candidates off the mmap instead
    # of uploading the corpus; results match the in-core LSH path
    l_i = incore.search(q, 10, mode="lsh")
    l_s = streamed.search(q, 10, mode="lsh")
    assert np.array_equal(l_i.indices, l_s.indices)
    assert np.array_equal(l_i.scores, l_s.scores)


def test_lsh_subbatch_pipeline_matches_single_batch(corpus_idx):
    """lsh_batch pipelining (async dispatch per sub-batch) returns the
    same results as one monolithic batch."""
    idx_path, meta, _ = corpus_idx
    index = load_index(idx_path)
    q = jnp.asarray(np.ascontiguousarray(index.words_host[5:18]))
    mono = IndexSearcher(index, backend="interpret", corpus_block=128)
    piped = IndexSearcher(index, backend="interpret", corpus_block=128,
                          lsh_batch=4)
    r_m = mono.search(q, 10, mode="lsh")
    r_p = piped.search(q, 10, mode="lsh")
    assert np.array_equal(r_m.indices, r_p.indices)
    assert np.array_equal(r_m.scores, r_p.scores)
    assert np.array_equal(r_m.n_candidates, r_p.n_candidates)


def test_candidates_batch_matches_per_query_buckets(corpus_idx):
    """The batched searchsorted candidate lookup equals a per-(query,
    band) bucket walk."""
    idx_path, meta, _ = corpus_idx
    index = load_index(idx_path)
    wire = jnp.asarray(np.ascontiguousarray(index.words_host[:8]))
    qkeys = np.asarray(band_keys_packed(wire, index.spec, index.banding))
    batch = index.candidates_batch(qkeys)
    for i in range(qkeys.shape[0]):
        per_band = [index.bucket(band, int(qkeys[i, band]))
                    for band in range(meta.n_bands)]
        want = (np.unique(np.concatenate(per_band)).astype(np.int64)
                if per_band else np.zeros(0, np.int64))
        np.testing.assert_array_equal(batch[i], want)


def test_blockloop_refuses_out_of_core_corpus(corpus_idx):
    """blockloop keeps the corpus device-resident, so combining it with
    a device window smaller than the payload must fail loudly instead of
    silently uploading past the cap."""
    idx_path, meta, _ = corpus_idx
    index = load_index(idx_path)
    searcher = IndexSearcher(index, backend="interpret", corpus_block=128,
                             exact_impl="blockloop",
                             max_device_bytes=meta.payload_bytes // 2)
    q = jnp.asarray(np.ascontiguousarray(index.words_host[:2]))
    with pytest.raises(ValueError, match="max_device_bytes"):
        searcher.search(q, 5, mode="exact")


def test_stream_plan_resident_bytes_within_budget(corpus_idx):
    """The out-of-core window plan must keep worst-case device-resident
    corpus bytes (inflight windows x window bytes) within the configured
    budget -- the old plan floored the window at corpus_block and could
    hold prefetch+1 windows over budget.  Below two rows' worth the
    budget is physically unsatisfiable; the plan floors at one row per
    window and that is the only excused case."""
    idx_path, meta, _ = corpus_idx
    index = load_index(idx_path)
    row_bytes = 4 * meta.words
    budgets = [2 * row_bytes, 5 * row_bytes, 64 * row_bytes,
               200 * row_bytes, meta.payload_bytes // 3,
               meta.payload_bytes // 2]
    for budget in budgets:
        s = IndexSearcher(index, backend="interpret", corpus_block=128,
                          max_device_bytes=budget)
        assert s.streamed
        p = s._stream_plan()
        assert p.resident_bytes <= budget, (
            f"budget {budget}: {p.inflight} x {p.window_bytes} B resident")
        assert p.window % p.block == 0 and p.block <= 128
    # hard floor: less than two rows of budget still yields a legal
    # (one-row-per-window) plan rather than dividing to zero
    tiny = IndexSearcher(index, backend="interpret", corpus_block=128,
                         max_device_bytes=row_bytes)
    assert tiny._stream_plan().window == 1


def test_streamed_tiny_budget_bit_identical(corpus_idx):
    """Even a budget that shrinks the scan block below corpus_block (the
    case the old plan violated) returns bit-identical results."""
    idx_path, meta, _ = corpus_idx
    index = load_index(idx_path)
    q = jnp.asarray(np.ascontiguousarray(index.words_host[20:26]))
    want = IndexSearcher(index, backend="interpret",
                         corpus_block=128).search(q, 10, mode="exact")
    row_bytes = 4 * meta.words
    tight = IndexSearcher(index, backend="interpret", corpus_block=128,
                          max_device_bytes=40 * row_bytes)
    plan = tight._stream_plan()
    assert plan.block < 128                      # budget forced a small block
    got = tight.search(q, 10, mode="exact")
    assert np.array_equal(got.indices, want.indices)
    assert np.array_equal(got.scores, want.scores)
