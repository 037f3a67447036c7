"""Device-parallel retrieval mesh (multidevice tier: 8 forced host
devices, selected with ``-m multidevice``).

The PR's acceptance bar: under ``shard_map`` dispatch with round-robin
shard placement on the mesh's ``"data"`` axis, ``ShardedIndex.search``
is **bit-identical** -- ids AND scores -- to the sequential host-merge
fan-out and to a single-index search, for the exact scan and the LSH
rerank, including under a concurrent spill-append.
"""

import glob
import os
import threading

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
from repro.index import (BandingConfig, IndexSearcher, build_index,
                         build_sharded, choose_band_config, load_index,
                         load_sharded)
from repro.kernels import SignatureEngine
from repro.launch.mesh import make_debug_mesh

pytestmark = pytest.mark.multidevice

K, S, B = 128, 16, 8


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """Synthetic corpus as .sig shards + one reference .idx."""
    tmp = str(tmp_path_factory.mktemp("mesh_corpus"))
    spec = DatasetSpec("meshtest", n=420, D=1 << S, avg_nnz=48,
                       n_prototypes=8, overlap=0.8, seed=11)
    raw = make_sharded_dataset(spec, os.path.join(tmp, "raw"), n_shards=5)
    fam = OPH.create(jax.random.PRNGKey(1), K, S, "2u", "rotation")
    preprocess_shards(raw, os.path.join(tmp, "sig"), fam, b=B,
                      chunk_size=64)
    sig_paths = sorted(glob.glob(os.path.join(tmp, "sig", "*.sig")))
    cfg = choose_band_config(K, B, threshold=0.5)
    idx_path = os.path.join(tmp, "single.idx")
    build_index(sig_paths, idx_path, cfg)
    return tmp, sig_paths, cfg, idx_path


def _queries(index, picks):
    return jnp.asarray(np.ascontiguousarray(index.words_host[picks]))


@pytest.mark.parametrize("n_shards,n_dev", [(2, 2), (3, 8), (5, 4), (6, 8)])
def test_mesh_dispatch_bit_identical(corpus, tmp_path, host_devices,
                                     n_shards, n_dev):
    """shard_map fan-out == sequential fan-out == single index, exact
    and LSH, including shard counts above the device count (round-robin
    wrap: 5 shards on 4 devices stacks two shards on device 0)."""
    tmp, sig_paths, cfg, idx_path = corpus
    single = IndexSearcher(load_index(idx_path), backend="interpret",
                           corpus_block=128)
    shard_dir = str(tmp_path / "shards")
    build_sharded(sig_paths, shard_dir, cfg, n_shards=n_shards)
    mesh = make_debug_mesh(n_dev, axes=("data",))
    router = load_sharded(shard_dir, mesh=mesh, backend="interpret",
                          corpus_block=128)
    n = single.index.n
    q = _queries(single.index, [0, 7, n // 3, n // 2, n - 2, n - 1])
    for mode in ("exact", "lsh"):
        want = single.search(q, 10, mode=mode)
        got = router.search(q, 10, mode=mode)            # auto -> mesh
        assert np.array_equal(got.indices, want.indices), mode
        assert np.array_equal(got.scores, want.scores), mode
        seq = router.search(q, 10, mode=mode, dispatch="sequential")
        assert np.array_equal(seq.indices, want.indices), mode
        assert np.array_equal(seq.scores, want.scores), mode
        # LSH candidate accounting survives the collective: the summed
        # per-shard union sizes equal the single index's unions
        # (disjoint shards), on both dispatch paths
        if mode == "lsh":
            assert np.array_equal(got.n_candidates, want.n_candidates)
            assert np.array_equal(seq.n_candidates, want.n_candidates)
    # the collective path (not the sequential loop) served the auto
    # dispatches above -- one shard_map LSH flush, one exact
    assert router.mesh_lsh_dispatches == 1
    assert router.mesh_exact_dispatches == 1


def test_mesh_placement_lands_on_distinct_devices(corpus, tmp_path,
                                                  host_devices):
    """Round-robin placement: with S <= D each shard searcher is pinned
    to its own data-axis device, and the searcher honors the pin."""
    tmp, sig_paths, cfg, _ = corpus
    shard_dir = str(tmp_path / "shards")
    build_sharded(sig_paths, shard_dir, cfg, n_shards=4)
    mesh = make_debug_mesh(8, axes=("data",))
    router = load_sharded(shard_dir, mesh=mesh, backend="interpret",
                          corpus_block=128)
    devs = [s.device for s in router.searchers]
    assert devs == list(host_devices[:4])
    # the pinned device actually holds each shard's corpus after a
    # sequential per-shard dispatch (every searcher uploads its corpus
    # inside its jax.default_device context)
    q = _queries(router.searchers[0].index, [0, 1])
    router.search(q, 5, mode="exact", dispatch="sequential")
    for s in router.searchers:
        assert s.index.corpus.devices() == {s.device}


def test_mesh_with_set_sizes_rerank(tmp_path, host_devices):
    """The exact Theorem-1 rerank (stored set sizes + query_sizes) flows
    through the shard_map dispatch bit-identically."""
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
    mesh = make_debug_mesh(8, axes=("data",))
    router = load_sharded(str(tmp_path / "sh"), mesh=mesh,
                          backend="interpret", corpus_block=32)
    q = jnp.asarray(np.asarray(wire.data[:5]))
    qs = sizes[:5]
    for mode in ("exact", "lsh"):
        want = single.search(q, 8, mode=mode, query_sizes=qs)
        got = router.search(q, 8, mode=mode, query_sizes=qs)
        assert np.array_equal(got.indices, want.indices), mode
        assert np.array_equal(got.scores, want.scores), mode
    assert router.mesh_lsh_dispatches == 1
    # forgetting query_sizes fails loudly on the mesh path too
    with pytest.raises(ValueError, match="query_sizes"):
        router.search(q, 8)
    with pytest.raises(ValueError, match="query_sizes"):
        router.search(q, 8, mode="lsh")


def test_mesh_submit_flush_admission(corpus, tmp_path, host_devices):
    """Batched admission drains through the mesh dispatcher: per-ticket
    rows equal the single index's batch rows."""
    tmp, sig_paths, cfg, idx_path = corpus
    single = IndexSearcher(load_index(idx_path), backend="interpret",
                           corpus_block=128)
    shard_dir = str(tmp_path / "shards")
    build_sharded(sig_paths, shard_dir, cfg, n_shards=3)
    router = load_sharded(shard_dir,
                          mesh=make_debug_mesh(8, axes=("data",)),
                          backend="interpret", corpus_block=128)
    n = single.index.n
    rows = [np.asarray(single.index.words_host[i])
            for i in (3, n // 2 + 1, n - 5)]
    tickets = [router.submit(r) for r in rows]
    out = router.flush(5, mode="exact")
    want = single.search(jnp.asarray(np.stack(rows)), 5, mode="exact")
    for i, t in enumerate(tickets):
        assert np.array_equal(out[t].indices[0], want.indices[i])
        assert np.array_equal(out[t].scores[0], want.scores[i])


def test_mesh_streamed_shards_rejected(corpus, tmp_path, host_devices):
    """An out-of-core (device-window) shard cannot be mesh-dispatched:
    fail loudly instead of silently falling back."""
    tmp, sig_paths, cfg, _ = corpus
    shard_dir = str(tmp_path / "shards")
    build_sharded(sig_paths, shard_dir, cfg, n_shards=2)
    mesh = make_debug_mesh(4, axes=("data",))
    router = load_sharded(shard_dir, mesh=mesh, backend="interpret",
                          corpus_block=64, max_device_bytes=4096)
    assert any(s.streamed for s in router.searchers)
    q = _queries(router.searchers[0].index, [0, 1])
    with pytest.raises(ValueError, match="max_device_bytes"):
        router.search(q, 5)
    # the sequential fan-out still streams fine -- but not through a
    # device pin, so build it without the mesh
    plain = load_sharded(shard_dir, backend="interpret", corpus_block=64,
                         max_device_bytes=4096)
    out = plain.search(q, 5, dispatch="sequential")
    assert out.indices.shape == (2, 5)


def test_mesh_search_racing_spill_append_never_torn(corpus, tmp_path,
                                                    host_devices):
    """Concurrent spill-appends (new shards materialize mid-run) while
    the mesh dispatcher serves: every result is bit-identical to a
    sequential search against the SAME generation's corpus -- never a
    torn mix, and the stacked mesh corpus never outlives its state."""
    tmp, sig_paths, cfg, _ = corpus
    shard_dir = str(tmp_path / "shards")
    build_sharded(sig_paths[:3], shard_dir, cfg, n_shards=2)
    mesh = make_debug_mesh(8, axes=("data",))
    writer = load_sharded(shard_dir, backend="interpret", corpus_block=128,
                          max_shard_docs=80)
    reader = load_sharded(shard_dir, mesh=mesh, backend="interpret",
                          corpus_block=128)
    q = _queries(reader.searchers[0].index, [0, 5, 11])

    stop = threading.Event()
    failures = []

    def appender():
        try:
            for sig in sig_paths[3:]:
                writer.append([sig])
        except Exception as e:                     # pragma: no cover
            failures.append(e)
        finally:
            stop.set()

    t = threading.Thread(target=appender)
    t.start()
    try:
        while not stop.is_set():
            reader.refresh()
            got = reader.search(q, 10)                       # mesh
            want = reader.search(q, 10, dispatch="sequential")
            assert np.array_equal(got.indices, want.indices)
            assert np.array_equal(got.scores, want.scores)
    finally:
        t.join()
    assert not failures
    # final converged state: spilled shards exist, placed, and the mesh
    # result matches a from-scratch single index over everything
    reader.refresh()
    assert reader.n_shards > 2
    assert [s.device for s in reader.searchers] == \
        [host_devices[i % 8] for i in range(reader.n_shards)]
    full_idx = str(tmp_path / "full.idx")
    build_index(sig_paths, full_idx, cfg)
    single = IndexSearcher(load_index(full_idx), backend="interpret",
                           corpus_block=128)
    want = single.search(q, 10)
    got = reader.search(q, 10)
    assert np.array_equal(got.indices, want.indices)
    assert np.array_equal(got.scores, want.scores)
