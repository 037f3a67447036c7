"""Search scale-out benchmark: fused scan vs per-block loop, out-of-core
streaming, and the sharded-index router.

The PR-5 serving claims, measured end to end on synthetic corpora:

  * exact q/s of the fused in-jit scan (ONE traced computation per
    flush) vs the PR-4 per-block host loop, across corpus sizes --
    the dispatch-overhead story behind the paper's "bounded by data
    movement, not hashing" thesis (PAPER.md §1, §3),
  * a successful out-of-core run: corpus payload bytes strictly greater
    than the configured device window, block windows streamed off the
    mmap'd ``.idx`` through the double-buffered H2D pipeline,
  * router q/s vs shard count -- the sequential fan-out AND (when more
    than one device is visible) the mesh-parallel ``shard_map`` dispatch
    with round-robin shard placement -- each checked bit-identical to
    the single-index search.

``--json PATH`` writes the rows as a JSON artifact (uploaded by the
slow-tier CI job next to ``search_index.json``; the CI step forces 8
host devices via XLA_FLAGS so the mesh rows are populated).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

# Force multiple host devices for the mesh-dispatch rows.  Must land
# before jax initialises; respect an explicit setting (CI) and never
# fight an already-imported jax (e.g. when run via a driver script).
if "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, fmt_rows, time_fn
from repro.data.pipeline import make_sharded_dataset
from repro.data.preprocess import preprocess_shards
from repro.data.synthetic import DatasetSpec
from repro.index import (IndexSearcher, build_index, build_sharded,
                         choose_band_config, load_index, load_sharded)
from repro.train.online import make_family

D_BITS = 16
K, B = 128, 8
N_QUERIES = 16
TOPK = 10
CORPUS_SIZES = (1024, 4096)
SHARD_COUNTS = (2, 4, 8)
CORPUS_BLOCK = 512
REPEATS = 3


def _median_qps(searcher, queries, *, mode: str = "exact") -> float:
    us = time_fn(lambda: searcher.search(queries, TOPK, mode=mode),
                 warmup=1, iters=REPEATS)
    return N_QUERIES / (us * 1e-6)


def _build_corpus(tmp: str, n: int):
    spec = DatasetSpec(f"scale_{n}", n=n, D=2**D_BITS, avg_nnz=64,
                       n_prototypes=8, overlap=0.8, seed=0)
    fam = make_family(jax.random.PRNGKey(0), "oph", K, D_BITS,
                      densify="rotation")
    raw = make_sharded_dataset(spec, os.path.join(tmp, f"raw{n}"),
                               n_shards=8)
    # chunk small enough that every corpus yields >= 8 .sig files, so
    # the largest SHARD_COUNTS row is buildable (file-granularity split)
    preprocess_shards(raw, os.path.join(tmp, f"sig{n}"), fam, b=B,
                      chunk_size=max(64, n // 16))
    return sorted(glob.glob(os.path.join(tmp, f"sig{n}", "*.sig")))


def run() -> list[Row]:
    rows: list[Row] = []
    cfg = choose_band_config(K, B, threshold=0.5)
    with tempfile.TemporaryDirectory(prefix="repro_search_scale_") as tmp:
        for n in CORPUS_SIZES:
            sig_paths = _build_corpus(tmp, n)
            idx_path = os.path.join(tmp, f"c{n}.idx")
            build_index(sig_paths, idx_path, cfg)
            index = load_index(idx_path)
            rng = np.random.default_rng(7)
            picks = rng.integers(0, index.n, N_QUERIES)
            queries = jnp.asarray(np.ascontiguousarray(
                index.words_host[picks]))

            fused = IndexSearcher(index, corpus_block=CORPUS_BLOCK)
            blockloop = IndexSearcher(index, corpus_block=CORPUS_BLOCK,
                                      exact_impl="blockloop")
            qps_fused = _median_qps(fused, queries)
            qps_block = _median_qps(blockloop, queries)
            speedup = qps_fused / qps_block
            ref = fused.search(queries, TOPK)
            r_block = blockloop.search(queries, TOPK)
            same = (np.array_equal(ref.indices, r_block.indices)
                    and np.array_equal(ref.scores, r_block.scores))
            rows.append((f"scaling/exact_fused_n{n}",
                         1e6 / qps_fused, {
                             "docs": n, "queries_per_s": round(qps_fused, 1),
                             "blocks": n // CORPUS_BLOCK}))
            rows.append((f"scaling/exact_blockloop_n{n}",
                         1e6 / qps_block, {
                             "docs": n, "queries_per_s": round(qps_block, 1)}))
            rows.append((f"scaling/fused_speedup_n{n}", 0.0, {
                "speedup": round(speedup, 3),
                "bit_identical": bool(same),
                "acceptance": "fused q/s >= per-block baseline",
                "ok": bool(speedup >= 1.0 and same)}))

            if n == CORPUS_SIZES[-1]:
                # out-of-core: device window strictly smaller than the
                # packed corpus forces the streamed mmap-window scan
                window = index.meta.payload_bytes // 4
                streamed = IndexSearcher(index, corpus_block=CORPUS_BLOCK,
                                         max_device_bytes=window)
                assert streamed.streamed
                qps_stream = _median_qps(streamed, queries)
                r_stream = streamed.search(queries, TOPK)
                same_stream = (np.array_equal(r_stream.indices, ref.indices)
                               and np.array_equal(r_stream.scores,
                                                  ref.scores))
                rows.append((f"scaling/exact_streamed_n{n}",
                             1e6 / qps_stream, {
                                 "docs": n,
                                 "queries_per_s": round(qps_stream, 1),
                                 "corpus_bytes": index.meta.payload_bytes,
                                 "device_window": window,
                                 "bit_identical": bool(same_stream),
                                 "acceptance": "corpus bytes > device "
                                               "window with identical "
                                               "results",
                                 "ok": bool(
                                     index.meta.payload_bytes > window
                                     and same_stream)}))

                n_dev = len(jax.devices())
                mesh = None
                if n_dev > 1:
                    from repro.launch.mesh import make_debug_mesh
                    mesh = make_debug_mesh(n_dev, axes=("data",))
                for n_shards in SHARD_COUNTS:
                    if n_shards > len(sig_paths):
                        # splits are at .sig-file granularity
                        continue
                    shard_dir = os.path.join(tmp, f"shards{n}_{n_shards}")
                    t0 = time.perf_counter()
                    build_sharded(sig_paths, shard_dir, cfg,
                                  n_shards=n_shards)
                    t_build = time.perf_counter() - t0
                    router = load_sharded(shard_dir,
                                          corpus_block=CORPUS_BLOCK)
                    qps_router = _median_qps(router, queries)
                    res = router.search(queries, TOPK)
                    identical = (np.array_equal(res.indices, ref.indices)
                                 and np.array_equal(res.scores, ref.scores))
                    rows.append((f"scaling/router_seq_s{n_shards}_n{n}",
                                 1e6 / qps_router, {
                                     "docs": n, "shards": n_shards,
                                     "dispatch": "sequential",
                                     "queries_per_s": round(qps_router, 1),
                                     "build_s": round(t_build, 2),
                                     "bit_identical": bool(identical),
                                     "acceptance": "merged top-k == "
                                                   "single-index top-k",
                                     "ok": bool(identical)}))
                    if mesh is None:
                        continue
                    mrouter = load_sharded(shard_dir, mesh=mesh,
                                           corpus_block=CORPUS_BLOCK)
                    qps_mesh = _median_qps(mrouter, queries)
                    mres = mrouter.search(queries, TOPK)
                    m_ident = (np.array_equal(mres.indices, ref.indices)
                               and np.array_equal(mres.scores, ref.scores))
                    rows.append((f"scaling/router_mesh_s{n_shards}_n{n}",
                                 1e6 / qps_mesh, {
                                     "docs": n, "shards": n_shards,
                                     "dispatch": "mesh", "devices": n_dev,
                                     "queries_per_s": round(qps_mesh, 1),
                                     "qps_vs_sequential": round(
                                         qps_mesh / qps_router, 3),
                                     "bit_identical": bool(m_ident),
                                     "acceptance": "shard_map top-k == "
                                                   "single-index top-k",
                                     "ok": bool(m_ident)}))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as a JSON artifact")
    args = ap.parse_args()
    rows = run()
    print(fmt_rows(rows))
    if args.json:
        doc = [{"name": name, "us_per_call": us, **derived}
               for name, us, derived in rows]
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)


if __name__ == "__main__":
    main()
