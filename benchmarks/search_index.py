"""Similarity-search index benchmark: build throughput, query rates, recall.

The retrieval workload (``repro.index``) measured end to end on a
synthetic corpus:

  * index build throughput (``.sig`` shards -> ``.idx``, docs/s),
  * queries/s for the exact kernel brute-force path vs the banded
    LSH-candidates + kernel-rerank path (batched admission),
  * recall@10 of the LSH path against the exact top-10, with the
    S-curve-predicted band configuration
    (``repro.index.banding.choose_band_config``),
  * mean candidate fraction (the selectivity the banding buys).

``--json PATH`` writes the rows as a JSON artifact (uploaded by the
slow-tier CI job next to ``signature_engine.json``).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import tempfile
import time

import jax
import numpy as np

from benchmarks.common import Row, fmt_rows
from repro.data.pipeline import make_sharded_dataset
from repro.data.preprocess import preprocess_shards
from repro.data.synthetic import DatasetSpec
from repro.index import (IndexSearcher, build_index, choose_band_config,
                         load_index)
from repro.train.online import make_family

D_BITS = 16
K, B = 128, 8
N_DOCS = 1024
N_QUERIES = 32
TOPK = 10
THRESHOLD = 0.5


def _recall_at_k(lsh_idx: np.ndarray, exact_idx: np.ndarray) -> float:
    """Mean |top-k(lsh) ∩ top-k(exact)| / k over the query batch."""
    hits = [len(set(l.tolist()) & set(e.tolist())) / exact_idx.shape[1]
            for l, e in zip(lsh_idx, exact_idx)]
    return float(np.mean(hits))


def run() -> list[Row]:
    spec = DatasetSpec("search_index", n=N_DOCS, D=2**D_BITS, avg_nnz=64,
                       n_prototypes=8, overlap=0.8, seed=0)
    fam = make_family(jax.random.PRNGKey(0), "oph", K, D_BITS,
                      densify="rotation")
    rows: list[Row] = []
    with tempfile.TemporaryDirectory(prefix="repro_search_bench_") as tmp:
        raw = make_sharded_dataset(spec, os.path.join(tmp, "raw"),
                                   n_shards=4)
        preprocess_shards(raw, os.path.join(tmp, "sig"), fam, b=B,
                          chunk_size=256)
        sig_paths = sorted(glob.glob(os.path.join(tmp, "sig", "*.sig")))
        cfg = choose_band_config(K, B, threshold=THRESHOLD)

        t0 = time.perf_counter()
        meta = build_index(sig_paths, os.path.join(tmp, "c.idx"), cfg)
        t_build = time.perf_counter() - t0
        rows.append(("index/build", t_build * 1e6, {
            "docs": meta.n, "docs_per_s": round(meta.n / t_build, 1),
            "n_bands": cfg.n_bands, "rows_per_band": cfg.rows_per_band,
            "payload_bytes": meta.payload_bytes}))

        index = load_index(os.path.join(tmp, "c.idx"))
        searcher = IndexSearcher(index, corpus_block=512)
        rng = np.random.default_rng(7)
        picks = rng.integers(0, meta.n, N_QUERIES)
        queries = np.ascontiguousarray(index.words_host[picks])

        results = {}
        for mode in ("exact", "lsh"):
            searcher.search(queries, TOPK, mode=mode)     # compile once
            t0 = time.perf_counter()
            results[mode] = searcher.search(queries, TOPK, mode=mode)
            dt = time.perf_counter() - t0
            derived = {"queries_per_s": round(N_QUERIES / dt, 1),
                       "topk": TOPK}
            if mode == "lsh":
                derived["mean_candidates"] = round(
                    float(np.mean(results[mode].n_candidates)), 1)
                derived["candidate_frac"] = round(
                    float(np.mean(results[mode].n_candidates)) / meta.n, 4)
            rows.append((f"index/query_{mode}", dt / N_QUERIES * 1e6,
                         derived))

        recall = _recall_at_k(results["lsh"].indices,
                              results["exact"].indices)
        rows.append(("index/recall_at_10", 0.0, {
            "recall": round(recall, 4),
            "threshold": THRESHOLD,
            "acceptance": "recall >= 0.9",
            "ok": recall >= 0.9}))
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
