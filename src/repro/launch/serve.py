"""Serving launcher: similarity-search serving over a packed signature
index.

    PYTHONPATH=src python -m repro.launch.serve --index [--mode exact|lsh]
        [--docs N] [--queries N] [--requests N] [--topk K] [--densify d]
        [--shards S] [--device-window BYTES]
        [--serve --rate QPS --max-delay-ms MS --workers N
         --admission none|reject|shed-oldest|degrade-to-lsh
         --max-queue Q --deadline-budget-ms MS]

The launcher drives the retrieval workload (``repro.index``): shard a
synthetic corpus, hash it to packed ``.sig`` shards, build the banded
``.idx``, then serve ``--requests`` batches of top-k queries through the
packed-Hamming kernel, reporting p50 and max latency.  ``--index`` names
that workload and is the only one; it is accepted, not required.
``--shards S`` builds S ``.idx`` shards and serves them through the
``ShardedIndex`` router (bit-identical merge); ``--device-window`` caps
the device-resident packed corpus bytes -- beyond it the exact path
streams mmap windows (out-of-core serving).  ``--serve`` puts the
continuous-batching ``SearchServer`` in front of the searcher and
replays Zipf-popular queries at a Poisson ``--rate`` offered load,
reporting the server's queue-wait / flush / end-to-end latency
percentiles instead of closed-loop batch latency.  ``--workers`` sizes
the dispatch pool (default: one per mesh data-axis device), and
``--admission``/``--max-queue``/``--deadline-budget-ms`` pick the
overload policy -- reject, shed-oldest, or degrade-to-lsh.
"""

from __future__ import annotations

import argparse
import glob
import os
import tempfile
import time

import jax


def serve_index(args) -> None:
    """The retrieval workload: build a .idx, serve batched queries."""
    import numpy as np

    from repro.data.pipeline import make_sharded_dataset
    from repro.data.preprocess import preprocess_shards
    from repro.data.synthetic import DatasetSpec
    from repro.index import (IndexSearcher, build_index, build_sharded,
                             choose_band_config, load_index, load_sharded)
    from repro.train.online import make_family

    if args.mesh > len(jax.devices()):
        raise SystemExit(f"--mesh {args.mesh} needs {args.mesh} devices, "
                         f"JAX sees {len(jax.devices())}")
    k, b, s = args.k, args.b, 16
    spec = DatasetSpec("serve_index", n=args.docs, D=1 << s,
                       avg_nnz=64, n_prototypes=8, overlap=0.8, seed=0)
    with tempfile.TemporaryDirectory(prefix="repro_serve_index_") as tmp:
        raw = make_sharded_dataset(spec, os.path.join(tmp, "raw"),
                                   n_shards=4)
        fam = make_family(jax.random.PRNGKey(0), args.scheme, k, s,
                          densify=args.densify)
        t0 = time.perf_counter()
        preprocess_shards(raw, os.path.join(tmp, "sig"), fam, b=b,
                          chunk_size=max(64, args.docs // 4))
        t_hash = time.perf_counter() - t0
        sig_paths = sorted(glob.glob(os.path.join(tmp, "sig", "*.sig")))
        cfg = choose_band_config(
            k, b, code_bits=(b + 1 if args.densify == "sentinel" else b),
            threshold=args.threshold)
        t0 = time.perf_counter()
        if args.shards > 1:
            shard_dir = os.path.join(tmp, "shards")
            built = build_sharded(sig_paths, shard_dir, cfg,
                                  n_shards=args.shards)
            t_build = time.perf_counter() - t0
            n_total = sum(m.n for _, m in built)
            payload = sum(m.payload_bytes for _, m in built)
            mesh = None
            if args.mesh:
                from repro.launch.mesh import make_debug_mesh
                n_dev = args.mesh
                mesh = make_debug_mesh(n_dev, axes=("data",))
            searcher = load_sharded(shard_dir, mesh=mesh,
                                    max_device_bytes=args.device_window)
            words_of = _sharded_row_reader(searcher)
            what = f"{args.shards} shards"
            if mesh is not None:
                what += (f" on {n_dev} device(s) "
                         f"(shard_map exact dispatch)")
        else:
            meta = build_index(sig_paths, os.path.join(tmp, "corpus.idx"),
                               cfg)
            t_build = time.perf_counter() - t0
            n_total, payload = meta.n, meta.payload_bytes
            index = load_index(os.path.join(tmp, "corpus.idx"))
            searcher = IndexSearcher(index,
                                     max_device_bytes=args.device_window)
            words_of = lambda i: np.asarray(index.words_host[i])
            what = "1 index"
        streamed = (any(s.streamed for s in searcher.searchers)
                    if args.shards > 1 else searcher.streamed)
        print(f"indexed {n_total} docs into {what} (k={k} b={b} "
              f"bands={cfg.n_bands}x{cfg.rows_per_band}): "
              f"hash {t_hash:.2f}s, build {t_build:.2f}s, "
              f"payload {payload:,} B"
              + (f", streamed (window {args.device_window:,} B)"
                 if streamed else ""))
        if args.serve:
            _serve_traffic(searcher, words_of, n_total, args)
            return
        rng = np.random.default_rng(1)
        lat = []
        hits0 = None
        for r in range(args.requests):
            picks = rng.integers(0, n_total, args.queries)
            for i in picks:
                searcher.submit(words_of(int(i)))
            t0 = time.perf_counter()
            out = searcher.flush(args.topk, mode=args.mode)
            lat.append((time.perf_counter() - t0) * 1e3)
            if hits0 is None:
                hits0 = np.mean([float(res.indices[0, 0] == q)
                                 for res, q in zip(out.values(), picks)])
        lat = sorted(lat)
        qps = args.queries * args.requests / (sum(lat) / 1e3)
        print(f"{args.requests} batches x {args.queries} queries "
              f"({args.mode}): p50={lat[len(lat) // 2]:.1f}ms "
              f"max={lat[-1]:.1f}ms {qps:.0f} q/s "
              f"self-hit@1={hits0:.2f}")


def _serve_traffic(searcher, words_of, n_total: int, args) -> None:
    """Open-loop serving: SearchServer under Zipf/Poisson traffic."""
    from repro.launch.server import RequestShed, SearchServer, ZipfianTraffic
    from repro.obs.trace import get_tracer

    exporter = None
    if args.metrics_port is not None:
        from repro.obs.export import start_http_exporter
        exporter = start_http_exporter(port=args.metrics_port)
        print(f"metrics: {exporter.url}/metrics "
              f"(JSON {exporter.url}/metrics.json, "
              f"trace {exporter.url}/trace)")
    tracer = get_tracer()
    if args.trace_out:
        tracer.reset(enabled=True)

    traffic = ZipfianTraffic(n_total, alpha=args.zipf_alpha, seed=1)
    m = args.requests * args.queries
    ids = traffic.ids(m)
    arrivals = traffic.arrival_offsets(m, args.rate)
    budget = (args.deadline_budget_ms / 1e3
              if args.deadline_budget_ms is not None else None)
    server = SearchServer(searcher, max_batch=args.queries,
                          max_delay_s=args.max_delay_ms / 1e3,
                          topk=args.topk, mode=args.mode,
                          num_workers=args.workers,
                          admission=args.admission,
                          max_queue=args.max_queue,
                          deadline_budget_s=budget,
                          on_shard_failure=args.on_shard_failure)
    try:
        with server:
            t_start = time.monotonic()
            handles = []
            for doc, at in zip(ids, arrivals):
                lag = at - (time.monotonic() - t_start)
                if lag > 0:
                    time.sleep(lag)
                handles.append(server.submit(words_of(int(doc)),
                                             deadline_s=budget))
            for h in handles:
                try:
                    h.result(timeout=120.0)
                except RequestShed:
                    pass                # accounted in stats.shed
            elapsed = time.monotonic() - t_start
    finally:
        if args.trace_out:
            n_ev = tracer.export(args.trace_out)
            print(f"trace: wrote {n_ev} events to {args.trace_out} "
                  "(open in https://ui.perfetto.dev)")
        if exporter is not None:
            exporter.close()
    snap = server.stats.snapshot()
    print(f"served {snap['requests']} requests in {snap['batches']} "
          f"micro-batches over {snap['workers']} worker(s) "
          f"(mean {snap['mean_batch']:.1f}/batch, "
          f"offered {args.rate:.0f} q/s, achieved "
          f"{snap['requests'] / elapsed:.0f} q/s)")
    print(f"latency p50={snap['latency_p50_ms']:.1f}ms "
          f"p99={snap['latency_p99_ms']:.1f}ms  queue-wait "
          f"p50={snap['queue_wait_p50_ms']:.1f}ms  flush "
          f"p50={snap['flush_p50_ms']:.1f}ms  triggers: "
          f"full={snap['flush_full']} aged={snap['flush_aged']} "
          f"deadline={snap['flush_deadline']} drain={snap['flush_drain']}")
    occ = " ".join(f"{o:.2f}" for o in snap["worker_occupancy"])
    print(f"admission={args.admission}: shed={snap['shed']} "
          f"(rate {snap['shed_rate']:.3f}) degraded={snap['degraded']} "
          f"deadline-miss rate {snap['deadline_miss_rate']:.3f}  "
          f"worker occupancy [{occ}]")
    if args.on_shard_failure == "partial" or snap["partial"]:
        print(f"fault tolerance: partial={snap['partial']} "
              f"(rate {snap['partial_rate']:.3f}) "
              f"mean coverage {snap['mean_coverage']:.3f} "
              f"worker restarts {snap['worker_restarts']}")


def _sharded_row_reader(sharded):
    """Global doc id -> packed query row, off the shards' mmaps."""
    import numpy as np
    offsets = list(sharded.offsets) + [sharded.n]

    def words_of(i: int) -> np.ndarray:
        shard = int(np.searchsorted(offsets, i, side="right")) - 1
        local = i - int(offsets[shard])
        return np.asarray(
            sharded.searchers[shard].index.words_host[local])
    return words_of


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=4,
                    help="query batches served (the total is --requests x "
                         "--queries under --serve)")
    ap.add_argument("--index", action="store_true",
                    help="serve the similarity-search index workload (the "
                         "only workload; accepted, not required)")
    ap.add_argument("--mode", choices=("exact", "lsh"), default="lsh")
    ap.add_argument("--docs", type=int, default=2048)
    ap.add_argument("--queries", type=int, default=16,
                    help="queries admitted per batch (--index); the "
                         "server's max_batch under --serve")
    ap.add_argument("--topk", type=int, default=10)
    ap.add_argument("--k", type=int, default=128)
    ap.add_argument("--b", type=int, default=8)
    ap.add_argument("--scheme", default="oph")
    ap.add_argument("--densify", default="rotation")
    ap.add_argument("--threshold", type=float, default=0.5)
    ap.add_argument("--shards", type=int, default=1,
                    help="serve through a ShardedIndex router over S "
                         ".idx shards (--index)")
    ap.add_argument("--device-window", type=int, default=None,
                    help="max device-resident packed-corpus bytes; larger "
                         "corpora stream mmap windows (--index)")
    ap.add_argument("--mesh", type=int, default=0,
                    help="place shards round-robin on a D-device "
                         '("data",) mesh and run the exact scan as one '
                         "shard_map dispatch (--index --shards; clamped "
                         "to the available devices; 0 = single-device "
                         "sequential fan-out)")
    ap.add_argument("--serve", action="store_true",
                    help="drive the continuous-batching SearchServer "
                         "under open-loop Zipf/Poisson traffic (--index)")
    ap.add_argument("--rate", type=float, default=500.0,
                    help="offered load in queries/s (--serve)")
    ap.add_argument("--zipf-alpha", type=float, default=1.1,
                    help="query-popularity Zipf exponent (--serve)")
    ap.add_argument("--max-delay-ms", type=float, default=5.0,
                    help="micro-batching window: max time the oldest "
                         "queued request waits before a flush (--serve)")
    ap.add_argument("--workers", type=int, default=None,
                    help="dispatch workers draining the admission queue "
                         "(--serve; default: one per data-axis mesh "
                         "device, else 1)")
    ap.add_argument("--admission", default="none",
                    choices=("none", "reject", "shed-oldest",
                             "degrade-to-lsh"),
                    help="overload policy when the queue is full or the "
                         "projected wait blows the deadline budget "
                         "(--serve)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded admission-queue depth; beyond it the "
                         "--admission policy fires (--serve)")
    ap.add_argument("--on-shard-failure", default=None,
                    choices=("fail", "partial"),
                    help="shard-failure policy threaded to the sharded "
                         "router: 'partial' serves surviving shards with "
                         "coverage accounting instead of failing the "
                         "whole batch (--serve --shards)")
    ap.add_argument("--deadline-budget-ms", type=float, default=None,
                    help="per-request latency budget the admission "
                         "policy defends (--serve)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve live Prometheus metrics on this port "
                         "(/metrics, /metrics.json, /trace; 0 = "
                         "ephemeral; --serve)")
    ap.add_argument("--trace-out", default=None,
                    help="enable request tracing and write the "
                         "Perfetto-loadable trace-event JSON here on "
                         "exit (--serve)")
    return ap


def main():
    args = build_parser().parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    serve_index(args)


if __name__ == "__main__":
    main()
