"""Continuous-batching serving benchmark: the ``SearchServer`` under
open-loop Zipf/Poisson traffic -- multi-worker dispatch, admission
control, live appends, and a roofline gap per load level.

The serving-lane claims, measured end to end on a synthetic sharded
corpus:

  * p50/p99 end-to-end latency, queue-wait, achieved q/s, deadline-miss
    rate, shed rate, and per-worker occupancy at several offered loads
    (Poisson arrivals, Zipf-popular query ids) through the
    deadline-aware micro-batching dispatch loop,
  * the same load served by ONE dispatch worker vs a worker pool
    (``serving/multiworker_speedup``): overlapped flushes must beat the
    single thread at the same offered load, with results bit-identical
    either way (when >1 JAX device is present the router is placed on a
    ``("data",)`` mesh, so worker flushes land on the collective
    ``shard_map`` dispatch),
  * one deliberately unserveable load (``serving/overload_shed``)
    driving the bounded-queue ``shed-oldest`` admission policy: the
    server must shed instead of deadlocking, and every NON-shed request
    still meets its deadline,
  * an open-loop run while a concurrent appender thread grows the last
    shard via ``ShardedIndex.append`` and the server's per-flush
    ``refresh`` picks the growth up live,
  * micro-batched results checked bit-identical per query to a direct
    ``search`` call on the same searcher (single- AND multi-worker),
  * predicted vs measured bytes/flush for the exact hamming scan
    (``repro.roofline.search``): each load row carries the memory-bound
    prediction and the measured roofline gap, the autotuning lane's
    steering metric,
  * the cost of the observability layer itself
    (``serving/instrumentation_overhead``): the same closed-loop run
    with tracing+metrics enabled vs bare, median of 3 interleaved runs
    each -- the instrumented server must stay within 2% q/s of bare,
  * the cost of the fault-tolerance layer
    (``serving/resilience_overhead``): the same closed-loop fan-out
    with every shard client wrapped in ``ResilientShardClient`` vs the
    bare local clients, median of 3 interleaved -- the healthy path
    must stay within 3% q/s of bare,
  * degraded serving under injected chaos (``serving/chaos_*pct``):
    seeded ``ChaosShardClient`` faults (latency / OSError / hang /
    drop) at 0% / 10% / 25% per-dispatch fault rates through a
    partial-mode server -- reporting availability, achieved q/s, and
    mean coverage; every request must resolve.

``--json PATH`` writes the rows as a JSON artifact (uploaded by the
slow-tier AND the multidevice CI jobs next to ``search_scaling.json``).
``--chaos-json PATH`` writes just the resilience/chaos rows (the CI
chaos artifact).
``--metrics-port P`` serves the live ``repro.obs`` registry over HTTP
while the benchmark runs; ``--prom-out PATH`` saves the last good
Prometheus scrape (taken by a background scraper thread, i.e. a real
scrape under load, falling back to a direct registry dump);
``--trace-out PATH`` enables the global tracer and writes the
Perfetto-loadable trace-event JSON on exit.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import threading
import time

import jax
import numpy as np

from benchmarks.common import Row, fmt_rows
from repro.data.pipeline import make_sharded_dataset
from repro.data.preprocess import preprocess_shards
from repro.data.synthetic import DatasetSpec
from repro.index import build_sharded, choose_band_config, load_sharded
from repro.launch.server import RequestShed, SearchServer, ZipfianTraffic
from repro.roofline.search import exact_scan_cost, roofline_gap
from repro.train.online import make_family

D_BITS = 16
K, B = 128, 8
N_DOCS = 2048
N_SHARDS = 2
N_APPEND_SHARDS = 3
CORPUS_BLOCK = 512
TOPK = 10
MAX_BATCH = 8
MAX_DELAY_S = 0.002
RATES_QPS = (200.0, 2000.0)
N_REQUESTS = 192
MULTI_WORKERS = 4
OVERLOAD_QPS = 50_000.0          # >> capacity: forces the shedding path
OVERLOAD_QUEUE = 32
OVERLOAD_DEADLINE_S = 2.0
CHAOS_RATES = (0.0, 0.10, 0.25)  # injected per-dispatch fault rates
CHAOS_REQUESTS = 96
CHAOS_DEADLINE_S = 0.1           # per-attempt; an injected hang blows it
CHAOS_HANG_S = 0.4


def _build_sigs(tmp: str, name: str, n: int, seed: int) -> list:
    spec = DatasetSpec(name, n=n, D=2**D_BITS, avg_nnz=64,
                       n_prototypes=8, overlap=0.8, seed=seed)
    fam = make_family(jax.random.PRNGKey(0), "oph", K, D_BITS,
                      densify="rotation")
    raw = make_sharded_dataset(spec, os.path.join(tmp, f"raw_{name}"),
                               n_shards=4)
    preprocess_shards(raw, os.path.join(tmp, f"sig_{name}"), fam, b=B,
                      chunk_size=max(128, n // 4))
    return sorted(glob.glob(os.path.join(tmp, f"sig_{name}", "*.sig")))


def _row_reader(router):
    offsets = list(router.offsets) + [router.n]

    def words_of(i: int) -> np.ndarray:
        shard = int(np.searchsorted(offsets, i, side="right")) - 1
        return np.asarray(router.searchers[shard]
                          .index.words_host[i - int(offsets[shard])])
    return words_of


def _warmup(router, words_of) -> None:
    """Compile every query-batch shape a flush can produce (1..MAX_BATCH),
    so the timed open-loop runs measure serving, not tracing."""
    for nq in range(1, MAX_BATCH + 1):
        q = np.stack([words_of(i % router.n) for i in range(nq)])
        router.search(q, TOPK, mode="exact")


def _drive(router, words_of, n_docs: int, rate: float, m: int, seed: int,
           *, workers: int = 1, admission: str = "none",
           max_queue=None, deadline_s=None) -> dict:
    """One open-loop run: m Zipf queries at Poisson rate; returns the
    server's stats snapshot + achieved q/s (served requests over wall
    clock -- shed traffic does not count as served)."""
    traffic = ZipfianTraffic(n_docs, alpha=1.1, seed=seed)
    ids = traffic.ids(m)
    arrivals = traffic.arrival_offsets(m, rate)
    server = SearchServer(router, max_batch=MAX_BATCH,
                          max_delay_s=MAX_DELAY_S, topk=TOPK, mode="exact",
                          num_workers=workers, admission=admission,
                          max_queue=max_queue)
    with server:
        t_start = time.monotonic()
        handles = []
        for doc, at in zip(ids, arrivals):
            lag = at - (time.monotonic() - t_start)
            if lag > 0:
                time.sleep(lag)
            handles.append(server.submit(words_of(int(doc)),
                                         deadline_s=deadline_s))
        for h in handles:
            try:
                h.result(timeout=120.0)
            except RequestShed:
                pass                             # accounted in snap["shed"]
        elapsed = time.monotonic() - t_start
    snap = server.stats.snapshot()
    snap["achieved_qps"] = snap["requests"] / elapsed
    return snap


def _closed_loop_qps(router, words_of, n_docs: int, m: int,
                     tracer, registry) -> float:
    """Closed-loop throughput through one dispatch worker: submit m
    requests back to back, wait for all; q/s over wall clock.  The
    tracer/registry are injected so the instrumentation-overhead row can
    compare enabled vs disabled on otherwise identical servers."""
    server = SearchServer(router, max_batch=MAX_BATCH,
                          max_delay_s=MAX_DELAY_S, topk=TOPK, mode="exact",
                          num_workers=1, registry=registry, tracer=tracer)
    with server:
        t0 = time.monotonic()
        handles = [server.submit(words_of(i % n_docs)) for i in range(m)]
        for h in handles:
            h.result(timeout=120.0)
        return m / (time.monotonic() - t0)


def _router_closed_qps(router, words_of, m: int) -> float:
    """Closed-loop fan-out throughput straight through the router (no
    server): MAX_BATCH-query batches back to back, q/s over wall clock.
    Used to price the resilience wrapper on the healthy path."""
    n = router.n
    t0 = time.monotonic()
    done = 0
    while done < m:
        nq = min(MAX_BATCH, m - done)
        q = np.stack([words_of((done + j) % n) for j in range(nq)])
        router.search(q, TOPK, mode="exact")
        done += nq
    return m / (time.monotonic() - t0)


def _chaos_row(shard_dir: str, fault_frac: float, seed: int) -> dict:
    """One degraded-serving run: a partial-mode server over resilient +
    chaos-wrapped sequential clients at the given per-dispatch fault
    rate.  Returns availability / q/s / coverage accounting."""
    from repro.index import ChaosSchedule, ResiliencePolicy
    from repro.index import resilient_client_factory

    policy = ResiliencePolicy(deadline_s=CHAOS_DEADLINE_S, max_retries=1,
                              backoff_base_s=0.001, backoff_cap_s=0.01)
    chaos = None
    if fault_frac > 0.0:
        chaos = lambda i: ChaosSchedule(seed=seed + i,
                                        fault_rate=fault_frac,
                                        latency_s=0.002,
                                        hang_s=CHAOS_HANG_S)
    # warm the jit caches through a plain router first: a cold compile
    # takes seconds and would blow every per-attempt deadline below
    plain = load_sharded(shard_dir, dispatch="sequential",
                         corpus_block=CORPUS_BLOCK)
    _warmup(plain, _row_reader(plain))
    fac = resilient_client_factory(policy, chaos=chaos, seed=seed)
    router = load_sharded(shard_dir, dispatch="sequential",
                          corpus_block=CORPUS_BLOCK, client_factory=fac,
                          on_shard_failure="partial")
    words_of = _row_reader(router)
    n = router.n
    resolved = errors = 0
    coverages = []
    server = SearchServer(router, max_batch=MAX_BATCH,
                          max_delay_s=MAX_DELAY_S, topk=TOPK,
                          mode="exact", num_workers=2,
                          on_shard_failure="partial")
    with server:
        t0 = time.monotonic()
        handles = [server.submit(words_of(i % n))
                   for i in range(CHAOS_REQUESTS)]
        for h in handles:
            try:
                res = h.result(timeout=120.0)
                resolved += 1
                coverages.append(float(res.coverage))
            except Exception:
                errors += 1
        elapsed = time.monotonic() - t0
    snap = server.stats.snapshot()
    faults = sum(sum(1 for _, k in c.fault_log if k is not None)
                 for c in fac.chaos_clients)
    return {
        "fault_rate": fault_frac,
        "availability": round(resolved / CHAOS_REQUESTS, 4),
        "achieved_qps": round(resolved / elapsed, 1),
        "mean_coverage": round(float(np.mean(coverages)), 4)
        if coverages else 0.0,
        "requests": CHAOS_REQUESTS,
        "resolved": resolved,
        "errors": errors,
        "partial": snap["partial"],
        "worker_restarts": snap["worker_restarts"],
        "injected_faults": faults,
    }


def _load_fields(snap: dict, n_docs: int, words: int) -> dict:
    """The shared per-load row payload: latency/throughput, admission
    outcomes, per-worker occupancy, and the roofline comparison for the
    measured mean flush."""
    q = max(1, int(round(snap["mean_batch"])))
    cost = exact_scan_cost(n_docs, words, q, topk=TOPK)
    gap = roofline_gap(cost["bytes"], snap["flush_p50_ms"] / 1e3)
    return {
        "achieved_qps": round(snap["achieved_qps"], 1),
        "latency_p50_ms": round(snap["latency_p50_ms"], 3),
        "latency_p99_ms": round(snap["latency_p99_ms"], 3),
        "queue_wait_p50_ms": round(snap["queue_wait_p50_ms"], 3),
        "flush_p50_ms": round(snap["flush_p50_ms"], 3),
        "mean_batch": round(snap["mean_batch"], 2),
        "flush_full": snap["flush_full"],
        "flush_aged": snap["flush_aged"],
        "requests": snap["requests"],
        "workers": snap["workers"],
        "deadline_miss_rate": round(snap["deadline_miss_rate"], 4),
        "shed_rate": round(snap["shed_rate"], 4),
        "worker_occupancy": [round(o, 3)
                             for o in snap["worker_occupancy"]],
        "predicted_bytes_per_flush": int(cost["bytes"]),
        "roofline_predicted_flush_us": round(gap["predicted_s"] * 1e6, 3),
        "roofline_gap": round(gap["gap"], 1),
        "achieved_gbps": round(gap["achieved_gbps"], 3),
    }


def run() -> list[Row]:
    rows: list[Row] = []
    cfg = choose_band_config(K, B, threshold=0.5)
    with tempfile.TemporaryDirectory(prefix="repro_search_serving_") as tmp:
        sig_paths = _build_sigs(tmp, "corpus", N_DOCS, seed=0)
        extra_sigs = _build_sigs(tmp, "extra", N_DOCS // 4, seed=9)
        shard_dir = os.path.join(tmp, "shards")
        build_sharded(sig_paths, shard_dir, cfg, n_shards=N_SHARDS)
        mesh = None
        if len(jax.devices()) > 1:
            # multidevice CI tier: place shards on the mesh so every
            # worker flush runs the collective shard_map dispatch
            from repro.launch.mesh import make_debug_mesh
            mesh = make_debug_mesh(min(N_SHARDS, len(jax.devices())),
                                   axes=("data",))
        router = load_sharded(shard_dir, mesh=mesh,
                              corpus_block=CORPUS_BLOCK)
        words_of = _row_reader(router)
        n0 = router.n
        words = int(router.searchers[0].index.words_host.shape[1])
        _warmup(router, words_of)

        # -- micro-batched == direct (bit-identity), both worker counts --
        rng = np.random.default_rng(3)
        picks = rng.integers(0, n0, 16)
        direct = router.search(
            np.stack([words_of(int(i)) for i in picks]), TOPK, mode="exact")
        identical = {}
        for nw in (1, MULTI_WORKERS):
            with SearchServer(router, max_batch=MAX_BATCH,
                              max_delay_s=MAX_DELAY_S, topk=TOPK,
                              mode="exact", num_workers=nw) as srv:
                served = [srv.submit(words_of(int(i))) for i in picks]
                served = [h.result(timeout=120.0) for h in served]
            identical[nw] = all(
                np.array_equal(res.indices[0], direct.indices[j])
                and np.array_equal(res.scores[0], direct.scores[j])
                for j, res in enumerate(served))
        rows.append(("serving/bit_identical", 0.0, {
            "queries": len(picks), "workers_checked": [1, MULTI_WORKERS],
            "acceptance": "micro-batched results == direct search(), "
                          "single- and multi-worker",
            "ok": bool(identical[1] and identical[MULTI_WORKERS])}))

        # -- instrumentation overhead: tracing must stay off the hot path
        from repro.obs.metrics import MetricsRegistry
        from repro.obs.trace import Tracer
        m_over = 256
        _closed_loop_qps(router, words_of, n0, m_over,
                         Tracer(enabled=False), MetricsRegistry())  # warm
        bare, instr = [], []
        for _ in range(3):                  # interleave to share drift
            bare.append(_closed_loop_qps(
                router, words_of, n0, m_over,
                Tracer(enabled=False), MetricsRegistry()))
            instr.append(_closed_loop_qps(
                router, words_of, n0, m_over,
                Tracer(enabled=True), MetricsRegistry()))
        bare_qps, instr_qps = sorted(bare)[1], sorted(instr)[1]
        overhead = 1.0 - instr_qps / bare_qps
        rows.append(("serving/instrumentation_overhead", 0.0, {
            "bare_qps": round(bare_qps, 1),
            "instrumented_qps": round(instr_qps, 1),
            "overhead_frac": round(overhead, 4),
            "requests_per_run": m_over, "runs_each": 3,
            "acceptance": "full tracing + metrics registry cost < 2% "
                          "q/s vs a bare server (median of 3)",
            "ok": bool(overhead < 0.02)}))

        # -- latency/throughput vs offered load, 1 vs N workers ----------
        qps_by_workers = {}
        for rate in RATES_QPS:
            for nw in (1, MULTI_WORKERS):
                snap = _drive(router, words_of, n0, rate, N_REQUESTS,
                              seed=5, workers=nw)
                qps_by_workers[(rate, nw)] = snap["achieved_qps"]
                suffix = "" if nw == 1 else f"_w{nw}"
                rows.append((f"serving/load_{int(rate)}qps{suffix}",
                             snap["latency_p50_ms"] * 1e3,
                             {"offered_qps": rate,
                              **_load_fields(snap, n0, words)}))

        # -- multi-worker speedup at the saturating load -----------------
        rate = max(RATES_QPS)
        single = qps_by_workers[(rate, 1)]
        multi = qps_by_workers[(rate, MULTI_WORKERS)]
        rows.append(("serving/multiworker_speedup", 0.0, {
            "offered_qps": rate,
            "single_worker_qps": round(single, 1),
            "multi_worker_qps": round(multi, 1),
            "workers": MULTI_WORKERS,
            "cpu_cores": os.cpu_count(),     # <2 cores can't overlap
            "speedup": round(multi / single, 3),
            "acceptance": "worker pool outserves one dispatch thread at "
                          "the same offered load, bit-identically",
            "ok": bool(multi > single and identical[MULTI_WORKERS])}))

        # -- overload: bounded queue + shed-oldest must shed, not stall --
        snap = _drive(router, words_of, n0, OVERLOAD_QPS, N_REQUESTS,
                      seed=8, workers=MULTI_WORKERS,
                      admission="shed-oldest", max_queue=OVERLOAD_QUEUE,
                      deadline_s=OVERLOAD_DEADLINE_S)
        rows.append(("serving/overload_shed",
                     snap["latency_p50_ms"] * 1e3, {
                         "offered_qps": OVERLOAD_QPS,
                         "max_queue": OVERLOAD_QUEUE,
                         "deadline_budget_ms": OVERLOAD_DEADLINE_S * 1e3,
                         **_load_fields(snap, n0, words),
                         "shed": snap["shed"],
                         "deadline_misses": snap["deadline_misses"],
                         "acceptance": "overload sheds per policy; every "
                                       "non-shed request meets its "
                                       "deadline; nothing deadlocks",
                         "ok": bool(snap["shed"] > 0
                                    and snap["requests"] + snap["shed"]
                                    == N_REQUESTS
                                    and snap["deadline_misses"] == 0)}))

        # -- serving while a concurrent appender grows the index ---------
        stop = threading.Event()
        appended = []

        def appender():
            for sig in extra_sigs[:N_APPEND_SHARDS]:
                if stop.is_set():
                    return
                router.append([sig])
                appended.append(router.n)
                time.sleep(0.02)

        t = threading.Thread(target=appender)
        t.start()
        try:
            snap = _drive(router, words_of, n0, RATES_QPS[0],
                          N_REQUESTS, seed=6)
        finally:
            stop.set()
            t.join()
        router.refresh()
        grew = router.n > n0
        rows.append(("serving/with_live_appends",
                     snap["latency_p50_ms"] * 1e3, {
                         "offered_qps": RATES_QPS[0],
                         "achieved_qps": round(snap["achieved_qps"], 1),
                         "latency_p50_ms": round(snap["latency_p50_ms"], 3),
                         "latency_p99_ms": round(snap["latency_p99_ms"], 3),
                         "docs_before": n0, "docs_after": router.n,
                         "appends": len(appended),
                         "requests": snap["requests"],
                         "errors": snap["errors"],
                         "acceptance": "all requests served while the "
                                       "corpus grows under the reader",
                         "ok": bool(grew and snap["errors"] == 0
                                    and snap["requests"] == N_REQUESTS)}))

        # -- resilience wrapper price on the healthy path ----------------
        from repro.index import ResiliencePolicy, resilient_client_factory
        bare_r = load_sharded(shard_dir, dispatch="sequential",
                              corpus_block=CORPUS_BLOCK)
        res_r = load_sharded(
            shard_dir, dispatch="sequential", corpus_block=CORPUS_BLOCK,
            client_factory=resilient_client_factory(ResiliencePolicy()))
        wb, wr = _row_reader(bare_r), _row_reader(res_r)
        _warmup(bare_r, wb)
        _warmup(res_r, wr)
        picks = np.random.default_rng(12).integers(0, bare_r.n, 8)
        q = np.stack([wb(int(i)) for i in picks])
        a, b = bare_r.search(q, TOPK), res_r.search(q, TOPK)
        same = bool(np.array_equal(a.indices, b.indices)
                    and np.array_equal(a.scores, b.scores))
        m_res = 256
        bare_q, res_q = [], []
        for _ in range(3):                  # interleave to share drift
            bare_q.append(_router_closed_qps(bare_r, wb, m_res))
            res_q.append(_router_closed_qps(res_r, wr, m_res))
        bq, rq = sorted(bare_q)[1], sorted(res_q)[1]
        overhead = 1.0 - rq / bq
        rows.append(("serving/resilience_overhead", 0.0, {
            "bare_qps": round(bq, 1),
            "resilient_qps": round(rq, 1),
            "overhead_frac": round(overhead, 4),
            "bit_identical": same,
            "requests_per_run": m_res, "runs_each": 3,
            "acceptance": "healthy-path ResilientShardClient fan-out "
                          "bit-identical and within 3% q/s of bare "
                          "local clients (median of 3)",
            "ok": bool(same and overhead < 0.03)}))

        # -- degraded serving under injected chaos -----------------------
        # (keep these LAST: the prom scrape retained at exit must still
        # see the live partial-mode servers' serve_* collectors)
        for j, frac in enumerate(CHAOS_RATES):
            fields = _chaos_row(shard_dir, frac, seed=17 + 31 * j)
            ok = fields["resolved"] == CHAOS_REQUESTS
            if frac == 0.0:
                ok = (ok and fields["errors"] == 0
                      and fields["mean_coverage"] == 1.0)
            rows.append((f"serving/chaos_{int(round(frac * 100))}pct",
                         0.0, {
                             **fields,
                             "acceptance": "every request resolves under "
                                           "seeded injected faults; "
                                           "partial-mode coverage "
                                           "accounted",
                             "ok": bool(ok)}))
    return rows


class _Scraper(threading.Thread):
    """Background thread that keeps re-scraping /metrics while the
    benchmark runs, keeping the LAST GOOD body -- so ``--prom-out`` is a
    real scrape taken under serving load, not a post-mortem dump."""

    def __init__(self, url: str, period_s: float = 0.25):
        super().__init__(daemon=True)
        self.url = url
        self.period_s = period_s
        self.last: str = ""
        self.scrapes = 0
        # NB: not named _stop -- that would shadow threading.Thread._stop
        self._halt = threading.Event()

    def run(self) -> None:
        import urllib.request
        while not self._halt.is_set():
            try:
                with urllib.request.urlopen(self.url, timeout=5.0) as r:
                    self.last = r.read().decode("utf-8")
                    self.scrapes += 1
            except OSError:
                pass                       # keep the previous good scrape
            self._halt.wait(self.period_s)

    def stop(self) -> None:
        self._halt.set()
        self.join(timeout=10.0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as a JSON artifact")
    ap.add_argument("--chaos-json", default=None, metavar="PATH",
                    help="write just the resilience/chaos rows (the CI "
                         "chaos artifact)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve live Prometheus metrics on this port "
                         "while the benchmark runs (0 = ephemeral)")
    ap.add_argument("--prom-out", default=None, metavar="PATH",
                    help="write the last good /metrics scrape here "
                         "(implies a background scraper when "
                         "--metrics-port is up)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable request tracing; write trace-event "
                         "JSON here on exit")
    args = ap.parse_args()

    from repro.obs.metrics import get_registry
    from repro.obs.trace import get_tracer

    exporter = scraper = None
    if args.metrics_port is not None:
        from repro.obs.export import start_http_exporter
        exporter = start_http_exporter(port=args.metrics_port)
        print(f"# metrics: {exporter.url}/metrics", file=sys.stderr)
        if args.prom_out:
            scraper = _Scraper(exporter.url + "/metrics")
            scraper.start()
    if args.trace_out:
        get_tracer().reset(enabled=True)
    try:
        rows = run()
    finally:
        if scraper is not None:
            scraper.stop()
        if args.prom_out:
            text = scraper.last if (scraper and scraper.last) \
                else get_registry().prometheus_text()
            with open(args.prom_out, "w") as f:
                f.write(text)
            print(f"# prom-out: {args.prom_out} "
                  f"({scraper.scrapes if scraper else 0} live scrapes)",
                  file=sys.stderr)
        if args.trace_out:
            n_ev = get_tracer().export(args.trace_out)
            print(f"# trace-out: {args.trace_out} ({n_ev} events)",
                  file=sys.stderr)
        if exporter is not None:
            exporter.close()
    print(fmt_rows(rows))
    if args.json:
        doc = [{"name": name, "us_per_call": us, **derived}
               for name, us, derived in rows]
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
    if args.chaos_json:
        doc = [{"name": name, "us_per_call": us, **derived}
               for name, us, derived in rows
               if name.startswith(("serving/chaos_",
                                   "serving/resilience_overhead"))]
        with open(args.chaos_json, "w") as f:
            json.dump(doc, f, indent=2)


if __name__ == "__main__":
    main()
