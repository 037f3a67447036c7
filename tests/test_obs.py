"""The observability layer: registry semantics, tracer span trees under
concurrent dispatch workers, and the HTTP exporter.

The serving-path integration matters most here: ISSUE 9's acceptance is
that a traced multi-worker run produces (a) per-request span trees whose
direct children partition the recorded end-to-end latency (±5%), (b)
spans that never tear across workers (ids consistent, clocks monotonic),
(c) counter totals identical across ``num_workers`` ∈ {1, 4} for the
same seeded traffic, and (d) results bit-identical to direct
``search()``.
"""

import collections
import glob
import json
import os
import threading
import urllib.request

import numpy as np
import pytest

import jax

from repro.data.pipeline import make_sharded_dataset
from repro.data.preprocess import preprocess_shards
from repro.data.synthetic import DatasetSpec
from repro.index import (IndexSearcher, build_index, build_sharded,
                         choose_band_config, load_index, load_sharded)
from repro.launch.server import SearchServer, ZipfianTraffic
from repro.obs.export import start_http_exporter
from repro.obs.metrics import MetricsRegistry, Sample, get_registry
from repro.obs.trace import Tracer, get_tracer, request_tree
from repro.train.online import make_family


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("obs_test_total", "a counter")
    c.inc()
    c.inc(2.5)
    g = reg.gauge("obs_depth", "a gauge")
    g.set(7)
    g.dec(2)
    h = reg.histogram("obs_lat_seconds", "a histogram")
    for v in range(100):
        h.observe(v / 100)
    vals = reg.values()
    assert vals["obs_test_total"] == 3.5
    assert vals["obs_depth"] == 5.0
    assert vals["obs_lat_seconds_count"] == 100
    assert vals["obs_lat_seconds_sum"] == pytest.approx(49.5)
    assert vals['obs_lat_seconds{quantile="0.5"}'] == pytest.approx(0.5, abs=0.05)


def test_counter_rejects_negative_and_type_conflicts():
    reg = MetricsRegistry()
    c = reg.counter("obs_mono_total", "monotone")
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        reg.gauge("obs_mono_total", "same name, different type")


def test_labeled_children_and_prometheus_text():
    reg = MetricsRegistry()
    fam = reg.counter("obs_flushes_total", "flushes", labels=("trigger",))
    fam.labels(trigger="full").inc(3)
    fam.labels(trigger="aged").inc()
    text = reg.prometheus_text()
    assert "# TYPE obs_flushes_total counter" in text
    assert 'obs_flushes_total{trigger="full"} 3' in text
    assert 'obs_flushes_total{trigger="aged"} 1' in text


def test_weakref_collector_lives_and_dies_with_holder():
    reg = MetricsRegistry()

    class Holder:
        n = 5

    def collect(h):
        yield Sample("obs_holder_n", "gauge", "held value", (), float(h.n))

    h = Holder()
    reg.register_object(h, collect)
    assert reg.values()["obs_holder_n"] == 5.0
    del h
    assert "obs_holder_n" not in reg.values()


def test_snapshot_sums_identical_series_across_holders():
    reg = MetricsRegistry()

    def collect(h):
        yield Sample("obs_shared_total", "counter", "shared", (), 2.0)

    class Holder:
        pass

    a, b = Holder(), Holder()
    reg.register_object(a, collect)
    reg.register_object(b, collect)
    assert reg.values()["obs_shared_total"] == 4.0
    del a, b  # keep referenced until here


def test_reset_clears_values_but_keeps_live_collectors():
    reg = MetricsRegistry()
    reg.counter("obs_gone_total", "cleared by reset").inc(9)

    class Holder:
        pass

    def collect(h):
        yield Sample("obs_kept", "gauge", "survives reset", (), 1.0)

    h = Holder()
    reg.register_object(h, collect)
    reg.reset()
    vals = reg.values()
    assert "obs_gone_total" not in vals
    assert vals["obs_kept"] == 1.0
    del h


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def test_disabled_tracer_emits_nothing():
    tr = Tracer(enabled=False)
    with tr.span("outer"):
        sp = tr.start_span("inner")
        tr.end_span(sp)
    tr.add_span("retro", 0.0, 1.0)
    assert tr.events() == []


def test_span_kinds_and_nesting():
    tr = Tracer(enabled=True)
    with tr.span("outer") as outer:
        with tr.span("inner") as inner:
            assert inner.parent_id == outer.span_id
    root = tr.start_span("request", kind="async")
    root.trace_id = root.span_id
    child = tr.start_span("flush", parent=root, kind="async")
    assert child.trace_id == root.trace_id
    tr.end_span(child)
    tr.end_span(root)
    phs = collections.Counter(e["ph"] for e in tr.events())
    assert phs["X"] == 2                       # outer + inner
    assert phs["b"] == 2 and phs["e"] == 2     # request + flush


def test_phase_channel_drains_per_thread():
    tr = Tracer(enabled=True)
    with tr.phase("mesh_dispatch"):
        pass
    with tr.phase("merge"):
        pass
    phases = tr.take_phases()
    assert [p[0] for p in phases] == ["mesh_dispatch", "merge"]
    assert all(t1 >= t0 for _, t0, t1 in phases)
    assert tr.take_phases() == []              # drained

    got = {}

    def other():
        got["phases"] = tr.take_phases()

    t = threading.Thread(target=other)
    with tr.phase("mine"):
        pass
    t.start()
    t.join()
    assert got["phases"] == []                 # phase notes are per-thread
    assert [p[0] for p in tr.take_phases()] == ["mine"]


def test_bounded_buffer_counts_drops():
    tr = Tracer(enabled=True, max_events=4)
    for i in range(10):
        tr.add_span(f"s{i}", 0.0, 1.0)
    assert len(tr.events()) <= 4
    assert tr.dropped > 0


@pytest.mark.parametrize("enabled,annotations,want", [
    (True, True, ["outer", "inner", "mesh_dispatch"]),
    (True, False, []),
    (False, True, []),
])
def test_live_spans_open_one_profiler_annotation_each(monkeypatch, enabled,
                                                      annotations, want):
    """Live spans (``span``, ``phase``) reach the profiler under their
    plain names, args left out; retroactive spans cannot and do not; a
    disabled tracer builds no annotation at all."""
    made = []

    class Counting:
        def __init__(self, name, **kwargs):
            made.append((name, kwargs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    tr = Tracer(enabled=enabled, jax_annotations=annotations)
    with tr.span("outer", args={"batch": 3}):
        with tr.span("inner"):
            pass
    with tr.phase("mesh_dispatch", args={"mode": "exact"}):
        pass
    tr.add_span("queue", 0.0, 1.0)
    tr.end_span(tr.start_span("request", kind="async"))
    assert made == [(name, {}) for name in want]
    assert len(tr.events()) == (6 if enabled else 0)


# ---------------------------------------------------------------------------
# HTTP exporter
# ---------------------------------------------------------------------------

def _get(url: str) -> bytes:
    with urllib.request.urlopen(url, timeout=10.0) as r:
        return r.read()


def test_exporter_serves_metrics_json_trace_and_health():
    reg = MetricsRegistry()
    reg.counter("obs_http_total", "served").inc(2)
    tr = Tracer(enabled=True)
    tr.add_span("hello", 0.0, 0.001)
    with start_http_exporter(port=0, registry=reg, tracer=tr) as exp:
        assert _get(exp.url + "/healthz") == b"ok"
        text = _get(exp.url + "/metrics").decode()
        assert "obs_http_total 2" in text
        snap = json.loads(_get(exp.url + "/metrics.json"))
        assert snap["obs_http_total"]["samples"][0]["value"] == 2.0
        doc = json.loads(_get(exp.url + "/trace"))
        assert doc["traceEvents"][0]["name"] == "hello"
        with pytest.raises(urllib.error.HTTPError):
            _get(exp.url + "/nope")


# ---------------------------------------------------------------------------
# Serving integration: traced SearchServer over a real index
# ---------------------------------------------------------------------------

K, B, S = 64, 8, 16
N_DOCS = 512
TOPK = 5


@pytest.fixture(scope="module")
def small_index(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("obs_idx")
    spec = DatasetSpec("obs_serving", n=N_DOCS, D=1 << S, avg_nnz=32,
                       n_prototypes=4, overlap=0.8, seed=0)
    raw = make_sharded_dataset(spec, str(tmp / "raw"), n_shards=2)
    fam = make_family(jax.random.PRNGKey(0), "oph", K, S,
                      densify="rotation")
    preprocess_shards(raw, str(tmp / "sig"), fam, b=B, chunk_size=256)
    sig = sorted(glob.glob(str(tmp / "sig" / "*.sig")))
    cfg = choose_band_config(K, B, code_bits=B, threshold=0.5)
    build_index(sig, str(tmp / "c.idx"), cfg)
    index = load_index(str(tmp / "c.idx"))
    return index, IndexSearcher(index)


def _drive_traced(searcher, index, *, workers: int, n: int = 48):
    reg = MetricsRegistry()
    tr = Tracer(enabled=True)
    traffic = ZipfianTraffic(int(index.words_host.shape[0]),
                             alpha=1.1, seed=7)
    ids = traffic.ids(n)
    server = SearchServer(searcher, max_batch=8, max_delay_s=0.002,
                          topk=TOPK, mode="exact", num_workers=workers,
                          registry=reg, tracer=tr)
    with server:
        handles = [server.submit(np.asarray(index.words_host[int(i)]))
                   for i in ids]
        results = [h.result(timeout=60.0) for h in handles]
    # the registry holds only a weakref to the server; hand the server
    # back so callers can still collect its samples
    return reg, tr, ids, results, server


def test_multiworker_spans_never_tear(small_index):
    """Concurrent workers: every request tree has exactly one root, all
    parent ids resolve inside the same trace, clocks are monotonic per
    span, and the direct children partition the root (±5%)."""
    index, searcher = small_index
    reg, tr, ids, _, _srv = _drive_traced(searcher, index, workers=4)

    events = tr.events()
    assert tr.dropped == 0
    by_id = {}
    for ev in events:
        args = ev["args"]
        by_id.setdefault(args["span_id"], []).append(ev)
    # every span's begin/end carry the same identity, and t1 >= t0
    for span_id, evs in by_id.items():
        ts = sorted(e["ts"] for e in evs)
        assert ts[-1] >= ts[0]
        assert len({(e["args"]["parent_id"], e["args"]["trace_id"])
                    for e in evs}) == 1

    trees = request_tree(events)
    trees.pop(0, None)                       # batch-level (X) spans
    assert len(trees) == len(ids)
    for tid, evs in trees.items():
        begins = [e for e in evs if e["ph"] == "b"]
        ends = {e["args"]["span_id"]: e for e in evs if e["ph"] == "e"}
        roots = [e for e in begins if e["name"] == "request"]
        assert len(roots) == 1               # exactly one root per request
        root = roots[0]
        span_ids = {e["args"]["span_id"] for e in begins}
        for e in begins:                     # parents resolve in-tree
            if e is not root:
                assert e["args"]["parent_id"] in span_ids
        kids = [e for e in begins
                if e["args"]["parent_id"] == root["args"]["span_id"]]
        assert sorted(e["name"] for e in kids) == ["admission", "flush",
                                                   "queue"]
        root_dur = ends[root["args"]["span_id"]]["ts"] - root["ts"]
        ksum = sum(ends[e["args"]["span_id"]]["ts"] - e["ts"]
                   for e in kids)
        if root_dur > 0:
            assert abs(ksum - root_dur) <= 0.05 * root_dur


def test_counter_totals_identical_across_worker_counts(small_index):
    """Same seeded traffic through 1 vs 4 workers: identical request /
    shed / degraded / error totals, identical summed batch sizes, and
    bit-identical results."""
    index, searcher = small_index
    totals = {}
    results = {}
    for nw in (1, 4):
        reg, _, ids, res, _srv = _drive_traced(searcher, index, workers=nw)
        vals = reg.values()
        totals[nw] = {k: vals[k] for k in
                      ("serve_requests_total", "serve_shed_total",
                       "serve_degraded_total", "serve_errors_total",
                       "serve_batch_size_sum")}
        results[nw] = res
    assert totals[1] == totals[4]
    assert totals[1]["serve_requests_total"] == 48.0
    for a, b in zip(results[1], results[4]):
        assert np.array_equal(np.asarray(a.indices), np.asarray(b.indices))
        assert np.array_equal(np.asarray(a.scores), np.asarray(b.scores))


def test_server_exports_occupancy_and_queue_depth(small_index):
    index, searcher = small_index
    reg, _, _, _, _srv = _drive_traced(searcher, index, workers=2)
    vals = reg.values()
    assert 'serve_worker_occupancy{worker="0"}' in vals
    assert 'serve_worker_occupancy{worker="1"}' in vals
    assert vals["serve_queue_depth"] == 0.0    # drained at close


@pytest.mark.parametrize("routed", [False, True])
def test_searcher_spans_go_to_the_servers_tracer(small_index, routed):
    """The searcher's ``search_dispatch`` and ``flush:split`` and a
    router's phases land on the tracer the server was given, never on
    the process-wide default; the server adds one ``flush:submit`` and
    one ``flush:w<i>`` per flush."""
    from repro.index import ShardedIndex

    index, searcher = small_index
    if routed:
        searcher = ShardedIndex([index])
    get_tracer().reset(enabled=True)
    reg, tr, ids, _, srv = _drive_traced(searcher, index, workers=1)
    assert get_tracer().events() == []
    names = collections.Counter(e["name"] for e in tr.events()
                                if e["ph"] == "X")
    flushes = srv.stats.batches
    for name in ("flush:submit", "flush:w0", "search_dispatch",
                 "flush:split", "worker_flush"):
        assert names[name] == flushes, name
    phases = {"shard_dispatch", "harvest", "merge"}
    assert all(names[p] == (flushes if routed else 0) for p in phases)
    trees = request_tree(tr.events())
    trees.pop(0, None)
    assert len(trees) == len(ids)
    for evs in trees.values():
        replayed = {e["name"] for e in evs if e["ph"] == "b"}
        assert (phases <= replayed) == routed


def test_trace_counts_alias_still_behaves_like_the_old_dict(small_index):
    """S1 back-compat: ``query.TRACE_COUNTS`` reads/writes route through
    the registry but keep the mapping idiom the old tests rely on."""
    from repro.index import query

    before = query.TRACE_COUNTS["exact_scan"]
    query.TRACE_COUNTS["exact_scan"] += 1
    assert query.TRACE_COUNTS["exact_scan"] == before + 1
    assert "exact_scan" in query.TRACE_COUNTS
    assert set(query.TRACE_COUNTS.keys()) == {"exact_scan"}
    with pytest.raises(ValueError):
        query.TRACE_COUNTS["exact_scan"] = 0   # counters are monotone
    # the same series is visible in the registry snapshot
    vals = get_registry().values()
    assert vals["index_exact_scan_retraces_total"] == before + 1


# ---------------------------------------------------------------------------
# Multidevice acceptance: mesh router + 4 workers, scraped live
# ---------------------------------------------------------------------------

@pytest.mark.multidevice
def test_mesh_serving_scrape_and_trace(host_devices, tmp_path):
    """ISSUE 9 acceptance: a seeded serving run on the device mesh with
    4 workers yields a Prometheus scrape carrying queue-depth,
    shed/degraded, per-worker occupancy and mesh dispatch counters; a
    trace whose request trees cover
    admission→flush→dispatch→merge and partition the latency (±5%); and
    bit-identical results vs direct search()."""
    from repro.launch.mesh import make_debug_mesh

    spec = DatasetSpec("obs_mesh", n=N_DOCS, D=1 << S, avg_nnz=32,
                       n_prototypes=4, overlap=0.8, seed=0)
    raw = make_sharded_dataset(spec, str(tmp_path / "raw"), n_shards=2)
    fam = make_family(jax.random.PRNGKey(0), "oph", K, S,
                      densify="rotation")
    preprocess_shards(raw, str(tmp_path / "sig"), fam, b=B, chunk_size=256)
    sig = sorted(glob.glob(str(tmp_path / "sig" / "*.sig")))
    cfg = choose_band_config(K, B, code_bits=B, threshold=0.5)
    build_sharded(sig, str(tmp_path / "shards"), cfg, n_shards=2)
    mesh = make_debug_mesh(2, axes=("data",))
    router = load_sharded(str(tmp_path / "shards"), mesh=mesh)

    def words_of(i):
        offsets = list(router.offsets) + [router.n]
        shard = int(np.searchsorted(offsets, i, side="right")) - 1
        return np.asarray(
            router.searchers[shard].index.words_host[i - offsets[shard]])

    # production wiring: the router registered itself into the DEFAULT
    # registry at construction, so scrape that one (conftest's _reset_obs
    # fixture cleans both singletons up afterwards)
    reg = get_registry()
    tr = get_tracer()
    tr.reset(enabled=True)
    traffic = ZipfianTraffic(router.n, alpha=1.1, seed=11)
    ids = traffic.ids(48)
    server = SearchServer(router, max_batch=8, max_delay_s=0.002,
                          topk=TOPK, mode="exact", num_workers=4)
    with start_http_exporter(port=0, registry=reg, tracer=tr) as exp:
        with server:
            handles = [server.submit(words_of(int(i))) for i in ids]
            results = [h.result(timeout=120.0) for h in handles]
            live = _get(exp.url + "/metrics").decode()   # scrape under load
        final = _get(exp.url + "/metrics").decode()

    for text in (live, final):
        for name in ("serve_queue_depth", "serve_shed_total",
                     "serve_degraded_total", "serve_worker_occupancy",
                     "index_mesh_dispatches_total"):
            assert name in text, f"{name} missing from scrape"
    assert 'index_mesh_dispatches_total{mode="exact"}' in final
    assert reg.values()["index_mesh_dispatches_total{mode=\"exact\"}"] > 0

    # span trees: children cover dispatch+merge and partition latency
    trees = request_tree(tr.events())
    trees.pop(0, None)
    assert len(trees) == len(ids)
    saw_mesh = saw_merge = False
    for tid, evs in trees.items():
        begins = [e for e in evs if e["ph"] == "b"]
        ends = {e["args"]["span_id"]: e for e in evs if e["ph"] == "e"}
        root = next(e for e in begins if e["name"] == "request")
        kids = [e for e in begins
                if e["args"]["parent_id"] == root["args"]["span_id"]]
        assert sorted(e["name"] for e in kids) == ["admission", "flush",
                                                   "queue"]
        flush = next(e for e in kids if e["name"] == "flush")
        under_flush = {e["name"] for e in begins
                       if e["args"]["parent_id"]
                       == flush["args"]["span_id"]}
        saw_mesh |= "mesh_dispatch" in under_flush
        saw_merge |= "merge" in under_flush
        root_dur = ends[root["args"]["span_id"]]["ts"] - root["ts"]
        ksum = sum(ends[e["args"]["span_id"]]["ts"] - e["ts"]
                   for e in kids)
        if root_dur > 0:
            assert abs(ksum - root_dur) <= 0.05 * root_dur
    assert saw_mesh and saw_merge

    # trace JSON is valid trace-event format
    out = tmp_path / "trace.json"
    tr.export(str(out))
    doc = json.loads(out.read_text())
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    assert all({"name", "ph", "ts", "pid", "tid"} <= set(e)
               for e in doc["traceEvents"])

    # still bit-identical to direct search
    direct = router.search(np.stack([words_of(int(i)) for i in ids]),
                           TOPK, mode="exact")
    for j, res in enumerate(results):
        assert np.array_equal(np.asarray(res.indices[0]),
                              np.asarray(direct.indices[j]))
        assert np.array_equal(np.asarray(res.scores[0]),
                              np.asarray(direct.scores[j]))
