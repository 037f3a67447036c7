"""Fault-tolerant fan-out (``repro.index.resilience``):

  * ``ResilientShardClient`` healthy path is a bit-identical
    pass-through; retries recover from transient faults with the
    documented backoff + metrics + ``retry`` spans,
  * per-attempt deadlines abandon hung dispatches; hedged dispatch
    races a second attempt and records win/loss,
  * the circuit breaker opens after consecutive failures,
    short-circuits without touching the transport, half-opens a probe,
    and closes on success -- every transition visible in the
    ``shard_breaker_state`` gauge and ``breaker`` spans,
  * ``on_shard_failure="partial"`` serves survivors bit-identically to
    a healthy router restricted to those shards, with exact
    ``coverage``; every query resolves under seeded 25% mixed chaos
    through a live ``SearchServer``,
  * the seeded ``ChaosShardClient`` is deterministic: same schedule =>
    identical fault sequences and identical partial results.
"""

import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.core.oph import OPH
from repro.data.pipeline import make_sharded_dataset
from repro.data.preprocess import preprocess_shards
from repro.data.synthetic import DatasetSpec
from repro.index import (ChaosSchedule, ChaosShardClient, CircuitOpenError,
                         IndexSearcher, LocalShardClient, ResiliencePolicy,
                         ResilientShardClient, ShardDispatchTimeout,
                         build_index, build_sharded, choose_band_config,
                         load_index, load_sharded, merge_topk,
                         resilient_client_factory)
from repro.launch.server import SearchServer
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer

K, S, B = 128, 16, 8


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("chaos_corpus"))
    spec = DatasetSpec("chaostest", n=300, D=1 << S, avg_nnz=48,
                       n_prototypes=8, overlap=0.8, seed=31)
    raw = make_sharded_dataset(spec, os.path.join(tmp, "raw"), n_shards=4)
    fam = OPH.create(jax.random.PRNGKey(6), K, S, "2u", "rotation")
    preprocess_shards(raw, os.path.join(tmp, "sig"), fam, b=B,
                      chunk_size=64)
    sig_paths = sorted(glob.glob(os.path.join(tmp, "sig", "*.sig")))
    cfg = choose_band_config(K, B, threshold=0.5)
    idx_path = os.path.join(tmp, "single.idx")
    build_index(sig_paths, idx_path, cfg)
    shard_dir = os.path.join(tmp, "shards")
    build_sharded(sig_paths, shard_dir, cfg, n_shards=3)
    return tmp, shard_dir, idx_path


@pytest.fixture(scope="module")
def single(corpus):
    _, _, idx_path = corpus
    return IndexSearcher(load_index(idx_path), backend="interpret",
                         corpus_block=64)


def _queries(single, m=4):
    n = single.index.n
    ids = [0, n // 3, n // 2, n - 1][:m]
    return np.ascontiguousarray(single.index.words_host[ids])


class ScriptedClient:
    """``ShardClient`` whose calls follow a plan.

    Plan entries: ``"err"`` -> OSError at dispatch; a float -> the
    harvest sleeps that long then returns the real result; ``0`` ->
    plain pass-through.  Past the end of the plan, every call is ok.
    """

    def __init__(self, searcher, plan=()):
        self.inner = LocalShardClient(searcher)
        self.plan = list(plan)
        self.calls = 0

    @property
    def n(self):
        return self.inner.n

    def dispatch(self, qwords, topk, *, mode="exact", query_sizes=None,
                 qkeys=None):
        step = self.plan[self.calls] if self.calls < len(self.plan) else 0
        self.calls += 1
        if step == "err":
            raise OSError("scripted dispatch failure")
        inner = self.inner.dispatch(qwords, topk, mode=mode,
                                    query_sizes=query_sizes, qkeys=qkeys)

        def harvest():
            if step:
                time.sleep(step)
            return inner()
        return harvest


# ---------------------------------------------------------------------------
# ResilientShardClient
# ---------------------------------------------------------------------------

def test_resilient_healthy_path_is_passthrough(corpus, single):
    """No faults: resilient fan-out == plain local fan-out, zero
    retries/hedges/breaker movement, coverage 1.0."""
    _, shard_dir, _ = corpus
    reg = MetricsRegistry()
    fac = resilient_client_factory(ResiliencePolicy(), registry=reg)
    router = load_sharded(shard_dir, backend="interpret", corpus_block=64,
                          dispatch="sequential", client_factory=fac)
    plain = load_sharded(shard_dir, backend="interpret", corpus_block=64,
                         dispatch="sequential")
    q = _queries(single)
    for mode in ("exact", "lsh"):
        got = router.search(q, 10, mode=mode)
        want = plain.search(q, 10, mode=mode)
        assert np.array_equal(got.indices, want.indices), mode
        assert np.array_equal(got.scores, want.scores), mode
        assert got.coverage == 1.0 and got.failed_shards == ()
    vals = reg.values()
    for i in range(3):
        assert vals[f'shard_dispatch_retries_total{{shard="{i}"}}'] == 0.0
        assert vals[f'shard_breaker_state{{shard="{i}"}}'] == 0.0


def test_retry_recovers_with_backoff_metrics_and_spans(single):
    reg, tr = MetricsRegistry(), Tracer(enabled=True)
    sleeps = []
    inner = ScriptedClient(single, ["err", "err", 0])
    client = ResilientShardClient(
        inner, ResiliencePolicy(max_retries=2, backoff_base_s=0.001,
                                backoff_cap_s=0.01),
        registry=reg, tracer=tr, sleep=sleeps.append)
    q = _queries(single, 2)
    got = client.dispatch(q, 5)()
    want = single.dispatch(q, 5)()
    assert np.array_equal(got.indices, want.indices)
    assert np.array_equal(got.scores, want.scores)
    assert inner.calls == 3
    vals = reg.values()
    assert vals['shard_dispatch_retries_total{shard="0"}'] == 2.0
    assert vals['shard_dispatch_failures_total{shard="0"}'] == 2.0
    # decorrelated-jitter backoff: bounded by [base, cap], one per retry
    assert len(sleeps) == 2
    assert all(0.001 <= s <= 0.01 for s in sleeps)
    retry_spans = [e for e in tr.events() if e.get("name") == "retry"]
    assert [s["args"]["attempt"] for s in retry_spans] == [1, 2]
    assert all(s["args"]["error"] == "OSError" for s in retry_spans)


def test_retry_budget_exhausted_raises_last_error(single):
    inner = ScriptedClient(single, ["err", "err", "err"])
    client = ResilientShardClient(
        inner, ResiliencePolicy(max_retries=2, backoff_base_s=0.0,
                                backoff_cap_s=0.0),
        registry=MetricsRegistry())
    with pytest.raises(OSError, match="scripted"):
        client.dispatch(_queries(single, 1), 5)()
    assert inner.calls == 3


def test_deadline_abandons_hung_dispatch(single):
    reg = MetricsRegistry()
    client = ResilientShardClient(
        ScriptedClient(single, [0.5, 0.5]),
        ResiliencePolicy(deadline_s=0.05, max_retries=0),
        registry=reg)
    t0 = time.monotonic()
    with pytest.raises(ShardDispatchTimeout):
        client.dispatch(_queries(single, 1), 5)()
    assert time.monotonic() - t0 < 0.4          # did not wait out the hang
    assert reg.values()['shard_dispatch_timeouts_total{shard="0"}'] == 1.0


def test_hedge_wins_against_slow_primary(single):
    reg, tr = MetricsRegistry(), Tracer(enabled=True)
    inner = ScriptedClient(single, [0.5, 0])     # primary slow, hedge fast
    client = ResilientShardClient(
        inner, ResiliencePolicy(hedge=True, hedge_min_s=0.01,
                                hedge_max_s=0.01),
        registry=reg, tracer=tr)
    q = _queries(single, 2)
    t0 = time.monotonic()
    got = client.dispatch(q, 5)()
    assert time.monotonic() - t0 < 0.4           # hedge, not the primary
    want = single.dispatch(q, 5)()
    assert np.array_equal(got.indices, want.indices)
    assert inner.calls == 2
    key = 'shard_hedges_total{outcome="win",shard="0"}'
    assert reg.values()[key] == 1.0
    spans = [e for e in tr.events() if e.get("name") == "hedge"]
    assert len(spans) == 1 and spans[0]["args"]["outcome"] == "win"


def test_breaker_lifecycle_short_circuits_and_recovers(single):
    reg, tr = MetricsRegistry(), Tracer(enabled=True)
    inner = ScriptedClient(single, ["err", "err", 0])
    client = ResilientShardClient(
        inner, ResiliencePolicy(max_retries=0, breaker_failures=2,
                                breaker_reset_s=0.05),
        registry=reg, tracer=tr)
    q = _queries(single, 1)
    key = 'shard_breaker_state{shard="0"}'

    for _ in range(2):                           # two consecutive failures
        with pytest.raises(OSError):
            client.dispatch(q, 5)()
    assert reg.values()[key] == 2.0              # open

    calls_before = inner.calls
    with pytest.raises(CircuitOpenError):        # short-circuit: no
        client.dispatch(q, 5)                    # transport touched
    assert inner.calls == calls_before

    time.sleep(0.06)                             # reset window elapses
    got = client.dispatch(q, 5)()                # the half-open probe
    want = single.dispatch(q, 5)()
    assert np.array_equal(got.indices, want.indices)
    assert reg.values()[key] == 0.0              # closed again

    trans = [(e["args"]["from"], e["args"]["to"])
             for e in tr.events() if e.get("name") == "breaker"]
    assert trans == [("closed", "open"), ("open", "half_open"),
                     ("half_open", "closed")]


# ---------------------------------------------------------------------------
# partial fan-out + chaos
# ---------------------------------------------------------------------------

def _dead_shard_router(shard_dir, dead, **kw):
    fac = resilient_client_factory(
        ResiliencePolicy(max_retries=0, backoff_base_s=0.0),
        chaos=lambda i: (ChaosSchedule(seed=7, fault_rate=1.0,
                                       faults=("oserror",))
                         if i == dead else None))
    return load_sharded(shard_dir, backend="interpret", corpus_block=64,
                        dispatch="sequential", client_factory=fac, **kw)


def test_partial_serves_survivors_bit_identically(corpus, single):
    """Dead shard under "partial": results == healthy router restricted
    to the survivors, coverage == surviving doc fraction exactly."""
    _, shard_dir, _ = corpus
    router = _dead_shard_router(shard_dir, dead=2,
                                on_shard_failure="partial")
    healthy = load_sharded(shard_dir, backend="interpret", corpus_block=64,
                           dispatch="sequential")
    q = _queries(single)
    got = router.search(q, 10)
    assert got.failed_shards == (2,)
    keep = [0, 1]
    want = merge_topk(
        [healthy.searchers[i].dispatch(q, 10)() for i in keep],
        healthy.offsets[keep], 10)
    assert np.array_equal(got.indices, want.indices)
    assert np.array_equal(got.scores, want.scores)
    n_live = sum(healthy.searchers[i].index.n for i in keep)
    assert got.coverage == n_live / single.index.n


def test_partial_not_requested_still_fails(corpus):
    _, shard_dir, _ = corpus
    router = _dead_shard_router(shard_dir, dead=0)     # default "fail"
    with pytest.raises(OSError):
        router.search(np.zeros((1, router.searchers[0].index.words_host
                                .shape[1]), np.uint32), 5)


def test_all_shards_failed_raises(corpus):
    _, shard_dir, _ = corpus
    fac = resilient_client_factory(
        ResiliencePolicy(max_retries=0),
        chaos=ChaosSchedule(seed=1, fault_rate=1.0, faults=("oserror",)))
    router = load_sharded(shard_dir, backend="interpret", corpus_block=64,
                          dispatch="sequential", client_factory=fac,
                          on_shard_failure="partial")
    q = np.zeros((1, router.searchers[0].index.words_host.shape[1]),
                 np.uint32)
    with pytest.raises(RuntimeError, match="all 3 shards failed"):
        router.search(q, 5)


def test_chaos_survival_through_server(corpus, single):
    """Seeded 25% mixed faults (latency/oserror/hang/drop) through a
    live 2-worker SearchServer in partial mode: every request resolves,
    nothing hangs, coverage is accounted."""
    _, shard_dir, _ = corpus
    fac = resilient_client_factory(
        ResiliencePolicy(deadline_s=0.25, max_retries=1,
                         backoff_base_s=0.001, backoff_cap_s=0.005),
        chaos=lambda i: ChaosSchedule(seed=100 + i, fault_rate=0.25,
                                      latency_s=0.002, hang_s=1.0),
        seed=9)
    router = load_sharded(shard_dir, backend="interpret", corpus_block=64,
                          dispatch="sequential", client_factory=fac,
                          on_shard_failure="partial")
    rows = [np.asarray(r) for r in _queries(single)] * 6
    with SearchServer(router, max_batch=4, max_delay_s=0.002, topk=5,
                      num_workers=2, on_shard_failure="partial") as srv:
        handles = [srv.submit(r) for r in rows]
        results = [h.result(timeout=120.0) for h in handles]
    assert len(results) == len(rows)             # every query resolved
    assert all(h.outcome in ("served", "partial") for h in handles)
    for res in results:
        assert res.indices.shape == (1, 5)
        assert 0.0 < res.coverage <= 1.0
        if res.failed_shards:
            n_live = sum(s.index.n for i, s in enumerate(router.searchers)
                         if i not in res.failed_shards)
            assert res.coverage == n_live / single.index.n
    snap = srv.stats.snapshot()
    assert snap["requests"] == len(rows)
    if any(h.outcome == "partial" for h in handles):
        assert snap["partial"] > 0
        assert snap["mean_coverage"] < 1.0


def test_chaos_is_seed_deterministic(corpus, single):
    """Same ChaosSchedule seeds => identical fault sequences AND
    identical (partial) results, run to run."""
    _, shard_dir, _ = corpus
    q = _queries(single)

    def run():
        fac = resilient_client_factory(
            ResiliencePolicy(max_retries=1, backoff_base_s=0.0,
                             backoff_cap_s=0.0),
            chaos=lambda i: ChaosSchedule(seed=40 + i, fault_rate=0.5,
                                          faults=("oserror", "drop",
                                                  "latency"),
                                          latency_s=0.0),
            seed=3)
        router = load_sharded(shard_dir, backend="interpret",
                              corpus_block=64, dispatch="sequential",
                              client_factory=fac,
                              on_shard_failure="partial")
        out = [router.search(q, 10) for _ in range(6)]
        logs = [tuple(c.fault_log) for c in fac.chaos_clients]
        return out, logs

    out_a, logs_a = run()
    out_b, logs_b = run()
    assert logs_a == logs_b                      # identical fault sequences
    assert any(k is not None for log in logs_a for _, k in log)
    for ra, rb in zip(out_a, out_b):
        assert np.array_equal(ra.indices, rb.indices)
        assert np.array_equal(ra.scores, rb.scores)
        assert ra.coverage == rb.coverage
        assert ra.failed_shards == rb.failed_shards


def test_chaos_client_draw_log_matches_schedule(single):
    """fault_log replays the schedule's seeded draw stream exactly."""
    sched = ChaosSchedule(seed=11, fault_rate=0.5, faults=("latency",),
                          latency_s=0.0)
    client = ChaosShardClient(LocalShardClient(single), sched)
    q = _queries(single, 1)
    for _ in range(8):
        client.dispatch(q, 3)()
    rng = np.random.default_rng(11)
    want = []
    for i in range(8):
        kind = None
        if float(rng.random()) < 0.5:
            rng.integers(1)
            kind = "latency"
        want.append((i, kind))
    assert client.fault_log == want
