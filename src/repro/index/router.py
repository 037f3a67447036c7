"""Sharded-index router: fan a query batch across ``.idx`` shards and
merge per-shard top-k bit-identically to a single-index search.

``build_sharded`` (``repro.index.builder``) splits a corpus into S
contiguous-doc-range shards; this module serves them as one logical
index:

  * ``ShardedIndex``  -- per-shard ``IndexSearcher``s + the global doc-id
    offsets, reached through a transport-agnostic ``ShardClient`` seam.
    ``search`` fans the query batch out, then ``merge_topk`` folds the
    per-shard results.  Two fan-out dispatchers:

      - ``sequential``: every shard's fused exact scan / LSH rerank
        dispatches before any result is harvested -- jax's async
        dispatch overlaps the shards, on one device or (with a mesh)
        on each shard's placed device.
      - ``mesh``: the exact scan AND the LSH rerank each run as ONE
        ``shard_map``-dispatched computation per flush.  Shards are
        placed round-robin on the devices of the mesh's ``"data"`` axis
        (``repro.sharding.rules.place_shards``); the exact path scans
        each device's stacked shards with a per-device running top-k
        carried in-jit, the LSH path gathers each device's padded/
        masked candidate rows (host bucket probe per shard, band keys
        computed once per batch) and reranks them in one collective
        kernel launch; either way the per-device ``(best_s, best_i)``
        are gathered across the mesh and folded through the same
        ``merge_topk`` rule -- adding devices divides the scan, instead
        of adding per-shard latency.

  * ``merge_topk``    -- lexicographic (descending score, ascending
    global id) fold of per-shard (scores, local ids): exactly
    ``lax.top_k``'s tie rule over the whole corpus, so the merged top-k
    (ids AND scores) is bit-identical to a single-index search over the
    same documents, regardless of how the corpus was partitioned or in
    what order partial results arrive.
  * ``load_sharded``  -- read ``manifest.json`` + shards from a
    ``build_sharded`` output directory.

Live growth under readers: ``ShardedIndex.append`` extends the LAST
shard via ``repro.index.builder.append_index`` under the directory's
lock file (``sharded_lock``), rewrites the manifest atomically with a
bumped ``generation``, and swaps the router's state in one assignment --
a concurrently running ``search``/``flush`` reads ONE consistent
snapshot (taken once at entry), so it returns results against either the
pre- or the post-append corpus, never a torn mix.  With a
``max_shard_docs`` budget, an append that would push the last shard past
the budget *spills* into NEW tail shards instead (published atomically:
temp write + ``os.replace``, manifest last, so a crash mid-spill leaves
readers on the old generation with no torn shard visible).  ``refresh``
is the reader side: re-read the manifest (written atomically, so never
torn) and reload only the shards whose (name, doc count) changed --
spilled shards pick up their round-robin device placement here, and
unchanged shards keep their device-resident corpus
(``repro.launch.server.SearchServer`` calls it before every flush).
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.data.sigshard import read_sig_meta
from repro.index.banding import band_keys_packed
from repro.index.builder import (MANIFEST_NAME, SigIndex, append_index,
                                 build_index, load_index, read_manifest,
                                 sharded_lock, write_manifest)
from repro.index.query import (IndexSearcher, SearchResult, _BatchedAdmission,
                               _query_words, exact_scan_ids, lsh_rerank_ids)
from repro.kernels import PackedSignatures
from repro.obs.metrics import Sample, get_registry
from repro.obs.trace import Tracer, get_tracer
from repro.sharding.rules import data_axis_devices, place_shards


def _router_samples(router: "ShardedIndex"):
    """Registry collector over one live ``ShardedIndex`` (weakref'd):
    the per-instance mesh-dispatch ints (kept per-instance -- tests pin
    them) roll up into process counters, plus the served manifest
    generation / corpus size as gauges."""
    state = router._state
    yield Sample("index_mesh_dispatches_total", "counter",
                 "shard_map collective dispatches taken",
                 (("mode", "exact"),), float(router.mesh_exact_dispatches))
    yield Sample("index_mesh_dispatches_total", "counter",
                 "shard_map collective dispatches taken",
                 (("mode", "lsh"),), float(router.mesh_lsh_dispatches))
    yield Sample("index_generation", "gauge",
                 "manifest generation currently served", (),
                 float(state.generation))
    yield Sample("index_docs", "gauge", "documents served", (),
                 float(state.n))
    yield Sample("index_shards", "gauge", "shards served", (),
                 float(len(state.searchers)))


def merge_topk(results: Sequence[SearchResult], offsets: Sequence[int],
               topk: int) -> SearchResult:
    """Fold per-shard top-k (local ids) into global top-k.

    Scores are computed by the same kernel path on every shard, so
    sorting the concatenated candidates lexicographically by
    (descending score, ascending global id) reproduces ``lax.top_k``
    over the unpartitioned corpus bit-exactly -- ids AND scores.  The
    rule is a pure function of (score, global id), which makes the merge
    independent of shard order and contiguity: the sequential fan-out
    (ascending contiguous ranges) and the mesh fan-out's gathered
    per-device partials (round-robin interleaved ranges) share this one
    code path.  Padding entries (id -1) carry -inf scores and sort last.
    """
    if not results:
        raise ValueError("merge_topk needs at least one shard result")
    cat_s = np.concatenate([r.scores for r in results], axis=1)
    cat_i = np.concatenate(
        [np.where(r.indices >= 0, r.indices + off, np.int64(-1))
         for r, off in zip(results, offsets)], axis=1)
    order = np.lexsort((cat_i, -cat_s), axis=1)[:, :topk]
    out_s = np.take_along_axis(cat_s, order, axis=1)
    out_i = np.take_along_axis(cat_i, order, axis=1)
    pad = topk - out_s.shape[1]
    if pad > 0:
        out_s = np.pad(out_s, ((0, 0), (0, pad)),
                       constant_values=-np.inf)
        out_i = np.pad(out_i, ((0, 0), (0, pad)), constant_values=-1)
    n_cand = None
    if all(r.n_candidates is not None for r in results):
        n_cand = np.sum([r.n_candidates for r in results], axis=0)
    return SearchResult(out_i, out_s.astype(np.float32), n_cand)


# ---------------------------------------------------------------------------
# The RPC seam
# ---------------------------------------------------------------------------

class ShardClient:
    """Transport seam between the router and one shard's searcher.

    ``ShardedIndex``'s fan-out speaks only this protocol: ``dispatch``
    starts the shard's work NOW and returns a zero-arg harvest callable
    producing the shard's ``SearchResult`` (scores + LOCAL doc ids) --
    local ids plus kernel scores are the entire wire contract, so the
    router's merge is transport-agnostic.  ``LocalShardClient`` is the
    in-process implementation; a multi-host deployment swaps in a client
    whose ``dispatch`` ships the packed query batch over RPC and whose
    harvest blocks on the remote reply, with no change to the router.
    """

    @property
    def n(self) -> int:
        """Documents served by this shard."""
        raise NotImplementedError

    def dispatch(self, qwords, topk: int, *, mode: str = "exact",
                 query_sizes=None,
                 qkeys=None) -> Callable[[], SearchResult]:
        raise NotImplementedError


class LocalShardClient(ShardClient):
    """In-process ``ShardClient``: a direct ``IndexSearcher.dispatch``."""

    def __init__(self, searcher: IndexSearcher):
        self.searcher = searcher

    @property
    def n(self) -> int:
        return self.searcher.index.n

    def dispatch(self, qwords, topk: int, *, mode: str = "exact",
                 query_sizes=None,
                 qkeys=None) -> Callable[[], SearchResult]:
        return self.searcher.dispatch(qwords, topk, mode=mode,
                                      query_sizes=query_sizes, _qkeys=qkeys)


@dataclasses.dataclass(frozen=True)
class _RouterState:
    """One immutable, internally consistent view of the shard set.

    Mutations (``append``, ``refresh``) build a whole new state and swap
    it in with a single attribute assignment; every ``search`` snapshots
    ``self._state`` exactly once, so a racing mutation can never hand a
    query old offsets with new searchers (a torn view).  ``cache`` holds
    per-state derived device data (the mesh dispatcher's stacked
    corpus); it dies with the state, so a swapped-in corpus can never be
    served against stale offsets.
    """

    searchers: Tuple[IndexSearcher, ...]
    clients: Tuple[ShardClient, ...]
    offsets: np.ndarray            # global doc-id offset per shard
    paths: Optional[Tuple[str, ...]]
    generation: int
    cache: dict = dataclasses.field(default_factory=dict)

    @property
    def n(self) -> int:
        return int(sum(s.index.n for s in self.searchers))


def _plan_spill(last_n: int, counts: Sequence[int],
                budget: int) -> List[Tuple[bool, List[int]]]:
    """Greedy ``.sig``-file assignment for a budgeted append.

    Returns ``[(extend_last, [file indices]), ...]``: files keep landing
    in the current target shard while its doc count is below ``budget``
    (so a shard can overshoot by at most one file -- splits stay at
    ``.sig``-file granularity, like ``build_sharded``), then spill into
    a NEW shard.  The first group extends the last existing shard only
    if it still had headroom.
    """
    groups: List[Tuple[bool, List[int]]] = []
    cur: List[int] = []
    cur_n = last_n
    extend = True
    for i, c in enumerate(counts):
        if cur_n >= budget:
            if cur:
                groups.append((extend, cur))
            cur, cur_n, extend = [], 0, False
        cur.append(i)
        cur_n += c
    if cur:
        groups.append((extend, cur))
    return groups


class ShardedIndex(_BatchedAdmission):
    """One logical index over S ``.idx`` shards with contiguous doc ranges.

    Mirrors the ``IndexSearcher`` serving API (``search`` plus the
    shared ``submit``/``flush`` batched admission) and returns *global*
    doc ids.  ``searcher_kwargs`` flow to every per-shard
    ``IndexSearcher`` (backend, corpus_block, max_device_bytes, ... --
    an out-of-core device window applies per shard).

    ``mesh`` places shards round-robin on the devices of the mesh's
    ``"data"`` axis and enables the ``shard_map`` exact dispatcher;
    ``dispatch`` picks the fan-out ("auto" = mesh iff a mesh was given,
    overridable per ``search`` call).  ``max_shard_docs`` is the spill
    budget for ``append``; ``client_factory`` wraps each searcher in a
    ``ShardClient`` (default: in-process).
    """

    def __init__(self, indexes: Sequence[SigIndex], *,
                 paths: Optional[Sequence[str]] = None,
                 manifest_dir: Optional[str] = None,
                 generation: int = 0,
                 mesh: Optional[Mesh] = None,
                 dispatch: str = "auto",
                 max_shard_docs: Optional[int] = None,
                 client_factory: Optional[Callable[[IndexSearcher],
                                                   ShardClient]] = None,
                 on_shard_failure: str = "fail",
                 **searcher_kwargs):
        if not indexes:
            raise ValueError("ShardedIndex needs at least one shard")
        if dispatch not in ("auto", "sequential", "mesh"):
            raise ValueError(f"dispatch must be 'auto', 'sequential' or "
                             f"'mesh', got {dispatch!r}")
        if on_shard_failure not in ("fail", "partial"):
            raise ValueError(f"on_shard_failure must be 'fail' or "
                             f"'partial', got {on_shard_failure!r}")
        if dispatch == "mesh" and mesh is None:
            raise ValueError("dispatch='mesh' needs a mesh")
        if max_shard_docs is not None and max_shard_docs < 1:
            raise ValueError(f"max_shard_docs must be >= 1, got "
                             f"{max_shard_docs}")
        spec0 = indexes[0].spec
        for i, idx in enumerate(indexes[1:], 1):
            if idx.spec != spec0 or idx.banding != indexes[0].banding:
                raise ValueError(
                    f"shard {i} wire/banding {idx.spec}/{idx.banding} != "
                    f"shard 0 {spec0}/{indexes[0].banding}")
        self._searcher_kwargs = dict(searcher_kwargs)
        self.manifest_dir = manifest_dir
        self.mesh = mesh
        self.max_shard_docs = max_shard_docs
        self._dispatch_default = dispatch
        self._client_factory = client_factory or LocalShardClient
        self.on_shard_failure = on_shard_failure
        reg = get_registry()
        self._m_shard_failures = reg.counter(
            "index_shard_failures_total",
            "shard dispatches that failed past their client's own "
            "retry/breaker budget", labels=("shard",))
        self._m_partial = reg.counter(
            "index_partial_searches_total",
            "searches served from surviving shards only "
            "(on_shard_failure='partial')")
        # the mesh's data-parallel rank set, as its own 1-axis mesh: the
        # shard_map dispatch and the placement rule both address devices
        # along "data" only, whatever other axes the caller's mesh has
        self._data_mesh = None
        if mesh is not None:
            self._data_mesh = Mesh(np.array(data_axis_devices(mesh)),
                                   ("data",))
        self._mesh_fns: dict = {}
        self._mesh_build_lock = threading.Lock()
        # observability: collective dispatches actually taken (tests pin
        # that the LSH path really went through ONE shard_map, not the
        # per-shard sequential loop); also exported through the metrics
        # registry by the weakref collector below
        self.mesh_exact_dispatches = 0
        self.mesh_lsh_dispatches = 0
        get_registry().register_object(self, _router_samples)
        # Serializes state swaps so a refresh that read an older manifest
        # can never overwrite a concurrent append's newer state
        # (generations only move forward).
        self._swap_lock = threading.Lock()
        devices = self._shard_devices(len(indexes))
        self._state = self._build_state(
            [self._make_searcher(idx, i, devices)
             for i, idx in enumerate(indexes)], paths, generation)
        self._admission_init()

    # -- placement + state construction ----------------------------------
    def _shard_devices(self, n_shards: int):
        """Round-robin shard -> device placement (None without a mesh).

        Stable by shard position (``repro.sharding.rules.place_shards``):
        tail growth never relocates an existing shard."""
        if self._data_mesh is None:
            return None
        return place_shards(n_shards, self._data_mesh)

    def _make_searcher(self, idx: SigIndex, shard_i: int,
                       devices) -> IndexSearcher:
        dev = devices[shard_i] if devices is not None else None
        return IndexSearcher(idx, device=dev, **self._searcher_kwargs)

    def _build_state(self, searchers: Sequence[IndexSearcher],
                     paths: Optional[Sequence[str]],
                     generation: int) -> _RouterState:
        offsets = np.cumsum([0] + [s.index.n for s in searchers])[:-1]
        return _RouterState(tuple(searchers),
                            tuple(self._client_factory(s) for s in searchers),
                            offsets, tuple(paths) if paths else None,
                            generation)

    # -- snapshot accessors (each reads self._state exactly once) --------
    @property
    def searchers(self) -> Tuple[IndexSearcher, ...]:
        return self._state.searchers

    @property
    def clients(self) -> Tuple[ShardClient, ...]:
        return self._state.clients

    @property
    def offsets(self) -> np.ndarray:
        return self._state.offsets

    @property
    def paths(self) -> Optional[Tuple[str, ...]]:
        return self._state.paths

    @property
    def generation(self) -> int:
        """The manifest generation this router currently serves."""
        return self._state.generation

    @property
    def n(self) -> int:
        return self._state.n

    @property
    def n_shards(self) -> int:
        return len(self._state.searchers)

    @property
    def spec(self):
        return self._state.searchers[0].index.spec

    # -- fan-out ---------------------------------------------------------
    def _use_mesh(self, dispatch: Optional[str]) -> bool:
        d = dispatch or self._dispatch_default
        if d not in ("auto", "sequential", "mesh"):
            raise ValueError(f"dispatch must be 'auto', 'sequential' or "
                             f"'mesh', got {d!r}")
        if d == "mesh" and self._data_mesh is None:
            raise ValueError("dispatch='mesh' needs a mesh (pass mesh= to "
                             "ShardedIndex / load_sharded)")
        return d == "mesh" or (d == "auto" and self._data_mesh is not None)

    def search(self, queries: Union[PackedSignatures, jax.Array, np.ndarray],
               topk: int = 10, *, mode: str = "exact",
               query_sizes: Optional[np.ndarray] = None,
               dispatch: Optional[str] = None,
               on_shard_failure: Optional[str] = None,
               tracer: Optional[Tracer] = None) -> SearchResult:
        """Global top-k: fan out to every shard, merge.

        With the mesh dispatcher, both modes run as ONE ``shard_map``
        computation per call: ``mode="exact"`` scans each device's
        placed shards with an in-jit running top-k; ``mode="lsh"``
        probes every shard's bucket tables on the host (band keys
        computed once per batch), then gathers + reranks each device's
        padded candidate rows in one collective kernel dispatch.  In
        both cases the per-device ``(best_s, best_i)`` partials are
        gathered across the mesh and ``merge_topk`` folds them --
        bit-identical (ids AND scores) to the sequential fan-out and to
        a single-index search.  The shard set is snapshotted ONCE here,
        so a concurrent ``append``/``refresh`` never tears this call's
        view.

        ``on_shard_failure`` (default: the constructor's) picks what a
        shard-client exception costs on the **sequential** fan-out:
        ``"fail"`` re-raises it (whole query dies, the seed behavior);
        ``"partial"`` serves the surviving shards -- the merge is then
        bit-identical to a healthy router over just those shards, and
        the result carries ``coverage`` (surviving docs / total docs)
        and the failed shard indices.  The mesh dispatcher is a single
        in-process collective with no per-shard failure domain, so the
        policy only applies to the client fan-out.

        The fan-out's phases (``Tracer.phase``) go to ``tracer``
        (default: the process-wide ``get_tracer()``); a ``SearchServer``
        passes its own, which replays them into each request's tree.
        """
        state = self._state
        tracer = tracer if tracer is not None else get_tracer()
        policy = on_shard_failure or self.on_shard_failure
        if policy not in ("fail", "partial"):
            raise ValueError(f"on_shard_failure must be 'fail' or "
                             f"'partial', got {policy!r}")
        qwords = _query_words(queries, state.searchers[0].index.spec)
        use_mesh = self._use_mesh(dispatch)
        if mode == "exact" and use_mesh:
            return self._mesh_exact(state, qwords, topk, query_sizes, tracer)
        qkeys = None
        if mode == "lsh":
            idx0 = state.searchers[0].index
            qkeys = np.asarray(band_keys_packed(qwords, idx0.spec,
                                                idx0.banding))
            if use_mesh:
                return self._mesh_lsh(state, qwords, topk, query_sizes,
                                      qkeys, tracer)
        if policy == "fail":
            with tracer.phase("shard_dispatch",
                              args={"mode": mode,
                                    "shards": len(state.clients)}):
                pending = [c.dispatch(qwords, topk, mode=mode,
                                      query_sizes=query_sizes, qkeys=qkeys)
                           for c in state.clients]
            with tracer.phase("harvest"):
                results = [p() for p in pending]
            with tracer.phase("merge"):
                return merge_topk(results, state.offsets, topk)
        return self._fanout_partial(state, qwords, topk, mode, query_sizes,
                                    qkeys, tracer)

    def _fanout_partial(self, state: "_RouterState", qwords, topk: int,
                        mode: str, query_sizes, qkeys,
                        tracer) -> SearchResult:
        """Sequential fan-out that survives shard-client failures.

        A shard can fail at dispatch time (e.g. its breaker is open) or
        at harvest time (transport fault past the retry budget); either
        way the shard drops out and the survivors merge **with their
        original offsets**, which is exactly what a healthy router
        restricted to the surviving shards would return
        (``merge_topk`` is a pure function of (score, global id)).
        """
        failed: dict = {}
        with tracer.phase("shard_dispatch",
                          args={"mode": mode,
                                "shards": len(state.clients)}):
            pending = []
            for si, c in enumerate(state.clients):
                try:
                    pending.append(c.dispatch(qwords, topk, mode=mode,
                                              query_sizes=query_sizes,
                                              qkeys=qkeys))
                except Exception as e:
                    pending.append(None)
                    failed[si] = e
        with tracer.phase("harvest"):
            results = []
            for si, p in enumerate(pending):
                if p is None:
                    results.append(None)
                    continue
                try:
                    results.append(p())
                except Exception as e:
                    results.append(None)
                    failed[si] = e
        if failed:
            for si in failed:
                self._m_shard_failures.labels(shard=str(si)).inc()
            if len(failed) == len(state.clients):
                raise RuntimeError(
                    f"all {len(state.clients)} shards failed "
                    f"(last: {failed[max(failed)]!r})") from failed[max(failed)]
            self._m_partial.inc()
        with tracer.phase("merge"):
            if not failed:
                return merge_topk(results, state.offsets, topk)
            keep = [si for si in range(len(results)) if si not in failed]
            merged = merge_topk([results[si] for si in keep],
                                state.offsets[keep], topk)
        n_total = state.n
        n_live = int(sum(state.searchers[si].index.n for si in keep))
        return dataclasses.replace(merged, coverage=n_live / n_total,
                                   failed_shards=tuple(sorted(failed)))

    # -- the shard_map exact dispatcher ----------------------------------
    def _mesh_layout(self, state: _RouterState) -> dict:
        """The stacked, mesh-sharded device corpus for one router state
        (built once per state, under a lock; dies with the state).

        Devices get their round-robin shards concatenated (ascending
        shard order, so rows stay in ascending global-id order per
        device -- the in-jit ``lax.top_k`` tie rule then resolves to the
        lowest global id within each device), each shard padded to a
        scan-block multiple and each device padded to the widest
        device's row count; padding rows carry id -1 and score -inf.
        """
        cached = state.cache.get("mesh_exact")
        if cached is not None:
            return cached
        with self._mesh_build_lock:
            cached = state.cache.get("mesh_exact")
            if cached is not None:
                return cached
            s0 = state.searchers[0]
            meta0 = s0.index.meta
            devs = data_axis_devices(self._data_mesh)
            D = len(devs)
            block = max(s.corpus_block for s in state.searchers)
            heights = [((s.index.n + block - 1) // block) * block
                       for s in state.searchers]
            per_dev = [[s for s in range(len(state.searchers))
                        if s % D == d] for d in range(D)]
            rows = max((sum(heights[s] for s in group) or block)
                       for group in per_dev)
            words = meta0.words
            has_sizes = (s0.index.set_sizes is not None and meta0.s > 0)
            corpus = np.zeros((D * rows, words), np.uint32)
            ids = np.full(D * rows, -1, np.int32)
            doc_sizes = np.zeros(D * rows, np.uint32) if has_sizes else None
            shard_pos = [None] * len(state.searchers)
            for d, group in enumerate(per_dev):
                pos = d * rows
                for s in group:
                    idx = state.searchers[s].index
                    n_s = idx.n
                    shard_pos[s] = (d, pos - d * rows)
                    corpus[pos:pos + n_s] = idx.words_host
                    ids[pos:pos + n_s] = (int(state.offsets[s])
                                          + np.arange(n_s, dtype=np.int32))
                    if has_sizes:
                        doc_sizes[pos:pos + n_s] = np.asarray(idx.set_sizes)
                    pos += heights[s]
            row_sh = NamedSharding(self._data_mesh, P("data"))
            layout = {
                "corpus": jax.device_put(
                    corpus, NamedSharding(self._data_mesh, P("data", None))),
                "ids": jax.device_put(ids, row_sh),
                "doc_sizes": (jax.device_put(doc_sizes, row_sh)
                              if has_sizes else None),
                # shard -> (device, row offset within the device block):
                # the LSH fan-out maps shard-local candidate ids to this
                # device-local row space
                "shard_pos": tuple(shard_pos),
                "block": block, "D": D,
                "D_univ": (1 << meta0.s) if has_sizes else 0,
                "statics": dict(k=meta0.k, b=meta0.b,
                                code_bits=meta0.code_bits,
                                sentinel=meta0.sentinel, backend=s0._be,
                                blk_q=s0._kb["blk_q"], blk_n=s0._kb["blk_n"]),
            }
            state.cache["mesh_exact"] = layout
            return layout

    def _mesh_scan_fn(self, *, block: int, kk: int, has_sizes: bool,
                      D_univ: int, statics: dict):
        """One jitted shard_map per (block, topk, statics) -- cached so
        repeated flushes reuse the compiled executable."""
        key = (block, kk, has_sizes, D_univ,
               tuple(sorted(statics.items())))
        fn = self._mesh_fns.get(key)
        if fn is not None:
            return fn
        mesh = self._data_mesh

        if has_sizes:
            def body(qwords, corpus, ids, q_sizes, doc_sizes):
                bs, bi = exact_scan_ids(qwords, corpus, ids, q_sizes,
                                        doc_sizes, block=block, topk=kk,
                                        D=D_univ, **statics)
                return bs[None], bi[None]
            in_specs = (P(None, None), P("data", None), P("data"),
                        P(None), P("data"))
        else:
            def body(qwords, corpus, ids):
                bs, bi = exact_scan_ids(qwords, corpus, ids, None, None,
                                        block=block, topk=kk, D=0,
                                        **statics)
                return bs[None], bi[None]
            in_specs = (P(None, None), P("data", None), P("data"))

        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                               out_specs=(P("data"), P("data")),
                               check_vma=False))
        self._mesh_fns[key] = fn
        return fn

    @staticmethod
    def _check_mesh_resident(state: _RouterState) -> None:
        streamed = [s for s in state.searchers if s.streamed]
        if streamed:
            raise ValueError(
                "mesh dispatch holds the stacked corpus device-resident "
                "and cannot honor max_device_bytes "
                f"({len(streamed)} shard(s) would stream); use "
                "dispatch='sequential' for out-of-core shards")

    def _mesh_exact(self, state: _RouterState, qwords, topk: int,
                    query_sizes, tracer: Tracer) -> SearchResult:
        if topk < 1:
            raise ValueError(f"topk must be >= 1, got {topk}")
        self._check_mesh_resident(state)
        layout = self._mesh_layout(state)
        has_sizes = layout["doc_sizes"] is not None
        if has_sizes and query_sizes is None:
            raise ValueError("index stores set sizes; pass query_sizes "
                             "to search() for the exact Theorem-1 rerank")
        kk = min(topk, state.n)
        fn = self._mesh_scan_fn(block=layout["block"], kk=kk,
                                has_sizes=has_sizes,
                                D_univ=layout["D_univ"],
                                statics=layout["statics"])
        with tracer.phase("mesh_dispatch", args={"mode": "exact",
                                                 "devices": layout["D"]}):
            if has_sizes:
                out_s, out_i = fn(qwords, layout["corpus"], layout["ids"],
                                  jnp.asarray(query_sizes),
                                  layout["doc_sizes"])
            else:
                out_s, out_i = fn(qwords, layout["corpus"], layout["ids"])
            # the jit output IS the cross-device gather: (D, Q, kk) partials
            self.mesh_exact_dispatches += 1
            out_s, out_i = np.asarray(out_s), np.asarray(out_i)
        per_dev = [SearchResult(out_i[d].astype(np.int64), out_s[d])
                   for d in range(layout["D"])]
        with tracer.phase("merge"):
            return merge_topk(per_dev, [0] * layout["D"], topk)

    # -- the shard_map LSH dispatcher ------------------------------------
    def _mesh_lsh_fn(self, *, kk: int, has_sizes: bool, D_univ: int,
                     statics: dict):
        """One jitted shard_map per (topk, statics) -- candidate widths
        are shape-polymorphic under the cached callable (jax retraces
        per padded width; widths are bucketed to powers of two so
        repeated flushes reuse compiled executables)."""
        key = ("lsh", kk, has_sizes, D_univ, tuple(sorted(statics.items())))
        fn = self._mesh_fns.get(key)
        if fn is not None:
            return fn
        mesh = self._data_mesh

        if has_sizes:
            def body(qwords, corpus, ids, cand, member, q_sizes, doc_sizes):
                ts, ti = lsh_rerank_ids(qwords, corpus, ids, cand[0],
                                        member[0], q_sizes, doc_sizes,
                                        topk=kk, D=D_univ, **statics)
                return ts[None], ti[None]
            in_specs = (P(None, None), P("data", None), P("data"),
                        P("data", None), P("data", None, None),
                        P(None), P("data"))
        else:
            def body(qwords, corpus, ids, cand, member):
                ts, ti = lsh_rerank_ids(qwords, corpus, ids, cand[0],
                                        member[0], None, None,
                                        topk=kk, D=0, **statics)
                return ts[None], ti[None]
            in_specs = (P(None, None), P("data", None), P("data"),
                        P("data", None), P("data", None, None))

        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                               out_specs=(P("data"), P("data")),
                               check_vma=False))
        self._mesh_fns[key] = fn
        return fn

    def _mesh_lsh(self, state: _RouterState, qwords, topk: int,
                  query_sizes, qkeys: np.ndarray,
                  tracer: Tracer) -> SearchResult:
        """LSH candidate-gen + rerank as ONE collective per flush.

        Candidate generation stays a host-side bucket probe per shard
        (the sorted key arrays are mmap'd host state), but the gather +
        kernel rerank + per-device top-k run as a single ``shard_map``
        dispatch over the SAME stacked mesh corpus the exact path uses:
        each device gathers its padded/masked candidate rows (shard-
        local candidate ids mapped through the layout's per-shard row
        offsets, ascending global-id order per device), reranks them in
        one kernel launch, and the gathered per-device partials fold
        through ``merge_topk`` -- bit-identical (ids AND scores) to the
        sequential per-shard fan-out and to a single unsharded index,
        including the Theorem-1 rerank.
        """
        if topk < 1:
            raise ValueError(f"topk must be >= 1, got {topk}")
        self._check_mesh_resident(state)
        layout = self._mesh_layout(state)
        has_sizes = layout["doc_sizes"] is not None
        if has_sizes and query_sizes is None:
            raise ValueError("index stores set sizes; pass query_sizes "
                             "to search() for the exact Theorem-1 rerank")
        D, q = layout["D"], qwords.shape[0]
        cand_cols: List[List[np.ndarray]] = [[] for _ in range(D)]
        mem_cols: List[List[np.ndarray]] = [[] for _ in range(D)]
        n_cand = np.zeros(q, np.int64)
        cand_span = tracer.start_span("candidates",
                                      args={"shards": len(state.searchers)})
        for s, searcher in enumerate(state.searchers):
            d, pos = layout["shard_pos"][s]
            per_q = searcher.index.candidates_batch(qkeys)
            n_cand += np.array([c.size for c in per_q], np.int64)
            if not any(c.size for c in per_q):
                continue
            # shards are disjoint doc ranges, so per-device columns are
            # the concatenation of the per-shard candidate unions --
            # ascending global ids (ascending shard order per device,
            # np.unique-sorted local ids within a shard)
            union = np.unique(np.concatenate(per_q))
            member = np.zeros((q, union.size), bool)
            for i, c in enumerate(per_q):
                member[i, np.searchsorted(union, c)] = True
            cand_cols[d].append((pos + union).astype(np.int32))
            mem_cols[d].append(member)
        tracer.end_span(cand_span)
        widths = [sum(a.size for a in cols) for cols in cand_cols]
        if max(widths) == 0:
            return SearchResult(np.full((q, topk), -1, np.int64),
                                np.full((q, topk), -np.inf, np.float32),
                                n_cand)
        # pad every device to one bucketed width so batch-to-batch
        # candidate counts reuse compiled kernels (same rule as the
        # single-searcher LSH rerank); padding slots point at row 0
        # with membership False -> -inf score, id -1
        c_pad = max(128, 1 << int(max(widths) - 1).bit_length())
        cand = np.zeros((D, c_pad), np.int32)
        member = np.zeros((D, q, c_pad), bool)
        for d in range(D):
            if not cand_cols[d]:
                continue
            cols = np.concatenate(cand_cols[d])
            cand[d, :cols.size] = cols
            member[d, :, :cols.size] = np.concatenate(mem_cols[d], axis=1)
        kk = min(topk, c_pad)
        fn = self._mesh_lsh_fn(kk=kk, has_sizes=has_sizes,
                               D_univ=layout["D_univ"],
                               statics=layout["statics"])
        with tracer.phase("mesh_dispatch", args={"mode": "lsh",
                                                 "devices": D}):
            if has_sizes:
                out_s, out_i = fn(qwords, layout["corpus"], layout["ids"],
                                  cand, member, jnp.asarray(query_sizes),
                                  layout["doc_sizes"])
            else:
                out_s, out_i = fn(qwords, layout["corpus"], layout["ids"],
                                  cand, member)
            self.mesh_lsh_dispatches += 1
            out_s, out_i = np.asarray(out_s), np.asarray(out_i)
        per_dev = [SearchResult(out_i[d].astype(np.int64), out_s[d])
                   for d in range(D)]
        with tracer.phase("merge"):
            merged = merge_topk(per_dev, [0] * D, topk)
        return SearchResult(merged.indices, merged.scores, n_cand)

    # -- live growth -----------------------------------------------------
    def append(self, sig_paths: Sequence[str], *,
               set_sizes: Optional[np.ndarray] = None
               ) -> List[Tuple[str, object]]:
        """Append new documents, concurrently safe with readers.

        Without a ``max_shard_docs`` budget the LAST shard grows
        (``append_index``; earlier shards would shift global ids).  With
        a budget, ``.sig`` files keep extending the last shard while it
        has headroom, then *spill* into NEW tail shards at file
        granularity -- spilled shards are published atomically (temp
        write + ``os.replace``) and become visible only through the
        manifest rewrite at the end, so a crash mid-spill leaves readers
        on the old generation with no torn shard visible.

        Holds the directory lock (two appenders serialize), refreshes
        first (picking up appends other processes landed), rewrites the
        manifest atomically with a bumped generation, and swaps this
        router's state in one assignment; spilled shards pick up their
        round-robin device placement in that swap (other processes: on
        their next ``refresh``).  Existing global ids are unchanged; a
        racing ``search`` sees the pre- or post-append corpus, never a
        mix.  Returns ``[(shard_path, IndexMeta), ...]`` for every
        touched shard.  Requires shard paths (construct via
        ``load_sharded``).
        """
        if not self.paths:
            raise ValueError("append needs shard paths; load this index "
                             "via load_sharded()")
        if not self.manifest_dir:
            raise ValueError("append needs a manifest dir; load this "
                             "index via load_sharded()")
        with sharded_lock(self.manifest_dir):
            self.refresh()
            state = self._state
            meta0 = state.searchers[0].index.meta
            if set_sizes is not None:
                set_sizes = np.ascontiguousarray(set_sizes, np.uint32)
            if meta0.has_set_sizes and set_sizes is None:
                raise ValueError("index stores set sizes; append needs "
                                 "set_sizes for the new documents")
            if not meta0.has_set_sizes and set_sizes is not None:
                raise ValueError("index has no set sizes; cannot add them "
                                 "on append")
            counts = [read_sig_meta(p).n for p in sig_paths]
            if self.max_shard_docs is None:
                groups = [(True, list(range(len(sig_paths))))]
            else:
                groups = _plan_spill(state.searchers[-1].index.n, counts,
                                     self.max_shard_docs)
            paths = list(state.paths)
            searchers = list(state.searchers)
            devices = self._shard_devices(
                len(paths) + sum(1 for ext, _ in groups if not ext))
            touched: List[Tuple[str, object]] = []
            doc0 = 0
            for extend, file_idx in groups:
                files = [sig_paths[i] for i in file_idx]
                n_g = sum(counts[i] for i in file_idx)
                sizes_g = (None if set_sizes is None
                           else set_sizes[doc0:doc0 + n_g])
                if extend:
                    last = paths[-1]
                    meta = append_index(last, files, set_sizes=sizes_g)
                    searchers[-1] = self._make_searcher(
                        load_index(last), len(paths) - 1, devices)
                    touched.append((last, meta))
                else:
                    path = os.path.join(self.manifest_dir,
                                        f"shard_{len(paths):05d}.idx")
                    meta = build_index(files, path, meta0.banding,
                                       set_sizes=sizes_g, s=meta0.s,
                                       atomic=True)
                    searchers.append(self._make_searcher(
                        load_index(path), len(paths), devices))
                    paths.append(path)
                    touched.append((path, meta))
                doc0 += n_g
            write_manifest(self.manifest_dir, paths,
                           [s.index.n for s in searchers],
                           generation=state.generation + 1)
            with self._swap_lock:
                self._state = self._build_state(searchers, paths,
                                                state.generation + 1)
        return touched

    def refresh(self, *, max_attempts: int = 5) -> bool:
        """Re-read the manifest; reload shards another process changed.

        Returns True when the served state moved.  Only shards whose
        (name, doc count) differ from the current snapshot are reloaded
        (a spilled shard is a NEW name, so it loads here and gets its
        round-robin device placement -- the stable-by-position rule
        guarantees no existing shard moves); unchanged shards keep their
        device-resident corpus.  If a writer replaces a shard file
        between the manifest read and the shard load (the loaded count
        disagrees with the manifest), the whole read retries -- the
        swapped-in state is always internally consistent.
        """
        if not self.manifest_dir:
            return False
        for _ in range(max_attempts):
            manifest = read_manifest(self.manifest_dir)
            state = self._state
            if manifest["generation"] == state.generation:
                return False
            names = manifest["shards"]
            counts = [int(b) - int(a) for a, b in
                      zip(manifest["offsets"],
                          list(manifest["offsets"][1:]) + [manifest["n"]])]
            paths = [os.path.join(self.manifest_dir, nm) for nm in names]
            devices = self._shard_devices(len(paths))
            old = {}
            if state.paths:
                old = {(p, s.index.n): s
                       for p, s in zip(state.paths, state.searchers)}
            searchers = []
            consistent = True
            for i, (path, count) in enumerate(zip(paths, counts)):
                keep = old.get((path, count))
                if keep is not None:
                    searchers.append(keep)
                    continue
                loaded = self._make_searcher(load_index(path), i, devices)
                if loaded.index.n != count:
                    consistent = False     # raced a writer; re-read
                    break
                searchers.append(loaded)
            if consistent:
                with self._swap_lock:
                    if manifest["generation"] <= self._state.generation:
                        return False   # a concurrent append moved further
                    self._state = self._build_state(searchers, paths,
                                                    manifest["generation"])
                return True
        raise RuntimeError(
            f"refresh({self.manifest_dir}) kept racing a writer: shard "
            f"doc counts never matched the manifest after "
            f"{max_attempts} attempts")


def load_sharded(shard_dir: str, *, mmap: bool = True,
                 mesh: Optional[Mesh] = None, dispatch: str = "auto",
                 max_shard_docs: Optional[int] = None,
                 **searcher_kwargs) -> ShardedIndex:
    """Load a ``build_sharded`` output directory into a ``ShardedIndex``.

    ``searcher_kwargs`` flow to every per-shard ``IndexSearcher``
    (``backend=``, ``corpus_block=``, ``max_device_bytes=``, ...);
    ``mesh``/``dispatch``/``max_shard_docs`` configure the device-
    parallel fan-out and the append spill budget.
    """
    manifest = read_manifest(shard_dir)
    man_path = os.path.join(shard_dir, MANIFEST_NAME)
    paths = [os.path.join(shard_dir, name) for name in manifest["shards"]]
    indexes = [load_index(p, mmap=mmap) for p in paths]
    sharded = ShardedIndex(indexes, paths=paths, manifest_dir=shard_dir,
                           generation=manifest["generation"], mesh=mesh,
                           dispatch=dispatch, max_shard_docs=max_shard_docs,
                           **searcher_kwargs)
    if sharded.n != manifest["n"]:
        raise ValueError(f"{man_path}: manifest n={manifest['n']} != "
                         f"loaded {sharded.n}")
    return sharded
