"""Query paths over a loaded ``.idx``: exact top-k and LSH + rerank.

One searcher, two serving paths sharing the scoring kernel and the
estimator rerank:

  * ``mode="exact"``  -- kernel brute force, fused into ONE traced
    computation per call: a ``jax.lax.fori_loop`` over fixed-size corpus
    blocks of the device-resident packed matrix runs the packed-Hamming
    kernel (``repro.kernels.hamming.packed_match``), debiases the match
    counts into resemblance estimates (Theorem 1) and carries the running
    top-k ``(best_s, best_i)`` *inside the jit* -- one dispatch per
    ``flush()`` instead of one per block, cached on
    (query batch, corpus shape, topk, block) so repeated flushes never
    retrace.  Exact in the sense of "exact over the signatures": the
    b-bit estimator itself is still an estimator.

    Corpora larger than the configured device window
    (``max_device_bytes``) never become device-resident at all: block
    windows stream straight off the mmap'd ``.idx`` packed payload
    through a double-buffered ``device_put`` pipeline
    (``repro.data.pipeline.device_put_iter``), overlapping the H2D copy
    of window i+1 with the fused scan over window i; the top-k carry
    threads across windows, so the result is bit-identical to the
    in-core scan.

  * ``mode="lsh"``    -- candidate generation through the banded bucket
    tables (one batched ``np.searchsorted`` per band over the mmap'd
    sorted key arrays -- ``SigIndex.candidates_batch``), then one kernel
    launch over the batch's candidate union with non-candidates masked
    out, then the same estimator rerank.  With ``lsh_batch`` set, a
    flush is split into sub-batches whose kernel reranks are dispatched
    asynchronously: host candidate generation for sub-batch i+1 overlaps
    the device rerank of sub-batch i, and results are harvested once at
    the end.  The S-curve (``repro.index.banding``) predicts the
    recall/selectivity trade the band config buys.

Batched query admission: ``submit`` queues single queries, ``flush``
runs them as one batch (one traced computation / one candidate union)
and returns per-ticket results -- the serving-launcher entry point
(``repro.launch.serve --index``).

Scores are resemblance estimates: the Li-Owen-Zhang normalization for
sentinel wires (matches / (k - jointly_empty)) and the Theorem-1
debiasing -- exact per-pair constants when the index stores set sizes
and the universe size, the sparse-limit constants (C1 = C2 = 2^-b)
otherwise.  Both debiasings are strictly monotone in the collision
fraction, so rankings do not depend on which one applies.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.estimator import bbit_constants
from repro.data.pipeline import device_put_iter
from repro.index.banding import band_keys_packed
from repro.index.builder import SigIndex
from repro.kernels import PackedSignatures, packed_match
from repro.kernels.hamming import _packed_match_run
from repro.obs.metrics import get_registry
from repro.obs.trace import Tracer, get_tracer

# jit-retrace accounting, read by tests: a second flush with the same
# (query batch, corpus window, topk, block) must be a jit-cache hit.
# Lives in the metrics registry (scrapeable while serving); the mapping
# below names the registry counter behind each legacy TRACE_COUNTS key.
_TRACE_METRICS = {"exact_scan": "index_exact_scan_retraces_total"}


class _TraceCounts:
    """Backward-compat, dict-like view over the registry retrace
    counters -- the old module-global mutable ``TRACE_COUNTS`` dict.

    Reads resolve the live registry counter (so ``set_registry`` /
    ``registry.reset()`` behave); writes only move forward (``+= n``
    increments the counter -- counters cannot go down; zero them via
    ``repro.obs.get_registry().reset()``).
    """

    @staticmethod
    def _family(key: str):
        return get_registry().counter(
            _TRACE_METRICS[key],
            "jit retraces of the fused exact scan (0 on a cache hit)")

    def __getitem__(self, key: str) -> int:
        return int(self._family(key).value)

    def __setitem__(self, key: str, value: int) -> None:
        fam = self._family(key)
        delta = value - fam.value
        if delta < 0:
            raise ValueError(
                f"TRACE_COUNTS[{key!r}] only goes up (registry counter); "
                f"reset via repro.obs.get_registry().reset()")
        fam.inc(delta)

    def __iter__(self):
        return iter(_TRACE_METRICS)

    def __contains__(self, key) -> bool:
        return key in _TRACE_METRICS

    def __len__(self) -> int:
        return len(_TRACE_METRICS)

    def keys(self):
        return _TRACE_METRICS.keys()

    def get(self, key, default=None):
        return self[key] if key in _TRACE_METRICS else default


TRACE_COUNTS = _TraceCounts()


def resemblance_scores(matches: jax.Array, both_empty: Optional[jax.Array],
                       k: int, b: int, *,
                       query_sizes: Optional[jax.Array] = None,
                       doc_sizes: Optional[jax.Array] = None,
                       D: int = 0) -> jax.Array:
    """(Q, N) match counts -> (Q, N) float32 resemblance estimates.

    ``both_empty`` applies the Li-Owen-Zhang denominator for sentinel
    wires; the Theorem-1 debias uses exact (C1, C2) when per-document
    set sizes and the universe size are known, the sparse-limit
    constants 2^-b otherwise.
    """
    matches = matches.astype(jnp.float32)
    if both_empty is not None:
        denom = jnp.maximum(k - both_empty.astype(jnp.float32), 1.0)
        p_hat = matches / denom
    else:
        # constant divisor: multiply by the f32 reciprocal explicitly --
        # XLA strength-reduces constant divisions to reciprocal multiplies
        # inside a jit, and the eager path must stay bit-identical to the
        # fused in-jit scan
        p_hat = matches * jnp.float32(1.0 / k)
    if query_sizes is not None and doc_sizes is not None and D:
        c = bbit_constants(jnp.asarray(query_sizes)[:, None],
                           jnp.asarray(doc_sizes)[None, :], D, b)
        return (p_hat - c.C1) / (1.0 - c.C2)
    c1 = float(2.0 ** -b)
    return (p_hat - jnp.float32(c1)) * jnp.float32(1.0 / (1.0 - c1))


@dataclasses.dataclass(frozen=True)
class StreamPlan:
    """Sizing of the out-of-core exact scan, honoring the device budget.

    ``inflight`` windows can be device-resident at once: the one being
    scanned, up to ``prefetch`` queued in the H2D pipeline, and one held
    by the producer thread while the queue is full -- so
    ``inflight * window_bytes <= max_device_bytes`` whenever the budget
    admits at least one corpus row per window (the hard floor).
    """

    window: int        # rows per streamed window (multiple of block)
    block: int         # scan block height (<= the searcher's corpus_block)
    prefetch: int      # H2D pipeline depth actually used
    row_bytes: int

    @property
    def inflight(self) -> int:
        return self.prefetch + 2

    @property
    def window_bytes(self) -> int:
        return self.window * self.row_bytes

    @property
    def resident_bytes(self) -> int:
        """Worst-case device bytes held by streamed corpus windows."""
        return self.inflight * self.window_bytes


@dataclasses.dataclass
class SearchResult:
    """Top-k per query: global doc ids (-1 past the candidate count) and
    their resemblance estimates (-inf where the id is -1).

    ``coverage`` / ``failed_shards`` carry the router's degraded-mode
    accounting (``on_shard_failure="partial"``): the fraction of corpus
    docs actually searched and the shard indices that failed.  A full
    healthy search leaves them at their defaults.
    """

    indices: np.ndarray          # (Q, topk) int64
    scores: np.ndarray           # (Q, topk) float32
    n_candidates: Optional[np.ndarray] = None    # (Q,) for the LSH path
    coverage: float = 1.0        # docs searched / docs total
    failed_shards: Tuple[int, ...] = ()

    def __len__(self) -> int:
        return self.indices.shape[0]


def _query_words(queries, spec) -> jax.Array:
    if isinstance(queries, PackedSignatures):
        if (queries.k, queries.b, queries.sentinel) != \
                (spec.k, spec.b, spec.sentinel):
            raise ValueError(
                f"query wire (k={queries.k}, b={queries.b}, "
                f"sentinel={queries.sentinel}) != index wire (k={spec.k}, "
                f"b={spec.b}, sentinel={spec.sentinel})")
        return queries.data
    words = jnp.asarray(queries)
    if words.ndim != 2 or words.shape[1] != spec.words:
        raise ValueError(f"raw queries must be (Q, {spec.words}) uint32 "
                         f"packed words, got {words.shape}")
    return words


@jax.jit
def _topk_merge(best_s, best_i, sc, ids):
    """Running top-k merge: [best so far || block scores] -> new best.

    Ties break toward the earlier concatenation position, i.e. toward
    the lowest doc id -- identical to a full-matrix ``lax.top_k``.
    """
    cat_s = jnp.concatenate([best_s, sc], axis=1)
    cat_i = jnp.concatenate(
        [best_i, jnp.broadcast_to(ids[None, :], sc.shape)], axis=1)
    new_s, sel = jax.lax.top_k(cat_s, best_s.shape[1])
    return new_s, jnp.take_along_axis(cat_i, sel, axis=1)


@functools.partial(jax.jit, static_argnames=(
    "n", "block", "k", "b", "code_bits", "sentinel", "backend",
    "blk_q", "blk_n", "D"))
def _exact_scan(qwords, corpus, best_s, best_i, id_start, q_sizes, doc_sizes,
                *, n, block, k, b, code_bits, sentinel, backend,
                blk_q, blk_n, D):
    """ONE traced computation: fori_loop over ``corpus``'s blocks with the
    running top-k carried inside the jit.

    ``corpus`` is a (rows, words) device window whose row count is a
    multiple of ``block``; ``id_start`` (traced) is the window's global
    doc offset, so the same executable serves every window of a streamed
    out-of-core scan.  Rows with global id >= ``n`` are padding and are
    masked to -inf before the merge.
    """
    TRACE_COUNTS["exact_scan"] += 1
    n_blocks = corpus.shape[0] // block

    def body(t, carry):
        best_s, best_i = carry
        cblk = jax.lax.dynamic_slice_in_dim(corpus, t * block, block, axis=0)
        ids = id_start + t * block + jnp.arange(block, dtype=jnp.int32)
        out = _packed_match_run(qwords, cblk, k=k, code_bits=code_bits,
                                sentinel=sentinel, backend=backend,
                                blk_q=blk_q, blk_n=blk_n)
        matches, both_empty = out if sentinel else (out, None)
        if doc_sizes is not None:
            dsz = jnp.take(doc_sizes,
                           jnp.minimum(ids, doc_sizes.shape[0] - 1))
            sc = resemblance_scores(matches, both_empty, k, b,
                                    query_sizes=q_sizes, doc_sizes=dsz, D=D)
        else:
            sc = resemblance_scores(matches, both_empty, k, b)
        sc = jnp.where(ids[None, :] < n, sc, -jnp.inf)
        return _topk_merge(best_s, best_i, sc, ids)

    return jax.lax.fori_loop(0, n_blocks, body, (best_s, best_i))


def exact_scan_ids(qwords, corpus, ids, q_sizes, doc_sizes, *, block, k, b,
                   code_bits, sentinel, backend, blk_q, blk_n, D,
                   topk):
    """Blocked exact scan over a corpus slice carrying *explicit* global
    doc ids (-1 marks a padding row) -- the per-device body of the mesh
    fan-out (``repro.index.router``).

    Unlike ``_exact_scan``, row identity comes from the ``ids`` operand
    rather than ``id_start + position``: the mesh dispatcher stacks the
    shards assigned to one device (round-robin placement interleaves
    non-adjacent global ranges) into a single padded corpus whose rows
    are in ascending-global-id order per device, so the in-jit
    ``lax.top_k`` tie rule still resolves to the lowest global id within
    the device.  Not jitted here: callers trace it inside their own
    ``shard_map``/``jit``.
    """
    q = qwords.shape[0]
    n_blocks = corpus.shape[0] // block
    best_s = jnp.full((q, topk), -jnp.inf, jnp.float32)
    best_i = jnp.full((q, topk), -1, jnp.int32)

    def body(t, carry):
        best_s, best_i = carry
        cblk = jax.lax.dynamic_slice_in_dim(corpus, t * block, block, axis=0)
        idblk = jax.lax.dynamic_slice_in_dim(ids, t * block, block, axis=0)
        out = _packed_match_run(qwords, cblk, k=k, code_bits=code_bits,
                                sentinel=sentinel, backend=backend,
                                blk_q=blk_q, blk_n=blk_n)
        matches, both_empty = out if sentinel else (out, None)
        if doc_sizes is not None:
            dsz = jax.lax.dynamic_slice_in_dim(doc_sizes, t * block, block,
                                               axis=0)
            sc = resemblance_scores(matches, both_empty, k, b,
                                    query_sizes=q_sizes, doc_sizes=dsz, D=D)
        else:
            sc = resemblance_scores(matches, both_empty, k, b)
        sc = jnp.where(idblk[None, :] >= 0, sc, -jnp.inf)
        return _topk_merge(best_s, best_i, sc, idblk)

    return jax.lax.fori_loop(0, n_blocks, body, (best_s, best_i))


def lsh_rerank_ids(qwords, corpus, ids, cand, member, q_sizes, doc_sizes, *,
                   k, b, code_bits, sentinel, backend, blk_q, blk_n,
                   D, topk):
    """Candidate gather + kernel rerank over a corpus slice carrying
    explicit global doc ids -- the per-device body of the mesh LSH
    fan-out (``repro.index.router``).

    ``cand`` is a (C,) padded vector of LOCAL row indices into this
    device's stacked corpus block (ascending global-id order -- the
    ``lax.top_k`` tie rule then resolves to the lowest global id within
    the device, matching the single-index rerank over the ascending-id
    candidate union); ``member`` is the (Q, C) per-query membership
    mask.  Padding slots point at row 0 with ``member`` False, so they
    score -inf and surface id -1.  Scores go through the same kernel +
    estimator pipeline as ``IndexSearcher._lsh_dispatch`` -- elementwise
    identical, so the cross-device ``merge_topk`` fold is bit-identical
    to the per-shard sequential rerank and to a single unsharded index.
    Not jitted here: callers trace it inside their own ``shard_map``.
    """
    cwords = jnp.take(corpus, cand, axis=0)
    out = _packed_match_run(qwords, cwords, k=k, code_bits=code_bits,
                            sentinel=sentinel, backend=backend,
                            blk_q=blk_q, blk_n=blk_n)
    matches, both_empty = out if sentinel else (out, None)
    if doc_sizes is not None:
        dsz = jnp.take(doc_sizes, cand)
        sc = resemblance_scores(matches, both_empty, k, b,
                                query_sizes=q_sizes, doc_sizes=dsz, D=D)
    else:
        sc = resemblance_scores(matches, both_empty, k, b)
    sc = jnp.where(member, sc, -jnp.inf)
    top_s, sel = jax.lax.top_k(sc, topk)
    gids = jnp.take(ids, cand)
    top_i = jnp.take(gids, sel)
    top_i = jnp.where(jnp.isneginf(top_s), jnp.int32(-1), top_i)
    return top_s, top_i


class _BatchedAdmission:
    """The submit/flush batched-admission protocol, shared by
    ``IndexSearcher`` and the sharded router
    (``repro.index.router.ShardedIndex``).

    Hosts queue single queries with ``submit`` and run the whole queue
    as ONE batch with ``flush``.  Requires the host class to provide
    ``spec`` (the wire format) and ``search``.  ``flush`` records its
    spans on ``tracer`` (default: the process-wide ``get_tracer()``).
    """

    def _admission_init(self, tracer: Optional[Tracer] = None) -> None:
        self._pending: List[Tuple[int, jax.Array, Optional[int]]] = []
        self._next_ticket = 0
        self._tracer = tracer

    def submit(self, query: Union[PackedSignatures, jax.Array, np.ndarray],
               *, query_size: Optional[int] = None) -> int:
        """Queue one query (a single packed row); returns its ticket.

        ``query_size`` (the query set's original nonzero count) feeds
        the exact Theorem-1 rerank on indexes that store set sizes.
        """
        qwords = _query_words(
            query if isinstance(query, PackedSignatures)
            else jnp.asarray(query).reshape(1, -1), self.spec)
        if qwords.shape[0] != 1:
            raise ValueError("submit() takes exactly one query row")
        ticket = self._next_ticket
        self._next_ticket += 1
        self._pending.append((ticket, qwords, query_size))
        return ticket

    def flush(self, topk: int = 10, *, mode: str = "exact"
              ) -> Dict[int, SearchResult]:
        """Run all queued queries as ONE batch; per-ticket results."""
        if not self._pending:
            return {}
        tickets = [t for t, _, _ in self._pending]
        batch = jnp.concatenate([w for _, w, _ in self._pending], axis=0)
        sizes = [sz for _, _, sz in self._pending]
        self._pending = []
        if any(sz is not None for sz in sizes):
            if any(sz is None for sz in sizes):
                raise ValueError("either every submitted query carries a "
                                 "query_size or none does")
            qsizes = np.asarray(sizes, np.uint32)
        else:
            qsizes = None
        tracer = self._tracer if self._tracer is not None else get_tracer()
        with tracer.span("search_dispatch",
                         args={"mode": mode, "batch": len(tickets)}):
            res = self.search(batch, topk, mode=mode, query_sizes=qsizes)
        with tracer.span("flush:split"):
            return {t: SearchResult(res.indices[i:i + 1],
                                    res.scores[i:i + 1],
                                    None if res.n_candidates is None
                                    else res.n_candidates[i:i + 1],
                                    coverage=res.coverage,
                                    failed_shards=res.failed_shards)
                    for i, t in enumerate(tickets)}


class IndexSearcher(_BatchedAdmission):
    """Serving front end over one ``SigIndex``.

    ``backend`` picks the kernel execution (SignatureEngine registry);
    ``corpus_block`` is the brute-force block height (fixed, so every
    block reuses one compiled kernel); ``blocks`` overrides the
    TuningTable kernel tile sizes.  ``max_device_bytes`` is the device
    window for the exact path: a packed corpus larger than it is never
    uploaded whole -- block windows stream off the mmap'd payload
    (double-buffered H2D) through the same fused scan.
    ``exact_impl="blockloop"`` selects the pre-fusion per-block host
    loop, kept as the reference for parity tests and the
    ``benchmarks/search_scaling.py`` baseline.  ``lsh_batch`` splits an
    LSH flush into asynchronously-dispatched sub-batches (host candidate
    generation overlaps the previous sub-batch's device rerank).
    """

    def __init__(self, index: SigIndex, *, backend: Optional[str] = None,
                 corpus_block: int = 4096, blocks: Optional[dict] = None,
                 max_device_bytes: Optional[int] = None,
                 exact_impl: str = "fused", lsh_batch: Optional[int] = None,
                 stream_prefetch: int = 2,
                 device: Optional[jax.Device] = None):
        if exact_impl not in ("fused", "blockloop"):
            raise ValueError(f"exact_impl must be 'fused' or 'blockloop', "
                             f"got {exact_impl!r}")
        self.index = index
        self.backend = backend
        # pin this searcher's corpus + kernel work to one device (the
        # sharded router's per-shard placement); None = default device
        self.device = device
        self.blocks = blocks
        self.corpus_block = min(corpus_block, max(index.n, 1))
        self.max_device_bytes = max_device_bytes
        self.exact_impl = exact_impl
        self.lsh_batch = lsh_batch
        self.stream_prefetch = stream_prefetch
        self._admission_init()
        self._corpus_padded = None
        self._doc_sizes_dev = None
        n_pad = ((index.n + self.corpus_block - 1)
                 // self.corpus_block) * self.corpus_block
        self._n_pad = n_pad
        # resolve the kernel execution + tile sizes once; the fused scan,
        # the blockloop reference and the LSH rerank all share them
        from repro.kernels.engine import (HAMMING_BLOCKS,
                                          default_tuning_table,
                                          resolve_backend)
        self._be = resolve_backend(backend).name
        spec = index.spec
        self._kb = dict(blocks or default_tuning_table().lookup(
            self._be, "hamming", spec.k, spec.words) or HAMMING_BLOCKS)

    # -- scoring ---------------------------------------------------------
    @property
    def spec(self):
        return self.index.spec

    @property
    def streamed(self) -> bool:
        """True when the exact path streams windows instead of holding the
        whole packed corpus on device."""
        return (self.max_device_bytes is not None
                and self.index.meta.payload_bytes > self.max_device_bytes)

    def _padded_corpus(self):
        """Device corpus padded to a block multiple (computed once)."""
        if self._corpus_padded is None:
            corpus = self.index.corpus
            if self._n_pad != corpus.shape[0]:
                corpus = jnp.pad(
                    corpus, ((0, self._n_pad - corpus.shape[0]), (0, 0)))
            self._corpus_padded = corpus
        return self._corpus_padded

    def _rerank_operands(self, q_sizes):
        """(query_sizes, padded device doc sizes, D) for the Theorem-1
        rerank; (None, None, 0) on sparse-limit indexes."""
        meta = self.index.meta
        sizes = self.index.set_sizes
        if sizes is None or not meta.s:
            return None, None, 0
        if q_sizes is None:
            raise ValueError("index stores set sizes; pass query_sizes "
                             "to search() for the exact Theorem-1 rerank")
        if self._doc_sizes_dev is None:
            pad = np.zeros(self._n_pad, np.uint32)
            pad[:meta.n] = np.asarray(sizes)
            self._doc_sizes_dev = jnp.asarray(pad)
        return q_sizes, self._doc_sizes_dev, 1 << meta.s

    def _score(self, qwords, cwords, doc_ids, q_sizes):
        """Kernel match counts -> resemblance estimates for given docs."""
        meta = self.index.meta
        out = packed_match(qwords, cwords, self.index.spec,
                           backend=self.backend, blocks=self._kb)
        matches, both_empty = out if meta.sentinel else (out, None)
        sizes = self.index.set_sizes
        if sizes is not None and meta.s:
            if q_sizes is None:
                raise ValueError("index stores set sizes; pass query_sizes "
                                 "to search() for the exact Theorem-1 rerank")
            doc_sizes = jnp.asarray(sizes)[doc_ids]
            return resemblance_scores(matches, both_empty, meta.k, meta.b,
                                      query_sizes=q_sizes,
                                      doc_sizes=doc_sizes, D=1 << meta.s)
        return resemblance_scores(matches, both_empty, meta.k, meta.b)

    # -- exact brute force ----------------------------------------------
    def _scan_statics(self) -> dict:
        meta = self.index.meta
        return dict(n=meta.n, block=self.corpus_block, k=meta.k, b=meta.b,
                    code_bits=meta.code_bits, sentinel=meta.sentinel,
                    backend=self._be, blk_q=self._kb["blk_q"],
                    blk_n=self._kb["blk_n"])

    def _exact_fused(self, qwords, topk: int, q_sizes):
        """One traced computation: the whole blocked scan + top-k merge.
        Returns the harvest closure (host sync deferred)."""
        n, q = self.index.n, qwords.shape[0]
        kk = min(topk, n)
        q_sizes, doc_sizes, D = self._rerank_operands(q_sizes)
        best_s = jnp.full((q, kk), -jnp.inf, jnp.float32)
        best_i = jnp.full((q, kk), -1, jnp.int32)
        best_s, best_i = _exact_scan(
            qwords, self._padded_corpus(), best_s, best_i, jnp.int32(0),
            q_sizes, doc_sizes, D=D, **self._scan_statics())
        return lambda: self._pad_result(best_i, best_s, q, topk, kk)

    def _stream_plan(self) -> StreamPlan:
        """Size the streamed windows so the budget is actually honored.

        ``inflight = prefetch + 2`` windows can be device-resident at
        once (scanned + queued + producer-held), so each window gets
        ``max_device_bytes // inflight`` bytes, floored to a ``block``
        multiple.  When that leaves less than one ``corpus_block`` of
        rows, the pipeline depth shrinks first (bigger windows beat
        deeper prefetch) and then the scan block itself shrinks below
        ``corpus_block`` -- down to the hard floor of one row per
        window, the only case where the stated budget is physically
        unsatisfiable.
        """
        row_bytes = 4 * self.index.meta.words
        budget = self.max_device_bytes or 0

        def plan(prefetch: int) -> StreamPlan:
            rows = budget // ((prefetch + 2) * row_bytes)
            block = min(self.corpus_block, max(1, rows))
            window = max(block, rows // block * block)
            return StreamPlan(window, block, prefetch, row_bytes)

        p = plan(self.stream_prefetch)
        while p.prefetch > 0 and p.block < self.corpus_block:
            p = plan(p.prefetch - 1)
        return p

    def _exact_streamed(self, qwords, topk: int, q_sizes):
        """Out-of-core exact scan: windows of the mmap'd packed payload
        stream through a double-buffered H2D pipeline; the top-k carry
        threads across windows (bit-identical to the in-core scan).
        Returns the harvest closure (host sync deferred)."""
        n, q = self.index.n, qwords.shape[0]
        kk = min(topk, n)
        words = self.index.words_host
        w = self.index.meta.words
        p = self._stream_plan()
        q_sizes, doc_sizes, D = self._rerank_operands(q_sizes)
        statics = self._scan_statics()
        statics["block"] = p.block

        def host_windows():
            for lo in range(0, n, p.window):
                hi = min(lo + p.window, n)
                if hi - lo == p.window:
                    # full window: hand the contiguous mmap slice straight
                    # to device_put (no host memset/copy on the hot path)
                    yield np.int32(lo), words[lo:hi]
                else:
                    buf = np.zeros((p.window, w), np.uint32)
                    buf[:hi - lo] = words[lo:hi]
                    yield np.int32(lo), buf

        best_s = jnp.full((q, kk), -jnp.inf, jnp.float32)
        best_i = jnp.full((q, kk), -1, jnp.int32)
        for lo, win in device_put_iter(host_windows, p.prefetch):
            best_s, best_i = _exact_scan(qwords, win, best_s, best_i, lo,
                                         q_sizes, doc_sizes, D=D, **statics)
            # backpressure: wait out window i's scan before pulling more
            # windows off the pipeline, so dispatched-but-unexecuted scans
            # never pin extra windows beyond the inflight accounting
            best_s.block_until_ready()
        return lambda: self._pad_result(best_i, best_s, q, topk, kk)

    def _exact_blockloop(self, qwords, topk: int, q_sizes):
        """The pre-fusion reference: one kernel dispatch + merge per block,
        driven from a host loop (kept for parity tests / benchmarks)."""
        n, q = self.index.n, qwords.shape[0]
        kk = min(topk, n)
        corpus = self._padded_corpus()
        best_s = jnp.full((q, kk), -jnp.inf, jnp.float32)
        best_i = jnp.full((q, kk), -1, jnp.int32)
        for start in range(0, self._n_pad, self.corpus_block):
            cblk = jax.lax.dynamic_slice_in_dim(corpus, start,
                                                self.corpus_block, axis=0)
            ids = start + jnp.arange(self.corpus_block, dtype=jnp.int32)
            sc = self._score(qwords, cblk, ids, q_sizes)
            sc = jnp.where(ids[None, :] < n, sc, -jnp.inf)
            best_s, best_i = _topk_merge(best_s, best_i, sc, ids)
        return lambda: self._pad_result(best_i, best_s, q, topk, kk)

    def _exact(self, qwords, topk: int, q_sizes):
        if self.streamed and self.device is not None:
            raise ValueError(
                "a device-pinned searcher cannot stream the exact scan "
                "(the H2D pipeline's producer thread places windows on "
                "the default device); raise max_device_bytes or drop the "
                "placement")
        if self.exact_impl == "blockloop":
            if self.streamed:
                raise ValueError(
                    "exact_impl='blockloop' keeps the whole corpus "
                    "device-resident and cannot honor max_device_bytes "
                    f"({self.max_device_bytes} < payload "
                    f"{self.index.meta.payload_bytes}); use the fused "
                    "impl for out-of-core corpora")
            return self._exact_blockloop(qwords, topk, q_sizes)
        if self.streamed:
            return self._exact_streamed(qwords, topk, q_sizes)
        return self._exact_fused(qwords, topk, q_sizes)

    @staticmethod
    def _pad_result(best_i, best_s, q: int, topk: int, kk: int,
                    n_candidates=None) -> SearchResult:
        """Pad to the requested width so every mode returns (Q, topk)."""
        out_i = np.full((q, topk), -1, np.int64)
        out_s = np.full((q, topk), -np.inf, np.float32)
        out_i[:, :kk] = np.asarray(best_i)
        out_s[:, :kk] = np.asarray(best_s)
        return SearchResult(out_i, out_s, n_candidates)

    # -- LSH candidates + rerank ----------------------------------------
    def _lsh_dispatch(self, qwords, topk: int, q_sizes, cand):
        """Dispatch one sub-batch's rerank; returns device handles (no
        host sync -- the caller harvests after the loop)."""
        q = qwords.shape[0]
        n_cand = np.array([c.size for c in cand], np.int64)
        union = (np.unique(np.concatenate(cand)) if any(c.size for c in cand)
                 else np.zeros(0, np.int64))
        if union.size == 0:
            return (np.full((q, topk), -1, np.int64),
                    np.full((q, topk), -np.inf, np.float32), n_cand, topk)
        member = np.zeros((q, union.size), bool)
        for i, c in enumerate(cand):
            member[i, np.searchsorted(union, c)] = True
        # pad the candidate union to a bucketed width so batch-to-batch
        # candidate counts reuse compiled kernels
        c_pad = max(128, 1 << int(union.size - 1).bit_length())
        ids = np.zeros(c_pad, np.int32)
        ids[:union.size] = union
        mem = np.zeros((q, c_pad), bool)
        mem[:, :union.size] = member
        ids_dev = jnp.asarray(ids)
        if self.streamed:
            # out-of-core corpus: gather ONLY the candidate rows off the
            # mmap'd payload instead of uploading the whole matrix
            cwords = jnp.asarray(
                np.ascontiguousarray(self.index.words_host[ids]))
        else:
            cwords = jnp.take(self.index.corpus, ids_dev, axis=0)
        sc = self._score(qwords, cwords, ids_dev, q_sizes)
        sc = jnp.where(jnp.asarray(mem), sc, -jnp.inf)
        kk = min(topk, c_pad)
        top_s, sel = jax.lax.top_k(sc, kk)
        top_i = jnp.take(ids_dev, sel)
        top_i = jnp.where(jnp.isneginf(top_s), -1, top_i)
        return top_i, top_s, n_cand, kk

    def _lsh(self, qwords, topk: int, q_sizes, qkeys=None):
        q = qwords.shape[0]
        if qkeys is None:
            qkeys = np.asarray(band_keys_packed(qwords, self.index.spec,
                                                self.index.banding))
        cand = self.index.candidates_batch(qkeys)
        step = self.lsh_batch or q
        # dispatch every sub-batch before harvesting anything: jax
        # dispatch is asynchronous, so generating candidates/masks for
        # sub-batch i+1 on the host overlaps the device rerank of i
        inflight = []
        for lo in range(0, q, step):
            hi = min(lo + step, q)
            sizes = None if q_sizes is None else q_sizes[lo:hi]
            inflight.append(self._lsh_dispatch(qwords[lo:hi], topk, sizes,
                                               cand[lo:hi]))

        def harvest() -> SearchResult:
            out_i = np.full((q, topk), -1, np.int64)
            out_s = np.full((q, topk), -np.inf, np.float32)
            n_cand = np.zeros(q, np.int64)
            row = 0
            for top_i, top_s, nc, kk in inflight:
                m = nc.shape[0]
                out_i[row:row + m, :kk] = np.asarray(top_i)[:, :topk]
                out_s[row:row + m, :kk] = np.asarray(top_s)[:, :topk]
                n_cand[row:row + m] = nc
                row += m
            return SearchResult(out_i, out_s, n_cand)
        return harvest

    # -- public API ------------------------------------------------------
    def dispatch(self, queries: Union[PackedSignatures, jax.Array,
                                      np.ndarray], topk: int = 10, *,
                 mode: str = "exact",
                 query_sizes: Optional[np.ndarray] = None,
                 _qkeys: Optional[np.ndarray] = None):
        """Dispatch a batch's device work NOW; defer the host sync.

        Returns a zero-arg harvest callable producing the
        ``SearchResult``.  The sharded router dispatches every shard
        before harvesting any, so shard i+1's candidate generation and
        kernel launches overlap shard i's device work.  With ``device``
        set, the dispatch runs under that device (queries are moved
        there, the corpus uploads there, and the kernel + top-k execute
        there), so searchers placed on distinct devices by the router's
        mesh placement genuinely run in parallel.  ``_qkeys``
        (router-internal) passes precomputed band keys so the fan-out
        computes them once per batch, not once per shard.
        """
        if topk < 1:
            raise ValueError(f"topk must be >= 1, got {topk}")
        qwords = _query_words(queries, self.index.spec)
        q_sizes = None if query_sizes is None else jnp.asarray(query_sizes)
        if self.device is not None:
            with jax.default_device(self.device):
                qwords = jax.device_put(qwords, self.device)
                if q_sizes is not None:
                    q_sizes = jax.device_put(q_sizes, self.device)
                return self._dispatch_mode(qwords, topk, mode, q_sizes,
                                           _qkeys)
        return self._dispatch_mode(qwords, topk, mode, q_sizes, _qkeys)

    def _dispatch_mode(self, qwords, topk: int, mode: str, q_sizes, _qkeys):
        if mode == "exact":
            return self._exact(qwords, topk, q_sizes)
        if mode == "lsh":
            return self._lsh(qwords, topk, q_sizes, _qkeys)
        raise ValueError(f"mode must be 'exact' or 'lsh', got {mode!r}")

    def search(self, queries: Union[PackedSignatures, jax.Array,
                                    np.ndarray], topk: int = 10, *,
               mode: str = "exact",
               query_sizes: Optional[np.ndarray] = None) -> SearchResult:
        """Top-k most resembling documents for a batch of packed queries.

        ``queries``: a ``PackedSignatures`` batch or a raw (Q, words)
        uint32 array in the index's wire format.  ``mode``: ``"exact"``
        (fused kernel brute force) or ``"lsh"`` (banded candidates +
        kernel rerank).  ``query_sizes`` feeds the exact Theorem-1 debias
        when the index stores set sizes.
        """
        return self.dispatch(queries, topk, mode=mode,
                             query_sizes=query_sizes)()
