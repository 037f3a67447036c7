#!/usr/bin/env python3
"""Smoke run of the main path on a TPU, through the public entry points.

    python chip_smoke.py [--seed N]        # one chip: every phase below
    python chip_smoke.py --chips 4         # four chips: mesh search only

One chip runs, in one process:

  1. device check: JAX sees a TPU and the kernels resolve to the
     compiled ``tpu`` backend;
  2. preprocessing (paper §3) at webspam width (D = 2^24, ~3728 nonzeros
     per example): ``preprocess_shards`` -> packed ``.sig`` shards for
     2U and 4U k-pass minhash and OPH (rotation and sentinel), k=512,
     b=8; sampled rows compared bit for bit with the ``ref`` backend;
  3. online learning (paper §6): ``OnlineTrainer`` over a
     ``SignatureCache`` for 3 epochs -- epoch 0 hashes, later epochs
     replay the packed cache;
  4. search: a 2^20-document corpus (k=512, b=8) -> ``build_index`` ->
     ``IndexSearcher`` served by ``SearchServer`` in exact and LSH mode;
     sampled answers compared with a ``ref``-backend searcher, and the
     server must report no errors, partial results, sheds or worker
     restarts.

``--chips 4`` runs only the sharded search: the same corpus split into 4
shards on a ``("data",)`` mesh (one ``shard_map`` per search) against
the sequential fan-out, which must agree bit for bit, with each shard on
its own chip.

Every array is generated from ``--seed``.  Lines starting with
``[smoke]`` are progress output (shapes, times, match counts), not
metrics.  The last line is one JSON object, ``{"ok": true, "device":
{...}}``.  A failed check, a host where JAX finds no TPU, or a directory
without the rest of the repository exits non-zero with no such line.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(RuntimeError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, **fields) -> None:
    print(f"[smoke] {phase}: "
          + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Shapes of one smoke run (the defaults are the chip run's)."""

    prep_rows: int = 16384      # webspam-width examples hashed per scheme
    prep_nnz: int = 3728        # webspam's mean nonzeros per example
    s: int = 24                 # D = 2^24 (webspam's 16.6M features)
    k: int = 512
    b: int = 8
    chunk: int = 4096           # examples per kernel call
    sample_rows: int = 256      # rows compared with the ref backend
    epochs: int = 3
    corpus_docs: int = 1 << 20  # 512 MiB of packed words at k=512, b=8
    corpus_nnz: int = 128
    requests: int = 256         # served per search mode
    ref_queries: int = 32       # of those, compared with the ref backend
    topk: int = 10
    shards: int = 4             # --chips 4 only


def _generate(name: str, rows: int, nnz: int, s: int, seed: int,
              prototypes: int = 8):
    """(train, test) with ``rows`` training examples (generate() keeps
    80% of what it makes for training), drawn around ``prototypes``
    prototype sets per class."""
    from repro.data.synthetic import DatasetSpec, generate
    spec = DatasetSpec(name, n=rows * 5 // 4, D=1 << s, avg_nnz=nnz,
                       n_prototypes=prototypes, seed=seed)
    return generate(spec)


def _read_words(sig_dir: str):
    import numpy as np
    from repro.data.sigshard import read_sig_shard
    paths = sorted(glob.glob(os.path.join(sig_dir, "*.sig")))
    return np.concatenate([read_sig_shard(p)[0] for p in paths]), paths


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def device_check(chips: int) -> dict:
    import jax
    from repro.kernels import resolve_backend
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    say("device", **info)
    check(info["platform"] == "tpu", f"no TPU: JAX runs on {info}")
    check(len(devs) >= chips, f"need {chips} chips, JAX sees {len(devs)}")
    check(resolve_backend().name == "tpu",
          f"kernels resolve to {resolve_backend().name!r}, not 'tpu'")
    return info


def preprocess_phase(sz: Sizes, seed: int, work: str):
    """Paper §3: raw shards -> packed .sig shards for four schemes."""
    import jax
    import numpy as np
    from repro.data.pipeline import batch_to_shards
    from repro.data.preprocess import preprocess_shards
    from repro.data.sparse import SparseBatch
    from repro.kernels import (SignatureEngine, batch_signatures,
                               resolve_backend)
    from repro.train.online import make_family

    t0 = time.perf_counter()
    train, test = _generate("webspam_width", sz.prep_rows, sz.prep_nnz,
                            sz.s, seed)
    raw = batch_to_shards(train, os.path.join(work, "raw_webspam"), 4)
    say("preprocess.data", rows=train.n, padded_nnz=train.indices.shape[1],
        D=f"2^{sz.s}", gen_s=f"{time.perf_counter() - t0:.1f}")
    rng = np.random.default_rng(seed)
    rows = np.sort(rng.choice(train.n, sz.sample_rows, replace=False))
    sample = SparseBatch(np.asarray(train.indices)[rows],
                         np.asarray(train.mask)[rows], None)
    key = jax.random.PRNGKey(seed)
    schemes = [("minhash-2u", "2u", None), ("minhash-4u", "4u", None),
               ("oph-rotation", "oph", "rotation"),
               ("oph-sentinel", "oph", "sentinel")]
    for name, scheme, densify in schemes:
        fam = make_family(key, scheme, sz.k, sz.s, densify=densify)
        backend = SignatureEngine(fam, b=sz.b, packed=True).backend
        check(backend == resolve_backend().name,
              f"{name}: engine backend {backend!r}")
        out = os.path.join(work, f"sig_{name}")
        t0 = time.perf_counter()
        stats = preprocess_shards(raw, out, fam, b=sz.b, chunk_size=sz.chunk)
        wall = time.perf_counter() - t0
        words, _ = _read_words(out)
        check(words.shape[0] == train.n,
              f"{name}: {words.shape[0]} signature rows for {train.n}")
        want = np.concatenate([
            np.asarray(batch_signatures(
                SparseBatch(sample.indices[i:i + 128],
                            sample.mask[i:i + 128], None),
                fam, b=sz.b, packed=True, backend="ref").data)
            for i in range(0, rows.size, 128)])
        same = int(np.sum(np.all(words[rows] == want, axis=1)))
        say(f"preprocess.{name}", backend=backend, shape=words.shape,
            wall_s=f"{wall:.2f}", kernel_s=f"{stats.kernel_s:.2f}",
            rows_equal_ref=f"{same}/{rows.size}")
        check(same == rows.size,
              f"{name}: {rows.size - same} sampled rows differ from ref")
    return raw, test


def learning_phase(sz: Sizes, seed: int, raw, test) -> None:
    """Paper §6: hash once, replay the packed cache for later epochs."""
    import jax
    import numpy as np
    from repro.data.pipeline import SignatureStream
    from repro.kernels import batch_signatures, resolve_backend
    from repro.models.linear import make_loss_fn
    from repro.train import OnlineTrainer, SignatureCache, make_family

    fam = make_family(jax.random.PRNGKey(seed + 1), "oph", sz.k, sz.s,
                      densify="rotation")
    stream = SignatureStream(raw, fam, b=sz.b, chunk_size=sz.chunk,
                             packed=True)
    check(stream.engine.backend == resolve_backend().name,
          f"stream backend {stream.engine.backend!r}")
    sig_te = batch_signatures(test, fam, b=sz.b)
    y_te = np.asarray(test.labels)
    with SignatureCache(stream) as cache, \
            OnlineTrainer(k=sz.k, b=sz.b, kind="svm", average=True,
                          lam=1e-4, eta0=0.5, batch_size=256) as trainer:
        _, stats, evals = trainer.fit(
            cache, sz.epochs,
            eval_fn=lambda tr: tr.evaluate(sig_te, test.labels))
        loss = float(make_loss_fn("svm", "hashed", sz.b, 1.0)(
            trainer.model, sig_te, test.labels))
    for es, acc in zip(stats, evals):
        say(f"learning.epoch{es.epoch}", source=es.source,
            examples=es.examples, bytes_read=es.bytes_read,
            load_s=f"{es.load_s:.2f}", train_s=f"{es.train_s:.2f}",
            test_acc=f"{acc:.4f}")
    chance = max(np.mean(y_te > 0), np.mean(y_te < 0))
    say("learning", test_loss=f"{loss:.4f}", chance=f"{chance:.4f}")
    check(len(stats) == sz.epochs, f"{len(stats)} epochs ran")
    check(np.isfinite(loss), f"test loss {loss}")
    check(evals[-1] > chance, f"test accuracy {evals[-1]} <= chance {chance}")
    check(stats[0].source == "hash", f"epoch 0 came from {stats[0].source}")
    for es in stats[1:]:
        check(es.source == "cache", f"epoch {es.epoch} came from {es.source}")
        check(es.bytes_read < stats[0].bytes_read,
              f"replay epoch {es.epoch} read {es.bytes_read} B, "
              f"epoch 0 read {stats[0].bytes_read} B")


def build_corpus(sz: Sizes, seed: int, work: str):
    """Corpus signatures (.sig shards) and its banding config.

    A near-duplicate corpus: clusters of about 16 documents around one
    prototype each, hashed with 2U k-pass minhash, so an LSH query has a
    handful of candidates as in deduplication.
    """
    import jax
    from repro.data.pipeline import batch_to_shards
    from repro.data.preprocess import preprocess_shards
    from repro.index import choose_band_config
    from repro.train.online import make_family

    t0 = time.perf_counter()
    corpus, _ = _generate("search_corpus", sz.corpus_docs, sz.corpus_nnz,
                          sz.s, seed + 2, prototypes=sz.corpus_docs // 32)
    raw = batch_to_shards(corpus, os.path.join(work, "raw_corpus"), 8)
    gen_s = time.perf_counter() - t0
    fam = make_family(jax.random.PRNGKey(seed + 3), "2u", sz.k, sz.s)
    t0 = time.perf_counter()
    sig_dir = os.path.join(work, "sig_corpus")
    # 16 .sig files: build_sharded splits at file granularity
    preprocess_shards(raw, sig_dir, fam, b=sz.b,
                      chunk_size=sz.corpus_docs // 16)
    words, paths = _read_words(sig_dir)
    say("search.corpus", docs=words.shape[0], words=words.shape[1],
        packed_MiB=f"{words.nbytes / 2**20:.0f}", gen_s=f"{gen_s:.1f}",
        hash_s=f"{time.perf_counter() - t0:.1f}")
    check(words.shape[0] == sz.corpus_docs,
          f"{words.shape[0]} corpus rows, want {sz.corpus_docs}")
    return words, paths, choose_band_config(sz.k, sz.b)


def _same(a, b) -> bool:
    import numpy as np
    return (np.array_equal(a.indices, b.indices)
            and np.array_equal(a.scores, b.scores))


def search_phase(sz: Sizes, seed: int, work: str) -> None:
    """One index on one chip, served by SearchServer, against ref."""
    import numpy as np
    from repro.index import IndexSearcher, build_index, load_index
    from repro.kernels import resolve_backend
    from repro.launch.server import SearchServer

    words, paths, cfg = build_corpus(sz, seed, work)
    idx_path = os.path.join(work, "corpus.idx")
    t0 = time.perf_counter()
    build_index(paths, idx_path, cfg)
    index = load_index(idx_path)
    say("search.index", n=index.n, bands=cfg.n_bands,
        rows_per_band=cfg.rows_per_band,
        build_s=f"{time.perf_counter() - t0:.1f}")
    searcher = IndexSearcher(index)
    backend = resolve_backend(searcher.backend).name
    ref = IndexSearcher(index, backend="ref")
    rng = np.random.default_rng(seed + 4)
    qids = rng.choice(index.n, sz.requests, replace=False)
    queries = words[qids]
    for mode in ("exact", "lsh"):
        t0 = time.perf_counter()
        with SearchServer(searcher, max_batch=32, max_delay_s=0.005,
                          topk=sz.topk, mode=mode) as server:
            handles = [server.submit(q) for q in queries]
            results = [h.result(timeout=600.0) for h in handles]
        wall = time.perf_counter() - t0
        snap = server.stats.snapshot()
        want = ref.search(queries[:sz.ref_queries], sz.topk, mode=mode)
        got_i = np.concatenate([r.indices for r in
                                results[:sz.ref_queries]])
        got_s = np.concatenate([r.scores for r in results[:sz.ref_queries]])
        equal = int(np.sum(np.all(got_i == want.indices, axis=1)
                           & np.all(got_s == want.scores, axis=1)))
        self_hits = int(np.sum(np.concatenate(
            [r.indices[:, 0] for r in results]) == qids))
        cands = [float(r.n_candidates[0]) for r in results
                 if r.n_candidates is not None]
        say(f"search.{mode}", backend=backend, requests=snap["requests"],
            batches=snap["batches"], wall_s=f"{wall:.2f}",
            errors=snap["errors"], partial=snap["partial"],
            shed=snap["shed"], worker_restarts=snap["worker_restarts"],
            answers_equal_ref=f"{equal}/{sz.ref_queries}",
            top1_is_query=f"{self_hits}/{sz.requests}",
            mean_candidates=f"{np.mean(cands):.1f}" if cands else "all")
        for field in ("errors", "partial", "shed", "worker_restarts"):
            check(snap[field] == 0, f"{mode}: server {field}={snap[field]}")
        check(snap["requests"] == sz.requests,
              f"{mode}: {snap['requests']} of {sz.requests} served")
        check(equal == sz.ref_queries,
              f"{mode}: {sz.ref_queries - equal} answers differ from ref")


def mesh_search_phase(sz: Sizes, seed: int, work: str) -> None:
    """Four shards on a ("data",) mesh vs the sequential fan-out."""
    import numpy as np
    from repro.index import build_sharded, load_sharded
    from repro.launch.mesh import make_debug_mesh

    words, paths, cfg = build_corpus(sz, seed, work)
    shard_dir = os.path.join(work, "corpus_shards")
    build_sharded(paths, shard_dir, cfg, n_shards=sz.shards)
    mesh = make_debug_mesh(sz.shards, axes=("data",))
    router = load_sharded(shard_dir, mesh=mesh, dispatch="mesh")
    seq = load_sharded(shard_dir, dispatch="sequential")
    devices = [s.device for s in router.searchers]
    say("mesh.placement", shards=len(devices),
        devices=",".join(str(d.id) for d in devices))
    check(len(devices) == sz.shards
          and len({d.id for d in devices}) == sz.shards,
          f"shards placed on devices {devices}")
    rng = np.random.default_rng(seed + 4)
    queries = words[rng.choice(len(words), sz.ref_queries, replace=False)]
    for mode in ("exact", "lsh"):
        before = (router.mesh_exact_dispatches, router.mesh_lsh_dispatches)
        t0 = time.perf_counter()
        got = router.search(queries, sz.topk, mode=mode)
        wall = time.perf_counter() - t0
        want = seq.search(queries, sz.topk, mode=mode)
        took = (router.mesh_exact_dispatches - before[0]
                + router.mesh_lsh_dispatches - before[1])
        say(f"mesh.{mode}", queries=len(queries), mesh_dispatches=took,
            wall_s=f"{wall:.2f}", equal_to_sequential=_same(got, want))
        check(took == 1, f"{mode}: {took} shard_map dispatches, want 1")
        check(_same(got, want), f"{mode}: mesh != sequential fan-out")


# ---------------------------------------------------------------------------

def run(sz: Sizes, seed: int, chips: int) -> dict:
    info = device_check(chips)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        if chips == 1:
            raw, test = preprocess_phase(sz, seed, work)
            learning_phase(sz, seed, raw, test)
            search_phase(sz, seed, work)
        else:
            mesh_search_phase(sz, seed, work)
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: run from a checkout of the repository ({e})",
              file=sys.stderr)
        return 2
    say("compile_cache", dir=enable_compile_cache())
    t0 = time.perf_counter()
    try:
        info = run(Sizes(), args.seed, args.chips)
    except Exception:                # report every failure, print no result
        traceback.print_exc()
        return 1
    say("done", wall_s=f"{time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
