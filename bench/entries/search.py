"""Entry ``search``: an open loop of lookups into ``SearchServer`` over one
``IndexSearcher`` (exact scan or LSH candidates plus rerank).

Set-up makes the packed corpus from the seed, builds the index through
the program's own pieces (``SigIndex``; for LSH, band keys on the
device and ``build_band_tables``), makes the query mix and its arrival schedule, and drives
every shape the window can meet through the searcher's batched admission
(``submit`` then ``flush``): each batch size from 1 to ``max_batch`` and,
for LSH, each candidate-width bucket that a batch of that size can reach.
The window is one client thread that submits each request when it is
due and never waits for answers; a request's latency runs from its due
time to its answer.  The check compares a sample of the answered
requests, drawn from the seed, with the plain reference.
"""

from __future__ import annotations

import time

import numpy as np

from bench import gen


def _build_index(cfg, words, mode):
    """The index over ``words``; its band tables only where LSH reads
    them (the exact scan never does, so an exact cell skips the build)."""
    import jax.numpy as jnp
    from repro.index import band_keys_packed, choose_band_config
    from repro.index.builder import IndexMeta, SigIndex, build_band_tables
    from repro.kernels.pack import PackSpec
    n, k, b = words.shape[0], int(cfg["k"]), int(cfg["b"])
    band = choose_band_config(k, b)
    want = (int(cfg["n_bands"]), int(cfg["rows_per_band"]))
    if (band.n_bands, band.rows_per_band) != want:
        raise ValueError(f"choose_band_config({k}, {b}) gives "
                         f"{band.n_bands}x{band.rows_per_band}, the "
                         f"configuration states {want[0]}x{want[1]}")
    spec = PackSpec(k, b)
    if mode == "lsh":
        step = 1 << 16
        keys = np.concatenate([
            np.asarray(band_keys_packed(jnp.asarray(words[lo:lo + step]),
                                        spec, band))
            for lo in range(0, n, step)])
        offs, skeys, boffs, post = build_band_tables(keys)
    else:
        offs = np.zeros(band.n_bands + 1, np.int64)
        skeys, boffs = np.zeros(0, np.int64), np.zeros(1, np.int64)
        post = np.zeros(0, np.uint32)
    meta = IndexMeta(n=n, k=k, b=b, code_bits=b, words=words.shape[1],
                     sentinel=False, has_set_sizes=False,
                     n_bands=band.n_bands, rows_per_band=band.rows_per_band,
                     n_keys=int(skeys.size))
    return SigIndex(meta=meta, labels=np.zeros(n, np.float32),
                    set_sizes=None, band_offsets=offs, keys=skeys,
                    bucket_offsets=boffs, postings=post, words_host=words)


def _warm_batches(cfg, traffic, index, words, seed):
    """Batches that reach every (batch size, candidate bucket) the window
    can: exact search needs each size once; LSH needs each power-of-two
    candidate width that unions of that many queries can fill."""
    from repro.index import band_keys_packed
    max_batch = int(traffic["max_batch"])
    rng = gen.host_rng(seed, gen.STREAM_SAMPLE + 100)
    fresh = gen.rcv1x_queries(cfg, words, np.zeros(max_batch, np.int64),
                              np.zeros(max_batch, np.float32), seed + 1)
    if traffic["mode"] == "exact":
        return [fresh[:q] for q in range(1, max_batch + 1)]
    cluster = int(cfg["assumed"]["cluster_size"])
    docs = rng.choice(index.n // cluster, 4 * max_batch,
                      replace=False) * cluster
    keys = np.asarray(band_keys_packed(words[docs], index.spec,
                                       index.banding))
    sizes = np.array([c.size for c in index.candidates_batch(keys)])
    order = np.argsort(-sizes)
    docs, total = docs[order], np.cumsum(sizes[order])
    out = []
    for q in range(1, max_batch + 1):
        out.append(fresh[:q])                       # no candidate at all
        for width in (128, 256, 512, 1024):
            lo = 0 if width == 128 else width // 2
            fits = [j for j in range(1, q + 1) if lo < total[j - 1] <= width]
            if fits:
                j = fits[0]
                out.append(np.concatenate([words[docs[:j]], fresh[:q - j]]))
    return out


def setup(cfg, traffic, seed, seconds, ctx):
    from repro.index import IndexSearcher
    from repro.kernels import resolve_backend
    words = gen.rcv1x_corpus(cfg, seed)
    index = _build_index(cfg, words, traffic["mode"])
    searcher = IndexSearcher(index)
    ctx.expect_backend(resolve_backend(searcher.backend).name)
    due, src, r = gen.query_schedule(cfg, traffic, seed, seconds)
    queries = gen.rcv1x_queries(cfg, words, src, r, seed)
    topk, mode = int(traffic["topk"]), traffic["mode"]
    for batch in _warm_batches(cfg, traffic, index, words, seed):
        for row in batch:
            searcher.submit(row)
        searcher.flush(topk, mode=mode)
    return {"cfg": cfg, "traffic": traffic, "seed": seed, "ctx": ctx,
            "words": words, "searcher": searcher, "due": due,
            "queries": queries, "dup": r > 0}


def window(st, seconds):
    from repro.launch.server import SearchServer
    from repro.obs.trace import Tracer
    tr = st["traffic"]
    tracer = (Tracer(enabled=True, jax_annotations=True)
              if st["ctx"].trace else None)
    server = SearchServer(st["searcher"], max_batch=int(tr["max_batch"]),
                          max_delay_s=float(tr["max_delay_s"]),
                          topk=int(tr["topk"]), mode=tr["mode"],
                          num_workers=1, tracer=tracer)
    due, queries = st["due"], st["queries"]
    handles, late = [], np.zeros(len(due))
    with server:
        t0 = time.monotonic() + 0.01
        for i, d in enumerate(due):
            wait = t0 + d - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            handles.append(server.submit(queries[i]))
            late[i] = handles[-1].t_submit - (t0 + d)
        close = t0 + due[-1]
        answers, lat = [], np.full(len(due), np.inf)
        for i, h in enumerate(handles):
            try:
                res = h.result(timeout=max(0.0, close + 60.0
                                           - time.monotonic()))
            except TimeoutError:
                answers.append("never")
                continue
            except Exception as e:          # shed or failed: counted
                answers.append(e)
                continue
            answers.append(res)
            lat[i] = h.t_submit + h.latency_s - (t0 + due[i])
        st["elapsed"] = time.monotonic() - t0
    st.update(server_stats=server.stats, answers=answers, latency=lat,
              late=late)


def results(st):
    stats, answers, lat = st["server_stats"], st["answers"], st["latency"]
    served = [a for a in answers if not isinstance(a, (str, Exception))]
    lat_ms = np.sort(lat) * 1e3
    p95 = float(lat_ms[int(np.ceil(0.95 * lat_ms.size)) - 1])
    with stats.lock:
        waits = np.asarray(stats.queue_wait_s, np.float64)
        batches = list(stats.batch_sizes)
    late = st["late"] * 1e3
    cands = [float(a.n_candidates[0]) for a in served
             if a.n_candidates is not None]
    return {
        "end_to_end": {"search_p95_ms": p95},
        "attempted": len(answers), "failed": len(answers) - len(served),
        "complete": not any(isinstance(a, str) for a in answers),
        "notes": {
            "client lateness ms (p50, p99, max)":
                f"{np.percentile(late, 50):.3f}, "
                f"{np.percentile(late, 99):.3f}, {late.max():.3f}",
            "requests, flushes, mean batch":
                f"{len(answers)}, {len(batches)}, "
                f"{np.mean(batches) if batches else 0:.2f}",
        },
        "stats": {
            "window_s": st["elapsed"], "batch_sizes": batches,
            "queue_wait_p95_ms": (float(np.percentile(waits, 95)) * 1e3
                                  if waits.size else None),
            "candidates": cands,
            "n_docs": int(st["cfg"]["n"]),
            "words": int(st["words"].shape[1]),
            "topk": int(st["traffic"]["topk"]),
        },
    }


def release(st):
    st.pop("searcher")


# -- check ------------------------------------------------------------------

def sample(st, n_sample):
    """Answered requests drawn from the seed."""
    ok = np.array([not isinstance(a, (str, Exception))
                   for a in st["answers"]])
    idx = np.flatnonzero(ok)
    rng = gen.host_rng(st["seed"], gen.STREAM_SAMPLE)
    return np.sort(rng.choice(idx, min(n_sample, idx.size), replace=False))


def reference(st, ref, rows, score_dtype="float64"):
    import jax.numpy as jnp
    cfg, tr = st["cfg"], st["traffic"]
    corpus = jnp.asarray(st["words"])
    out = ref.search(corpus, st["queries"][rows], k=int(cfg["k"]),
                     b=int(cfg["b"]), topk=int(tr["topk"]),
                     rows_per_band=(int(cfg["rows_per_band"])
                                    if tr["mode"] == "lsh" else 0),
                     score_dtype=score_dtype)
    del corpus
    return out


def compare(st, got, want):
    (gi, gs, gc), (wi, ws, wc) = got, want
    differ = np.any(gi != wi, axis=1)
    both = np.isfinite(gs) & np.isfinite(ws)
    nums = {"ids_differ": int(differ.sum()),
            "score_gap": float(np.max(np.abs(gs[both] - ws[both]),
                                      initial=0.0))}
    if st["traffic"]["mode"] == "lsh":
        nums["candidates_differ"] = int(np.sum(gc != wc))
    return nums


def check(st, ref, n_sample):
    rows = sample(st, n_sample)
    ans = [st["answers"][i] for i in rows]
    got = (np.concatenate([a.indices for a in ans]),
           np.concatenate([a.scores for a in ans]).astype(np.float64),
           np.array([-1 if a.n_candidates is None else int(a.n_candidates[0])
                     for a in ans]))
    return compare(st, got, reference(st, ref, rows)), {
        "answers_checked": int(rows.size),
        "near_duplicates_checked": int(st["dup"][rows].sum())}


def control(st, ref, n_sample):
    """The control in the program's place: estimates in bfloat16."""
    rows = sample(st, n_sample)
    return compare(st, reference(st, ref, rows, "bfloat16"),
                   reference(st, ref, rows))
