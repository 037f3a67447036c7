"""Entry ``prep_ragged``: heavy-tailed raw shards -> packed ``.sig``
shards with 4U hashing, pass after pass (paper §3.4), through
``repro.data.preprocess.preprocess_shards``.

Set-up first imports the program's segmented chunk layout: a program
without it pads every row of a chunk to the chunk's longest, which for
these rows is tens of GB a chunk, so the run stops there (exit 3).  It
then writes the raw shards (row lengths from the configuration's law,
ids made on the device) and runs one whole pass, which compiles every
shape the window uses.  The window, the rate and the check are those of
``prep``; the check's sample always holds the longest and the shortest
row of the shards.
"""

from __future__ import annotations

import functools
import os
import statistics
import time

import numpy as np

from bench import gen, work
from bench.entries.prep import compare, program_rows

P = 2**31 - 1


def row_lengths(a: dict, n: int) -> np.ndarray:
    """The fixed multiset of per-row nonzero counts of one shard: the
    term-count law's midpoint quantiles, m = round(median * exp(sigma *
    z)), at least ``terms_min``, expanded to m + C(m,2) + floor(C(m,3) /
    triples_kept)."""
    z = np.array([statistics.NormalDist().inv_cdf(q)
                  for q in (np.arange(n) + 0.5) / n])
    m = np.maximum(int(a["terms_min"]),
                   np.rint(float(a["terms_median"])
                           * np.exp(float(a["terms_sigma"]) * z))
                   ).astype(np.int64)
    triples = m * (m - 1) * (m - 2) // 6
    return m + m * (m - 1) // 2 + triples // int(a["triples_kept"])


def coefficients(seed: int, k: int) -> np.ndarray:
    """4U coefficients (4, k) uint32, each below p, from the seed."""
    st = gen.seed_state(seed, gen.STREAM_HASH, 4 * k).astype(np.int64)
    return (st % P).astype(np.uint32).reshape(4, k)


@functools.lru_cache(maxsize=None)
def _ids_fn(total: int, D: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def ids(key, shard_id):
        return jax.random.randint(jax.random.fold_in(key, shard_id),
                                  (total,), 0, D, jnp.int32)

    return ids


def ragged_shards(cfg: dict, seed: int, n_shards: int, rows_per_shard: int,
                  out_dir: str):
    """Write ``n_shards`` raw shards of heavy-tailed rows in the
    program's binary shard layout (int32 ``indices``, int64 ``offsets``,
    float32 ``labels`` in one stored ``.npz``).  Every shard holds the
    same multiset of row lengths in a seeded order, ids uniform over
    [0, D) and balanced +1/-1 labels.  Returns ``(paths, lengths)``."""
    import jax
    lengths = row_lengths(cfg["assumed"], rows_per_shard)
    fn = _ids_fn(int(lengths.sum()), int(cfg["D"]))
    key = gen.device_key(seed, gen.STREAM_ROWS)
    rng = gen.host_rng(seed, gen.STREAM_ROWS)
    labels = np.where(np.arange(rows_per_shard) % 2 == 0, 1.0, -1.0
                      ).astype(np.float32)
    os.makedirs(out_dir, exist_ok=True)
    paths, lens = [], []
    for i in range(n_shards):
        own = rng.permutation(lengths)
        offsets = np.zeros(rows_per_shard + 1, np.int64)
        np.cumsum(own, out=offsets[1:])
        ids = np.asarray(jax.device_get(fn(key, i)))
        path = os.path.join(out_dir, f"shard_{i:05d}.npz")
        np.savez(path, indices=ids, offsets=offsets,
                 labels=rng.permutation(labels))
        paths.append(path)
        lens.append(own)
    return paths, np.concatenate(lens)


def setup(cfg, traffic, seed, seconds, ctx):
    from repro.data.sparse import SegmentedBatch  # noqa: F401 (see above)
    import jax.numpy as jnp
    from repro.core.hashing import Hash4U
    from repro.data.preprocess import preprocess_shards
    from repro.kernels import SignatureEngine
    coef = coefficients(seed, int(cfg["k"]))
    fam = Hash4U(a=jnp.asarray(coef), s=int(cfg["s"]))
    backend = SignatureEngine(fam, b=int(cfg["b"]), packed=True).backend
    ctx.expect_backend(backend)
    shards, rows = int(traffic["shards"]), int(traffic["rows_per_shard"])
    raw, lens = ragged_shards(cfg, seed, shards, rows,
                              os.path.join(ctx.work, "raw"))
    st = {"cfg": cfg, "traffic": traffic, "seed": seed, "ctx": ctx,
          "fam": fam, "coef": coef, "raw": raw, "lens": lens,
          "nnz": int(lens.sum()), "rows": shards * rows, "passes": [],
          "backend": backend}
    preprocess_shards(raw, os.path.join(ctx.work, "warm"), fam,
                      b=int(cfg["b"]), chunk_size=int(traffic["chunk_size"]))
    return st


def window(st, seconds):
    from repro.data.preprocess import preprocess_shards
    cfg, traffic, ctx = st["cfg"], st["traffic"], st["ctx"]
    t0 = time.perf_counter()
    while not st["passes"] or time.perf_counter() - t0 < seconds:
        out = os.path.join(ctx.work, f"pass_{len(st['passes']):04d}")
        with ctx.span("bench.prep.pass"):
            stats = preprocess_shards(st["raw"], out, st["fam"],
                                      b=int(cfg["b"]),
                                      chunk_size=int(traffic["chunk_size"]))
        st["passes"].append((out, stats))
    st["elapsed"] = time.perf_counter() - t0


def results(st):
    passes = st["passes"]
    done = sum(s.examples for _, s in passes)
    el = st["elapsed"]
    return {
        "end_to_end": {"prep_rows_per_s": done / el},
        "attempted": len(passes) * st["rows"],
        "failed": len(passes) * st["rows"] - done,
        "notes": {"passes": len(passes),
                  "slots_hashed_per_pass": passes[0][1].slots_hashed},
        "stats": {
            "window_s": el, "passes": len(passes),
            "load_s": sum(s.load_s for _, s in passes),
            "kernel_s": sum(s.kernel_s for _, s in passes),
            "store_s": sum(s.store_s for _, s in passes),
            "nonzeros": len(passes) * st["nnz"],
            "slots_hashed": sum(s.slots_hashed for _, s in passes),
            "hash_evals": work.hash_evaluations(
                len(passes) * st["nnz"], int(st["cfg"]["k"])),
        },
    }


def release(st):
    st.pop("fam", None)


# -- check ------------------------------------------------------------------

def sample(st, n_sample):
    """(pass, global row) pairs drawn from the seed: distinct rows, the
    longest and the shortest of the shards among them, each from one of
    the window's passes."""
    rng = gen.host_rng(st["seed"], gen.STREAM_SAMPLE)
    n = min(n_sample, st["rows"])
    ends = np.unique([int(np.argmax(st["lens"])), int(np.argmin(st["lens"]))])
    rest = np.setdiff1d(np.arange(st["rows"]), ends)
    rows = np.sort(np.concatenate([
        ends, rng.choice(rest, max(0, n - ends.size), replace=False)]))
    return rng.integers(0, len(st["passes"]), size=rows.size), rows


def reference_rows(st, ref, rows, narrow=False):
    per = int(st["traffic"]["rows_per_shard"])
    cfg = st["cfg"]
    words, labels = [], []
    for shard in np.unique(rows // per):
        sel = rows[rows // per == shard] % per
        sets, lab = gen.read_rows(st["raw"][shard], sel)
        words.append(ref.minhash_packed(sets, st["coef"], int(cfg["s"]),
                                        int(cfg["b"]), narrow=narrow))
        labels.append(lab)
    return np.concatenate(words), np.concatenate(labels)


def check(st, ref, n_sample):
    passes, rows = sample(st, n_sample)
    got = program_rows(st, passes, rows)
    nums = compare(got, reference_rows(st, ref, rows))
    return nums, {"rows_checked": int(rows.size),
                  "longest_checked": int(st["lens"].max())}


def control(st, ref, n_sample):
    """The control in the program's place: Horner steps mod 2^32."""
    _, rows = sample(st, n_sample)
    return compare(reference_rows(st, ref, rows, narrow=True),
                   reference_rows(st, ref, rows))
