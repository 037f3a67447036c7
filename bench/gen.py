"""Seeded, vectorised generators for every cell's inputs.

Everything a run feeds the program is made here from ``--seed``: raw
sparse rows (made on the device, one jitted call per shard), the hash
coefficients, the packed code-space search corpus, the query mix and
the arrival schedule.  Every seed gets the same *sizes* (row lengths,
request counts, near-duplicate share, resemblance grid, gaps between
arrivals), drawn in another order, so that runs with different seeds do
the same amount of work.

Nothing here imports the program: the benchmark hands the program only
the arrays and files made here.
"""

from __future__ import annotations

import functools
import os

import numpy as np

# streams of one seed: each generator draws from its own
STREAM_ROWS, STREAM_HASH, STREAM_CORPUS, STREAM_QUERIES, STREAM_SAMPLE = \
    range(1, 6)


def seed_state(seed: int, stream: int, words: int = 2) -> np.ndarray:
    """``words`` uint32 drawn from (seed, stream); any seed up to 2**64."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.SeedSequence([seed, stream]).generate_state(
        words, np.uint32)


def host_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def device_key(seed: int, stream: int):
    """A raw ``uint32[2]`` JAX key from (seed, stream)."""
    import jax.numpy as jnp
    return jnp.asarray(seed_state(seed, stream))


def spread(lo: float, hi: float, n: int) -> np.ndarray:
    """``n`` values spread evenly over [lo, hi] (midpoint quantiles)."""
    return lo + (hi - lo) * (np.arange(n) + 0.5) / n


# ---------------------------------------------------------------------------
# webspam: raw sparse rows
# ---------------------------------------------------------------------------

def row_lengths(nnz_min: int, nnz_max: int, n: int) -> np.ndarray:
    """The fixed multiset of per-row nonzero counts of one shard."""
    return np.rint(spread(nnz_min - 0.5, nnz_max + 0.5, n)).astype(np.int32)


def hash_coefficients(seed: int, k: int):
    """2U multiply-shift coefficients (a1, a2 odd), uint32, from the seed."""
    st = seed_state(seed, STREAM_HASH, 2 * k)
    return st[:k].copy(), st[k:] | np.uint32(1)


@functools.lru_cache(maxsize=None)
def _shard_fn(n_rows: int, width: int, D: int, n_protos: int,
              overlap: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def shard(key, shard_id, lengths):
        kp, ks = jax.random.split(key)
        protos = jax.random.randint(kp, (n_protos, width), 0, D, jnp.int32)
        k1, k2, k3, k4 = jax.random.split(jax.random.fold_in(ks, shard_id), 4)
        lens = jax.random.permutation(k1, lengths)
        proto = jax.random.randint(k2, (n_rows,), 0, n_protos)
        keep = jax.random.uniform(k3, (n_rows, width)) < overlap
        fresh = jax.random.randint(k4, (n_rows, width), 0, D, jnp.int32)
        idx = jnp.where(keep, protos[proto], fresh)
        mask = jnp.arange(width)[None, :] < lens[:, None]
        labels = jnp.where(proto < n_protos // 2, -1.0, 1.0)
        return jnp.where(mask, idx, 0), lens, labels.astype(jnp.float32)

    return shard


def webspam_shards(cfg: dict, seed: int, n_shards: int, rows_per_shard: int,
                   out_dir: str):
    """Write ``n_shards`` raw binary shards of webspam-like rows.

    Each row copies each slot from one of ``n_prototypes`` prototype sets
    (half per class, the row's label) with probability ``overlap`` and
    draws it uniformly from the D feature ids otherwise; every shard holds the same
    multiset of row lengths.  Returns ``(paths, nnz_per_shard)``.  The
    shards use the program's binary shard layout (``indices``,
    ``offsets``, ``labels`` in one ``.npz``) with int32 indices.
    """
    import jax
    a = cfg["assumed"]
    width = int(a["nnz_max"])
    fn = _shard_fn(rows_per_shard, width, int(cfg["D"]),
                   int(a["n_prototypes"]), float(a["overlap"]))
    lengths = row_lengths(int(a["nnz_min"]), int(a["nnz_max"]),
                          rows_per_shard)
    key = device_key(seed, STREAM_ROWS)
    os.makedirs(out_dir, exist_ok=True)
    paths, nnz = [], []
    for i in range(n_shards):
        idx, lens, labels = jax.device_get(fn(key, i, lengths))
        mask = np.arange(width)[None, :] < lens[:, None]
        offsets = np.zeros(rows_per_shard + 1, np.int64)
        np.cumsum(lens, out=offsets[1:])
        path = os.path.join(out_dir, f"shard_{i:05d}.npz")
        np.savez(path, indices=idx[mask], offsets=offsets, labels=labels)
        paths.append(path)
        nnz.append(int(offsets[-1]))
    return paths, nnz


def read_rows(path: str, rows: np.ndarray):
    """Rows of one raw shard as a list of int64 index arrays + labels."""
    with np.load(path) as z:
        flat, offsets, labels = z["indices"], z["offsets"], z["labels"]
    return ([flat[offsets[r]:offsets[r + 1]].astype(np.int64) for r in rows],
            labels[rows])


# ---------------------------------------------------------------------------
# rcv1x: packed code-space corpus and the query mix
# ---------------------------------------------------------------------------

def pack_codes(codes, b: int):
    """(n, k) codes < 2^b -> (n, k*b/32) uint32 words, code j at bits
    [j*b, (j+1)*b) of the row's little-endian bitstream (b divides 32)."""
    import jax.numpy as jnp
    per = 32 // b
    n, k = codes.shape
    c = codes.astype(jnp.uint32).reshape(n, k // per, per)
    shifts = jnp.arange(per, dtype=jnp.uint32) * jnp.uint32(b)
    return jnp.sum(c << shifts, axis=-1, dtype=jnp.uint32)


@functools.lru_cache(maxsize=None)
def _corpus_fn(block: int, k: int, b: int, cluster: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def corpus(key, protos, start, r):
        kk, kd = jax.random.split(key)
        keep = jax.random.uniform(kk, (block, k)) < r[:, None]
        redraw = jax.random.randint(kd, (block, k), 0, 1 << b, jnp.int32)
        own = protos[jnp.minimum((start + jnp.arange(block)) // cluster,
                                 protos.shape[0] - 1)]
        return pack_codes(jnp.where(keep, own, redraw), b)

    return corpus


def rcv1x_corpus(cfg: dict, seed: int, block: int = 1 << 16) -> np.ndarray:
    """The packed corpus on the host: (n, k*b/32) uint32.

    Documents come in clusters of ``cluster_size``; a member keeps each
    of its prototype's codes with probability R (its own, from a fixed
    grid over [member_r_min, member_r_max]) and redraws it uniformly
    otherwise -- the b-bit collision law of Theorem 1 in the sparse
    limit, P[equal] = R + (1 - R) 2^-b.  Made on the device in blocks of
    ``block`` rows, so that the device never holds more than one block's
    temporaries.
    """
    import jax
    import jax.numpy as jnp
    a = cfg["assumed"]
    n, k, b = int(cfg["n"]), int(cfg["k"]), int(cfg["b"])
    cluster = int(a["cluster_size"])
    block = min(block, n)
    key = device_key(seed, STREAM_CORPUS)
    protos = jax.random.randint(jax.random.fold_in(key, 0),
                                (-(-n // cluster), k), 0, 1 << b, jnp.int32)
    r = host_rng(seed, STREAM_CORPUS).permutation(
        spread(a["member_r_min"], a["member_r_max"], n)).astype(np.float32)
    fn = _corpus_fn(block, k, b, cluster)
    out = np.empty((n, k * b // 32), np.uint32)
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        rb = np.zeros(block, np.float32)
        rb[:hi - lo] = r[lo:hi]
        words = fn(jax.random.fold_in(key, 1 + lo // block), protos,
                   jnp.int32(lo), jnp.asarray(rb))
        out[lo:hi] = np.asarray(jax.device_get(words))[:hi - lo]
    return out


def query_schedule(cfg: dict, traffic: dict, seed: int, seconds: float):
    """Open-loop schedule: due times and, per request, its source doc and
    resemblance R (R = 0: a fresh signature; src is then unused).

    ``round(rate * seconds)`` requests, a fixed share of them perturbed
    near-duplicates of corpus members with R from a fixed grid over
    [dup_r_min, dup_r_max]; Poisson gaps taken as the midpoint quantiles
    of the exponential law, shuffled, scaled so that they sum to
    ``seconds``; the first request is due at 0.
    """
    rng = host_rng(seed, STREAM_QUERIES)
    m = max(1, int(round(float(traffic["rate_qps"]) * seconds)))
    n_dup = int(round(float(traffic["dup_share"]) * m))
    r = np.zeros(m, np.float32)
    r[:n_dup] = spread(traffic["dup_r_min"], traffic["dup_r_max"], n_dup)
    r = rng.permutation(r)
    src = rng.integers(0, int(cfg["n"]), size=m)
    gaps = rng.permutation(-np.log1p(-(np.arange(m) + 0.5) / m))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (seconds
                                                          / gaps.sum())
    return due, src, r


def rcv1x_queries(cfg: dict, corpus: np.ndarray, src: np.ndarray,
                  r: np.ndarray, seed: int) -> np.ndarray:
    """Packed query rows, (m, words) uint32: each code of the source row
    kept with probability R and redrawn uniformly otherwise."""
    k, b = int(cfg["k"]), int(cfg["b"])
    rng = host_rng(seed, STREAM_QUERIES + 100)
    per = 32 // b
    shifts = (np.arange(per, dtype=np.uint32) * b).astype(np.uint32)
    own = ((corpus[src][:, :, None] >> shifts)
           & np.uint32((1 << b) - 1)).reshape(len(src), -1)[:, :k]
    keep = rng.random((len(src), k)) < r[:, None]
    codes = np.where(keep, own, rng.integers(0, 1 << b, (len(src), k),
                                             dtype=np.uint32))
    c = codes.astype(np.uint32).reshape(len(src), k // per, per)
    return np.bitwise_or.reduce(c << shifts, axis=-1).astype(np.uint32)
