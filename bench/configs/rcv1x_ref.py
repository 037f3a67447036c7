"""Plain reference for the rcv1x search configuration, in ``jax.numpy``.

For each query: the number of equal b-bit codes with every corpus row
(the b-bit collision count), the resemblance estimate of Theorem 1 in
the sparse limit, R = (m/k - 2^-b) / (1 - 2^-b), and the top-k rows by
estimate with ties going to the lowest doc id.  With ``rows_per_band``
set, only LSH candidates compete: rows that agree with the query on all
``rows_per_band`` codes of at least one band (bands are consecutive runs
of codes), and their count is returned too.

Written from the paper and the wire format (code j at bits
[j*b, (j+1)*b) of a row's little-endian bitstream); imports nothing of
the program.  ``score_dtype="bfloat16"`` is the control: estimates
rounded to bfloat16 before ranking, which must fail the comparison.
"""

from __future__ import annotations

import functools

import numpy as np

ID_BITS = 21                      # doc ids < 2^21 in the ranking key
QUERY_BLOCK = 64


def score_table(k: int, b: int, score_dtype: str = "float64"):
    """(estimate for m = 0..k, dense rank of that estimate)."""
    m = np.arange(k + 1, dtype=np.float64)
    c1 = 2.0 ** -b
    s = (m / k - c1) / (1.0 - c1)
    if score_dtype == "bfloat16":
        import ml_dtypes
        s = s.astype(np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)
    elif score_dtype != "float64":
        raise ValueError(f"score_dtype must be float64 or bfloat16, got "
                         f"{score_dtype!r}")
    _, rank = np.unique(s, return_inverse=True)
    return s, rank.astype(np.int32)


@functools.lru_cache(maxsize=None)
def _search_fn(n: int, n_pad: int, k: int, b: int, topk: int,
               rows_per_band: int, block: int):
    import jax
    import jax.numpy as jnp

    per = 32 // b
    mask = jnp.uint32((1 << b) - 1)
    shifts = jnp.arange(per, dtype=jnp.uint32) * jnp.uint32(b)
    low = (1 << ID_BITS) - 1

    def codes(words):
        c = (words[:, :, None] >> shifts) & mask
        return c.reshape(words.shape[0], -1)[:, :k]

    @jax.jit
    def search(corpus, queries, rank):
        qc = codes(queries)
        q = queries.shape[0]

        def body(i, carry):
            best, n_cand = carry
            blk = jax.lax.dynamic_slice_in_dim(corpus, i * block, block)
            eq = qc[:, None, :] == codes(blk)[None, :, :]
            m = jnp.sum(eq, axis=-1, dtype=jnp.int32)
            ids = i * block + jnp.arange(block, dtype=jnp.int32)
            ok = jnp.broadcast_to(ids[None, :] < n, m.shape)
            if rows_per_band:
                bands = eq[..., :k // rows_per_band * rows_per_band]
                bands = bands.reshape(q, block, -1, rows_per_band)
                ok = ok & jnp.any(jnp.all(bands, axis=-1), axis=-1)
            key = jnp.where(ok, (rank[m] << ID_BITS) + (low - ids)[None, :],
                            -1)
            top, _ = jax.lax.top_k(key, topk)
            merged, _ = jax.lax.top_k(jnp.concatenate([best, top], 1), topk)
            return merged, n_cand + jnp.sum(ok, axis=1, dtype=jnp.int32)

        best = jnp.full((q, topk), -1, jnp.int32)
        return jax.lax.fori_loop(0, n_pad // block, body,
                                 (best, jnp.zeros(q, jnp.int32)))

    return search


def search(corpus, queries: np.ndarray, *, k: int, b: int, topk: int,
           rows_per_band: int = 0, score_dtype: str = "float64",
           block: int = 8192):
    """Reference top-k of ``queries`` ((Q, words) uint32) over the
    device-resident packed ``corpus``.

    Returns ``(ids (Q, topk) int64, -1 where fewer rows compete;
    estimates (Q, topk) float64, -inf there; candidate counts (Q,))``.
    """
    import jax
    import jax.numpy as jnp
    n = int(corpus.shape[0])
    if n >= 1 << ID_BITS:
        raise ValueError(f"reference ranks ids below 2^{ID_BITS}, got n={n}")
    block = min(block, -(-n // 8) * 8)
    n_pad = -(-n // block) * block
    if n_pad != n:
        corpus = jnp.pad(corpus, ((0, n_pad - n), (0, 0)))
    scores, rank = score_table(k, b, score_dtype)
    by_rank = np.full(int(rank.max()) + 1, -np.inf)
    by_rank[rank] = scores
    fn = _search_fn(n, n_pad, k, b, topk, rows_per_band, block)
    qn = queries.shape[0]
    ids = np.full((qn, topk), -1, np.int64)
    est = np.full((qn, topk), -np.inf)
    cand = np.zeros(qn, np.int64)
    rank_dev = jnp.asarray(rank)
    for lo in range(0, qn, QUERY_BLOCK):
        chunk = np.zeros((QUERY_BLOCK, queries.shape[1]), np.uint32)
        hi = min(lo + QUERY_BLOCK, qn)
        chunk[:hi - lo] = queries[lo:hi]
        key, nc = jax.device_get(fn(corpus, jnp.asarray(chunk), rank_dev))
        key = key[:hi - lo].astype(np.int64)
        hit = key >= 0
        ids[lo:hi] = np.where(hit, (1 << ID_BITS) - 1 - (key & ((1 << ID_BITS) - 1)), -1)
        est[lo:hi] = np.where(hit, by_rank[np.maximum(key, 0) >> ID_BITS],
                              -np.inf)
        cand[lo:hi] = nc[:hi - lo]
    return ids, est, cand
