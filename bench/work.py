"""Work and bytes that the algorithms must do, counted from shapes.

These are the benchmark's own counts, independent of how the program
pads, tiles or fuses: the numerators of ``minhash_ghash_per_s`` and of
``hamming_roofline``.
"""

from __future__ import annotations

from typing import Iterable


def hash_evaluations(real_nonzeros: int, k: int) -> int:
    """k-pass minwise hashing evaluates k hash functions at every real
    (unpadded) nonzero of every row."""
    if real_nonzeros < 0 or k < 1:
        raise ValueError(f"need real_nonzeros >= 0 and k >= 1, got "
                         f"{real_nonzeros}, {k}")
    return int(real_nonzeros) * int(k)


def exact_flush_bytes(n_docs: int, words: int, n_queries: int,
                      topk: int) -> int:
    """The least HBM bytes one exact-scan flush must move: every packed
    corpus row read once, the query rows read once, and the top-k ids
    (int32) and scores (float32) written once."""
    if min(n_docs, words, n_queries, topk) < 1:
        raise ValueError("every size must be >= 1")
    return 4 * words * (n_docs + n_queries) + 8 * n_queries * topk


def exact_window_bytes(n_docs: int, words: int, batch_sizes: Iterable[int],
                       topk: int) -> int:
    """``exact_flush_bytes`` summed over the flushes of a window."""
    return sum(exact_flush_bytes(n_docs, words, q, topk)
               for q in batch_sizes)
