"""Plain reference for the rcv1x_4u configuration, in NumPy on the host.

``minhash_packed``: 4U k-pass minwise hashing (paper Eq. 9 and §3.4):
for each of the k functions, h(t) = ((a4 t^3 + a3 t^2 + a2 t + a1) mod
p) mod 2^s with p = 2^31 - 1, by Horner's rule in uint64, ``% p`` after
each step (every product is below 2^62); the minimum over the row's ids,
taken in blocks of at most ``BLOCK`` ids; the lowest b bits of each
minimum; packed as a little-endian bitstream with code j at bits
[j*b, (j+1)*b) of the row (b divides 32).

Written from the paper's equations; imports nothing of the program.
``narrow=True`` is the control: the Horner steps wrap mod 2^32 instead
of reducing mod p, which must fail the comparison.
"""

from __future__ import annotations

import concurrent.futures
import os
from typing import Sequence

import numpy as np

P = np.uint64(2**31 - 1)
BLOCK = 16384
_MASK32 = np.uint64(0xFFFFFFFF)


def _block_min(t: np.ndarray, a: np.ndarray, s: int,
               narrow: bool) -> np.ndarray:
    """(k,) minima of the 4U hashes of ids ``t`` under coefficients ``a``
    (4, k)."""
    t = t.astype(np.uint64)[:, None]
    acc = np.empty((t.shape[0], a.shape[1]), np.uint64)
    acc[:] = a[3]
    for coef in (a[2], a[1], a[0]):
        acc *= t
        acc += coef
        if narrow:
            acc &= _MASK32
        else:
            acc %= P
    acc &= np.uint64((1 << s) - 1)
    return acc.min(axis=0)


def minhash_codes(rows: Sequence[np.ndarray], a: np.ndarray, s: int, b: int,
                  narrow: bool = False) -> np.ndarray:
    """(m, k) b-bit codes of the sets ``rows`` (int ids); an empty set
    gets the all-ones code.  Blocks run on threads (NumPy releases the
    interpreter lock in its loops)."""
    a = np.asarray(a, np.uint64)
    k = a.shape[1]
    mins = np.full((len(rows), k), np.iinfo(np.uint64).max, np.uint64)
    jobs = [(i, lo) for i, r in enumerate(rows)
            for lo in range(0, len(r), BLOCK)]
    workers = min(8, os.cpu_count() or 1)
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        done = pool.map(lambda job: _block_min(
            rows[job[0]][job[1]:job[1] + BLOCK], a, s, narrow), jobs)
        for (i, _), m in zip(jobs, done):
            np.minimum(mins[i], m, out=mins[i])
    return (mins & np.uint64((1 << b) - 1)).astype(np.uint32)


def pack(codes: np.ndarray, b: int) -> np.ndarray:
    """(m, k) codes < 2^b -> (m, k*b/32) uint32 words, little-endian."""
    if 32 % b:
        raise ValueError(f"the pack needs b | 32, got b={b}")
    per = 32 // b
    m, k = codes.shape
    c = codes.astype(np.uint32).reshape(m, k // per, per)
    shifts = (np.arange(per, dtype=np.uint32) * np.uint32(b))
    return np.bitwise_or.reduce(c << shifts, axis=-1).astype(np.uint32)


def minhash_packed(rows: Sequence[np.ndarray], a: np.ndarray, s: int,
                   b: int, narrow: bool = False) -> np.ndarray:
    return pack(minhash_codes(rows, a, s, b, narrow), b)
