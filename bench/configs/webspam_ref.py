"""Plain reference for the webspam configuration, in NumPy on the host.

  * ``minhash_packed``: 2U k-pass minwise hashing (paper Eq. 10,
    high-bits variant), the lowest b bits of each minimum, packed as a
    little-endian bitstream with code j at bits [j*b, (j+1)*b);
  * ``svm_steps``: the mini-batch SGD step of a linear SVM on the Eq. 5
    expansion (features scaled by 1/sqrt(k)), Bottou's rate
    eta0 / (1 + lam * eta0 * t) (paper §6, Eq. 11-12).

Both are written from the paper's equations and import nothing of the
program.  ``narrow=True`` and ``dtype=bfloat16`` are the controls: the
same computation in the next narrower precision (16-bit hashing
arithmetic; bfloat16 weights and sums), which must fail the comparison.
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np


def minhash_codes(row: np.ndarray, a1: np.ndarray, a2: np.ndarray, s: int,
                  b: int, narrow: bool = False) -> np.ndarray:
    """(k,) b-bit codes of one set ``row`` (int ids)."""
    if narrow:                                    # control: 16-bit lanes
        if not 16 < s < 32:
            raise ValueError(f"the 16-bit control needs 16 < s < 32, got {s}")
        t = row.astype(np.uint16)[:, None]
        v = a1.astype(np.uint16)[None, :] + a2.astype(np.uint16)[None, :] * t
        h = v >> np.uint16(32 - s)                # the low bits of the 2U hash
        return (h.min(axis=0) & np.uint16((1 << b) - 1)).astype(np.uint32)
    t = row.astype(np.uint32)[:, None]
    v = a1[None, :] + a2[None, :] * t             # wraps mod 2^32
    h = v >> np.uint32(32 - s)
    return h.min(axis=0) & np.uint32((1 << b) - 1)


def pack(codes: np.ndarray, b: int) -> np.ndarray:
    """(m, k) codes -> (m, ceil(k*b/32)) uint32 little-endian bitstream."""
    m, k = codes.shape
    words = np.zeros((m, (k * b + 31) // 32), np.uint64)
    for j in range(k):
        w, sh = divmod(j * b, 32)
        c = codes[:, j].astype(np.uint64)
        words[:, w] |= (c << np.uint64(sh)) & np.uint64(0xFFFFFFFF)
        if sh + b > 32:
            words[:, w + 1] |= c >> np.uint64(32 - sh)
    return words.astype(np.uint32)


def unpack(words: np.ndarray, k: int, b: int) -> np.ndarray:
    """Inverse of ``pack``: (m, words) uint32 -> (m, k) int64 codes."""
    w64 = words.astype(np.uint64)
    out = np.zeros((words.shape[0], k), np.int64)
    for j in range(k):
        w, sh = divmod(j * b, 32)
        v = w64[:, w] >> np.uint64(sh)
        if sh + b > 32:
            v |= w64[:, w + 1] << np.uint64(32 - sh)
        out[:, j] = (v & np.uint64((1 << b) - 1)).astype(np.int64)
    return out


def minhash_packed(rows: Sequence[np.ndarray], a1: np.ndarray,
                   a2: np.ndarray, s: int, b: int,
                   narrow: bool = False) -> np.ndarray:
    codes = np.stack([minhash_codes(r, a1, a2, s, b, narrow) for r in rows])
    return pack(codes, b)


def svm_loss(w: np.ndarray, bias: float, words: np.ndarray, y: np.ndarray,
             *, k: int, b: int, lam: float, dtype=np.float64) -> float:
    """lam/2 ||w||^2 + mean hinge(y * margin) of one packed batch."""
    tok = unpack(words, k, b) + (np.arange(k) << b)[None, :]
    w = w.astype(dtype)
    m = (w[tok].sum(axis=1, dtype=dtype) * dtype(1.0 / np.sqrt(k))
         + dtype(bias))
    hinge = np.maximum(dtype(0.0), dtype(1.0) - y.astype(dtype) * m)
    return float(dtype(lam / 2) * (w * w).sum(dtype=dtype)
                 + hinge.mean(dtype=dtype))


def svm_steps(batches: List[np.ndarray], labels: List[np.ndarray], *,
              k: int, b: int, lam: float, eta0: float, dtype=np.float64,
              fault: str = ""):
    """Run len(batches) SGD steps from zero weights.

    ``batches`` hold packed (n, words) rows.  Returns the loss before
    each step on that step's batch, and the (w, bias) after each step.
    ``fault`` plants a fault for reading limits: ``"half"`` takes each
    step over the first half of its batch only.
    """
    dim = k << b
    w = np.zeros(dim, dtype)
    bias = dtype(0.0)
    scale = dtype(1.0 / np.sqrt(k))
    cols = np.arange(k) << b
    losses, states = [], []
    for t, (words, y) in enumerate(zip(batches, labels)):
        if fault == "half":
            words, y = words[:len(y) // 2], y[:len(y) // 2]
        y = y.astype(dtype)
        tok = unpack(words, k, b) + cols[None, :]
        losses.append(svm_loss(w, bias, words, y, k=k, b=b, lam=lam,
                               dtype=dtype))
        m = w[tok].sum(axis=1, dtype=dtype) * scale + bias
        coef = np.where(y * m < 1, -y, dtype(0.0)) / dtype(len(y))
        gw = np.zeros(dim, dtype)
        np.add.at(gw, tok.ravel(),
                  np.repeat(coef * scale, k).astype(dtype))
        eta = dtype(eta0 / (1.0 + lam * eta0 * t))
        w = (w - eta * (dtype(lam) * w + gw)).astype(dtype)
        bias = dtype(bias - eta * coef.sum(dtype=dtype))
        states.append((w.astype(np.float64), float(bias)))
    return losses, states
