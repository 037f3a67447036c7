"""Entry ``prep``: raw shards -> packed ``.sig`` shards, pass after pass
(paper §3), through ``repro.data.preprocess.preprocess_shards``.

Set-up writes the raw shards and runs one shard through the pipeline to
compile its only shape (every shard holds the same multiset of row
lengths, so every chunk pads to the same width).  The window runs whole
passes over all shards, each into a fresh output directory, until
``seconds`` have passed; the rate counts every row of every pass over
the whole time the passes took.  The check reads sampled rows of the
``.sig`` files back with the benchmark's own parser and compares them,
and their labels, with the plain reference.
"""

from __future__ import annotations

import os
import struct
import time

import numpy as np

from bench import gen, work


def _family(cfg, seed):
    import jax.numpy as jnp
    from repro.core.hashing import Hash2U
    a1, a2 = gen.hash_coefficients(seed, int(cfg["k"]))
    return Hash2U(a1=jnp.asarray(a1), a2=jnp.asarray(a2), s=int(cfg["s"])), \
        (a1, a2)


def setup(cfg, traffic, seed, seconds, ctx):
    from repro.data.preprocess import preprocess_shards
    from repro.kernels import SignatureEngine
    fam, coeffs = _family(cfg, seed)
    backend = SignatureEngine(fam, b=int(cfg["b"]), packed=True).backend
    ctx.expect_backend(backend)
    shards, rows = int(traffic["shards"]), int(traffic["rows_per_shard"])
    raw, nnz = gen.webspam_shards(cfg, seed, shards, rows,
                                  os.path.join(ctx.work, "raw"))
    st = {"cfg": cfg, "traffic": traffic, "seed": seed, "ctx": ctx,
          "fam": fam, "coeffs": coeffs, "raw": raw, "nnz": sum(nnz),
          "rows": shards * rows, "passes": [], "backend": backend}
    preprocess_shards(raw[:1], os.path.join(ctx.work, "warm"), fam,
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
        "stats": {
            "window_s": el, "passes": len(passes),
            "load_s": sum(s.load_s for _, s in passes),
            "kernel_s": sum(s.kernel_s for _, s in passes),
            "store_s": sum(s.store_s for _, s in passes),
            "hash_evals": work.hash_evaluations(
                len(passes) * st["nnz"], int(st["cfg"]["k"])),
        },
    }


def release(st):
    st.pop("fam", None)


# -- check ------------------------------------------------------------------

SIG_HEADER = 64


def read_sig_rows(path: str, rows: np.ndarray):
    """Rows of a ``.sig`` file, parsed by the benchmark: header
    (b"RSIG", version, n, k, b, code_bits, words, flags), float32 labels,
    then the uint32 payload at the next 64-byte boundary."""
    with open(path, "rb") as f:
        head = f.read(SIG_HEADER)
        if head[:4] != b"RSIG":
            raise ValueError(f"{path}: not a .sig file")
        _, n, _, _, _, words, _ = struct.unpack("<7I", head[4:32])
        labels = np.frombuffer(f.read(4 * n), np.float32)
    off = -(-(SIG_HEADER + 4 * n) // 64) * 64
    payload = np.memmap(path, np.uint32, "r", offset=off, shape=(n, words))
    return np.array(payload[rows]), labels[rows]


def sample(st, n_sample):
    """(pass, global row) pairs drawn from the seed: distinct rows, each
    from one of the window's passes."""
    rng = gen.host_rng(st["seed"], gen.STREAM_SAMPLE)
    rows = np.sort(rng.choice(st["rows"], min(n_sample, st["rows"]),
                              replace=False))
    return rng.integers(0, len(st["passes"]), size=rows.size), rows


def program_rows(st, passes, rows):
    chunk = int(st["traffic"]["chunk_size"])
    words, labels = [], []
    for p, r in zip(passes, rows):
        path = os.path.join(st["passes"][p][0], f"sig_{r // chunk:05d}.sig")
        w, lab = read_sig_rows(path, np.array([r % chunk]))
        words.append(w[0])
        labels.append(lab[0])
    return np.stack(words), np.asarray(labels)


def reference_rows(st, ref, rows, narrow=False):
    per = int(st["traffic"]["rows_per_shard"])
    cfg = st["cfg"]
    a1, a2 = st["coeffs"]
    words, labels = [], []
    for shard in np.unique(rows // per):
        sel = rows[rows // per == shard] % per
        sets, lab = gen.read_rows(st["raw"][shard], sel)
        words.append(ref.minhash_packed(sets, a1, a2, int(cfg["s"]),
                                        int(cfg["b"]), narrow=narrow))
        labels.append(lab)
    return np.concatenate(words), np.concatenate(labels)


def compare(got, want):
    (gw, gl), (ww, wl) = got, want
    differ = np.any(gw != ww, axis=1) | (gl != wl)
    return {"rows_differ": int(differ.sum())}


def check(st, ref, n_sample):
    passes, rows = sample(st, n_sample)
    got = program_rows(st, passes, rows)
    nums = compare(got, reference_rows(st, ref, rows))
    return nums, {"rows_checked": int(rows.size)}


def control(st, ref, n_sample):
    """The control in the program's place: 16-bit hashing arithmetic."""
    _, rows = sample(st, n_sample)
    return compare(reference_rows(st, ref, rows, narrow=True),
                   reference_rows(st, ref, rows))
