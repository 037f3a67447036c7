"""Distributed preprocessing driver: raw shards -> packed ``.sig`` shards.

This is the paper's §3 production pipeline as a service: stream raw sparse
shards through the signature engine in chunks, write bit-packed ``.sig``
signature shards (k*b bits per example -- the Table-2/§6 wire accounting,
sentinel OPH included via (b+1)-bit codes), and account the three phases
(load / kernel / store) exactly as Figures 1-3 split them, on the host
clock and as spans on the caller's thread (``prep.wait``, ``prep.hash``,
``prep.store``; the loader thread adds ``prep.read``, ``prep.pad`` and
``prep.upload``).  Multiple
workers own disjoint shard slices (the ChunkedLoader's straggler
machinery applies); the ``backend`` argument picks execution through the
``repro.kernels.SignatureEngine`` registry (compiled on TPU, interpret on
CPU hosts, jnp fallback on GPU until the triton lowering lands).
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import time
from typing import Optional, Sequence

import jax
import numpy as np

from repro.core.hashing import Hash2U, Hash4U
from repro.core.oph import OPH
from repro.data.pipeline import ChunkedLoader
from repro.data.sigshard import read_sig_shard, write_sig_shard
from repro.kernels import SignatureEngine
from repro.obs.trace import Tracer, get_tracer


@dataclasses.dataclass
class PreprocessStats:
    """Host-clock phase times of one ``preprocess_shards`` call.

    ``load_s`` is the time the caller waited on the loader's prefetch
    queue, not the time spent reading: with prefetch on (the default)
    the loader reads, pads and uploads on its own thread, overlapping
    the kernel and the store.
    ``kernel_s`` runs to the packed words being ready on the device;
    ``store_s`` covers the copy back and the ``.sig`` write.
    ``nonzeros`` counts the real ids hashed and ``slots_hashed`` the index
    slots the kernels hashed for them (segments times segment width,
    padding included), so their ratio is the share of the kernels' work
    that fell on real ids.
    """

    examples: int = 0
    load_s: float = 0.0
    kernel_s: float = 0.0
    store_s: float = 0.0
    bytes_in: int = 0
    bytes_out: int = 0
    nonzeros: int = 0
    slots_hashed: int = 0

    def reduction(self) -> float:
        return self.bytes_in / max(self.bytes_out, 1)


def preprocess_shards(shard_paths: Sequence[str], out_dir: str, family, *,
                      b: int = 8, chunk_size: int = 10_000,
                      n_workers: int = 1, backend: Optional[str] = None,
                      loader_kwargs: Optional[dict] = None,
                      tracer: Optional[Tracer] = None
                      ) -> PreprocessStats:
    """Run the full preprocessing pipeline; returns phase accounting.

    family: Hash2U / Hash4U (k-pass minwise hashing) or an ``OPH`` scheme
    over a 2U/4U base (single-pass one-permutation hashing, ~k x fewer
    hash evaluations).  The permutation path is deliberately not offered
    here -- the paper's Issue 3: no permutation matrices at scale.  All
    densification modes pack: rotation/optimal signatures pack as b-bit
    codes; sentinel signatures pack as (b+1)-bit codes with EMPTY stored
    as 2^b, so even the estimator-facing sentinel scheme ships the
    paper's per-example bit budget.

    Every family and configuration takes one layout: the loader cuts
    each row into fixed-width segments (``repro.data.sparse.
    SegmentedBatch``), the kernels hash the segments, and the engine
    takes each row's minimum over its segments before the b-bit step and
    the pack.  The ``.sig`` rows come out in file order.

    Spans (one each per chunk) go to ``tracer``, by default the
    process-wide ``get_tracer()``, and to the loader's too.
    """
    if isinstance(family, OPH):
        if not isinstance(family.base, (Hash2U, Hash4U)):
            raise TypeError("production OPH preprocessing uses 2U/4U bases")
    elif not isinstance(family, (Hash2U, Hash4U)):
        raise TypeError("production preprocessing uses 2U/4U/OPH families")
    engine = SignatureEngine(family, b=b, packed=True, backend=backend)
    os.makedirs(out_dir, exist_ok=True)
    stats = PreprocessStats()
    tracer = tracer if tracer is not None else get_tracer()
    loader = ChunkedLoader(shard_paths, chunk_size=chunk_size,
                           n_workers=n_workers, tracer=tracer,
                           **(loader_kwargs or {}))
    chunks = iter(loader)
    for idx in itertools.count():
        t_mark = time.perf_counter()
        with tracer.span("prep.wait"):
            chunk = next(chunks, None)
        t_loaded = time.perf_counter()
        stats.load_s += t_loaded - t_mark
        if chunk is None:
            break
        stats.examples += chunk.n
        stats.bytes_in += chunk.nbytes()

        with tracer.span("prep.hash"):
            packed = engine.packed_signatures(chunk)     # packed on device
            jax.block_until_ready(packed.data)
        t_kernel = time.perf_counter()
        stats.kernel_s += t_kernel - t_loaded

        with tracer.span("prep.store"):
            out_path = os.path.join(out_dir, f"sig_{idx:05d}.sig")
            labels = (np.asarray(chunk.labels) if chunk.labels is not None
                      else np.zeros((chunk.n,), np.float32))
            write_sig_shard(out_path, np.asarray(packed.data), labels,
                            k=packed.k, b=packed.b,
                            code_bits=packed.code_bits,
                            sentinel=packed.sentinel)
            stats.bytes_out += os.path.getsize(out_path)
        stats.store_s += time.perf_counter() - t_kernel
    stats.nonzeros = loader.stats.nonzeros
    stats.slots_hashed = loader.stats.slots
    return stats


def read_signature_shard(path: str):
    """Load a ``.sig`` shard back: (packed uint32 (n, words), labels, k, b).

    Kept for compatibility with the old npz reader's 4-tuple, whose
    documented pairing is ``unpack_signatures(words, b, k)`` -- that is
    only correct for plain b-bit layouts, so this reader refuses
    sentinel/(b+1)-bit shards instead of silently returning words a
    legacy caller would misdecode.  Use
    ``repro.data.sigshard.read_sig_shard`` for full metadata and any
    layout.
    """
    words, labels, meta = read_sig_shard(path)
    if meta.sentinel or meta.code_bits != meta.b:
        raise ValueError(
            f"{path}: {meta.code_bits}-bit"
            f"{' sentinel' if meta.sentinel else ''} codes cannot be "
            "decoded through the legacy (words, labels, k, b) contract; "
            "use repro.data.sigshard.read_sig_shard")
    return words, labels, meta.k, meta.b
