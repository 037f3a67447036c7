"""Unified SignatureEngine: backend-aware kernel dispatch + packed wire.

This module is the ONE seam between hashing schemes and hardware:

  * ``SignaturePlan``  -- a frozen description of a signature computation:
    scheme x family x (k, s, b, densify) x block sizes x backend x wire
    format.  Everything static; the arrays live in the hash family.
  * ``Backend`` / ``BACKENDS`` -- the execution registry.  ``interpret``
    runs the Pallas kernels in interpret mode (CPU / CI), ``tpu`` runs
    them compiled by Mosaic, ``ref`` forces the pure-jnp oracles.
    ``auto`` resolves per ``jax.default_backend()``.
    This replaces the scattered ``interpret=not _on_tpu()`` flags.
  * ``TuningTable``    -- JSON-persisted block-size table keyed on
    (backend, scheme, k, nnz-bucket), the hook for the ROADMAP TPU tuning
    items; ships with seed defaults in ``tuning_table.json``.
  * ``SignatureEngine`` -- owns padding/tiling and scheme dispatch
    (a registry keyed on (scheme, family) -- no isinstance chains), and
    emits either unpacked (n, k) signatures or the packed wire format.
  * ``PackedSignatures`` -- the wire format itself: k*b bits per example
    ((b+1)-bit codes for sentinel OPH, EMPTY stored as 2^b), produced
    inside the kernel jit so only packed words cross the host boundary.

``repro.kernels.ops`` re-exports the legacy wrappers (``minhash2u``,
``oph2u``, ``batch_signatures``, ...) from here; no module outside
``repro/kernels/`` touches a ``*_pallas`` builder directly.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Dict, Optional, Union

import jax
import jax.numpy as jnp

from repro.core.bbit import pack_codes
from repro.core.hashing import Hash2U, Hash4U, PermutationFamily
from repro.core.oph import OPH, densify_and_bbit, oph_signatures
from repro.data.sparse import SegmentedBatch, SparseBatch
from repro.kernels import ref as kref
from repro.kernels.minhash import minhash2u_pallas, minhash4u_pallas
from repro.kernels.oph import oph2u_pallas, oph4u_pallas
from repro.kernels.pack import (PackSpec, can_pack_in_kernel, encode_sentinel,
                                pack_device, unpack_device)
from repro.kernels.sigbag import sigbag_pallas

# what the engine hashes: padded rows, or fixed-width segments of rows
Batch = Union[SparseBatch, SegmentedBatch]


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Backend:
    """One way to execute the signature kernels.

    ``use_pallas=False`` routes to the pure-jnp oracles in
    ``kernels/ref.py`` (bit-exact by the kernel test suite); otherwise
    ``interpret`` selects Pallas interpret vs compiled mode.
    """

    name: str
    use_pallas: bool
    interpret: bool
    notes: str = ""


BACKENDS: Dict[str, Backend] = {}


def register_backend(backend: Backend) -> Backend:
    BACKENDS[backend.name] = backend
    return backend


register_backend(Backend("interpret", True, True,
                         "Pallas interpret mode (CPU hosts, CI)"))
register_backend(Backend("tpu", True, False,
                         "compiled Pallas TPU (Mosaic)"))
register_backend(Backend("ref", False, False,
                         "pure-jnp oracles (kernels/ref.py)"))


def resolve_backend(name: Optional[str] = None) -> Backend:
    """Map a backend name (or None/"auto") to a registered Backend."""
    if name is None or name == "auto":
        plat = jax.default_backend()
        name = "tpu" if plat == "tpu" else "interpret"
    try:
        return BACKENDS[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r}; registered: "
                         f"{sorted(BACKENDS)}") from None


# ---------------------------------------------------------------------------
# Block-size tuning table
# ---------------------------------------------------------------------------

# blk_n examples ride the 128-lane axis, so it is a multiple of 128 on TPU
MINHASH_BLOCKS = {"blk_n": 128, "blk_t": 128, "blk_k": 128}
OPH_BLOCKS = {"blk_n": 128, "blk_t": 128, "blk_k": 0}   # blk_k 0 = all bins
# retrieval scoring (kernels/hamming.py): query x corpus output tile (the
# word axis is always whole rows); table entries keyed on the word count
HAMMING_BLOCKS = {"blk_q": 8, "blk_n": 128}


def nnz_bucket(nnz: int) -> int:
    """Bucket a padded nnz width to the next power of two (>= 128)."""
    return max(128, 1 << max(0, int(nnz) - 1).bit_length())


class TuningTable:
    """JSON-persisted block-size choices keyed on
    (backend, scheme, k, nnz-bucket).

    The seam for the ROADMAP "tune (BLK_N, BLK_T, BLK_K) on real TPU"
    item: a profiling run records winners with ``record`` + ``save``, and
    every engine on that host picks them up via ``lookup``.  Unknown keys
    fall back to the per-scheme defaults, so the table is always
    optional.  The scheme is part of the key because block conventions
    differ (``blk_k=0`` means "all bins in one lane block" for OPH but
    is invalid for minhash).  The retrieval kernel registers as scheme
    ``"hamming"`` with (blk_q, blk_n, blk_k) blocks keyed on the packed
    word count instead of nnz (``repro.kernels.hamming.packed_match``).
    """

    def __init__(self, entries: Optional[dict] = None,
                 path: Optional[str] = None):
        self.entries = dict(entries or {})
        self.path = path

    @staticmethod
    def key(backend: str, scheme: str, k: int, bucket: int) -> str:
        return f"{backend}/{scheme}/k={k}/nnz<={bucket}"

    def lookup(self, backend: str, scheme: str, k: int,
               nnz: int) -> Optional[dict]:
        return self.entries.get(
            self.key(backend, scheme, k, nnz_bucket(nnz)))

    def record(self, backend: str, scheme: str, k: int, nnz: int,
               blocks: dict) -> None:
        self.entries[self.key(backend, scheme, k, nnz_bucket(nnz))] = \
            dict(blocks)

    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path
        if not path:
            raise ValueError("no path given and table has none")
        with open(path, "w") as f:
            json.dump({"version": 1, "entries": self.entries}, f, indent=2,
                      sort_keys=True)
        self.path = path
        return path

    @staticmethod
    def load(path: str) -> "TuningTable":
        with open(path) as f:
            doc = json.load(f)
        return TuningTable(doc.get("entries", {}), path=path)


_DEFAULT_TABLE: Optional[TuningTable] = None


def default_tuning_table() -> TuningTable:
    """The process-wide table: ``$REPRO_TUNING_TABLE`` if set, else the
    packaged ``tuning_table.json`` seed defaults."""
    global _DEFAULT_TABLE
    if _DEFAULT_TABLE is None:
        path = os.environ.get("REPRO_TUNING_TABLE") or os.path.join(
            os.path.dirname(__file__), "tuning_table.json")
        _DEFAULT_TABLE = (TuningTable.load(path) if os.path.exists(path)
                          else TuningTable())
    return _DEFAULT_TABLE


# ---------------------------------------------------------------------------
# Wire format
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PackedSignatures:
    """Bit-packed signatures: (n, words) uint32, k*code_bits bits/example.

    The device-to-host / disk / SGD wire format.  ``sentinel=True`` means
    (b+1)-bit codes with EMPTY stored as 2^b; ``unpack`` restores the
    exact (n, k) uint32 signatures (EMPTY marker included).  Registered
    as a pytree (data leaf + static meta) so it can cross jit boundaries.
    """

    data: jax.Array          # (n, words) uint32
    k: int
    b: int
    sentinel: bool = False

    @property
    def spec(self) -> PackSpec:
        return PackSpec(self.k, self.b, self.sentinel)

    @property
    def code_bits(self) -> int:
        return self.spec.code_bits

    @property
    def n(self) -> int:
        return self.data.shape[0]

    @property
    def nbytes(self) -> int:
        return int(self.data.size) * 4

    def unpack(self) -> jax.Array:
        """(n, k) uint32 signatures, EMPTY restored for sentinel codes."""
        return unpack_device(self.data, self.spec)

    def __getitem__(self, idx) -> "PackedSignatures":
        return PackedSignatures(self.data[idx], self.k, self.b, self.sentinel)

    def __len__(self) -> int:
        return self.n


jax.tree_util.register_pytree_node(
    PackedSignatures,
    lambda p: ((p.data,), (p.k, p.b, p.sentinel)),
    lambda meta, children: PackedSignatures(children[0], *meta))


# ---------------------------------------------------------------------------
# Plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SignaturePlan:
    """Static description of one signature computation (no arrays)."""

    scheme: str                  # "minhash" | "oph"
    family: str                  # "2u" | "4u" | "perm"
    k: int
    s: int
    b: int = 0
    densify: Optional[str] = None   # OPH only
    variant: str = "high"           # 2U only
    backend: str = "interpret"      # resolved Backend name
    blk_n: int = 128
    blk_t: int = 128
    blk_k: int = 128                # OPH: 0 = all bins in one block
    packed: bool = False

    @property
    def sentinel(self) -> bool:
        return self.densify == "sentinel"

    @property
    def pack_spec(self) -> PackSpec:
        return PackSpec(self.k, self.b, self.sentinel)


def _family_statics(family) -> dict:
    """The single isinstance seam: hash-family object -> plan statics."""
    if isinstance(family, OPH):
        base = family.base
        if isinstance(base, Hash2U):
            fam = "2u"
        elif isinstance(base, Hash4U):
            fam = "4u"
        elif isinstance(base, PermutationFamily):
            fam = "perm"
        else:
            raise TypeError(f"unsupported OPH base {type(base)}")
        return dict(scheme="oph", family=fam, k=family.k, s=family.s,
                    densify=family.densify,
                    variant=getattr(base, "variant", "high"))
    if isinstance(family, Hash2U):
        return dict(scheme="minhash", family="2u", k=family.k, s=family.s,
                    variant=family.variant)
    if isinstance(family, Hash4U):
        return dict(scheme="minhash", family="4u", k=family.k, s=family.s)
    raise TypeError(
        f"SignatureEngine supports 2U/4U/OPH families, got {type(family)}")


# ---------------------------------------------------------------------------
# Padding helpers + jitted runners (the only callers of *_pallas builders)
# ---------------------------------------------------------------------------

def _pad_axis(x, mult, axis, value=0):
    size = x.shape[axis]
    target = ((size + mult - 1) // mult) * mult
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value)


def _rows_from_segments(out, rows, n):
    """Row minima from segment minima: a row's minimum is the least of
    its segments' (``rows`` maps segment to row, non-decreasing; None
    where segment i is row i).  Padding segments hash to the maximum
    and name no row, so they change nothing."""
    if rows is None:
        return out[:n]
    return jax.ops.segment_min(out, rows, num_segments=n,
                               indices_are_sorted=True)


def _bbit(out, b):
    return out & jnp.uint32((1 << b) - 1) if b > 0 else out


@functools.partial(jax.jit, static_argnames=("n", "s", "b", "variant",
                                             "backend", "blk_n", "blk_t",
                                             "blk_k", "packed"))
def _minhash2u_run(indices, counts, a1, a2, rows=None, *, n=None, s, b,
                   variant, backend, blk_n, blk_t, blk_k, packed=False):
    """Signatures of ``n`` rows (default: one per index row) from
    segments ``indices`` with ``counts`` real ids each; with ``rows``
    the kernel emits raw segment minima and the b-bit step and the pack
    follow the reduction to rows, else both may be fused in the kernel."""
    k = a1.shape[0]
    n = indices.shape[0] if n is None else n
    kb = b if rows is None else 0
    counts = counts.reshape(-1, 1).astype(jnp.int32)
    be = BACKENDS[backend]
    if not be.use_pallas:
        out = kref.minhash2u_ref(indices, counts, a1, a2, s=s, b=kb,
                                 variant=variant)
    else:
        idx = _pad_axis(_pad_axis(indices, blk_t, 1), blk_n, 0)
        cts = _pad_axis(counts, blk_n, 0)
        a1p = _pad_axis(a1, blk_k, 0)
        a2p = _pad_axis(a2, blk_k, 0, value=1)
        if kb and packed and can_pack_in_kernel(a1p.shape[0], k, b, blk_k):
            words = minhash2u_pallas(idx, cts, a1p, a2p, s=s, b=b,
                                     blk_n=blk_n, blk_t=blk_t, blk_k=blk_k,
                                     variant=variant, pack=True,
                                     interpret=be.interpret)
            return words[:n]
        out = minhash2u_pallas(idx, cts, a1p, a2p, s=s, b=kb, blk_n=blk_n,
                               blk_t=blk_t, blk_k=blk_k, variant=variant,
                               interpret=be.interpret)[:, :k]
    out = _rows_from_segments(out, rows, n)
    if rows is not None:
        out = _bbit(out, b)
    return pack_device(out, PackSpec(k, b)) if packed else out


@functools.partial(jax.jit, static_argnames=("n", "s", "b", "backend",
                                             "blk_n", "blk_t", "blk_k",
                                             "packed"))
def _minhash4u_run(indices, counts, a, rows=None, *, n=None, s, b, backend,
                   blk_n, blk_t, blk_k, packed=False):
    """``_minhash2u_run`` for the 4U family."""
    k = a.shape[1]
    n = indices.shape[0] if n is None else n
    kb = b if rows is None else 0
    counts = counts.reshape(-1, 1).astype(jnp.int32)
    be = BACKENDS[backend]
    if not be.use_pallas:
        out = kref.minhash4u_ref(indices, counts, a, s=s, b=kb)
    else:
        idx = _pad_axis(_pad_axis(indices, blk_t, 1), blk_n, 0)
        cts = _pad_axis(counts, blk_n, 0)
        ap = _pad_axis(a, blk_k, 1, value=1)
        if kb and packed and can_pack_in_kernel(ap.shape[1], k, b, blk_k):
            words = minhash4u_pallas(idx, cts, ap, s=s, b=b, blk_n=blk_n,
                                     blk_t=blk_t, blk_k=blk_k, pack=True,
                                     interpret=be.interpret)
            return words[:n]
        out = minhash4u_pallas(idx, cts, ap, s=s, b=kb, blk_n=blk_n,
                               blk_t=blk_t, blk_k=blk_k,
                               interpret=be.interpret)[:, :k]
    out = _rows_from_segments(out, rows, n)
    if rows is not None:
        out = _bbit(out, b)
    return pack_device(out, PackSpec(k, b)) if packed else out


def _oph_lanes(k: int, blk_k: int):
    """(k_lanes, blk_k) for an OPH call: k padded to a full lane block."""
    if k < 1 or k & (k - 1):
        raise ValueError(f"OPH bin count k must be a power of two, got {k}")
    k_lanes = max(k, 128)
    if blk_k <= 0:
        blk_k = min(k_lanes, 512)             # all bins in one pass for k<=512
    return max(k_lanes, blk_k), blk_k


@functools.partial(jax.jit, static_argnames=("s", "bin_bits", "variant",
                                             "backend", "k_lanes", "blk_n",
                                             "blk_t", "blk_k", "code_b"))
def _oph2u_raw(indices, counts, a1, a2, *, s, bin_bits, variant, backend,
               k_lanes, blk_n, blk_t, blk_k, code_b=0):
    be = BACKENDS[backend]
    if not be.use_pallas:
        raw = kref.oph2u_ref(indices, counts, a1, a2, s=s, bin_bits=bin_bits,
                             k_lanes=k_lanes, variant=variant)
        return encode_sentinel(raw, code_b) if code_b > 0 else raw
    idx = _pad_axis(_pad_axis(indices, blk_t, 1), blk_n, 0)
    cts = _pad_axis(counts, blk_n, 0)
    return oph2u_pallas(idx, cts, a1, a2, s=s, bin_bits=bin_bits, blk_n=blk_n,
                        blk_t=blk_t, blk_k=blk_k, variant=variant,
                        code_b=code_b, interpret=be.interpret)


@functools.partial(jax.jit, static_argnames=("s", "bin_bits", "backend",
                                             "k_lanes", "blk_n", "blk_t",
                                             "blk_k", "code_b"))
def _oph4u_raw(indices, counts, a, *, s, bin_bits, backend, k_lanes,
               blk_n, blk_t, blk_k, code_b=0):
    be = BACKENDS[backend]
    if not be.use_pallas:
        raw = kref.oph4u_ref(indices, counts, a, s=s, bin_bits=bin_bits,
                             k_lanes=k_lanes)
        return encode_sentinel(raw, code_b) if code_b > 0 else raw
    idx = _pad_axis(_pad_axis(indices, blk_t, 1), blk_n, 0)
    cts = _pad_axis(counts, blk_n, 0)
    return oph4u_pallas(idx, cts, a, s=s, bin_bits=bin_bits, blk_n=blk_n,
                        blk_t=blk_t, blk_k=blk_k, code_b=code_b,
                        interpret=be.interpret)


@functools.partial(jax.jit, static_argnames=("n", "k", "s", "bin_bits",
                                             "densify", "b", "packed",
                                             "coded"))
def _oph_epilogue_jit(raw, rows=None, *, n=None, k, s, bin_bits, densify, b,
                      packed=False, coded=False):
    """Reduce segment bin minima to ``n`` rows (EMPTY, the maximum, is
    what an empty bin holds), slice lane padding, densify, extract b
    bits, optionally pack.

    Shares ``repro.core.oph.densify_and_bbit`` with the jnp reference so
    the kernel path is bit-exact against it.  ``coded=True`` means the
    kernel already emitted (b+1)-bit sentinel codes (fused epilogue, one
    segment per row) and only the bitstream pack remains.
    """
    sig = _rows_from_segments(raw, rows, n)[:, :k]
    spec = PackSpec(k, b, sentinel=(densify == "sentinel")) if packed else None
    if coded:
        return pack_codes(sig, spec.code_bits)
    sig = densify_and_bbit(sig, 1 << (s - bin_bits), densify, b)
    if packed:
        return pack_device(sig, spec)
    return sig


# ---------------------------------------------------------------------------
# Legacy-compatible jitted wrappers (public API, re-exported by ops.py)
# ---------------------------------------------------------------------------

def _legacy_backend(use_pallas: bool, backend: Optional[str]) -> str:
    return "ref" if not use_pallas else resolve_backend(backend).name


def minhash2u(indices: jax.Array, counts: jax.Array, a1: jax.Array,
              a2: jax.Array, *, s: int, b: int = 0, variant: str = "high",
              use_pallas: bool = True, backend: Optional[str] = None,
              blk_n: int = 128, blk_t: int = 128,
              blk_k: int = 128) -> jax.Array:
    """Batched 2U minhash signatures. counts: (n,) or (n,1) int32."""
    return _minhash2u_run(indices, counts, a1, a2, s=s, b=b, variant=variant,
                          backend=_legacy_backend(use_pallas, backend),
                          blk_n=blk_n, blk_t=blk_t, blk_k=blk_k)


def minhash4u(indices: jax.Array, counts: jax.Array, a: jax.Array, *, s: int,
              b: int = 0, use_pallas: bool = True,
              backend: Optional[str] = None, blk_n: int = 128,
              blk_t: int = 128, blk_k: int = 128) -> jax.Array:
    """Batched 4U minhash signatures (Mersenne BitMod path)."""
    return _minhash4u_run(indices, counts, a, s=s, b=b,
                          backend=_legacy_backend(use_pallas, backend),
                          blk_n=blk_n, blk_t=blk_t, blk_k=blk_k)


def oph2u(indices: jax.Array, counts: jax.Array, a1: jax.Array,
          a2: jax.Array, *, s: int, k: int, densify: str = "rotation",
          b: int = 0, variant: str = "high", use_pallas: bool = True,
          backend: Optional[str] = None, blk_n: int = 128, blk_t: int = 128,
          blk_k: int = 0) -> jax.Array:
    """Batched 2U OPH signatures: ONE hash pass -> (n, k) bin minima.

    Two jit stages: the Pallas raw-bin stage is independent of
    (densify, b), so sweeping those (tests, b-grids) reuses its compiled
    executable and only the cheap epilogue recompiles.
    """
    n, _ = indices.shape
    counts = counts.reshape(-1, 1).astype(jnp.int32)
    bin_bits = k.bit_length() - 1
    k_lanes, blk_k = _oph_lanes(k, blk_k)
    raw = _oph2u_raw(indices, counts, a1, a2, s=s, bin_bits=bin_bits,
                     variant=variant,
                     backend=_legacy_backend(use_pallas, backend),
                     k_lanes=k_lanes, blk_n=blk_n, blk_t=blk_t, blk_k=blk_k)
    return _oph_epilogue_jit(raw, k=k, s=s, bin_bits=bin_bits,
                             densify=densify, b=b)[:n]


def oph4u(indices: jax.Array, counts: jax.Array, a: jax.Array, *, s: int,
          k: int, densify: str = "rotation", b: int = 0,
          use_pallas: bool = True, backend: Optional[str] = None,
          blk_n: int = 128, blk_t: int = 128, blk_k: int = 0) -> jax.Array:
    """Batched 4U OPH signatures (Mersenne BitMod path); see ``oph2u``."""
    n, _ = indices.shape
    counts = counts.reshape(-1, 1).astype(jnp.int32)
    bin_bits = k.bit_length() - 1
    k_lanes, blk_k = _oph_lanes(k, blk_k)
    raw = _oph4u_raw(indices, counts, a, s=s, bin_bits=bin_bits,
                     backend=_legacy_backend(use_pallas, backend),
                     k_lanes=k_lanes, blk_n=blk_n, blk_t=blk_t, blk_k=blk_k)
    return _oph_epilogue_jit(raw, k=k, s=s, bin_bits=bin_bits,
                             densify=densify, b=b)[:n]


@functools.partial(jax.jit, static_argnames=("backend", "blk_n"))
def _sigbag_run(tokens, table, *, backend, blk_n):
    be = BACKENDS[backend]
    if not be.use_pallas:
        return kref.sigbag_ref(tokens, table)
    n = tokens.shape[0]
    tok = _pad_axis(tokens, blk_n, 0)
    out = sigbag_pallas(tok, table, blk_n=blk_n, interpret=be.interpret)
    return out[:n]


def sigbag(tokens: jax.Array, table: jax.Array, *, use_pallas: bool = True,
           backend: Optional[str] = None, blk_n: int = 128) -> jax.Array:
    """Signature embedding-bag: out[i] = sum_j table[j, tokens[i, j]]."""
    return _sigbag_run(tokens, table,
                       backend=_legacy_backend(use_pallas, backend),
                       blk_n=blk_n)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class SignatureEngine:
    """Backend-aware signature computation for one hash family.

    Owns padding/tiling, block-size choice (explicit ``blocks`` >
    ``TuningTable`` entry > per-scheme defaults) and scheme dispatch via
    the ``(scheme, family)`` runner registry.  ``signatures`` returns the
    legacy (n, k) uint32 layout; ``packed_signatures`` returns the
    ``PackedSignatures`` wire format, packed inside the kernel jit (fused
    into the kernel's final grid step where alignment allows).
    """

    def __init__(self, family, *, b: int = 0, backend: Optional[str] = None,
                 packed: bool = False, blocks: Optional[dict] = None,
                 tuning: Optional[TuningTable] = None):
        self.family_obj = family
        self.statics = _family_statics(family)
        self.b = b
        self.packed = packed
        self.backend = resolve_backend(backend).name
        self._blocks = dict(blocks) if blocks else None
        self._tuning = tuning
        if packed:
            PackSpec(self.statics["k"], b,
                     self.statics.get("densify") == "sentinel")  # validate b
        key = (self.statics["scheme"], self.statics["family"])
        if key not in _RUNNERS:
            raise TypeError(f"no runner for scheme/family {key}")
        self._runner = _RUNNERS[key]

    # -- plan / blocks --------------------------------------------------
    def blocks_for(self, nnz: int) -> dict:
        if self._blocks:
            return self._blocks
        table = self._tuning or default_tuning_table()
        hit = table.lookup(self.backend, self.statics["scheme"],
                           self.statics["k"], nnz)
        if hit:
            return hit
        return dict(MINHASH_BLOCKS if self.statics["scheme"] == "minhash"
                    else OPH_BLOCKS)

    def plan_for(self, nnz: int) -> SignaturePlan:
        blocks = self.blocks_for(nnz)
        return SignaturePlan(backend=self.backend, b=self.b,
                             packed=self.packed, **self.statics, **blocks)

    # -- execution ------------------------------------------------------
    def signatures(self, batch: Batch) -> jax.Array:
        """(n, k) uint32 signatures (b-bit masked when plan.b > 0)."""
        return self._runner(self, batch, self.plan_for(batch.indices.shape[1]),
                            packed=False)

    def packed_signatures(self, batch: Batch) -> PackedSignatures:
        """The packed wire format: k*code_bits bits per example."""
        plan = self.plan_for(batch.indices.shape[1])
        words = self._runner(self, batch, plan, packed=True)
        return PackedSignatures(words, plan.k, plan.b, plan.sentinel)

    def __call__(self, batch: Batch):
        return self.packed_signatures(batch) if self.packed \
            else self.signatures(batch)


def _segments(batch):
    """``(indices, counts, rows, n)``: the batch as fixed-width segments
    (a ``SparseBatch`` is one segment per row)."""
    if isinstance(batch, SegmentedBatch):
        return batch.indices, batch.counts, batch.rows, batch.n
    return (batch.indices, jnp.sum(batch.mask.astype(jnp.int32), axis=1),
            None, batch.n)


def _run_minhash_2u(eng, batch, plan, *, packed):
    fam = eng.family_obj
    idx, counts, rows, n = _segments(batch)
    return _minhash2u_run(idx, counts, fam.a1, fam.a2, rows, n=n, s=plan.s,
                          b=plan.b, variant=plan.variant,
                          backend=plan.backend, blk_n=plan.blk_n,
                          blk_t=plan.blk_t, blk_k=plan.blk_k, packed=packed)


def _run_minhash_4u(eng, batch, plan, *, packed):
    fam = eng.family_obj
    idx, counts, rows, n = _segments(batch)
    return _minhash4u_run(idx, counts, fam.a, rows, n=n, s=plan.s, b=plan.b,
                          backend=plan.backend, blk_n=plan.blk_n,
                          blk_t=plan.blk_t, blk_k=plan.blk_k, packed=packed)


def _run_oph(eng, batch, plan, *, packed, raw_fn, coeff_args):
    idx, counts, rows, n = _segments(batch)
    counts = counts.reshape(-1, 1).astype(jnp.int32)
    bin_bits = plan.k.bit_length() - 1
    k_lanes, blk_k = _oph_lanes(plan.k, plan.blk_k)
    # packed sentinel, one segment per row: the kernel's fused final-step
    # epilogue emits the (b+1)-bit codes; everything else uses the
    # raw-minima stage (shared across densify/b sweeps) + the jnp
    # epilogue, which first reduces segments to rows.
    coded = packed and plan.sentinel and rows is None
    raw = raw_fn(idx, counts, *coeff_args, s=plan.s,
                 bin_bits=bin_bits, backend=plan.backend, k_lanes=k_lanes,
                 blk_n=plan.blk_n, blk_t=plan.blk_t, blk_k=blk_k,
                 code_b=plan.b if coded else 0)
    return _oph_epilogue_jit(raw, rows, n=n, k=plan.k, s=plan.s,
                             bin_bits=bin_bits, densify=plan.densify,
                             b=plan.b, packed=packed, coded=coded)


def _run_oph_2u(eng, batch, plan, *, packed):
    base = eng.family_obj.base
    return _run_oph(eng, batch, plan, packed=packed,
                    raw_fn=functools.partial(_oph2u_raw, variant=plan.variant),
                    coeff_args=(base.a1, base.a2))


def _run_oph_4u(eng, batch, plan, *, packed):
    base = eng.family_obj.base
    return _run_oph(eng, batch, plan, packed=packed, raw_fn=_oph4u_raw,
                    coeff_args=(base.a,))


def _run_oph_perm(eng, batch, plan, *, packed):
    # permutation base: gold-standard jnp reference (tests/small D only);
    # raw bin minima per segment, reduced to rows, then densified
    oph = eng.family_obj
    idx, counts, rows, n = _segments(batch)
    mask = jnp.arange(idx.shape[1])[None, :] < counts[:, None]
    raw = oph_signatures(idx, mask, dataclasses.replace(oph,
                                                        densify="sentinel"))
    sig = densify_and_bbit(_rows_from_segments(raw, rows, n), oph.bin_width,
                           oph.densify, plan.b)
    return pack_device(sig, plan.pack_spec) if packed else sig


_RUNNERS = {
    ("minhash", "2u"): _run_minhash_2u,
    ("minhash", "4u"): _run_minhash_4u,
    ("oph", "2u"): _run_oph_2u,
    ("oph", "4u"): _run_oph_4u,
    ("oph", "perm"): _run_oph_perm,
}


# ---------------------------------------------------------------------------
# Batch entry point (legacy signature, engine-backed)
# ---------------------------------------------------------------------------

def batch_signatures(batch: SparseBatch, family, *, b: int = 0,
                     use_pallas: bool = True, backend: Optional[str] = None,
                     packed: bool = False):
    """Signatures for a SparseBatch via the SignatureEngine.

    ``family`` selects the scheme (Hash2U/Hash4U k-pass minwise, or an
    ``repro.core.oph.OPH`` scheme); ``backend`` selects execution
    ("auto" resolves per hardware); ``packed=True`` returns the
    ``PackedSignatures`` wire format instead of (n, k) uint32.
    """
    eng = SignatureEngine(family, b=b, packed=packed,
                          backend=_legacy_backend(use_pallas, backend))
    return eng(batch)


def _time_candidates(candidates, run_one, iters: int):
    """Shared tuning loop: compile once, time ``iters`` runs, keep the
    fastest candidate block dict."""
    import time
    candidates = list(candidates)
    if not candidates:
        raise ValueError("tune() needs at least one candidate block dict")
    best, best_t = None, float("inf")
    for blocks in candidates:
        run_one(blocks)                          # compile once
        t0 = time.perf_counter()
        for _ in range(iters):
            run_one(blocks)
        dt = (time.perf_counter() - t0) / iters
        if dt < best_t:
            best, best_t = dict(blocks), dt
    return best


def tune(engine, batch, candidates, iters: int = 3,
         table: Optional[TuningTable] = None,
         backend: Optional[str] = None) -> dict:
    """Time candidate block dicts and record the winner in the tuning
    table (the ROADMAP TPU tuning loop).

    Two schemes:
      * ``engine`` is a ``SignatureEngine`` and ``batch`` a
        ``SparseBatch`` -- tunes the signature kernels (minhash/oph).
      * ``engine`` is a ``PackSpec`` and ``batch`` a
        ``(qwords, cwords)`` pair of packed operands -- tunes the
        retrieval kernel (``repro.kernels.hamming.packed_match``),
        recording under scheme ``"hamming"`` keyed on the packed word
        count; ``backend`` resolves through the registry ("auto" per
        hardware).
    """
    if isinstance(engine, PackSpec):
        from repro.kernels.hamming import packed_match
        qwords, cwords = batch
        be = resolve_backend(backend).name

        def run_one(blocks):
            out = packed_match(qwords, cwords, engine, backend=be,
                               blocks=blocks)
            jax.block_until_ready(out[0] if isinstance(out, tuple) else out)

        best = _time_candidates(candidates, run_one, iters)
        tab = table or default_tuning_table()
        tab.record(be, "hamming", engine.k, engine.words, best)
        return best

    def run_one(blocks):
        probe = SignatureEngine(engine.family_obj, b=engine.b,
                                backend=engine.backend, packed=engine.packed,
                                blocks=blocks)
        out = probe(batch)
        jax.block_until_ready(out.data if isinstance(out, PackedSignatures)
                              else out)

    best = _time_candidates(candidates, run_one, iters)
    tab = table or engine._tuning or default_tuning_table()
    tab.record(engine.backend, engine.statics["scheme"],
               engine.statics["k"], batch.indices.shape[1], best)
    return best
