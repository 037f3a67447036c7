"""Pallas TPU kernels for the paper's compute hot-spot: minhash preprocessing.

  minhash.py  -- 2U / 4U minwise-hash signature kernels (the §3 GPU kernel,
                 re-derived for TPU: VMEM tiling, VPU lanes over hash
                 functions, running-min accumulation, in-kernel BitMod,
                 fused b-bit extraction + word packing in the final step).
  oph.py      -- One Permutation Hashing kernels: the same running-min
                 reduction, but ONE hash evaluation per nonzero feeds all
                 k bins (k x less hash work than minhash.py); fused
                 (b+1)-bit sentinel coding for the packed wire format.
  sigbag.py   -- Eq.(5) signature embedding-bag as one-hot MXU matmuls.
  hamming.py  -- packed-signature match counting for retrieval: XOR of
                 the wire words, zero code fields counted in-register,
                 sentinel-EMPTY aware (the repro.index scoring hot path).
  pack.py     -- the packed b-bit wire format (PackSpec, device pack /
                 unpack epilogues, in-kernel pack_block).
  engine.py   -- SignaturePlan / SignatureEngine: backend registry
                 (interpret / tpu / ref), JSON block-size tuning
                 table, padding/tiling, scheme dispatch, PackedSignatures.
  ops.py      -- legacy re-exports of the public wrappers.
  ref.py      -- pure-jnp oracles for allclose validation.

Only this package calls ``*_pallas`` builders; everything downstream goes
through the engine (or the legacy wrappers it backs).
"""

from repro.kernels.engine import (BACKENDS, HAMMING_BLOCKS, Backend,
                                  PackedSignatures, SignatureEngine,
                                  SignaturePlan, TuningTable,
                                  batch_signatures, default_tuning_table,
                                  minhash2u, minhash4u, oph2u, oph4u,
                                  register_backend, resolve_backend, sigbag)
from repro.kernels.hamming import packed_match
from repro.kernels.pack import PackSpec

__all__ = [
    "BACKENDS", "Backend", "HAMMING_BLOCKS", "PackSpec", "PackedSignatures",
    "SignatureEngine", "SignaturePlan", "TuningTable", "batch_signatures",
    "default_tuning_table", "minhash2u", "minhash4u", "oph2u", "oph4u",
    "packed_match", "register_backend", "resolve_backend", "sigbag",
]
