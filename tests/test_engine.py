"""SignatureEngine: dispatch parity, packed wire format, backends, tuning.

Four layers:

  * pack/unpack round-trip sweeps: b in {1,2,4,8,16} x non-word-aligned k
    x sentinel (b+1)-bit codes, plus the in-kernel fused pack vs the jnp
    bitstream pack,
  * engine-vs-reference bit-exactness across every (scheme, family,
    densify, b) combination (the legacy ``batch_signatures`` contract),
  * backend registry semantics (auto resolution, ref oracles) and
    TuningTable JSON persistence,
  * the ``.sig`` shard format round-trip (plain + mmap) and the
    layering rule that only ``repro/kernels/`` touches ``*_pallas``.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bbit import pack_codes, packed_words, unpack_codes
from repro.core.hashing import Hash2U, Hash4U
from repro.core.minhash import minhash_signatures
from repro.core.oph import EMPTY, OPH, oph_signatures
from repro.data.sparse import from_lists
from repro.kernels import (BACKENDS, PackSpec, PackedSignatures,
                           SignatureEngine, TuningTable, batch_signatures,
                           resolve_backend)
from repro.kernels.pack import pack_device, unpack_device

RNG = np.random.default_rng(23)


def _batch(n=5, max_set=250, s=16, seed=101, max_nnz=256):
    rng = np.random.default_rng(seed)
    sets = [rng.choice(1 << s, rng.integers(1, max_set + 1), replace=False)
            for _ in range(n)]
    return from_lists(sets, max_nnz=max_nnz)


@pytest.fixture(scope="module")
def batch16():
    return _batch()           # same shape as test_oph's fixture: jit reuse


# ---------------------------------------------------------------------------
# Wire format: bitstream round trips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b", [1, 2, 4, 8, 16])
@pytest.mark.parametrize("k", [60, 128, 129])       # non-word-aligned + aligned
@pytest.mark.parametrize("sentinel", [False, True])
def test_pack_roundtrip_sweep(b, k, sentinel):
    """(b, k, sentinel) sweep: pack -> unpack is the identity, at exactly
    ceil(k*code_bits/32) words per example."""
    rng = np.random.default_rng(b * 1000 + k)
    sig = rng.integers(0, 1 << b, (7, k)).astype(np.uint32)
    if sentinel:
        sig[rng.random((7, k)) < 0.3] = np.uint32(0xFFFFFFFF)   # EMPTY
    spec = PackSpec(k, b, sentinel)
    assert spec.code_bits == (b + 1 if sentinel else b)
    packed = pack_device(jnp.asarray(sig), spec)
    assert packed.shape == (7, packed_words(k, spec.code_bits))
    assert packed.dtype == jnp.uint32
    out = np.asarray(unpack_device(packed, spec))
    assert np.array_equal(out, sig)


def test_pack_codes_bit_layout():
    """Code j occupies bits [j*cb, (j+1)*cb) -- checked against a python
    big-integer bitstream, including word-straddling 9-bit codes."""
    k, cb = 23, 9
    v = np.arange(k, dtype=np.uint32) * 21 % (1 << cb)
    p = np.asarray(pack_codes(jnp.asarray(v[None, :]), cb))[0]
    stream = 0
    for j in range(k):
        stream |= int(v[j]) << (j * cb)
    for w in range(p.size):
        assert int(p[w]) == (stream >> (32 * w)) & 0xFFFFFFFF
    assert np.array_equal(
        np.asarray(unpack_codes(jnp.asarray(p[None, :]), cb, k))[0], v)


def test_fused_kernel_pack_matches_jnp_pack(batch16):
    """Lane-aligned minhash: the in-kernel final-step pack bit-equals the
    jnp bitstream pack of the unpacked signatures."""
    fam = Hash2U.create(jax.random.PRNGKey(0), 128, 16)
    sig = batch_signatures(batch16, fam, b=8)
    eng = SignatureEngine(fam, b=8, packed=True)
    p = eng.packed_signatures(batch16)
    assert np.array_equal(np.asarray(p.data),
                          np.asarray(pack_codes(sig, 8)))
    assert np.array_equal(np.asarray(p.unpack()), np.asarray(sig))


# ---------------------------------------------------------------------------
# Engine vs reference: every (scheme, family, densify, b)
# ---------------------------------------------------------------------------

_GRID = [("minhash", fam, None, b)
         for fam in ("2u", "4u") for b in (0, 8)] + \
        [("oph", fam, densify, b)
         for fam in ("2u", "4u")
         for densify in ("rotation", "sentinel", "optimal", "fast")
         for b in (0, 8)]
# fast tier: every b=8 row (all schemes/densify modes) + the minhash-2u
# baseline; the full product (b=0 rows, 4u duplicates) runs in the slow tier
_GRID = [pytest.param(*row, marks=[] if (row[3] == 8 or
                                         row[:2] == ("minhash", "2u"))
                      else [pytest.mark.slow])
        for row in _GRID]


def _make_family(scheme, fam, densify, k, s):
    import zlib
    key = jax.random.PRNGKey(
        zlib.crc32(repr((scheme, fam, densify)).encode()) % (2**31))
    if scheme == "minhash":
        return (Hash2U.create(key, k, s) if fam == "2u"
                else Hash4U.create(key, k, s))
    return OPH.create(key, k, s, fam, densify)


@pytest.mark.parametrize("scheme,fam,densify,b", _GRID)
def test_engine_matches_reference_grid(scheme, fam, densify, b, batch16):
    """Engine output == jnp reference == ref backend, and the packed wire
    format unpacks to the same signatures (b > 0)."""
    s, k = 16, 128
    family = _make_family(scheme, fam, densify, k, s)
    if scheme == "minhash":
        want = np.asarray(minhash_signatures(batch16.indices, batch16.mask,
                                             family))
        if b:
            want = want & ((1 << b) - 1)
    else:
        want = np.asarray(oph_signatures(batch16.indices, batch16.mask,
                                         family, b=b))
    eng = SignatureEngine(family, b=b)
    got = np.asarray(eng.signatures(batch16))
    assert np.array_equal(got, want), "engine vs reference"
    ref = np.asarray(SignatureEngine(family, b=b,
                                     backend="ref").signatures(batch16))
    assert np.array_equal(ref, want), "ref backend vs reference"
    legacy = np.asarray(batch_signatures(batch16, family, b=b))
    assert np.array_equal(legacy, want), "legacy wrapper vs reference"
    if b:
        packed = SignatureEngine(family, b=b,
                                 packed=True).packed_signatures(batch16)
        assert isinstance(packed, PackedSignatures)
        assert packed.sentinel == (densify == "sentinel")
        assert packed.data.shape == \
            (batch16.n, packed_words(k, packed.code_bits))
        assert np.array_equal(np.asarray(packed.unpack()), want), "packed"


def test_engine_perm_base_reference(batch16):
    """Permutation-base OPH routes to the gold-standard jnp reference."""
    oph = OPH.create(jax.random.PRNGKey(3), 32, 10, "perm", "sentinel")
    small = _batch(3, 60, 10, seed=5, max_nnz=64)
    want = oph_signatures(small.indices, small.mask, oph, b=4)
    got = SignatureEngine(oph, b=4).signatures(small)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    packed = SignatureEngine(oph, b=4, packed=True).packed_signatures(small)
    assert np.array_equal(np.asarray(packed.unpack()), np.asarray(want))


def test_packed_signatures_pytree_and_slicing(batch16):
    fam = Hash2U.create(jax.random.PRNGKey(1), 128, 16)
    p = SignatureEngine(fam, b=8, packed=True).packed_signatures(batch16)
    leaves, treedef = jax.tree_util.tree_flatten(p)
    assert len(leaves) == 1
    p2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert (p2.k, p2.b, p2.sentinel) == (p.k, p.b, p.sentinel)
    sl = p[1:3]
    assert sl.n == 2 and len(sl) == 2
    assert np.array_equal(np.asarray(sl.unpack()),
                          np.asarray(p.unpack())[1:3])
    assert p.nbytes == p.data.size * 4


# ---------------------------------------------------------------------------
# Backends + tuning table
# ---------------------------------------------------------------------------

def test_backend_registry_and_resolution(batch16):
    assert set(BACKENDS) == {"interpret", "tpu", "ref"}
    auto = resolve_backend(None)
    assert auto.name == ("tpu" if jax.default_backend() == "tpu" else
                         "interpret")
    with pytest.raises(ValueError):
        resolve_backend("gpu")
    # ref runs the jnp oracles and agrees with the kernel path
    assert not BACKENDS["ref"].use_pallas
    fam = Hash2U.create(jax.random.PRNGKey(0), 128, 16)
    want = np.asarray(batch_signatures(batch16, fam, b=8))
    got = np.asarray(SignatureEngine(fam, b=8,
                                     backend="ref").signatures(batch16))
    assert np.array_equal(got, want)


def test_tuning_table_persistence(tmp_path, batch16):
    table = TuningTable()
    table.record("tpu", "minhash", 128, 300,
                 {"blk_n": 16, "blk_t": 512, "blk_k": 128})
    path = table.save(str(tmp_path / "tuning.json"))
    loaded = TuningTable.load(path)
    assert loaded.lookup("tpu", "minhash", 128, 260) == \
        {"blk_n": 16, "blk_t": 512, "blk_k": 128}       # same nnz bucket
    assert loaded.lookup("tpu", "minhash", 128, 1000) is None  # other bucket
    assert loaded.lookup("tpu", "oph", 128, 300) is None       # other scheme
    assert loaded.lookup("interpret", "minhash", 128, 300) is None
    with open(path) as f:
        assert json.load(f)["version"] == 1
    # a table entry actually steers the engine's plan -- and only for its
    # own scheme (blk_k=0 is an OPH-only convention)
    tuned = TuningTable()
    tuned.record("interpret", "minhash", 128, batch16.indices.shape[1],
                 {"blk_n": 4, "blk_t": 64, "blk_k": 128})
    eng = SignatureEngine(Hash2U.create(jax.random.PRNGKey(0), 128, 16),
                          backend="interpret", tuning=tuned)
    plan = eng.plan_for(batch16.indices.shape[1])
    assert (plan.blk_n, plan.blk_t, plan.blk_k) == (4, 64, 128)
    oph_eng = SignatureEngine(OPH.create(jax.random.PRNGKey(0), 128, 16,
                                         "2u", "rotation"),
                              backend="interpret", tuning=tuned)
    assert oph_eng.plan_for(batch16.indices.shape[1]).blk_k == 0
    explicit = SignatureEngine(Hash2U.create(jax.random.PRNGKey(0), 128, 16),
                               blocks={"blk_n": 8, "blk_t": 128,
                                       "blk_k": 128}, tuning=tuned)
    assert explicit.plan_for(999).blk_n == 8            # explicit wins


def test_hamming_scheme_tuning_table_steers_kernel(batch16):
    """The retrieval kernel resolves 'hamming' TuningTable entries (keyed
    on the packed word count) and stays bit-exact under odd blocks."""
    from repro.kernels import packed_match
    fam = Hash2U.create(jax.random.PRNGKey(4), 128, 16)
    wire = SignatureEngine(fam, b=8, packed=True).packed_signatures(batch16)
    want = np.asarray(packed_match(wire.data, wire.data, wire.spec,
                                   backend="interpret"))
    tuned = TuningTable()
    words = wire.data.shape[1]
    tuned.record("interpret", "hamming", 128, words,
                 {"blk_q": 4, "blk_n": 64, "blk_k": 32})
    got = np.asarray(packed_match(wire.data, wire.data, wire.spec,
                                  backend="interpret", tuning=tuned))
    assert np.array_equal(got, want)
    assert tuned.lookup("interpret", "hamming", 128, words) == \
        {"blk_q": 4, "blk_n": 64, "blk_k": 32}


# ---------------------------------------------------------------------------
# .sig shard format + layering
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mmap", [False, True])
def test_sig_shard_roundtrip(tmp_path, mmap):
    from repro.data.sigshard import (SigShardMeta, read_sig_meta,
                                     read_sig_shard, write_sig_shard)
    rng = np.random.default_rng(0)
    words = rng.integers(0, 2**32, (37, 36), dtype=np.uint64).astype(np.uint32)
    labels = rng.normal(size=37).astype(np.float32)
    path = str(tmp_path / "chunk.sig")
    meta = write_sig_shard(path, words, labels, k=128, b=8, code_bits=9,
                           sentinel=True)
    assert meta == read_sig_meta(path)
    assert meta.payload_bytes == 37 * 36 * 4
    assert meta.payload_offset % 64 == 0
    w2, l2, m2 = read_sig_shard(path, mmap=mmap)
    assert m2 == SigShardMeta(37, 128, 8, 9, 36, True)
    assert np.array_equal(np.asarray(w2), words)
    assert np.array_equal(l2, labels)
    with pytest.raises(ValueError):
        bad = str(tmp_path / "bad.sig")
        with open(bad, "wb") as f:
            f.write(b"NOPE" + b"\0" * 60)
        read_sig_meta(bad)


def test_sig_shard_version_byte_roundtrip_and_mismatch(tmp_path):
    """The header's version byte survives a write/read round trip, and a
    bumped version fails loudly (clear error naming both versions)."""
    from repro.data.sigshard import VERSION, read_sig_meta, write_sig_shard
    path = str(tmp_path / "v.sig")
    words = np.arange(12, dtype=np.uint32).reshape(3, 4)
    write_sig_shard(path, words, np.zeros(3, np.float32), k=16, b=8,
                    code_bits=8)
    with open(path, "rb") as f:
        blob = bytearray(f.read())
    assert blob[4] == VERSION                            # little-endian u32
    read_sig_meta(path)                                  # current version ok
    blob[4] = VERSION + 41
    with open(path, "wb") as f:
        f.write(blob)
    with pytest.raises(ValueError, match=rf"version {VERSION + 41}.*"
                                         rf"reads version {VERSION}"):
        read_sig_meta(path)


def test_no_pallas_builders_outside_kernels():
    """Layering rule: only repro/kernels/ may touch a *_pallas builder or
    pallas_call (the ``use_pallas=`` keyword is fine everywhere)."""
    import re
    import repro
    builder = re.compile(r"\b(?:minhash|oph|sigbag)\w*_pallas\b"
                         r"|\bpallas_call\b")
    root = list(repro.__path__)[0]
    offenders = []
    for dirpath, _, files in os.walk(root):
        inside_kernels = os.path.basename(dirpath) == "kernels"
        for name in files:
            if not name.endswith(".py") or inside_kernels:
                continue
            with open(os.path.join(dirpath, name)) as f:
                src = f.read()
            if builder.search(src):
                offenders.append(os.path.join(dirpath, name))
    assert not offenders, offenders


def test_tune_accepts_packed_match_scheme():
    """engine.tune() with a PackSpec times packed_match candidates and
    records the winner under scheme "hamming" keyed on the word count."""
    import numpy as np

    from repro.kernels import packed_match
    from repro.kernels.engine import TuningTable, tune
    from repro.kernels.pack import PackSpec

    spec = PackSpec(128, 8)
    rng = np.random.default_rng(0)
    q = rng.integers(0, 2**32, (8, spec.words), dtype=np.uint64) \
        .astype(np.uint32)
    c = rng.integers(0, 2**32, (64, spec.words), dtype=np.uint64) \
        .astype(np.uint32)
    tab = TuningTable()
    candidates = [{"blk_q": 8, "blk_n": 64, "blk_k": 128},
                  {"blk_q": 8, "blk_n": 128, "blk_k": 128}]
    best = tune(spec, (q, c), candidates, iters=1, table=tab,
                backend="interpret")
    assert best in candidates
    assert tab.lookup("interpret", "hamming", spec.k, spec.words) == best
    # the recorded blocks drive packed_match and agree with the oracle
    out = packed_match(q, c, spec, backend="interpret", tuning=tab)
    want = packed_match(q, c, spec, backend="ref")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
