"""Ahead-of-time compiles of the main-path kernels for a described v5e.

Nothing here runs on a chip: the TPU compiler (Mosaic) lowers each
kernel through the engine's own ``tpu`` runners at real widths, for a
``v5e:2x2`` topology that is described, not attached.  That catches what
interpret mode cannot -- unaligned tiles, unsupported layouts and ops,
too much scoped VMEM -- at no chip time.

The topology is described inside a module-scoped fixture: only the
worker that runs this file loads the TPU library, and every worker
collects the same tests.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.bbit import packed_words
from repro.index.query import exact_scan_ids
from repro.kernels.engine import (HAMMING_BLOCKS, MINHASH_BLOCKS, OPH_BLOCKS,
                                  _minhash2u_run, _minhash4u_run, _oph2u_raw,
                                  _oph4u_raw, _oph_lanes, _sigbag_run,
                                  default_tuning_table)
from repro.kernels.hamming import _packed_match_run

N, NNZ, K, S, B = 1024, 4096, 512, 24, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _arg(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _blocks(scheme, k, width, default):
    return default_tuning_table().lookup("tpu", scheme, k, width) \
        or dict(default)


def _assert_kernel(fn, *args, **statics):
    compiled = fn.lower(*args, **statics).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("family", ["2u", "4u"])
def test_minhash_compiles(one_chip, family, packed):
    blocks = _blocks("minhash", K, NNZ, MINHASH_BLOCKS)
    idx = _arg(one_chip, (N, NNZ), jnp.int32)
    counts = _arg(one_chip, (N,), jnp.int32)
    common = dict(s=S, b=B, backend="tpu", packed=packed, **blocks)
    if family == "2u":
        a = _arg(one_chip, (K,), jnp.uint32)
        _assert_kernel(_minhash2u_run, idx, counts, a, a, variant="high",
                       **common)
    else:
        _assert_kernel(_minhash4u_run, idx, counts,
                       _arg(one_chip, (4, K), jnp.uint32), **common)


@pytest.mark.parametrize("family,segments,s", [
    ("2u", 3 * 4096, 24),       # webspam: 3 segments a row
    ("4u", 40960, 30),          # expanded rcv1: heavy-tailed rows
])
def test_segmented_minhash_compiles(one_chip, family, segments, s):
    """A chunk of 4,096 rows laid out as 1,280-wide segments: the kernel
    with raw minima, the reduction to rows and the pack."""
    idx = _arg(one_chip, (segments, 1280), jnp.int32)
    counts = _arg(one_chip, (segments,), jnp.int32)
    rows = _arg(one_chip, (segments,), jnp.int32)
    common = dict(n=4096, s=s, b=B, backend="tpu", packed=True,
                  **MINHASH_BLOCKS)
    if family == "2u":
        a = _arg(one_chip, (K,), jnp.uint32)
        _assert_kernel(_minhash2u_run, idx, counts, a, a, rows,
                       variant="high", **common)
    else:
        _assert_kernel(_minhash4u_run, idx, counts,
                       _arg(one_chip, (4, K), jnp.uint32), rows, **common)


@pytest.mark.parametrize("sentinel", [False, True])
@pytest.mark.parametrize("family", ["2u", "4u"])
def test_oph_compiles(one_chip, family, sentinel):
    blocks = _blocks("oph", K, NNZ, OPH_BLOCKS)
    k_lanes, blk_k = _oph_lanes(K, blocks.pop("blk_k"))
    idx = _arg(one_chip, (N, NNZ), jnp.int32)
    counts = _arg(one_chip, (N, 1), jnp.int32)
    common = dict(s=S, bin_bits=K.bit_length() - 1, backend="tpu",
                  k_lanes=k_lanes, blk_k=blk_k, code_b=B if sentinel else 0,
                  **blocks)
    if family == "2u":
        a = _arg(one_chip, (1,), jnp.uint32)
        _assert_kernel(_oph2u_raw, idx, counts, a, a, variant="high",
                       **common)
    else:
        _assert_kernel(_oph4u_raw, idx, counts,
                       _arg(one_chip, (4, 1), jnp.uint32), **common)


@pytest.mark.parametrize("k,code_bits,sentinel", [
    (128, 8, False), (512, 8, False), (512, 9, True)])
def test_packed_match_compiles(one_chip, k, code_bits, sentinel):
    words = packed_words(k, code_bits)
    blocks = _blocks("hamming", k, words, HAMMING_BLOCKS)
    _assert_kernel(_packed_match_run,
                   _arg(one_chip, (64, words), jnp.uint32),
                   _arg(one_chip, (4096, words), jnp.uint32),
                   k=k, code_bits=code_bits, sentinel=sentinel,
                   backend="tpu", **blocks)


def test_sigbag_compiles(one_chip):
    _assert_kernel(_sigbag_run, _arg(one_chip, (N, K), jnp.int32),
                   _arg(one_chip, (K, 1 << B, 16), jnp.bfloat16),
                   backend="tpu", blk_n=128)


def test_mesh_exact_scan_compiles_on_four_chips(topo):
    """The router's mesh path: one shard_map exact scan over a ("data",)
    mesh of the four described chips."""
    mesh = jax.sharding.Mesh(np.array(topo.devices[:4]), ("data",))
    k, b, rows, q, topk = 512, 8, 4 * 8192, 16, 10
    words = packed_words(k, b)
    blocks = _blocks("hamming", k, words, HAMMING_BLOCKS)

    def body(qwords, corpus, ids):
        s, i = exact_scan_ids(qwords, corpus, ids, None, None, block=4096,
                              k=k, b=b, code_bits=b, sentinel=False,
                              backend="tpu", D=0, topk=topk, **blocks)
        return s[None], i[None]

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P("data", None), P("data")),
        out_specs=(P("data"), P("data")), check_vma=False))
    rep, rows_sh = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    compiled = fn.lower(
        _arg(rep, (q, words), jnp.uint32),
        _arg(NamedSharding(mesh, P("data", None)), (rows, words), jnp.uint32),
        _arg(rows_sh, (rows,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < rows * words * 4          # the corpus is split
