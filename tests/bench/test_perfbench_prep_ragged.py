"""rcv1x_4u-prep at CPU size: a sound run is correct and the control
(4U Horner steps mod 2^32 in the program's place) is not; the plain 4U
reference agrees with the program's hash family; the new cell's readers
report nothing where the program or the trace gives nothing to read."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench_util import REPO, edit_json, make_tiny_root, run_cell

CELL = "rcv1x_4u-prep"
TINY = {
    "configs/rcv1x_4u.json": {"k": 128, "n": 256,
                              "assumed": {"terms_median": 16}},
    "traffic/prep-ragged.json": {"shards": 2, "rows_per_shard": 128,
                                 "chunk_size": 128, "check_sample": 12},
}


@pytest.fixture
def ragged_root(tmp_path):
    """The tiny benchmark tree with this cell, its configuration and its
    metrics taken from the repository's ``BENCHMARK.json``."""
    root = make_tiny_root(tmp_path / "root")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        tiny = json.load(f)
    cell = next(c for c in spec["workloads"] if c["name"] == CELL)
    tiny["workloads"].append(cell)
    tiny["configs"] += [c for c in spec["configs"]
                        if c["name"] == cell["config"]]
    tiny["per_layer"] += [m for m in spec["per_layer"]
                          if CELL in m.get("workloads", [])]
    for m in tiny["end_to_end"]:
        if m["name"] == "prep_rows_per_s":
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(tiny, f)
    for rel, changes in TINY.items():
        edit_json(os.path.join(root, "bench", rel), changes)
    return root


def control_readings(entry, st, ref, traffic):
    longest = int(np.argmax(st["lens"]))
    _, rows = entry.sample(st, int(traffic["check_sample"]))
    return {"control": entry.control(st, ref, int(traffic["check_sample"])),
            "longest_sampled": bool(longest in rows),
            "longest_segments": -(-int(st["lens"].max()) // 1280)}


def test_sound_run_correct_and_control_not(ragged_root):
    out = run_cell(ragged_root, CELL, readings=control_readings)
    assert out["correct"] is True
    assert out["checks"]["rows_differ"]["value"] == 0
    got = out["readings"]
    assert got["control"]["rows_differ"] > out["checks"]["rows_differ"][
        "limit"]
    assert got["longest_sampled"] and got["longest_segments"] > 1


def _ref():
    from bench import run as harness
    return harness.load_module(
        os.path.join(REPO, "bench", "configs", "rcv1x_4u_ref.py"),
        "bench_ref_rcv1x_4u_test")


@pytest.mark.parametrize("s", [30, 20])
def test_reference_matches_hash4u(s):
    """The plain uint64 reference equals ``repro.core.hashing.Hash4U``
    on random ids below 2^30; its narrow control does not."""
    from repro.core.hashing import Hash4U
    ref = _ref()
    rng = np.random.default_rng(s)
    rows = [rng.integers(0, 1 << 30, n) for n in (1, 37, 300)]
    fam = Hash4U.create(jax.random.PRNGKey(s), 64, s)
    a = np.asarray(fam.a)
    want = np.stack([np.asarray(jnp.min(fam(jnp.asarray(r, jnp.int32)),
                                        axis=0)) & 0xFF for r in rows])
    np.testing.assert_array_equal(ref.minhash_codes(rows, a, s, 8), want)
    assert (ref.minhash_codes(rows, a, s, 8, narrow=True) != want).any()


def test_reference_blocks_and_pack():
    """A row longer than a block gives its minimum over all blocks; the
    pack puts code j at bits [j*b, (j+1)*b)."""
    ref = _ref()
    rng = np.random.default_rng(3)
    a = rng.integers(0, 2**31 - 1, (4, 32)).astype(np.uint32)
    row = rng.integers(0, 1 << 30, 2 * ref.BLOCK + 5)
    whole = ref._block_min(row, a.astype(np.uint64), 30, False)
    np.testing.assert_array_equal(ref.minhash_codes([row], a, 30, 8)[0],
                                  whole & 0xFF)
    codes = np.arange(8, dtype=np.uint32).reshape(1, 8)
    assert ref.pack(codes, 8).tolist() == [[0x03020100, 0x07060504]]


def _reader(name):
    from bench import run as harness
    return harness.load_module(os.path.join(REPO, "bench", "metrics",
                                            name + ".py"), "m_" + name)


def test_slot_share_reader():
    read = _reader("minhash_slot_share").read
    rec = types.SimpleNamespace(stats={"nonzeros": 95, "slots_hashed": 100})
    assert read(rec) == pytest.approx(95.0)
    for stats in ({"nonzeros": 95, "slots_hashed": 0}, {"nonzeros": 95}):
        assert read(types.SimpleNamespace(stats=stats)) is None


def test_ghash4u_reader():
    read = _reader("minhash4u_ghash_per_s").read
    trace = types.SimpleNamespace(
        kernel_s=lambda p: {"minhash4u": 2.0}.get(p))
    rec = types.SimpleNamespace(stats={"hash_evals": 4e9}, trace=trace)
    assert read(rec) == pytest.approx(2.0)
    none = types.SimpleNamespace(kernel_s=lambda p: None)
    assert read(types.SimpleNamespace(stats={"hash_evals": 4e9},
                                      trace=none)) is None
