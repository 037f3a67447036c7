"""webspam-prep at CPU size: a sound run is correct; the control (16-bit
hashing in the program's place) and faults planted in the timed path
come out not correct."""

import jax.numpy as jnp
import pytest

from repro.kernels.engine import PackedSignatures, SignatureEngine

from perfbench_util import run_cell

CELL = "webspam-prep"


def control_readings(entry, st, ref, traffic):
    return entry.control(st, ref, int(traffic["check_sample"]))


def test_sound_run_correct_and_control_not(tiny_root):
    out = run_cell(tiny_root, CELL, readings=control_readings)
    assert out["correct"] is True
    assert out["checks"]["rows_differ"]["value"] == 0
    assert out["readings"]["rows_differ"] > out["checks"]["rows_differ"][
        "limit"]


def altered(words):
    return words.at[:, 0].set(words[:, 0] ^ jnp.uint32(1))


def half_rows(words):
    half = words.shape[0] // 2
    return jnp.concatenate([words[:half], words[:words.shape[0] - half]])


@pytest.mark.parametrize("fault", [altered, half_rows])
def test_fault_in_timed_path_is_caught(tiny_root, monkeypatch, fault):
    orig = SignatureEngine.packed_signatures

    def broken(self, batch):
        p = orig(self, batch)
        return PackedSignatures(fault(p.data), p.k, p.b, p.sentinel)

    monkeypatch.setattr(SignatureEngine, "packed_signatures", broken)
    out = run_cell(tiny_root, CELL)
    assert out["correct"] is False
