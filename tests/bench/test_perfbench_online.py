"""webspam-online at CPU size: a sound run is correct; the control (the
reference in bfloat16 in the program's place) and faults planted in the
training step come out not correct."""

import jax.numpy as jnp
import pytest

import repro.train.online as online

from perfbench_util import run_cell

CELL = "webspam-online"


def control_readings(entry, st, ref, traffic):
    return {kind: entry.control(st, ref, 0, kind)
            for kind in ("bfloat16", "half")}


def test_sound_run_correct_and_control_not(tiny_root):
    out = run_cell(tiny_root, CELL, readings=control_readings)
    assert out["correct"] is True
    limits = {n: c["limit"] for n, c in out["checks"].items()}
    for kind, nums in out["readings"].items():
        assert any(nums[n] > limits[n] for n in nums), (kind, nums)


def unchanged(step, state, feats, y, **kw):
    return state


def half_batch(step, state, feats, y, **kw):
    n = y.shape[0] // 2
    return step(state, feats[:n], y[:n], **kw)


def altered_token(step, state, feats, y, **kw):
    return step(state, feats.at[0, 0].set(feats[0, 0] ^ jnp.uint32(1)), y,
                **kw)


@pytest.mark.parametrize("fault", [unchanged, half_batch, altered_token])
def test_fault_in_training_step_is_caught(tiny_root, monkeypatch, fault):
    step = online.sgd_svm_step
    monkeypatch.setattr(online, "sgd_svm_step",
                        lambda state, feats, y, **kw:
                        fault(step, state, feats, y, **kw))
    out = run_cell(tiny_root, CELL)
    assert out["correct"] is False
