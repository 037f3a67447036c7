"""Checkpointing, restart, straggler heartbeat."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import (Heartbeat, TrainState, Trainer, checkpoint,
                         make_train_step, run_with_restarts)
from repro.optim import adamw, constant


def _toy_state():
    params = {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
              "b": jnp.ones((4,), jnp.bfloat16)}
    opt = adamw(constant(0.1))
    return TrainState.create(params, opt), opt


def test_save_restore_roundtrip(tmp_path):
    state, _ = _toy_state()
    d = str(tmp_path / "ckpt")
    checkpoint.save(d, 7, state)
    template = jax.tree_util.tree_map(jnp.zeros_like, state)
    restored, step = checkpoint.restore(d, template)
    assert step == 7
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_keep_n_gc_and_latest(tmp_path):
    state, _ = _toy_state()
    d = str(tmp_path / "ckpt")
    for s in (1, 2, 3, 4, 5):
        checkpoint.save(d, s, state, keep=2)
    assert checkpoint.latest_step(d) == 5
    kept = sorted(os.listdir(d))
    assert kept == ["step_00000004", "step_00000005"]


def test_atomicity_no_tmp_left(tmp_path):
    state, _ = _toy_state()
    d = str(tmp_path / "ckpt")
    checkpoint.save(d, 1, state)
    assert not [f for f in os.listdir(d) if f.startswith(".tmp")]


def test_trainer_restarts_after_failure(tmp_path):
    """A step that raises once mid-run resumes from checkpoint."""
    opt = adamw(constant(0.1))
    params = {"w": jnp.zeros((4,))}
    state = TrainState.create(params, opt)
    loss = lambda p, batch: jnp.sum((p["w"] - batch) ** 2)
    base_step = make_train_step(loss, opt)
    boom = {"armed": True}

    def flaky_step(st, batch):
        if boom["armed"] and int(st.step) == 7:
            boom["armed"] = False
            raise RuntimeError("injected node failure")
        return base_step(st, batch)

    tr = Trainer(flaky_step, ckpt_dir=str(tmp_path / "ck"), ckpt_every=5,
                 jit=False, max_failures=2)
    batches = lambda: iter([jnp.ones((4,))] * 20)
    final = tr.fit(state, batches, 20)
    assert int(final.step) == 20
    assert not boom["armed"]  # failure actually happened


def test_run_with_restarts_exhausts():
    def always_fails(state, step):
        raise ValueError("dead")

    with pytest.raises(ValueError):
        run_with_restarts(init_state=0, init_step=0, run_steps=always_fails,
                          restore_fn=lambda: (0, 0), max_failures=2)


def test_heartbeat_straggler_detection():
    hb = Heartbeat(deadline_s=0.1)
    assert not hb.observe(0.05)
    assert hb.observe(0.5)
    assert hb.stragglers == 1
    for _ in range(10):
        hb.observe(0.01)
    assert hb.adaptive_deadline(factor=3.0) == pytest.approx(0.03, rel=0.5)
