"""Optimizers and schedules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import (adafactor, adamw, apply_updates, constant,
                         inverse_time, sgd, warmup_cosine)


def _quadratic():
    A = jnp.asarray(np.random.default_rng(0).normal(size=(8, 8)), jnp.float32)
    A = A @ A.T + 0.5 * jnp.eye(8)
    b = jnp.ones((8,))

    def loss(params):
        x = params["x"]
        return 0.5 * x @ A @ x - b @ x + jnp.sum(params["y"]["z"] ** 2)

    params = {"x": jnp.ones((8,)) * 3.0, "y": {"z": jnp.ones((4, 4))}}
    return loss, params


@pytest.mark.parametrize("make_opt", [
    lambda: sgd(0.02, momentum=0.9),
    lambda: adamw(constant(0.1)),
    lambda: adafactor(constant(0.5)),
])
def test_optimizers_decrease_quadratic(make_opt):
    loss, params = _quadratic()
    opt = make_opt()
    state = opt.init(params)
    l0 = float(loss(params))
    step = jax.jit(lambda p, s: _opt_step(opt, loss, p, s))
    for _ in range(120):
        params, state = step(params, state)
    assert float(loss(params)) < 0.2 * l0


def _opt_step(opt, loss, params, state):
    g = jax.grad(loss)(params)
    u, state = opt.update(g, state, params)
    return apply_updates(params, u), state


def test_adafactor_state_is_factored():
    _, params = _quadratic()
    opt = adafactor(constant(0.1))
    state = opt.init(params)
    # matrix param (4,4) stores vr (4,) and vc (4,), not (4,4)
    assert state["v"]["y"]["z"]["vr"].shape == (4,)
    assert state["v"]["y"]["z"]["vc"].shape == (4,)
    # vector param keeps full second moment
    assert state["v"]["x"]["v"].shape == (8,)


def test_schedules():
    assert float(constant(0.1)(jnp.int32(5))) == pytest.approx(0.1)
    it = inverse_time(1.0, 0.1)
    assert float(it(jnp.int32(0))) == pytest.approx(1.0)
    assert float(it(jnp.int32(90))) == pytest.approx(1.0 / 10.0)
    wc = warmup_cosine(1.0, 10, 100)
    assert float(wc(jnp.int32(4))) == pytest.approx(0.5)   # (c+1)/warmup
    assert float(wc(jnp.int32(0))) > 0.0                   # step 0 trains
    assert float(wc(jnp.int32(100))) == pytest.approx(0.1, rel=1e-2)
