"""Optimizers, schedules, gradient compression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import (adafactor, adamw, apply_updates, constant,
                         compressed_psum_int8, dequantize_int8, inverse_time,
                         quantize_int8, sgd, topk_decompress,
                         topk_error_feedback, warmup_cosine)


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


def test_int8_quantization_error_bound():
    g = jnp.asarray(np.random.default_rng(1).normal(size=(1000,)), jnp.float32)
    q, scale = quantize_int8(g, jax.random.PRNGKey(0))
    back = dequantize_int8(q, scale)
    # error bounded by one quantization step
    assert float(jnp.max(jnp.abs(back - g))) <= float(scale) / 127.0 + 1e-6


def test_int8_stochastic_rounding_unbiased():
    g = jnp.full((20000,), 0.3337)
    q, scale = quantize_int8(g, jax.random.PRNGKey(1), scale=jnp.float32(1.0))
    mean = float(jnp.mean(dequantize_int8(q, scale)))
    assert abs(mean - 0.3337) < 5e-4


def test_compressed_psum_matches_mean():
    devs = jax.devices()
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(devs[:1]), ("dp",))
    g = jnp.asarray(np.random.default_rng(2).normal(size=(64,)), jnp.float32)

    def f(g):
        return compressed_psum_int8(g, jax.random.PRNGKey(0), "dp")

    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P()))(g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g), atol=0.1)


def test_make_compressed_allreduce_helper():
    from jax.sharding import Mesh
    from repro.optim.compression import make_compressed_allreduce
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("dp",))
    g = jnp.asarray(np.random.default_rng(3).normal(size=(32,)), jnp.float32)
    f = jax.jit(make_compressed_allreduce(mesh))
    out = f(g, jax.random.PRNGKey(1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(g), atol=0.1)


def test_topk_error_feedback_accumulates():
    g = jnp.asarray([1.0, -0.5, 0.25, 0.1])
    residual = jnp.zeros((4,))
    vals, idx, residual, sent = topk_error_feedback(g, residual, k=1)
    assert float(sent[0]) == pytest.approx(1.0)         # largest kept
    assert float(residual[1]) == pytest.approx(-0.5)    # rest carried
    # second step: residual re-enters; -0.5-0.5 = -1.0 now dominates
    vals, idx, residual, sent = topk_error_feedback(g * 0 - jnp.asarray(
        [0.0, 0.5, 0.0, 0.0]), residual, k=1)
    # corrected g[1] = -0.5 + (-0.5)... transmitted eventually
    dense = topk_decompress(vals, idx, (4,))
    assert np.count_nonzero(np.asarray(dense)) == 1
