"""Sharding rules, EP MoE vs dense oracle, fused optimizer parity,
HLO collective parsing."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.moe import (MoEConfig, _moe_ffn_dense, ep_layout,
                              init_moe_params, moe_ffn)
from repro.optim import adafactor, constant
from repro.optim.base import apply_updates
from repro.optim.optimizers import adafactor_fused
from repro.roofline.hlo import collective_bytes, shape_bytes
from repro.sharding.rules import constrain, set_mesh, spec


@pytest.fixture(scope="module")
def mesh8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs >= 8 devices (run under "
                    "--xla_force_host_platform_device_count)")
    return jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def test_constrain_is_noop_without_mesh():
    x = jnp.ones((4, 4))
    assert constrain(x, "batch", "model") is x


def test_spec_resolution(mesh8):
    def flat(entry):
        if entry is None:
            return ()
        return (entry,) if isinstance(entry, str) else tuple(entry)

    with set_mesh(mesh8):
        s = tuple(spec("batch", None, "model"))
        assert flat(s[0]) == ("data",)
        assert s[1] is None and flat(s[2]) == ("model",)
        s_all = tuple(spec("all"))
        assert flat(s_all[0]) == ("data", "model")


def test_constrain_drops_indivisible(mesh8):
    with set_mesh(mesh8):
        x = jnp.ones((6, 8))      # 6 % 2 == 0 but 6 % ... model=4: 8%4==0
        y = constrain(x, "model", None)   # 6 % 4 != 0 -> dropped
        assert y.shape == x.shape  # compiles as replicated, no error


def test_ep_layout():
    class M:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": 4}
    ep, ffn, rest = ep_layout(M, 8)
    assert ep == ("model", "data") and ffn == () and rest == ()
    ep, ffn, rest = ep_layout(M, 4)
    assert ep == ("model",) and ffn == ("data",) and rest == ("data",)


@pytest.mark.parametrize(
    "T", [64, pytest.param(6, marks=pytest.mark.slow)])  # a2a; psum fallback
def test_moe_ep_matches_dense(mesh8, T):
    cfg = MoEConfig(n_experts=8, top_k=2, d_ff=32, n_shared=1,
                    router="sigmoid", capacity_factor=8.0)
    params = init_moe_params(jax.random.PRNGKey(0), 64, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, 64))
    dense = _moe_ffn_dense(params, x, cfg)
    with set_mesh(mesh8):
        ep = jax.jit(lambda p, xx: moe_ffn(p, xx, cfg))(params, x)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ep),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_moe_ep_gradients(mesh8):
    cfg = MoEConfig(n_experts=8, top_k=2, d_ff=16, n_shared=0,
                    router="softmax", capacity_factor=4.0)
    params = init_moe_params(jax.random.PRNGKey(2), 32, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (32, 32))
    with set_mesh(mesh8):
        g = jax.jit(jax.grad(
            lambda p: jnp.sum(moe_ffn(p, x, cfg) ** 2)))(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    total = sum(float(jnp.sum(jnp.abs(l)))
                for l in jax.tree_util.tree_leaves(g))
    assert total > 0


def test_adafactor_fused_matches_unfused():
    """Fused (apply-included, layer-scanned) == plain adafactor + apply."""
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (12, 6, 8)),
              "b": jnp.ones((8,))}
    grads = jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.PRNGKey(1), p.shape) * 0.01,
        params)
    # huge clip threshold: per-slice vs whole-tensor update clipping is the
    # one intentional semantic difference; disable it to compare the math
    plain = adafactor(constant(0.1), momentum=None, clip_threshold=1e9)
    fused = adafactor_fused(constant(0.1), momentum=None,
                            scan_min_leading=4, clip_threshold=1e9)
    s1, s2 = plain.init(params), fused.init(params)
    u, s1 = plain.update(grads, s1, params)
    p_plain = apply_updates(params, u)
    p_fused, s2 = fused.update(grads, s2, params)
    for a, b in zip(jax.tree_util.tree_leaves(p_plain),
                    jax.tree_util.tree_leaves(p_fused)):
        # per-slice update clipping can differ from whole-tensor clipping
        # only when the clip is active; with tiny grads it is not
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-5)


def test_hlo_collective_parser():
    hlo = """
  %ar = f32[128,256]{1,0} all-reduce(f32[128,256]{1,0} %x), replica_groups={}
  %ag = bf16[64,64]{1,0} all-gather(bf16[8,64]{1,0} %y), dimensions={0}
  %dot = f32[4,4]{1,0} dot(f32[4,4]{1,0} %a, f32[4,4]{1,0} %b)
"""
    total, breakdown = collective_bytes(hlo)
    assert breakdown["all-reduce"] == 128 * 256 * 4
    assert breakdown["all-gather"] == 64 * 64 * 2      # max(result, operand)
    assert total == breakdown["all-reduce"] + breakdown["all-gather"]
    assert shape_bytes("bf16", "2,3") == 12


def test_param_specs_cover_all_archs():
    """Every arch's param tree gets a spec tree with matching structure."""
    from repro.configs import all_archs
    from repro.launch.steps import build_cell
    from repro.configs import cells_for, is_skipped
    for arch_id in sorted(all_archs()):
        cell = next(c for c in cells_for(arch_id)
                    if not is_skipped(arch_id, c.name))
        prog = build_cell(arch_id, cell.name, smoke=True)
        n_p = len(jax.tree_util.tree_leaves(prog.param_avals))
        n_s = len(jax.tree_util.tree_leaves(
            prog.param_specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
        assert n_p == n_s, arch_id
