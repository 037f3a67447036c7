"""Concrete optimizers: SGD(+momentum), AdamW, Adafactor.

Adafactor (Shazeer & Stern) factors second moments into row/col
statistics (O(n+m) instead of O(nm)) and keeps first-moment momentum in
bf16.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.optim.base import Optimizer


def sgd(lr: Callable[[jax.Array], jax.Array] | float,
        momentum: float = 0.0) -> Optimizer:
    lr_fn = lr if callable(lr) else (lambda _: lr)

    def init(params):
        mu = (jax.tree_util.tree_map(jnp.zeros_like, params)
              if momentum else ())
        return {"count": jnp.zeros((), jnp.int32), "mu": mu}

    def update(grads, state, params):
        count = state["count"]
        step = lr_fn(count)
        if momentum:
            mu = jax.tree_util.tree_map(
                lambda m, g: momentum * m + g, state["mu"], grads)
            upd = jax.tree_util.tree_map(lambda m: -step * m, mu)
            return upd, {"count": count + 1, "mu": mu}
        upd = jax.tree_util.tree_map(lambda g: -step * g, grads)
        return upd, {"count": count + 1, "mu": ()}

    return Optimizer(init, update)


def adamw(lr: Callable[[jax.Array], jax.Array] | float, b1: float = 0.9,
          b2: float = 0.999, eps: float = 1e-8,
          weight_decay: float = 0.0) -> Optimizer:
    lr_fn = lr if callable(lr) else (lambda _: lr)

    def init(params):
        zeros32 = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {"count": jnp.zeros((), jnp.int32),
                "m": jax.tree_util.tree_map(zeros32, params),
                "v": jax.tree_util.tree_map(zeros32, params)}

    def update(grads, state, params):
        count = state["count"] + 1
        cf = count.astype(jnp.float32)
        m = jax.tree_util.tree_map(
            lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32),
            state["m"], grads)
        v = jax.tree_util.tree_map(
            lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g.astype(jnp.float32)),
            state["v"], grads)
        mh = 1.0 - b1 ** cf
        vh = 1.0 - b2 ** cf
        step = lr_fn(state["count"])

        def upd(m_, v_, p):
            u = (m_ / mh) / (jnp.sqrt(v_ / vh) + eps)
            if weight_decay:
                u = u + weight_decay * p.astype(jnp.float32)
            return (-step * u).astype(p.dtype)

        updates = jax.tree_util.tree_map(upd, m, v, params)
        return updates, {"count": count, "m": m, "v": v}

    return Optimizer(init, update)


def adafactor(lr: Callable[[jax.Array], jax.Array] | float,
              momentum: Optional[float] = 0.9,
              momentum_dtype=jnp.bfloat16,
              decay: float = 0.8, eps: float = 1e-30,
              clip_threshold: float = 1.0) -> Optimizer:
    """Factored-second-moment optimizer for very large models."""
    lr_fn = lr if callable(lr) else (lambda _: lr)

    def _factored(p):
        return p.ndim >= 2

    def init(params):
        def v_for(p):
            if _factored(p):
                return {"vr": jnp.zeros(p.shape[:-1], jnp.float32),
                        "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32)}
            return {"v": jnp.zeros(p.shape, jnp.float32)}

        state = {"count": jnp.zeros((), jnp.int32),
                 "v": jax.tree_util.tree_map(v_for, params,
                                             is_leaf=lambda x: isinstance(x, jax.Array))}
        if momentum is not None:
            state["m"] = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, momentum_dtype), params)
        return state

    def update(grads, state, params):
        count = state["count"] + 1
        beta2 = 1.0 - count.astype(jnp.float32) ** (-decay)
        step = lr_fn(state["count"])

        def upd_one(g, p, v):
            g32 = g.astype(jnp.float32)
            g2 = jnp.square(g32) + eps
            if _factored(p):
                vr = beta2 * v["vr"] + (1 - beta2) * jnp.mean(g2, axis=-1)
                vc = beta2 * v["vc"] + (1 - beta2) * jnp.mean(g2, axis=-2)
                denom_r = vr / jnp.maximum(
                    jnp.mean(vr, axis=-1, keepdims=True), eps)
                precond = g32 / (jnp.sqrt(denom_r)[..., None]
                                 * jnp.sqrt(vc)[..., None, :] + eps)
                new_v = {"vr": vr, "vc": vc}
            else:
                vv = beta2 * v["v"] + (1 - beta2) * g2
                precond = g32 / (jnp.sqrt(vv) + eps)
                new_v = {"v": vv}
            # update clipping (RMS <= clip_threshold)
            rms = jnp.sqrt(jnp.mean(jnp.square(precond)) + 1e-30)
            precond = precond / jnp.maximum(1.0, rms / clip_threshold)
            return precond, new_v

        flat_g, tdef = jax.tree_util.tree_flatten(grads)
        flat_p = tdef.flatten_up_to(params)
        flat_v = tdef.flatten_up_to(state["v"])
        outs = [upd_one(g, p, v) for g, p, v in zip(flat_g, flat_p, flat_v)]
        precs = tdef.unflatten([o[0] for o in outs])
        new_v = tdef.unflatten([o[1] for o in outs])

        new_state = {"count": count, "v": new_v}
        if momentum is not None:
            m = jax.tree_util.tree_map(
                lambda m_, u: (momentum * m_.astype(jnp.float32)
                               + (1 - momentum) * u).astype(momentum_dtype),
                state["m"], precs)
            new_state["m"] = m
            updates = jax.tree_util.tree_map(
                lambda m_, p: (-step * m_.astype(jnp.float32)).astype(p.dtype),
                m, params)
        else:
            updates = jax.tree_util.tree_map(
                lambda u, p: (-step * u).astype(p.dtype), precs, params)
        return updates, new_state

    return Optimizer(init, update)
