"""Mixture-of-experts FFN with sort-based capacity dispatch.

Top-k routing -> stable-sort tokens by expert -> scatter into per-expert
capacity buffers -> batched expert einsum on the MXU -> weighted combine.
O(T*k) bookkeeping, no (T, E, C) one-hot tensor.  Experts are sharded over
the ``model`` mesh axis (expert parallelism); token buffers move between
data- and expert-sharded layouts, which XLA lowers to all-to-all style
collectives under GSPMD.

Follows DeepSeek-MoE structure: ``n_shared`` always-on shared experts plus
``n_experts`` routed experts with ``top_k`` routing and optional
sigmoid+bias (aux-loss-free) or softmax routing.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import swiglu
from repro.sharding.rules import constrain


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                    # per-expert hidden
    n_shared: int = 0
    capacity_factor: float = 1.25
    router: str = "softmax"      # "softmax" | "sigmoid" (aux-loss-free)


def init_moe_params(key, d_model: int, cfg: MoEConfig, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 5)
    E, f = cfg.n_experts, cfg.d_ff
    scale = d_model ** -0.5
    p = {
        "router": (jax.random.normal(ks[0], (d_model, E)) * scale
                   ).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (E, d_model, f)) * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d_model, f)) * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, f, d_model)) * (f ** -0.5)
                   ).astype(dtype),
    }
    if cfg.n_shared:
        fs = cfg.d_ff * cfg.n_shared
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": (jax.random.normal(k1, (d_model, fs)) * scale).astype(dtype),
            "w_up": (jax.random.normal(k2, (d_model, fs)) * scale).astype(dtype),
            "w_down": (jax.random.normal(k3, (fs, d_model)) * (fs ** -0.5)
                       ).astype(dtype),
        }
    return p


def moe_ffn(params: dict, x: jax.Array, cfg: MoEConfig) -> jax.Array:
    """x: (T, d_model) -> (T, d_model).

    Under an active mesh this dispatches to the expert-parallel shard_map
    implementation (``moe_ffn_ep``); the plain-GSPMD path below is the
    single-device / no-mesh reference.  (GSPMD cannot shard the
    data-dependent dispatch gather -- at deepseek-v3 scale the (T*k, d)
    gather is 28 GiB/chip -- so EP is structural, not a tuning choice.)
    """
    from repro.sharding.rules import current_mesh
    mesh = current_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        return moe_ffn_ep(params, x, cfg, mesh)
    return _moe_ffn_dense(params, x, cfg)


def _moe_ffn_dense(params: dict, x: jax.Array, cfg: MoEConfig) -> jax.Array:
    """Reference path (no mesh): sort-based capacity dispatch in plain jnp."""
    T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = _capacity(T, cfg)

    logits = (x.astype(jnp.float32) @ params["router"])      # (T, E)
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(scores, k)                    # (T, k)
    topv = topv / jnp.maximum(jnp.sum(topv, axis=-1, keepdims=True), 1e-9)

    flat_e = topi.reshape(-1)                                # (T*k,)
    flat_w = topv.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T), k)

    order = jnp.argsort(flat_e, stable=True)
    e_sorted = flat_e[order]
    tok_sorted = flat_tok[order]
    w_sorted = flat_w[order]
    starts = jnp.searchsorted(e_sorted, jnp.arange(E))       # (E,)
    pos_in_e = jnp.arange(T * k) - starts[e_sorted]
    keep = pos_in_e < C
    dest = jnp.where(keep, e_sorted * C + pos_in_e, E * C)   # OOB -> dropped

    buf = jnp.zeros((E * C, d), x.dtype).at[dest].set(
        x[tok_sorted], mode="drop").reshape(E, C, d)
    buf = constrain(buf, "model", None, None)     # expert-parallel buffers

    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"]))
         * jnp.einsum("ecd,edf->ecf", buf, params["w_up"]))
    h = constrain(h, "model", None, None)
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    out_buf = constrain(out_buf, "model", None, None)

    gathered = out_buf.reshape(E * C, d)[jnp.where(keep, dest, 0)]
    gathered = gathered * (keep[:, None] & True) * w_sorted[:, None].astype(x.dtype)
    out = jnp.zeros((T, d), x.dtype).at[tok_sorted].add(gathered)

    if "shared" in params:
        sp = params["shared"]
        out = out + swiglu(x, sp["w_gate"], sp["w_up"], sp["w_down"])
    return out


# ---------------------------------------------------------------------------
# Expert-parallel shard_map implementation
# ---------------------------------------------------------------------------

def ep_layout(mesh, E: int):
    """Expert-parallel group: as many mesh axes as E divides into.

    256-expert models span ("model", "data") = the whole 256-chip pod
    (1 expert/chip, full (d, f) weights, NO weight gathering -- the §Perf
    deepseek-v3 iteration); 16-expert models span ("model",) with d_ff
    FSDP'd over the remaining axes and gathered just-in-time.
    Returns (ep_axes, ffn_shard_axes, complement_token_axes).
    """
    ep_axes = []
    size = 1
    for name in ("model", "data"):
        if name in mesh.axis_names and E % (size * mesh.shape[name]) == 0:
            ep_axes.append(name)
            size *= mesh.shape[name]
    ep_axes = tuple(ep_axes)
    ffn_axes = tuple(n for n in ("data", "pod")
                     if n in mesh.axis_names and n not in ep_axes)
    tok_rest = tuple(n for n in ("pod", "data")
                     if n in mesh.axis_names and n not in ep_axes)
    return ep_axes, ffn_axes, tok_rest


def moe_ffn_ep(params: dict, x: jax.Array, cfg: MoEConfig, mesh) -> jax.Array:
    """Expert parallelism via shard_map with token all-to-all dispatch.

    Experts sharded over the EP group (see ep_layout); remaining d_ff
    sharding is FSDP'd and gathered just-in-time.  Fast path (token count
    divides the whole mesh): tokens sharded over every axis, dispatched to
    expert owners by all_to_all over the EP group and combined on the way
    back -- per-chip traffic ~ 2 * T_loc * top_k * d bytes/layer instead
    of re-gathering expert weights every pass.  Fallback (small/indivisible
    token counts, e.g. decode): tokens sharded over the complement axes,
    each chip computes its local experts' contributions, one psum over the
    EP group combines.
    """
    from jax.sharding import PartitionSpec as P
    T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    ep_axes, ffn_axes, tok_rest = ep_layout(mesh, E)
    n_ep = 1
    for n in ep_axes:
        n_ep *= mesh.shape[n]
    n_all = 1
    for n in mesh.axis_names:
        n_all *= mesh.shape[n]
    E_loc = E // n_ep

    wg_spec = P(ep_axes, None, ffn_axes if ffn_axes else None)
    wd_spec = P(ep_axes, ffn_axes if ffn_axes else None, None)

    # Enter shard_map in the activations' NATIVE layout -- tokens over the
    # batch axes, d over "model" -- and convert inside with an explicit
    # all_to_all.  Feeding GSPMD a token-sharded in_spec instead makes it
    # reshard at the boundary by FULL REPLICATION of the (T, d) fp32
    # cotangent (~3.5 GB/layer at deepseek-v3 scale).
    tp = mesh.shape.get("model", 1)
    batch_axes = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
    dp_b = 1
    for n in batch_axes:
        dp_b *= mesh.shape[n]
    d_loc = d // tp if d % tp == 0 else d
    d_spec = "model" if d % tp == 0 else None

    a2a = (T % n_all == 0) and (T // n_all > 0) and d % tp == 0
    if a2a:
        tok_spec = P(batch_axes if batch_axes else None, d_spec)
        T_loc = T // n_all
    else:
        n_rest = 1
        for n in tok_rest:
            n_rest *= mesh.shape[n]
        if tok_rest and T % n_rest == 0:
            tok_spec = P(tok_rest, d_spec)
            T_loc = T // n_rest
        else:
            tok_spec = P(None, d_spec)
            T_loc = T
    C = _capacity_local(T_loc, cfg)

    def _route(x_loc, router_w):
        logits = x_loc.astype(jnp.float32) @ router_w        # (T_loc, E)
        scores = (jax.nn.sigmoid(logits) if cfg.router == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        topv, topi = jax.lax.top_k(scores, k)                # (T_loc, k)
        topv = topv / jnp.maximum(jnp.sum(topv, -1, keepdims=True), 1e-9)
        return topv, topi

    def _dispatch(x_loc, ids, weights, n_buckets, bucket_cap):
        """Sort-based capacity dispatch of (T_loc*k) copies into
        (n_buckets, bucket_cap) slots. ids == n_buckets marks invalid."""
        order = jnp.argsort(ids, stable=True)
        ids_s = ids[order]
        tok_s = (jnp.repeat(jnp.arange(T_loc), k))[order]
        w_s = weights[order]
        starts = jnp.searchsorted(ids_s, jnp.arange(n_buckets))
        pos = jnp.arange(T_loc * k) - starts[ids_s]
        n_slots = n_buckets * bucket_cap
        sl = slice(0, min(n_slots, T_loc * k))
        ids_s, tok_s, w_s, pos = ids_s[sl], tok_s[sl], w_s[sl], pos[sl]
        keep = (ids_s < n_buckets) & (pos < bucket_cap)
        dest = jnp.where(keep, ids_s * bucket_cap + pos, n_slots)
        buf = jnp.zeros((n_slots, d), x_loc.dtype).at[dest].set(
            x_loc[tok_s], mode="drop")
        return buf, dest, tok_s, w_s, keep

    def _experts(buf_e, w_gate, w_up, w_down):
        h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf_e, w_gate))
             * jnp.einsum("ecd,edf->ecf", buf_e, w_up))
        return jnp.einsum("ecf,efd->ecd", h, w_down)

    def _gather_ffn(w_gate, w_up, w_down):
        if ffn_axes:
            w_gate = jax.lax.all_gather(w_gate, ffn_axes, axis=2, tiled=True)
            w_up = jax.lax.all_gather(w_up, ffn_axes, axis=2, tiled=True)
            w_down = jax.lax.all_gather(w_down, ffn_axes, axis=1, tiled=True)
        return w_gate, w_up, w_down

    def block_a2a(x_in, router_w, w_gate, w_up, w_down):
        # (T_b, d/tp) -> (T_b/tp, d): tokens split over "model", d-slices
        # reassembled -- the sequence-parallel -> EP layout switch
        if d_spec is not None and tp > 1:
            x_loc = jax.lax.all_to_all(x_in, "model", split_axis=0,
                                       concat_axis=1, tiled=True)
        else:
            x_loc = x_in
        w_gate, w_up, w_down = _gather_ffn(w_gate, w_up, w_down)
        topv, topi = _route(x_loc, router_w)
        # bucket id = global expert id; owner rank = e // E_loc
        buf, dest, tok_s, w_s, keep = _dispatch(
            x_loc, topi.reshape(-1), topv.reshape(-1), E, C)
        send = buf.reshape(n_ep, E_loc * C, d)
        recv = jax.lax.all_to_all(send, ep_axes, split_axis=0,
                                  concat_axis=0, tiled=True)
        # recv: (n_ep, E_loc*C, d) -- source-major; regroup per expert
        xs = recv.reshape(n_ep, E_loc, C, d).transpose(1, 0, 2, 3) \
            .reshape(E_loc, n_ep * C, d)
        ys = _experts(xs, w_gate, w_up, w_down)
        back = ys.reshape(E_loc, n_ep, C, d).transpose(1, 0, 2, 3) \
            .reshape(n_ep, E_loc * C, d)
        got = jax.lax.all_to_all(back, ep_axes, split_axis=0,
                                 concat_axis=0, tiled=True)
        out_flat = got.reshape(E * C, d)
        contrib = out_flat[jnp.where(keep, dest, 0)] \
            * (keep[:, None] & True) * w_s[:, None].astype(x_loc.dtype)
        y = jnp.zeros((T_loc, d), x_loc.dtype).at[tok_s].add(contrib)
        if d_spec is not None and tp > 1:   # back to (T_b, d/tp)
            y = jax.lax.all_to_all(y, "model", split_axis=1,
                                   concat_axis=0, tiled=True)
        return y

    def block_psum(x_in, router_w, w_gate, w_up, w_down):
        if d_spec is not None and tp > 1:
            x_loc = jax.lax.all_gather(x_in, "model", axis=1, tiled=True)
        else:
            x_loc = x_in
        w_gate, w_up, w_down = _gather_ffn(w_gate, w_up, w_down)
        rank = jnp.int32(0)
        mult = 1
        for n in reversed(ep_axes):
            rank = rank + jax.lax.axis_index(n) * mult
            mult *= mesh.shape[n]
        topv, topi = _route(x_loc, router_w)
        e_local = topi.reshape(-1) - rank * E_loc
        valid = (e_local >= 0) & (e_local < E_loc)
        ids = jnp.where(valid, e_local, E_loc)
        buf, dest, tok_s, w_s, keep = _dispatch(
            x_loc, ids, topv.reshape(-1), E_loc, C)
        ys = _experts(buf.reshape(E_loc, C, d), w_gate, w_up, w_down)
        out_flat = ys.reshape(E_loc * C, d)
        contrib = out_flat[jnp.where(keep, dest, 0)] \
            * (keep[:, None] & True) * w_s[:, None].astype(x_loc.dtype)
        y_loc = jnp.zeros((T_loc, d), x_loc.dtype).at[tok_s].add(contrib)
        y_loc = jax.lax.psum(y_loc, ep_axes)
        if d_spec is not None and tp > 1:   # hand back my d-slice
            j = jax.lax.axis_index("model")
            y_loc = jax.lax.dynamic_slice_in_dim(y_loc, j * d_loc, d_loc, 1)
        return y_loc

    y = jax.shard_map(
        block_a2a if a2a else block_psum, mesh=mesh,
        in_specs=(tok_spec, P(), wg_spec, wg_spec, wd_spec),
        out_specs=tok_spec, check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])

    if "shared" in params:
        sp = params["shared"]
        from repro.sharding.rules import constrain
        hs = jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])
        hs = constrain(hs, "batch", "model")
        y = y + hs @ sp["w_down"]
    return y


def _capacity_local(T_loc: int, cfg: MoEConfig) -> int:
    c = int(T_loc * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(4, ((c + 3) // 4) * 4)


def moe_load_balance_loss(logits: jax.Array, topi: jax.Array, E: int
                          ) -> jax.Array:
    """Switch-style aux loss: E * sum_e f_e * p_e."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    p_e = jnp.mean(probs, axis=0)
    f_e = jnp.mean(jax.nn.one_hot(topi[..., 0], E), axis=0)
    return E * jnp.sum(p_e * f_e)


def _capacity(T: int, cfg: MoEConfig) -> int:
    c = int(T * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(8, ((c + 7) // 8) * 8)
