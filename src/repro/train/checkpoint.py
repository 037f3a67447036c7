"""Step-atomic sharded checkpointing with keep-N GC and resume.

Layout:  <dir>/step_00001234/  arrays.npz  meta.json
Writes go to ``<dir>/.tmp_step_xxx`` then ``os.replace`` (atomic on POSIX),
so a crash mid-write never corrupts the latest checkpoint -- the
fault-tolerance contract the restart path relies on.

Arrays are flattened with their tree paths as keys, so restore works into
any pytree with the same structure, and ``restore(..., shardings=...)``
can place each array under a *different* sharding/mesh than it was saved
with.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import threading
import time
from typing import Any, Optional

import jax
import numpy as np

_STEP_RE = re.compile(r"^step_(\d{8})$")


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def save(ckpt_dir: str, step: int, tree: Any, *, keep: int = 3,
         extra_meta: Optional[dict] = None) -> str:
    """Save a pytree checkpoint. Returns the final directory path."""
    os.makedirs(ckpt_dir, exist_ok=True)
    name = f"step_{step:08d}"
    tmp = os.path.join(ckpt_dir, f".tmp_{name}_{os.getpid()}")
    final = os.path.join(ckpt_dir, name)
    os.makedirs(tmp, exist_ok=True)

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    arrays = {}
    dtypes = {}
    for path, leaf in flat:
        key = _path_str(path)
        arr = np.asarray(jax.device_get(leaf))
        if arr.dtype == np.dtype("bfloat16"):
            dtypes[key] = "bfloat16"
            arr = arr.view(np.uint16)
        arrays[key] = arr
    np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
    meta = {"step": step, "time": time.time(), "n_arrays": len(arrays),
            "bf16_keys": dtypes, **(extra_meta or {})}
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump(meta, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.replace(tmp, final)
    _gc(ckpt_dir, keep)
    return final


def save_async(ckpt_dir: str, step: int, tree: Any, *, keep: int = 3
               ) -> threading.Thread:
    """Fire-and-forget checkpoint write (device_get happens up front so the
    training loop can continue mutating device state)."""
    host_tree = jax.tree_util.tree_map(lambda x: np.asarray(jax.device_get(x)),
                                       tree)
    t = threading.Thread(target=save, args=(ckpt_dir, step, host_tree),
                         kwargs={"keep": keep}, daemon=True)
    t.start()
    return t


def latest_step(ckpt_dir: str) -> Optional[int]:
    if not os.path.isdir(ckpt_dir):
        return None
    steps = []
    for d in os.listdir(ckpt_dir):
        m = _STEP_RE.match(d)
        if m and os.path.exists(os.path.join(ckpt_dir, d, "meta.json")):
            steps.append(int(m.group(1)))
    return max(steps) if steps else None


def restore(ckpt_dir: str, template: Any, step: Optional[int] = None,
            shardings: Any = None) -> tuple[Any, int]:
    """Restore into the structure of ``template``.

    If ``shardings`` (a matching pytree of jax.sharding.Sharding or None) is
    given, each array is device_put under it, so a checkpoint saved on one
    mesh restores onto another.
    """
    step = step if step is not None else latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(f"no checkpoint in {ckpt_dir}")
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(d, "meta.json")) as f:
        meta = json.load(f)
    bf16_keys = meta.get("bf16_keys", {})
    data = np.load(os.path.join(d, "arrays.npz"))

    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    shard_flat = (jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: x is None or hasattr(x, "device_set"))
        if shardings is not None else [None] * len(flat))
    leaves = []
    for (path, leaf), shd in zip(flat, shard_flat):
        key = _path_str(path)
        arr = data[key]
        if key in bf16_keys:
            arr = arr.view(jax.numpy.bfloat16.dtype)
        if shd is not None:
            leaves.append(jax.device_put(arr, shd))
        else:
            leaves.append(jax.numpy.asarray(arr))
    return treedef.unflatten(leaves), step


def _gc(ckpt_dir: str, keep: int) -> None:
    steps = sorted(
        int(m.group(1)) for d in os.listdir(ckpt_dir)
        if (m := _STEP_RE.match(d)))
    for s in steps[:-keep] if keep > 0 else []:
        shutil.rmtree(os.path.join(ckpt_dir, f"step_{s:08d}"),
                      ignore_errors=True)
