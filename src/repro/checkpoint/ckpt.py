"""Flat-key npz checkpointing with pytree-structure round trip.

Sharding-aware in the practical sense: arrays are fetched with
``jax.device_get`` (gathering shards) and restored with an optional target
sharding tree, so a checkpoint written on one mesh restores onto another —
the launcher uses this for elastic restarts.
"""
from __future__ import annotations

import json
import os
from typing import Any, Optional

import jax
import numpy as np


SEP = "/"


def _key(path) -> str:
    return jax.tree_util.keystr(path, simple=True, separator=SEP)


def _flatten(tree) -> dict:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _key(path)
        flat[key] = np.asarray(jax.device_get(leaf))
    return flat


def save_checkpoint(path: str, tree, *, step: Optional[int] = None) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    flat = _flatten(tree)
    meta = {"step": step, "keys": sorted(flat)}
    np.savez(path, __meta__=json.dumps(meta), **flat)


def load_checkpoint(path: str, target_tree, *,
                    shardings=None):
    """Restore into the structure of ``target_tree`` (values replaced).
    ``shardings``: optional matching tree of NamedSharding for device_put."""
    with np.load(path, allow_pickle=False) as data:
        flat = {k: data[k] for k in data.files if k != "__meta__"}
        meta = json.loads(str(data["__meta__"]))
    leaves_p, treedef = jax.tree_util.tree_flatten_with_path(target_tree)
    shard_leaves = (jax.tree.leaves(shardings) if shardings is not None
                    else [None] * len(leaves_p))
    out = []
    for (path_k, leaf), shard in zip(leaves_p, shard_leaves):
        key = _key(path_k)
        if key not in flat:
            raise KeyError(f"checkpoint missing {key}")
        arr = flat[key]
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"{key}: shape {arr.shape} != {leaf.shape}")
        out.append(jax.device_put(arr, shard) if shard is not None
                   else jax.device_put(arr))
    return jax.tree_util.tree_unflatten(treedef, out), meta.get("step")
