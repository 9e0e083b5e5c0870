"""Checkpointing: flat-key .npz save/restore with partial (sliced) reads.

Inference weights are static (no periodic checkpointing needed — the
paper's point about training vs inference recovery), but the checkpoint
is the *disk source* for the role-switch path: a switched MoEExecutor
re-loads only its expert slice from here (§3.4).
"""
from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np


def _flatten(params) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        key = "/".join(_key_str(k) for k in path)
        flat[key] = np.asarray(leaf)
    return flat


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    if hasattr(k, "name"):
        return str(k.name)
    return str(k)


def save_checkpoint(path: str, params, extra: Optional[Dict] = None) -> float:
    """Returns elapsed seconds."""
    t0 = time.perf_counter()
    flat = _flatten(params)
    if extra:
        for k, v in extra.items():
            flat[f"__extra__/{k}"] = np.asarray(v)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    np.savez(path, **flat)
    return time.perf_counter() - t0


def load_flat(path: str) -> Dict[str, np.ndarray]:
    with np.load(path, allow_pickle=False) as z:
        return {k: z[k] for k in z.files if not k.startswith("__extra__/")}


def as_dtype(arr: np.ndarray, dtype) -> np.ndarray:
    """``arr`` as ``dtype``.  ``np.savez`` writes dtypes numpy does not
    know (bfloat16) as raw ``|V`` bytes; those are viewed, bit-exact, as
    the wanted dtype of the same width.  Anything else is cast."""
    dtype = np.dtype(dtype)
    if arr.dtype.kind == "V":
        assert arr.dtype.itemsize == dtype.itemsize, (arr.dtype, dtype)
        return arr.view(dtype)
    return arr.astype(dtype, copy=False)


def restore_like(path: str, template) -> Any:
    """Restore a pytree shaped like ``template`` from the checkpoint, in
    the template's dtypes."""
    flat = load_flat(path)
    paths, tdef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for p, leaf in paths:
        key = "/".join(_key_str(k) for k in p)
        arr = flat[key]
        assert arr.shape == leaf.shape, (key, arr.shape, leaf.shape)
        leaves.append(as_dtype(arr, leaf.dtype))
    return tdef.unflatten(leaves)


def load_keys(path: str, predicate: Callable[[str], bool],
              slicer: Optional[Callable[[str, np.ndarray], np.ndarray]] = None
              ) -> Dict[str, np.ndarray]:
    """Partial read: only keys matching ``predicate`` (e.g. one EP rank's
    expert slice) — the role-switch weight load."""
    out = {}
    with np.load(path, allow_pickle=False) as z:
        for k in z.files:
            if predicate(k):
                arr = z[k]
                out[k] = slicer(k, arr) if slicer else arr
    return out
