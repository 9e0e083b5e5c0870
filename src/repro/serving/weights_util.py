"""Expert-weight sharding utilities for the simulated multi-executor runtime.

The authoritative storage of routed-expert weights is per-EP-rank shards
(physically separate host numpy arrays), so a rank failure genuinely
destroys its weights.  The device holds the physical expert bank once,
inside the served params: a rank's death zeroes its slice of the bank in
place (the runtime never routes to it), and a rank that comes back has
its shard written into its slice in place — the bank is never rebuilt
or uploaded whole after start-up.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_LEAF_NAMES = ("gate", "up", "down")
EXPERT_AXIS = 1  # stacked layer params: (L, E_phys, ...)


def _path_keys(path) -> Tuple[str, ...]:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
        else:
            out.append(str(k))
    return tuple(out)


def is_expert_leaf(path) -> bool:
    keys = _path_keys(path)
    return "moe" in keys and keys[-1] in EXPERT_LEAF_NAMES


def path_str(path) -> str:
    return "/".join(_path_keys(path))


def split_experts(params, ep_size: int) -> List[Dict[str, np.ndarray]]:
    """Host copies of each EP rank's expert slots.

    shards[r]: {path_str: np.ndarray slice} — rank r's physical slots of
    every routed-expert leaf.  ``params`` is left as it is: it already is
    the assembled bank with every rank alive.
    """
    shards: List[Dict[str, np.ndarray]] = [dict() for _ in range(ep_size)]
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if not is_expert_leaf(path):
            continue
        E = leaf.shape[EXPERT_AXIS]
        assert E % ep_size == 0, (path_str(path), E, ep_size)
        per = E // ep_size
        arr = np.asarray(leaf)
        for r in range(ep_size):
            shards[r][path_str(path)] = np.array(
                arr[:, r * per:(r + 1) * per])
    return shards


def expert_leaf_keys(params) -> List[str]:
    """``path_str`` of every routed-expert leaf, in tree order."""
    return [path_str(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(params)[0]
            if is_expert_leaf(p)]


def _expert_leaves(params) -> list:
    return [leaf for p, leaf in
            jax.tree_util.tree_flatten_with_path(params)[0]
            if is_expert_leaf(p)]


def _with_expert_leaves(params, leaves):
    it = iter(leaves)
    return jax.tree_util.tree_map_with_path(
        lambda p, leaf: next(it) if is_expert_leaf(p) else leaf, params)


def bank_programs(params, ep_size: int) -> Dict[tuple, tuple]:
    """The in-place rank-slice updates of the expert bank, keyed for the
    graph cache: ``{key: (fn, arg_specs)}``, each ``fn`` to be jitted
    with argument 0 donated.

    * ``("bank_zero", 0, None)``: ``fn(leaves, start)`` zeroes slots
      ``[start, start + per)`` of every expert leaf (a rank died);
    * ``("bank_write", 0, shape)``: ``fn(leaf, value, start)`` writes a
      rank's host slice into one leaf of that shape (a rank came back) —
      one leaf at a time, so the device holds at most one leaf's slice
      beside the bank.

    ``start`` is traced, so one executable serves every rank."""
    leaves = _expert_leaves(params)
    per = leaves[0].shape[EXPERT_AXIS] // ep_size

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    def sliced(shape):
        return shape[:EXPERT_AXIS] + (per,) + shape[EXPERT_AXIS + 1:]

    def zero(leaves, start):
        return [jax.lax.dynamic_update_slice_in_dim(
            l, jnp.zeros(sliced(l.shape), l.dtype), start, axis=EXPERT_AXIS)
            for l in leaves]

    def write(leaf, value, start):
        return jax.lax.dynamic_update_slice_in_dim(
            leaf, value.astype(leaf.dtype), start, axis=EXPERT_AXIS)

    start = spec((), jnp.int32)
    progs = {("bank_zero", 0, None): (
        zero, ([spec(l.shape, l.dtype) for l in leaves], start))}
    for l in leaves:
        progs[("bank_write", 0, tuple(l.shape))] = (
            write, (spec(l.shape, l.dtype), spec(sliced(l.shape), l.dtype),
                    start))
    return progs


def update_rank_slice(params, compiled, rank: int, per: int, shard=None):
    """Zero rank ``rank``'s slice of the expert bank (``shard`` None) or
    write ``shard`` into it, in place.  ``compiled(key)`` returns the
    executable of a :func:`bank_programs` key; the old expert buffers
    are donated, so no second bank is ever allocated."""
    start = np.int32(rank * per)
    if shard is None:
        new = compiled(("bank_zero", 0, None))(_expert_leaves(params), start)
    else:
        new = [compiled(("bank_write", 0, tuple(l.shape)))(
            l, shard[k], start) for k, l in
            zip(expert_leaf_keys(params), _expert_leaves(params))]
    return _with_expert_leaves(params, new)


def shard_ckpt_path(workdir: str, ep_rank: int, ep_size: int) -> str:
    import os
    return os.path.join(workdir,
                        f"expert_shard_{ep_rank}_of_{ep_size}.npz")


def save_shard_checkpoints(workdir: str,
                           shards: List[Dict[str, np.ndarray]]) -> None:
    """Per-EP-rank shard files — production keeps each rank's expert
    weights addressable on disk, so a role switch reads exactly one
    rank's slice (§3.4), not the whole model."""
    import os
    for r, sh in enumerate(shards):
        path = shard_ckpt_path(workdir, r, len(shards))
        if not os.path.exists(path):
            np.savez(path, **{k.replace("/", "|"): v for k, v in sh.items()})


def load_expert_shard_from_checkpoint(ckpt_path: str, template_shard: Dict,
                                      ep_rank: int, ep_size: int, *,
                                      workdir: str = None
                                      ) -> Dict[str, np.ndarray]:
    """Role-switch weight load (§3.4): read this rank's expert shard from
    disk — the per-rank shard file when present, else slice the full
    checkpoint.  Arrays come back in the template shard's dtypes
    (bfloat16 is stored as raw bytes, see ``checkpoint.as_dtype``)."""
    import os
    from repro.training.checkpoint import as_dtype, load_keys
    wanted = set(template_shard.keys())
    if workdir is not None:
        spath = shard_ckpt_path(workdir, ep_rank, ep_size)
        if os.path.exists(spath):
            with np.load(spath, allow_pickle=False) as z:
                loaded = {k.replace("|", "/"): z[k] for k in z.files}
            assert set(loaded) == wanted
            return {k: as_dtype(v, template_shard[k].dtype)
                    for k, v in loaded.items()}

    def slicer(key: str, arr: np.ndarray) -> np.ndarray:
        E = arr.shape[EXPERT_AXIS]
        per = E // ep_size
        return as_dtype(np.array(arr[:, ep_rank * per:(ep_rank + 1) * per]),
                        template_shard[key].dtype)

    loaded = load_keys(ckpt_path, lambda k: k in wanted, slicer)
    assert set(loaded) == wanted, (sorted(wanted - set(loaded)))
    return loaded
