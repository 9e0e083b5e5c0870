"""The benchmark's weights, made from ``--seed``.

Each leaf of each layer has a key of its own, folded from the seed, the
leaf's name and the layer index, so the plain reference can make one
layer's weights again without holding the rest, and get the same bits
the program serves.  Draws are float32 normals times the leaf's scale,
rounded to the served dtype.

Which leaves a layer has, and how they sit in the program's parameter
tree (its stacks of layers, the physical expert bank with its replica
slots), the architecture module says (``bench/reference/<name>.py``,
passed as ``arch``); :func:`program_params` makes the whole tree, the
vocabulary padded with zero rows, in one jitted call on the device.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from bench.model import Shape

EMBED_STD = 0.02


def seed_key(seed: int):
    """A PRNG key from any non-negative seed below 2**64."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed out of range: {seed}")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed & 0xFFFFFFFF)


def _draw(key, name: str, layer, shape, scale: float, dtype):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()))
    k = jax.random.fold_in(k, layer)
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)


def layer_weights(key, arch, s: Shape, layer, dtype=jnp.bfloat16,
                  like=None) -> dict:
    """One layer's weights (logical experts, no replica slots): the leaves
    ``arch.layer_leaves(s, like)`` lists, drawn for ``layer``, and the
    two norms of a pre-norm layer as ones.  ``like`` is ``layer`` itself,
    or, where ``layer`` is traced, a static layer of the same kind."""
    leaves = arch.layer_leaves(s, layer if like is None else like)
    out = {n: _draw(key, n, layer, shp, sc, dtype)
           for n, (shp, sc) in leaves.items()}
    out["ln1"] = jnp.ones((s.d_model,), dtype)
    out["ln2"] = jnp.ones((s.d_model,), dtype)
    return out


def embed(key, s: Shape, dtype=jnp.bfloat16):
    """(vocab, d_model) input embedding."""
    return _draw(key, "embed", 0, (s.vocab, s.d_model), EMBED_STD, dtype)


def lm_head(key, s: Shape, dtype=jnp.bfloat16):
    """(d_model, vocab) output head."""
    return _draw(key, "lm_head", 0, (s.d_model, s.vocab), EMBED_STD, dtype)


def program_params(seed: int, arch, s: Shape, padded_vocab: int,
                   dtype=jnp.bfloat16) -> dict:
    """The weights in the program's parameter layout, made on the device
    in one jitted call; ``arch.program_params`` lays out the layers, one
    at a time, in the program's stacks."""
    pad = padded_vocab - s.vocab
    if pad < 0:
        raise ValueError("padded vocabulary smaller than the vocabulary")

    def make(key):
        def one_layer(i, like):
            return layer_weights(key, arch, s, i, dtype, like)

        return {"embed": jnp.pad(embed(key, s, dtype), ((0, pad), (0, 0))),
                "final_norm": jnp.ones((s.d_model,), dtype),
                "lm_head": jnp.pad(lm_head(key, s, dtype),
                                   ((0, 0), (0, pad))),
                **arch.program_params(one_layer, s)}

    return jax.jit(make)(seed_key(seed))


def check_layout(params, specs) -> None:
    """Raise unless ``params`` has the tree, shapes and dtypes of the
    program's ``specs`` (``Model.param_specs()``)."""
    got = jax.tree_util.tree_structure(params)
    want = jax.tree_util.tree_structure(specs)
    if got != want:
        raise ValueError(f"weight tree differs from the program's:\n"
                         f"{got}\nvs\n{want}")
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(specs)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"weight leaf {a.shape} {a.dtype} differs "
                             f"from the program's {b.shape} {b.dtype}")
