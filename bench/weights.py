"""The benchmark's weights, made from ``--seed``.

Each leaf of each layer has a key of its own, folded from the seed, the
leaf's name and the layer index, so the plain reference can make one
layer's weights again without holding the rest, and get the same bits
the program serves.  Draws are float32 normals times the leaf's scale,
rounded to the served dtype.

:func:`program_params` lays the same weights out as the program's
parameter tree (stacked layers, the physical expert bank with its
replica slots, the vocabulary padded with zero rows) in one jitted call
on the device.
"""
from __future__ import annotations

import zlib
from typing import Dict

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


def layer_leaves(s: Shape) -> Dict[str, tuple]:
    """name -> (shape, scale) of one layer's random leaves (norm weights
    are ones and are not listed)."""
    D, H, Hkv, Dh = s.d_model, s.heads, s.kv_heads, s.head_dim
    out = {"wq": ((D, H * Dh), D ** -0.5),
           "wk": ((D, Hkv * Dh), D ** -0.5),
           "wv": ((D, Hkv * Dh), D ** -0.5),
           "wo": ((H * Dh, D), (H * Dh) ** -0.5)}
    if s.moe:
        E, F = s.experts, s.expert_d_ff
        out.update({"router": ((D, E), D ** -0.5),
                    "gate": ((E, D, F), D ** -0.5),
                    "up": ((E, D, F), D ** -0.5),
                    "down": ((E, F, D), F ** -0.5)})
        if s.shared_d_ff:
            Fs = s.shared_d_ff
            out.update({"shared_gate": ((D, Fs), D ** -0.5),
                        "shared_up": ((D, Fs), D ** -0.5),
                        "shared_down": ((Fs, D), Fs ** -0.5)})
    else:
        F = s.d_ff
        out.update({"ffn_gate": ((D, F), D ** -0.5),
                    "ffn_up": ((D, F), D ** -0.5),
                    "ffn_down": ((F, D), F ** -0.5)})
    return out


def layer_weights(key, s: Shape, layer, dtype=jnp.bfloat16) -> dict:
    """One layer's weights (logical experts, no replica slots)."""
    out = {n: _draw(key, n, layer, shp, sc, dtype)
           for n, (shp, sc) in layer_leaves(s).items()}
    out["ln1"] = jnp.ones((s.d_model,), dtype)
    out["ln2"] = jnp.ones((s.d_model,), dtype)
    return out


def embed(key, s: Shape, dtype=jnp.bfloat16):
    """(vocab, d_model) input embedding."""
    return _draw(key, "embed", 0, (s.vocab, s.d_model), EMBED_STD, dtype)


def lm_head(key, s: Shape, dtype=jnp.bfloat16):
    """(d_model, vocab) output head."""
    return _draw(key, "lm_head", 0, (s.d_model, s.vocab), EMBED_STD, dtype)


def _program_layer(w: dict, s: Shape) -> dict:
    p = {"ln1": w["ln1"], "ln2": w["ln2"],
         "mixer": {k: w[k] for k in ("wq", "wk", "wv", "wo")}}
    if s.moe:
        slot = jnp.asarray(s.slot_expert(), jnp.int32)
        p["moe"] = {"router": w["router"], "gate": w["gate"][slot],
                    "up": w["up"][slot], "down": w["down"][slot]}
        if s.shared_d_ff:
            p["moe"]["shared"] = {"w_gate": w["shared_gate"],
                                  "w_up": w["shared_up"],
                                  "w_down": w["shared_down"]}
    else:
        p["ffn"] = {"w_gate": w["ffn_gate"], "w_up": w["ffn_up"],
                    "w_down": w["ffn_down"]}
    return p


def program_params(seed: int, s: Shape, padded_vocab: int,
                   dtype=jnp.bfloat16) -> dict:
    """The weights in the program's parameter layout, made on the device
    in one jitted call (layers one at a time, so the float32 draws of
    only one layer exist at once)."""
    pad = padded_vocab - s.vocab
    if pad < 0:
        raise ValueError("padded vocabulary smaller than the vocabulary")

    def make(key):
        layers = jax.lax.map(
            lambda i: _program_layer(layer_weights(key, s, i, dtype), s),
            jnp.arange(s.layers, dtype=jnp.int32))
        return {"embed": jnp.pad(embed(key, s, dtype), ((0, pad), (0, 0))),
                "final_norm": jnp.ones((s.d_model,), dtype),
                "lm_head": jnp.pad(lm_head(key, s, dtype),
                                   ((0, 0), (0, pad))),
                "layers": layers}

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
