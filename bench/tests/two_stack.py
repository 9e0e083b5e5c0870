"""A test-only architecture whose layers differ: ``first_dense`` leading
dense SwiGLU layers, then MoE layers, as DeepSeek-V3's
``first_k_dense_replace`` lays them out, with grouped-query attention in
every layer.  It is made of the Qwen1.5-MoE and InternLM2 pieces, and
shows that a module of its own is all the shared code needs to take
such a model: the program keeps it as two stacks (``dense_layers`` and
``layers``)."""
from __future__ import annotations

import dataclasses

from bench.reference import internlm2, qwen2_moe
from bench.reference.common import (attention_call, attention_flops,
                                    attention_leaves, attention_params,
                                    ffn_leaves, program_ffn, program_layer,
                                    stack)

__all__ = ["Shape", "shape", "program_config", "layer_leaves",
           "program_params", "matmul_params", "attention_flops",
           "attention_call", "experts_lost_with", "layer"]

experts_lost_with = qwen2_moe.experts_lost_with


@dataclasses.dataclass(frozen=True, kw_only=True)
class Shape(qwen2_moe.Shape):
    first_dense: int               # leading dense layers
    dense_d_ff: int                # their FFN width


def shape(cfg: dict) -> Shape:
    return Shape(**dataclasses.asdict(qwen2_moe.shape(cfg)),
                 first_dense=cfg["first_k_dense_replace"],
                 dense_d_ff=cfg["intermediate_size"])


def program_config(cfg: dict, s: Shape, base):
    out = qwen2_moe.program_config(cfg, s, base)
    return dataclasses.replace(out, moe=dataclasses.replace(
        out.moe, first_k_dense=s.first_dense, dense_d_ff=s.dense_d_ff))


def layer_leaves(s: Shape, i: int) -> dict:
    if i < s.first_dense:
        return {**attention_leaves(s), **ffn_leaves(s.d_model, s.dense_d_ff)}
    return qwen2_moe.layer_leaves(s, i)


def program_params(layer_weights, s: Shape) -> dict:
    return {"dense_layers": stack(layer_weights, 0, s.first_dense,
                                  lambda w: {**program_layer(w),
                                             "ffn": program_ffn(w)}),
            "layers": stack(layer_weights, s.first_dense, s.layers,
                            lambda w: {**program_layer(w),
                                       "moe": qwen2_moe.program_moe(w, s)})}


def matmul_params(s: Shape, i: int) -> int:
    if i < s.first_dense:
        return attention_params(s) + 3 * s.d_model * s.dense_d_ff
    return qwen2_moe.matmul_params(s, i)


def layer(w, x, positions, s, live, precision="f32"):
    """One decoder layer; its leaves say its kind."""
    if "router" in w:
        return qwen2_moe.layer(w, x, positions, s, live, precision)
    return internlm2.layer(w, x, positions, s, live, precision)
