"""InternLM2 (``InternLM2ForCausalLM``) described to the benchmark, and
its plain float32 reference decoder layer: pre-norm grouped-query
attention (no bias), then pre-norm SwiGLU feed-forward (w2(silu(w1 x) *
w3 x)).  The model has no experts: ``live`` is unused, and no EP rank's
loss takes an expert away."""
from __future__ import annotations

import dataclasses

from bench.reference.common import (GQAShape, attention, attention_call,
                                    attention_flops, attention_leaves,
                                    attention_params, ffn_leaves, gqa_program,
                                    gqa_sizes, program_ffn, program_layer,
                                    rms_norm, stack, swiglu)

__all__ = ["Shape", "shape", "program_config", "layer_leaves",
           "program_params", "matmul_params", "attention_flops",
           "attention_call", "experts_lost_with", "layer"]


@dataclasses.dataclass(frozen=True, kw_only=True)
class Shape(GQAShape):
    d_ff: int                      # dense FFN width


def shape(cfg: dict) -> Shape:
    return Shape(**gqa_sizes(cfg), d_ff=cfg["intermediate_size"])


def program_config(cfg: dict, s: Shape, base):
    return dataclasses.replace(base, **gqa_program(s), d_ff=s.d_ff)


def layer_leaves(s: Shape, i: int) -> dict:
    """name -> (shape, scale) of layer ``i``'s random leaves (every layer
    alike; norm weights are ones and are not listed)."""
    return {**attention_leaves(s), **ffn_leaves(s.d_model, s.d_ff)}


def program_params(layer_weights, s: Shape) -> dict:
    """The layers in the program's tree: one stack."""
    return {"layers": stack(layer_weights, 0, s.layers, lambda w: {
        **program_layer(w), "ffn": program_ffn(w)})}


def matmul_params(s: Shape, i: int) -> int:
    """Parameters one token multiplies with in layer ``i``."""
    return attention_params(s) + 3 * s.d_model * s.d_ff


def experts_lost_with(s: Shape, rank: int) -> list:
    return []


# -- the plain reference ------------------------------------------------------

def layer(w, x, positions, s, live=None, precision="f32"):
    """One decoder layer over one sequence x (T, D), float32."""
    x = x + attention(w, rms_norm(x, w["ln1"], s.eps), positions, s,
                      precision)
    return x + swiglu(rms_norm(x, w["ln2"], s.eps), w["ffn_gate"],
                      w["ffn_up"], w["ffn_down"], precision)
