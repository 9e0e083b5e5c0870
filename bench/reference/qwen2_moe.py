"""Qwen1.5-MoE (``Qwen2MoeForCausalLM``) described to the benchmark, and
its plain float32 reference decoder layer: pre-norm attention, then
pre-norm sparse MoE with routed top-k experts and always-on shared
experts.

Where the program departs from the published model the reference follows
the program, as the configuration's ``departures`` list: top-k weights
renormalised, no shared-expert gate, no q/k/v bias.  ``live`` lists the
experts that may be routed to (a lost expert's logit is -inf, as after a
revive that masks it).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.reference.common import (GQAShape, attention, attention_call,
                                    attention_flops, attention_leaves,
                                    attention_params, gqa_program, gqa_sizes,
                                    mm, program_layer, rms_norm, stack, swiglu)

__all__ = ["Shape", "shape", "program_config", "layer_leaves",
           "program_params", "matmul_params", "attention_flops",
           "attention_call", "experts_lost_with", "layer"]


@dataclasses.dataclass(frozen=True, kw_only=True)
class Shape(GQAShape):
    top_k: int
    expert_d_ff: int
    shared_d_ff: int = 0           # all shared experts together
    redundant: int = 0             # replica slots (deployment)
    ep_ranks: int                  # ranks the expert slots are split over

    @property
    def slots(self) -> int:
        return self.experts + self.redundant

    def slot_expert(self) -> list:
        """Logical expert held by each physical slot: slot s < experts
        holds expert s, the replica slots hold experts 0..redundant-1."""
        return list(range(self.experts)) + list(range(self.redundant))


def shape(cfg: dict) -> Shape:
    dep = cfg["deployment"]
    return Shape(**gqa_sizes(cfg), experts=cfg["num_experts"],
                 top_k=cfg["num_experts_per_tok"],
                 expert_d_ff=cfg["moe_intermediate_size"],
                 shared_d_ff=cfg.get("shared_expert_intermediate_size", 0),
                 redundant=dep.get("redundant_experts", 0),
                 ep_ranks=dep["num_dp"])


def program_config(cfg: dict, s: Shape, base):
    if s.shared_d_ff % s.expert_d_ff:
        raise ValueError("shared expert width is not a whole number "
                         "of expert widths")
    moe = dict(num_experts=s.experts, top_k=s.top_k,
               expert_d_ff=s.expert_d_ff,
               num_shared_experts=s.shared_d_ff // s.expert_d_ff,
               num_redundant_experts=s.redundant)
    if cfg["deployment"].get("drop_tokens") is False:
        # the one choice a file may pin: routing as the published
        # model routes, every token to its top-k experts (capacity =
        # tokens x top-k, so no slot can overflow)
        moe["capacity_factor"] = float(s.slots)
    return dataclasses.replace(base, **gqa_program(s),
                               moe=dataclasses.replace(base.moe, **moe))


def moe_leaves(s: Shape) -> dict:
    """name -> (shape, scale) of the router, the logical experts and the
    shared experts."""
    D, E, F = s.d_model, s.experts, s.expert_d_ff
    out = {"router": ((D, E), D ** -0.5),
           "gate": ((E, D, F), D ** -0.5),
           "up": ((E, D, F), D ** -0.5),
           "down": ((E, F, D), F ** -0.5)}
    if s.shared_d_ff:
        Fs = s.shared_d_ff
        out.update({"shared_gate": ((D, Fs), D ** -0.5),
                    "shared_up": ((D, Fs), D ** -0.5),
                    "shared_down": ((Fs, D), Fs ** -0.5)})
    return out


def layer_leaves(s: Shape, i: int) -> dict:
    """name -> (shape, scale) of layer ``i``'s random leaves (every layer
    alike; norm weights are ones and are not listed)."""
    return {**attention_leaves(s), **moe_leaves(s)}


def program_moe(w: dict, s: Shape) -> dict:
    """The MoE leaves in the program's tree: the physical expert bank,
    replica slots included."""
    slot = jnp.asarray(s.slot_expert(), jnp.int32)
    p = {"router": w["router"], "gate": w["gate"][slot],
         "up": w["up"][slot], "down": w["down"][slot]}
    if s.shared_d_ff:
        p["shared"] = {"w_gate": w["shared_gate"], "w_up": w["shared_up"],
                       "w_down": w["shared_down"]}
    return p


def program_params(layer_weights, s: Shape) -> dict:
    """The layers in the program's tree: one stack."""
    return {"layers": stack(layer_weights, 0, s.layers, lambda w: {
        **program_layer(w), "moe": program_moe(w, s)})}


def moe_params(s: Shape) -> int:
    """Parameters one token multiplies with in one MoE layer's FFN."""
    return (s.d_model * s.experts                        # router
            + s.top_k * 3 * s.d_model * s.expert_d_ff    # routed SwiGLU
            + 3 * s.d_model * s.shared_d_ff)             # shared SwiGLU


def matmul_params(s: Shape, i: int) -> int:
    """Parameters one token multiplies with in layer ``i``."""
    return attention_params(s) + moe_params(s)


def experts_lost_with(s: Shape, rank: int) -> list:
    """Experts with no slot left once EP rank ``rank`` is gone: EP rank r
    holds slots ``[r * per, (r + 1) * per)``."""
    per = s.slots // s.ep_ranks
    dead = set(range(rank * per, (rank + 1) * per))
    alive = {e for sl, e in enumerate(s.slot_expert()) if sl not in dead}
    return [e for e in range(s.experts) if e not in alive]


# -- the plain reference ------------------------------------------------------

def moe(w, h, s, live, precision="f32"):
    logits = mm(h, w["router"], precision)                   # (T, E)
    logits = jnp.where(live[None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    top, sel = jax.lax.top_k(probs, s.top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    comb = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], sel].set(top)       # (T, E)

    def expert(acc, xs):
        gate, up, down, c = xs
        return acc + c[:, None] * swiglu(h, gate, up, down, precision), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (w["gate"], w["up"], w["down"], comb.T))
    if s.shared_d_ff:
        out = out + swiglu(h, w["shared_gate"], w["shared_up"],
                           w["shared_down"], precision)
    return out


def layer(w, x, positions, s, live, precision="f32"):
    """One decoder layer over one sequence x (T, D), float32."""
    x = x + attention(w, rms_norm(x, w["ln1"], s.eps), positions, s,
                      precision)
    return x + moe(w, rms_norm(x, w["ln2"], s.eps), s, live, precision)
