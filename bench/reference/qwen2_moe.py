"""Plain float32 reference of a Qwen1.5-MoE (``Qwen2MoeForCausalLM``)
decoder layer: pre-norm attention, then pre-norm sparse MoE with routed
top-k experts and always-on shared experts.

Where the program departs from the published model the reference follows
the program, as the configuration's ``departures`` list: top-k weights
renormalised, no shared-expert gate, no q/k/v bias.  ``live`` lists the
experts that may be routed to (a lost expert's logit is -inf, as after a
revive that masks it).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import attention, mm, rms_norm, swiglu


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
