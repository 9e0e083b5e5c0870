"""Plain float32 reference of an InternLM2 (``InternLM2ForCausalLM``)
decoder layer: pre-norm grouped-query attention (no bias), then pre-norm
SwiGLU feed-forward (w2(silu(w1 x) * w3 x)).  ``live`` is unused: the
model has no experts."""
from __future__ import annotations

from bench.reference.common import attention, rms_norm, swiglu


def layer(w, x, positions, s, live=None, precision="f32"):
    """One decoder layer over one sequence x (T, D), float32."""
    x = x + attention(w, rms_norm(x, w["ln1"], s.eps), positions, s,
                      precision)
    return x + swiglu(rms_norm(x, w["ln2"], s.eps), w["ffn_gate"],
                      w["ffn_up"], w["ffn_down"], precision)
