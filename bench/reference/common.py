"""Pieces the architecture modules share.

The plain reference's layers: RMSNorm, rotary embedding, causal
multi-head attention with grouped KV heads, SwiGLU, written from the
published descriptions (HF ``modeling_qwen2_moe`` / ``modeling_internlm2``)
in ``jax.numpy``, with no cache, kernel or batching.  And the description
of grouped-query attention to the benchmark: its sizes
(:class:`GQAShape`), its leaves, its place in the program's tree and
configuration, and its operations and bytes.

Every matrix product goes through :func:`mm`, so the same forward pass
runs in float32 at the highest matmul precision (the reference) or with
its operands rounded to float8 e4m3 (the control, one precision below the
bfloat16 the configurations state).
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence, Tuple

import jax
import jax.numpy as jnp

from bench.model import Shape

F8_MAX = 448.0          # largest finite float8_e4m3fn


def _f8(x, axis):
    """``x`` rounded to float8 e4m3 with one scale per slice along
    ``axis`` (the largest magnitude maps to the format's largest value),
    returned in float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(a, b, precision: str = "f32"):
    """``a @ b`` (over the last axis of ``a`` and the first of ``b``) in
    float32, or with both operands first rounded to float8 (``"f8"``),
    ``a`` per row and ``b`` per column."""
    if precision == "f8":
        a, b = _f8(a, -1), _f8(b, 0)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, positions, theta):
    """HF rotary embedding (``rotate_half``): x (T, heads, dh)."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos + rot * sin


def attention(w, h, positions, s, precision="f32"):
    """Causal self-attention of one sequence h (T, D); query head i reads
    KV head i // (heads / kv_heads), as HF's ``repeat_kv``."""
    T = h.shape[0]
    H, Hkv, dh = s.heads, s.kv_heads, s.head_dim
    q = mm(h, w["wq"], precision).reshape(T, H, dh)
    k = mm(h, w["wk"], precision).reshape(T, Hkv, dh)
    v = mm(h, w["wv"], precision).reshape(T, Hkv, dh)
    q = rope(q, positions, s.rope_theta)
    k = rope(k, positions, s.rope_theta)
    rep = H // Hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    qh, kh, vh = (jnp.swapaxes(t, 0, 1) for t in (q, k, v))   # (H, T, dh)
    scores = jax.vmap(lambda a, b: mm(a, b.T, precision))(qh, kh)
    scores = scores / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jax.vmap(lambda a, b: mm(a, b, precision))(p, vh)  # (H, T, dh)
    out = jnp.swapaxes(out, 0, 1).reshape(T, H * dh)
    return mm(out, w["wo"], precision)


def swiglu(h, gate, up, down, precision="f32"):
    return mm(jax.nn.silu(mm(h, gate, precision)) * mm(h, up, precision),
              down, precision)


# -- grouped-query attention and SwiGLU, described to the benchmark -----------

@dataclasses.dataclass(frozen=True, kw_only=True)
class GQAShape(Shape):
    """Sizes of a decoder whose every layer has grouped-query attention."""
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float


def gqa_sizes(cfg: dict) -> dict:
    """:class:`GQAShape`'s fields from the published config.json keys."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(layers=cfg["num_hidden_layers"], d_model=D, heads=H,
                kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg.get("head_dim", D // H),
                vocab=cfg["vocab_size"], eps=float(cfg["rms_norm_eps"]),
                rope_theta=float(cfg["rope_theta"]))


def gqa_program(s: GQAShape) -> dict:
    """The program's ``ModelConfig`` fields the GQA sizes set."""
    return dict(num_layers=s.layers, d_model=s.d_model, num_heads=s.heads,
                num_kv_heads=s.kv_heads, head_dim=s.head_dim,
                vocab_size=s.vocab, norm_eps=s.eps, rope_theta=s.rope_theta)


def attention_leaves(s: GQAShape) -> dict:
    """name -> (shape, scale) of the attention's projections."""
    D, H, Hkv, Dh = s.d_model, s.heads, s.kv_heads, s.head_dim
    return {"wq": ((D, H * Dh), D ** -0.5),
            "wk": ((D, Hkv * Dh), D ** -0.5),
            "wv": ((D, Hkv * Dh), D ** -0.5),
            "wo": ((H * Dh, D), (H * Dh) ** -0.5)}


def ffn_leaves(D: int, F: int) -> dict:
    """name -> (shape, scale) of a dense SwiGLU feed-forward."""
    return {"ffn_gate": ((D, F), D ** -0.5),
            "ffn_up": ((D, F), D ** -0.5),
            "ffn_down": ((F, D), F ** -0.5)}


def program_ffn(w: dict) -> dict:
    """The dense feed-forward in the program's tree."""
    return {"w_gate": w["ffn_gate"], "w_up": w["ffn_up"],
            "w_down": w["ffn_down"]}


def program_layer(w: dict) -> dict:
    """The norms and the attention of one layer, in the program's tree."""
    return {"ln1": w["ln1"], "ln2": w["ln2"],
            "mixer": {k: w[k] for k in ("wq", "wk", "wv", "wo")}}


def stack(layer_weights, first: int, stop: int, lay_out):
    """Layers ``[first, stop)``, all of the kind of layer ``first``, laid
    out by ``lay_out`` and stacked on a leading axis, one layer at a time
    (``jax.lax.map``), so the float32 draws of only one layer exist at
    once."""
    return jax.lax.map(lambda i: lay_out(layer_weights(i, first)),
                       jnp.arange(first, stop, dtype=jnp.int32))


def attention_params(s: GQAShape) -> int:
    """Parameters one token multiplies with in one layer's attention."""
    D, H, Hkv, dh = s.d_model, s.heads, s.kv_heads, s.head_dim
    return D * H * dh * 2 + D * Hkv * dh * 2          # wq, wo, wk, wv


def attention_flops(s: GQAShape, ctx: int) -> int:
    """One token attending over ``ctx`` keys in one layer: QK^T and PV."""
    return 4 * ctx * s.heads * s.head_dim


def attention_call(s: GQAShape, seqs: Iterable[Sequence[int]],
                   itemsize: int = 2) -> Tuple[int, int]:
    """(FLOPs, bytes) one paged-attention call of one layer needs.
    ``seqs`` holds, for each sequence in the call, the valid lengths of
    its rows (one row for a decode token, one per position for a prefill
    chunk).  Each row attends over its own valid keys; each sequence's
    valid K and V are read once, however many of its rows the call
    holds; each row reads its query and writes its output."""
    flops = nbytes = 0
    for ctxs in seqs:
        if not ctxs:
            continue
        for c in ctxs:
            flops += attention_flops(s, c)
            nbytes += 2 * s.heads * s.head_dim * itemsize   # q and out
        nbytes += 2 * max(ctxs) * s.kv_heads * s.head_dim * itemsize
    return flops, nbytes
