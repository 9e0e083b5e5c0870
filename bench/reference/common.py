"""Pieces the plain references share: RMSNorm, rotary embedding, causal
multi-head attention with grouped KV heads, SwiGLU, written from the
published descriptions (HF ``modeling_qwen2_moe`` / ``modeling_internlm2``)
in ``jax.numpy``, with no cache, kernel or batching.

Every matrix product goes through :func:`mm`, so the same forward pass
runs in float32 at the highest matmul precision (the reference) or with
its operands rounded to float8 e4m3 (the control, one precision below the
bfloat16 the configurations state).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

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
