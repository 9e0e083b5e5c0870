"""Attention mixers: GQA (full/chunked-flash/decode), MLA, sliding window.

Full-sequence attention uses a chunked online-softmax ("flash") formulation
in pure JAX so peak memory stays bounded at 32k context: the (Sq, Skv)
score matrix is never materialized.  Decode paths operate against a
(ring-buffered when windowed) KV cache and update it in place.

Serving decode runs against paged block pools (``gqa_decode_paged`` /
``mla_decode_paged`` over ``ops.paged_attention`` — Pallas kernel on TPU,
jnp oracle on CPU); the ring-buffer decode here is the reference
semantics the paged path is proven against, and remains the dry-run /
training-eval path.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, dense_init, rms_norm, rope_sincos, split_keys

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# chunked flash attention (full sequence)
# ---------------------------------------------------------------------------

def _flash_one_q_chunk(qc, k, v, q_pos_c, kv_pos, *, causal, window,
                       kv_chunk, kv_chunks_limit, scale):
    """Online-softmax over kv chunks for one q chunk.

    qc: (B, Qc, Hkv, G, Dh); k: (B, Skv, Hkv, Dh); v: (B, Skv, Hkv, Dv).
    kv_chunks_limit: number of kv chunks this q chunk may attend to
    (static, derived from causality) — chunks beyond it are skipped.
    """
    B, Qc, Hkv, G, Dh = qc.shape
    Dv = v.shape[-1]
    nkv = kv_chunks_limit

    k_used = k[:, : nkv * kv_chunk].reshape(B, nkv, kv_chunk, Hkv, Dh)
    v_used = v[:, : nkv * kv_chunk].reshape(B, nkv, kv_chunk, Hkv, Dv)
    kv_pos_used = kv_pos[: nkv * kv_chunk].reshape(nkv, kv_chunk)

    def body(carry, xs):
        acc, m, l = carry
        kc, vc, pos_kv = xs
        s = jnp.einsum("bqkgd,bskd->bqkgs", qc, kc,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((Qc, kv_chunk), dtype=bool)
        if causal:
            mask &= q_pos_c[:, None] >= pos_kv[None, :]
        if window:
            mask &= (q_pos_c[:, None] - pos_kv[None, :]) < window
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqkgs,bskv->bqkgv", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, Qc, Hkv, G, Dv), jnp.float32)
    m0 = jnp.full((B, Qc, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Qc, Hkv, G), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0),
        (k_used.swapaxes(0, 1), v_used.swapaxes(0, 1), kv_pos_used))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(qc.dtype)


def flash_attention(q, k, v, q_pos, kv_pos, *, causal=True, window=0,
                    q_chunk=2048, kv_chunk=1024):
    """q: (B,Sq,H,Dh), k: (B,Skv,Hkv,Dh), v: (B,Skv,Hkv,Dv) -> (B,Sq,H,Dv).

    q_pos: (Sq,) int32 absolute positions; kv_pos: (Skv,).
    The python loop over q chunks keeps per-chunk kv scan bounds *static*,
    so causal attention skips future kv chunks entirely (no wasted FLOPs
    on fully-masked blocks).
    """
    B, Sq, H, Dh = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    scale = 1.0 / math.sqrt(Dh)

    q_chunk = min(q_chunk, Sq)
    while Sq % q_chunk:
        q_chunk //= 2
    kv_chunk = min(kv_chunk, Skv)
    while Skv % kv_chunk:
        kv_chunk //= 2
    assert q_chunk >= 1 and kv_chunk >= 1, (Sq, q_chunk, Skv, kv_chunk)
    nq = Sq // q_chunk
    nkv_total = Skv // kv_chunk

    qg = q.reshape(B, Sq, Hkv, G, Dh)
    outs = []
    for i in range(nq):
        qc = jax.lax.dynamic_slice_in_dim(qg, i * q_chunk, q_chunk, axis=1)
        q_pos_c = jax.lax.dynamic_slice_in_dim(q_pos, i * q_chunk, q_chunk)
        if causal:
            # q positions in this chunk are q_pos[i*qc : (i+1)*qc]; when both
            # sides share the same position grid (self-attn), kv chunks past
            # the q chunk end are fully masked -> skip them statically.
            limit = min(nkv_total, (i + 1) * q_chunk // kv_chunk)
            limit = max(limit, 1)
        else:
            limit = nkv_total
        outs.append(_flash_one_q_chunk(
            qc, k, v, q_pos_c, kv_pos, causal=causal, window=window,
            kv_chunk=kv_chunk, kv_chunks_limit=limit, scale=scale))
    out = jnp.concatenate(outs, axis=1)
    return out.reshape(B, Sq, H, v.shape[-1])


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_init(key, cfg: ModelConfig, dtype=jnp.float32):
    D = cfg.d_model
    Dh = cfg.resolved_head_dim()
    ks = split_keys(key, 4)
    return {
        "wq": dense_init(ks[0], D, cfg.num_heads * Dh, dtype),
        "wk": dense_init(ks[1], D, cfg.num_kv_heads * Dh, dtype),
        "wv": dense_init(ks[2], D, cfg.num_kv_heads * Dh, dtype),
        "wo": dense_init(ks[3], cfg.num_heads * Dh, D, dtype),
    }


def gqa_forward(p, cfg: ModelConfig, x, positions, *, causal=True,
                kv_input=None, kv_positions=None, window=0, use_rope=True,
                return_kv=False):
    """Full-sequence GQA. kv_input overrides the kv source (cross-attn)."""
    B, S, D = x.shape
    Dh = cfg.resolved_head_dim()
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    src = x if kv_input is None else kv_input
    kv_pos = positions if kv_positions is None else kv_positions

    q = (x @ p["wq"]).reshape(B, S, H, Dh)
    k = (src @ p["wk"]).reshape(B, src.shape[1], Hkv, Dh)
    v = (src @ p["wv"]).reshape(B, src.shape[1], Hkv, Dh)
    if use_rope:
        sin_q, cos_q = rope_sincos(positions, Dh, cfg.rope_theta)
        sin_k, cos_k = rope_sincos(kv_pos, Dh, cfg.rope_theta)
        q = apply_rope(q, sin_q[None, :, None, :], cos_q[None, :, None, :])
        k = apply_rope(k, sin_k[None, :, None, :], cos_k[None, :, None, :])
    out = flash_attention(q, k, v, positions, kv_pos, causal=causal,
                          window=window)
    y = out.reshape(B, S, H * Dh) @ p["wo"]
    if return_kv:
        return y, (k, v)
    return y


def gqa_forward_with_kv(p, cfg: ModelConfig, x, positions):
    """Prefill variant: returns (y, (k, v)) with rope already applied to k,
    ready to be placed into the decode ring cache."""
    return gqa_forward(p, cfg, x, positions, causal=True,
                       window=cfg.sliding_window, return_kv=True)


class GQACache(NamedTuple):
    k: jnp.ndarray  # (B, W, Hkv, Dh) ring buffer (W = window or max_seq)
    v: jnp.ndarray


def gqa_init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.float32):
    W = cfg.sliding_window or max_seq
    W = min(W, max_seq)
    Dh = cfg.resolved_head_dim()
    shape = (batch, W, cfg.num_kv_heads, Dh)
    return GQACache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def _ring_validity(pos: jnp.ndarray, W: int):
    """For each ring slot s, the absolute position it currently holds and
    whether it is valid, given current token position ``pos`` (B,)."""
    s = jnp.arange(W)[None, :]                      # (1, W)
    cur = (pos % W)[:, None]                        # (B, 1)
    delta = (cur - s) % W                           # age of slot
    slot_pos = pos[:, None] - delta                 # absolute position held
    valid = slot_pos >= 0
    return slot_pos, valid


def gqa_decode(p, cfg: ModelConfig, x, cache: GQACache, pos, *, use_rope=True):
    """One-token decode. x: (B, D); pos: (B,) absolute position of x.

    Writes the new kv into the ring slot, attends over valid slots.
    """
    B, D = x.shape
    Dh = cfg.resolved_head_dim()
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    G = H // Hkv
    W = cache.k.shape[1]

    q = (x @ p["wq"]).reshape(B, H, Dh)
    k = (x @ p["wk"]).reshape(B, Hkv, Dh)
    v = (x @ p["wv"]).reshape(B, Hkv, Dh)
    if use_rope:
        sin, cos = rope_sincos(pos, Dh, cfg.rope_theta)  # (B, Dh/2)
        q = apply_rope(q, sin[:, None, :], cos[:, None, :])
        k = apply_rope(k, sin[:, None, :], cos[:, None, :])

    slot = pos % W
    k_cache = cache.k.at[jnp.arange(B), slot].set(k.astype(cache.k.dtype))
    v_cache = cache.v.at[jnp.arange(B), slot].set(v.astype(cache.v.dtype))

    slot_pos, valid = _ring_validity(pos, W)
    if cfg.sliding_window:
        valid &= (pos[:, None] - slot_pos) < cfg.sliding_window

    qg = q.reshape(B, Hkv, G, Dh)
    s = jnp.einsum("bkgd,bwkd->bkgw", qg, k_cache,
                   preferred_element_type=jnp.float32) / math.sqrt(Dh)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    pw = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgw,bwkd->bkgd", pw.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    out = out.reshape(B, H * Dh).astype(x.dtype)
    return out @ p["wo"], GQACache(k_cache, v_cache)


def gqa_paged_pools(cfg: ModelConfig, num_blocks: int, block_size: int,
                    dtype=jnp.float32):
    """One layer's paged K/V pools: (num_blocks, block_size, Hkv, Dh)."""
    Dh = cfg.resolved_head_dim()
    shape = (num_blocks, block_size, cfg.num_kv_heads, Dh)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def window_starts(cfg: ModelConfig, seq_lens):
    """Sliding-window lower bound per sequence (None = full attention)."""
    if not cfg.sliding_window:
        return None
    return jnp.maximum(seq_lens - cfg.sliding_window, 0)


def gqa_decode_qkv(p, cfg: ModelConfig, x, page, *, use_rope=True):
    """Shared q/k/v projection (+rope at ``seq_lens - 1``) for the paged
    decode paths — the composed chain and the decode megakernel both
    start from exactly these tensors."""
    B, D = x.shape
    Dh = cfg.resolved_head_dim()
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    pos = page["seq_lens"] - 1
    with jax.named_scope("attn.qkv"):
        q = (x @ p["wq"]).reshape(B, H, Dh)
        k = (x @ p["wk"]).reshape(B, Hkv, Dh)
        v = (x @ p["wv"]).reshape(B, Hkv, Dh)
        if use_rope:
            sin, cos = rope_sincos(pos, Dh, cfg.rope_theta)
            q = apply_rope(q, sin[:, None, :], cos[:, None, :])
            k = apply_rope(k, sin[:, None, :], cos[:, None, :])
    return q, k, v


def gqa_write_token(pools, page, k, v):
    """Scatter one incoming token's K/V into its (block, offset) rows
    (idle batch slots hit the trash block)."""
    with jax.named_scope("attn.kv_write"):
        k_pool = pools["k"].at[page["write_bid"], page["write_off"]].set(
            k.astype(pools["k"].dtype))
        v_pool = pools["v"].at[page["write_bid"], page["write_off"]].set(
            v.astype(pools["v"].dtype))
    return {"k": k_pool, "v": v_pool}


def gqa_decode_paged(p, cfg: ModelConfig, x, pools, page, *,
                     use_pallas: bool = False, use_rope=True):
    """One-token decode against paged K/V pools (one layer).

    x: (B, D); pools: {"k","v"} (nb, bs, Hkv, Dh); page: the per-step
    paging arrays — ``tables`` (B, max_blk), ``seq_lens`` (B,) valid
    length *including* the incoming token, ``write_bid``/``write_off``
    (B,) the physical slot position ``seq_lens - 1`` lands in (idle batch
    slots point at the trash block).
    """
    from repro.kernels import ops
    B, D = x.shape
    Dh = cfg.resolved_head_dim()
    H = cfg.num_heads
    q, k, v = gqa_decode_qkv(p, cfg, x, page, use_rope=use_rope)
    new_pools = gqa_write_token(pools, page, k, v)
    with jax.named_scope("attn.paged"):
        out = ops.paged_attention(q, new_pools["k"], new_pools["v"],
                                  page["tables"], page["seq_lens"],
                                  window_starts(cfg, page["seq_lens"]),
                                  use_pallas=use_pallas)
    with jax.named_scope("attn.out"):
        y = out.reshape(B, H * Dh).astype(x.dtype) @ p["wo"]
    return y, new_pools


def mla_paged_pools(cfg: ModelConfig, num_blocks: int, block_size: int,
                    dtype=jnp.float32):
    """One layer's paged latent pool: (nb, bs, 1, R + dr).

    MLA decode attends in the latent space, so one fused pool holds
    ``concat([c_kv, k_rope])`` per token (the Hkv=1 axis matches the
    paged-attention kernel's pool layout).
    """
    m = cfg.mla
    shape = (num_blocks, block_size, 1,
             m.kv_lora_rank + m.qk_rope_head_dim)
    return {"ckr": jnp.zeros(shape, dtype)}


def mla_decode_q_token(p, cfg: ModelConfig, x, page):
    """Absorbed latent query + fused pool token row for one MLA decode
    step.  The query is pre-scaled by ``sqrt(R+dr)/sqrt(dn+dr)`` so the
    paged-attention kernel's ``1/sqrt(R+dr)`` yields the MLA scale."""
    m = cfg.mla
    dn, dr = m.qk_nope_head_dim, m.qk_rope_head_dim
    R = m.kv_lora_rank
    pos = page["seq_lens"] - 1
    with jax.named_scope("attn.qkv"):
        q_nope, q_rope, c_kv, k_rope, sin, cos = _mla_qkr(p, cfg, x, pos)
        q_rope = apply_rope(q_rope, sin[:, None, :],
                            cos[:, None, :])                       # (B,H,dr)
        k_rope = apply_rope(k_rope, sin, cos)                      # (B,dr)
        q_lat = jnp.einsum("bhd,hdr->bhr", q_nope, p["wuk"])       # (B,H,R)
        token = jnp.concatenate([c_kv, k_rope], axis=-1)           # (B,R+dr)
        q_eff = jnp.concatenate([q_lat, q_rope], axis=-1) * (
            math.sqrt(R + dr) / math.sqrt(dn + dr))
    return q_eff, token


def mla_write_token(pools, page, token):
    """Scatter one incoming token's fused latent row into its block."""
    with jax.named_scope("attn.kv_write"):
        pool = pools["ckr"].at[page["write_bid"], page["write_off"], 0].set(
            token.astype(pools["ckr"].dtype))
    return {"ckr": pool}


def mla_post_matrix(p, cfg: ModelConfig):
    """Absorbed post-attention projection (H*(R+dr), D): ``wuv`` folded
    into ``wo``, zero rows for the rope columns — so the megakernel's
    single ``out @ w_post`` matmul equals the composed slice-then-two-
    einsum readout.  At deployment scale cache this per weight version;
    here it is rebuilt inside the jitted step (smoke-size folding)."""
    m = cfg.mla
    H = cfg.num_heads
    dr, dv = m.qk_rope_head_dim, m.v_head_dim
    D = p["wo"].shape[1]
    wov = jnp.einsum("hrv,hvd->hrd", p["wuv"], p["wo"].reshape(H, dv, D))
    return jnp.concatenate(
        [wov, jnp.zeros((H, dr, D), wov.dtype)], axis=1).reshape(-1, D)


def mla_decode_paged(p, cfg: ModelConfig, x, pools, page, *,
                     use_pallas: bool = False):
    """Absorbed-matmul MLA decode over the fused latent pool.

    Scores are ``q_lat . c_kv + q_rope . k_rope``, which is exactly one
    paged-attention call on the concatenated pool; the value readout uses
    the same pool (output columns beyond R are discarded).
    """
    from repro.kernels import ops
    B, D = x.shape
    m = cfg.mla
    H = cfg.num_heads
    dv = m.v_head_dim
    R = m.kv_lora_rank

    q_eff, token = mla_decode_q_token(p, cfg, x, page)
    new_pools = mla_write_token(pools, page, token)
    pool = new_pools["ckr"]
    with jax.named_scope("attn.paged"):
        out = ops.paged_attention(q_eff.astype(pool.dtype), pool, pool,
                                  page["tables"], page["seq_lens"],
                                  window_starts(cfg, page["seq_lens"]),
                                  use_pallas=use_pallas)
    with jax.named_scope("attn.out"):
        o_lat = out[..., :R]                                       # (B,H,R)
        o = jnp.einsum("bhr,hrv->bhv", o_lat.astype(x.dtype), p["wuv"])
        y = o.reshape(B, H * dv) @ p["wo"]
    return y, new_pools


def gqa_cross_decode(p, cfg: ModelConfig, x, ck, cv, kv_valid):
    """Cross-attention decode against precomputed encoder kv.

    ck/cv: (B, F, Hkv, Dh); kv_valid: (B, F) bool.
    """
    B, D = x.shape
    Dh = cfg.resolved_head_dim()
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    G = H // Hkv
    q = (x @ p["wq"]).reshape(B, Hkv, G, Dh)
    s = jnp.einsum("bkgd,bfkd->bkgf", q, ck,
                   preferred_element_type=jnp.float32) / math.sqrt(Dh)
    s = jnp.where(kv_valid[:, None, None, :], s, NEG_INF)
    pw = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgf,bfkd->bkgd", pw.astype(cv.dtype), cv,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H * Dh).astype(x.dtype) @ p["wo"]


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek/MiniCPM3 style)
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig, dtype=jnp.float32):
    m = cfg.mla
    D, H = cfg.d_model, cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    ks = split_keys(key, 7)
    return {
        "wdq": dense_init(ks[0], D, m.q_lora_rank, dtype),
        "q_norm": jnp.ones((m.q_lora_rank,), dtype),
        "wuq": dense_init(ks[1], m.q_lora_rank, H * (dn + dr), dtype),
        "wdkv": dense_init(ks[2], D, m.kv_lora_rank, dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "wkr": dense_init(ks[3], D, dr, dtype),
        "wuk": dense_init(ks[4], m.kv_lora_rank, H * dn, dtype)
            .reshape(m.kv_lora_rank, H, dn).transpose(1, 2, 0),  # (H, dn, R)
        "wuv": dense_init(ks[5], m.kv_lora_rank, H * dv, dtype)
            .reshape(m.kv_lora_rank, H, dv).transpose(1, 0, 2),  # (H, R, dv)
        "wo": dense_init(ks[6], H * dv, D, dtype),
    }


def _mla_qkr(p, cfg, x, positions):
    """Shared q / latent / rope-key computation. x: (B,S,D) or (B,D)."""
    m = cfg.mla
    H = cfg.num_heads
    dn, dr = m.qk_nope_head_dim, m.qk_rope_head_dim
    q_lat = rms_norm(x @ p["wdq"], p["q_norm"], cfg.norm_eps)
    q_all = (q_lat @ p["wuq"]).reshape(*x.shape[:-1], H, dn + dr)
    q_nope, q_rope = q_all[..., :dn], q_all[..., dn:]
    c_kv = rms_norm(x @ p["wdkv"], p["kv_norm"], cfg.norm_eps)
    k_rope = x @ p["wkr"]  # (..., dr), shared across heads
    sin, cos = rope_sincos(positions, dr, cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope, sin, cos


def mla_forward(p, cfg: ModelConfig, x, positions, *, causal=True, window=0,
                return_cache=False):
    """Full-sequence MLA: latent expanded to per-head k/v, flash attention."""
    B, S, D = x.shape
    m = cfg.mla
    H = cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    q_nope, q_rope, c_kv, k_rope, sin, cos = _mla_qkr(p, cfg, x, positions)
    q_rope = apply_rope(q_rope, sin[None, :, None, :], cos[None, :, None, :])
    k_rope = apply_rope(k_rope, sin[None, :, :], cos[None, :, :])
    k_nope = jnp.einsum("bsr,hdr->bshd", c_kv, p["wuk"])
    v = jnp.einsum("bsr,hrv->bshv", c_kv, p["wuv"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, dr))],
        axis=-1)
    out = flash_attention(q, k, v, positions, positions, causal=causal,
                          window=window)
    y = out.reshape(B, S, H * dv) @ p["wo"]
    if return_cache:
        return y, (c_kv, k_rope)
    return y


def mla_forward_with_cache(p, cfg: ModelConfig, x, positions):
    """Prefill variant: returns (y, (c_kv, k_rope)) for the latent cache."""
    return mla_forward(p, cfg, x, positions, causal=True,
                       window=cfg.sliding_window, return_cache=True)


class MLACache(NamedTuple):
    c_kv: jnp.ndarray    # (B, W, R) latent ring buffer
    k_rope: jnp.ndarray  # (B, W, dr)


def mla_init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.float32):
    m = cfg.mla
    W = cfg.sliding_window or max_seq
    W = min(W, max_seq)
    return MLACache(
        jnp.zeros((batch, W, m.kv_lora_rank), dtype),
        jnp.zeros((batch, W, m.qk_rope_head_dim), dtype),
    )


def mla_decode(p, cfg: ModelConfig, x, cache: MLACache, pos):
    """Absorbed-matmul MLA decode: attention runs in the latent space, the
    full per-head K/V is never materialized (the DeepSeek serving trick)."""
    B, D = x.shape
    m = cfg.mla
    H = cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    W = cache.c_kv.shape[1]

    q_nope, q_rope, c_kv, k_rope, sin, cos = _mla_qkr(p, cfg, x, pos)
    q_rope = apply_rope(q_rope, sin[:, None, :], cos[:, None, :])   # (B,H,dr)
    k_rope = apply_rope(k_rope, sin, cos)                            # (B,dr)

    slot = pos % W
    c_cache = cache.c_kv.at[jnp.arange(B), slot].set(c_kv.astype(cache.c_kv.dtype))
    r_cache = cache.k_rope.at[jnp.arange(B), slot].set(k_rope.astype(cache.k_rope.dtype))

    slot_pos, valid = _ring_validity(pos, W)
    if cfg.sliding_window:
        valid &= (pos[:, None] - slot_pos) < cfg.sliding_window

    q_lat = jnp.einsum("bhd,hdr->bhr", q_nope, p["wuk"])  # absorb W_uk
    s = (jnp.einsum("bhr,bwr->bhw", q_lat, c_cache,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,bwd->bhw", q_rope, r_cache,
                      preferred_element_type=jnp.float32))
    s = s / math.sqrt(dn + dr)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    pw = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhw,bwr->bhr", pw.astype(c_cache.dtype), c_cache,
                       preferred_element_type=jnp.float32)
    o = jnp.einsum("bhr,hrv->bhv", o_lat.astype(x.dtype), p["wuv"])
    return o.reshape(B, H * dv) @ p["wo"], MLACache(c_cache, r_cache)
