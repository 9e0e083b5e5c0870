"""Flash-attention prefill kernel (causal GQA, online softmax in VMEM).

The §Perf analysis showed pure-XLA chunked attention materializes the
(q_chunk, kv_chunk) probability tile to HBM on every inner step — at 32 k
context that is ~10 TB/step of avoidable traffic (the dominant roofline
term for every prefill shape).  This kernel keeps the score/probability
tile and the online-softmax state (m, l, acc) in VMEM scratch for the
whole kv sweep, so HBM sees only Q/K/V once and O once — the
memory-optimal schedule.

Tiling: grid = (B, Hkv, Sq/Bq, Skv/Bk), kv innermost (sequential);
q/o tiles are (G·Bq, Dh) with G = H/Hkv query heads per kv head —
MXU-aligned when G·Bq and Dh are multiples of 128.  Causal masking is
positional; fully-masked kv tiles are skipped via the index map (the
grid is still issued but the kernel exits early on the mask check).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_prefill_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                          l_ref, *, bq: int, bk: int, scale: float,
                          causal: bool):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # token positions of this tile pair
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    live = (not causal) or (qi * bq + bq - 1 >= kj * bk)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32)          # (G, bq, Dh)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, Dh)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, Dh)
        G = q.shape[0]
        s = jax.lax.dot_general(
            q.reshape(G * q.shape[1], -1), k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (G*bq, bk)
        s = s.reshape(G, -1, k.shape[0])
        if causal:
            mask = q_pos >= k_pos
            s = jnp.where(mask[None], s, NEG_INF)
        m_prev = m_ref[...]                           # (G, bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(mask[None], p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.reshape(-1, p.shape[-1]), v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv.reshape(acc_ref.shape)
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_prefill_pallas(q, k, v, *, causal: bool = True, block_q: int = 256,
                         block_k: int = 256, interpret: bool = False):
    """q: (B, S, H, Dh); k/v: (B, S, Hkv, Dh) -> (B, S, H, Dh)."""
    B, S, H, Dh = q.shape
    Hkv = k.shape[2]
    assert H % Hkv == 0
    G = H // Hkv
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    scale = 1.0 / (Dh ** 0.5)

    # layout: (B, Hkv, G, S, Dh) so one grid step owns a (G, bq, Dh) tile
    qg = q.reshape(B, S, Hkv, G, Dh).transpose(0, 2, 3, 1, 4)
    kg = k.transpose(0, 2, 1, 3)                     # (B, Hkv, S, Dh)
    vg = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_flash_prefill_kernel, bq=bq, bk=bk,
                               scale=scale, causal=causal)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, S // bq, S // bk),
        in_specs=[
            pl.BlockSpec((1, 1, G, bq, Dh),
                         lambda b, h, i, j: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, bq, Dh),
                               lambda b, h, i, j: (b, h, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, S, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, bq, Dh), jnp.float32),
            pltpu.VMEM((G, bq, 1), jnp.float32),
            pltpu.VMEM((G, bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qg, kg, vg)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, Dh)
