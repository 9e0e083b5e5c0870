"""Paged GQA decode-attention kernel (the per-step serving hot spot).

One query token per sequence attends over a paged KV pool through a block
table — the TPU-native analogue of the serving engine's paged cache.  The
block table and sequence lengths ride in as *scalar-prefetch* operands so
each grid step can DMA exactly the page it needs from HBM:

  grid = (B, max_blk); page j of sequence b is resolved to a physical
  pool page via block_table[b, j] inside the k/v BlockSpec index_map.

Online softmax (running max / denominator / accumulator in VMEM scratch,
carried across the sequential page axis) keeps the score matrix
unmaterialized; the output tile is written once on the final page.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(bt_ref, sl_ref, st_ref, q_ref, k_ref, v_ref, o_ref,
                       acc_ref, m_ref, l_ref, *, bs: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nblk = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                  # (H, Dh)
    k = k_ref[0].astype(jnp.float32)                  # (bs, Hkv, Dh)
    v = v_ref[0].astype(jnp.float32)
    H, Dh = q.shape
    Hkv = k.shape[1]
    G = H // Hkv

    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    # valid window: [start, len) — start > 0 models a sliding window
    valid = (pos < sl_ref[b]) & (pos >= st_ref[b])    # (1, bs)

    # per-kv-head matmuls: (G, Dh) x (Dh, bs) -> (G, bs)
    qg = q.reshape(Hkv, G, Dh)
    s_rows = []
    for h in range(Hkv):
        s_rows.append(jnp.dot(qg[h], k[:, h, :].T,
                              preferred_element_type=jnp.float32))
    s = jnp.stack(s_rows).reshape(H, bs) * scale      # (H, bs)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                               # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    pv_rows = []
    pg = p.reshape(Hkv, G, bs)
    for h in range(Hkv):
        pv_rows.append(jnp.dot(pg[h], v[:, h, :],
                               preferred_element_type=jnp.float32))
    pv = jnp.stack(pv_rows).reshape(H, Dh)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new

    @pl.when(j == nblk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pool, v_pool, block_table, seq_lens,
                           start_lens=None, *, interpret: bool = False):
    """q: (B,H,Dh); pools: (nb, bs, Hkv, Dh); block_table: (B, max_blk);
    seq_lens: (B,); start_lens: optional (B,) first valid position
    (sliding window) -> (B, H, Dh)."""
    B, H, Dh = q.shape
    nb, bs, Hkv, _ = k_pool.shape
    max_blk = block_table.shape[1]
    scale = 1.0 / (Dh ** 0.5)
    if start_lens is None:
        start_lens = jnp.zeros_like(seq_lens)

    kernel = functools.partial(_paged_attn_kernel, bs=bs, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, max_blk),
        in_specs=[
            pl.BlockSpec((1, H, Dh), lambda b, j, bt, sl, st: (b, 0, 0)),
            pl.BlockSpec((1, bs, Hkv, Dh),
                         lambda b, j, bt, sl, st: (bt[b, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, Hkv, Dh),
                         lambda b, j, bt, sl, st: (bt[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, Dh),
                               lambda b, j, bt, sl, st: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, Dh), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      start_lens.astype(jnp.int32), q, k_pool, v_pool)
