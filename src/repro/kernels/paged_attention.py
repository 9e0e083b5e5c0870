"""Paged GQA decode-attention kernel (the per-step serving hot spot).

One query token per sequence attends over a paged KV pool through a block
table — the TPU-native analogue of the serving engine's paged cache.

  grid = (B, cdiv(max_blk, P)); grid step (b, i) covers the P table
  entries [i*P, i*P + P) of row b.

Only the pages inside a row's valid window ``[start_lens, seq_lens)`` are
walked.  The pools stay in HBM; each grid step copies the valid pages of
its block into one half of a double-buffered VMEM scratch by async DMA,
after starting the copies of the next block that has any valid page (in
this row, or the first such block of a later row).  A block with no valid
page issues no copy and no compute, so a row costs its own length, not
``max_blk``; idle rows (``seq_len`` 0) only write their zero output.

P comes from static shapes alone: as many pages as fit ``BLOCK_BYTES`` of
K (the same of V), at most ``MAX_BLOCK_TOKENS`` tokens and at most the
table's width.

A page ``(bs, Hkv, Dh)`` is viewed as ``(bs*Hkv, Dh)`` rows, one row a
token's head: where Hkv fills the TPU's (8, 128) tiles of the pool, that
view is a bitcast.  (A ``(bs, Hkv*Dh)`` view would need a relayout: the
pool's tiles hold one token's heads.)  The score matmul takes every
query head against every row of the block and a static mask keeps each
query head's own KV head, so no head is sliced out of the page (a
strided load of bf16 rows does not lower); the pool's K feeds the MXU
in its own dtype with f32
accumulation, and the softmax statistics, the probabilities and the PV
product stay in f32 (V upcast).  Online softmax carries the running max,
denominator and accumulator in VMEM scratch across a row's blocks; the
output tile is written once, on the row's last grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_BYTES = 512 * 1024    # K bytes a grid step copies at most
MAX_BLOCK_TOKENS = 512
LANES = 128


def pages_per_step(bs: int, hkv: int, dh: int, itemsize: int,
                   max_blk: int) -> int:
    """Table entries one grid step covers (P), from the page's bytes."""
    page = bs * hkv * dh * itemsize
    return max(1, min(BLOCK_BYTES // page, MAX_BLOCK_TOKENS // bs, max_blk))


def _valid_end(sl_ref, b, bs: int, max_blk: int):
    """Row b's end of valid positions, within its table."""
    return lax.min(sl_ref[b], max_blk * bs)


def _window(sl_ref, st_ref, b, bs: int, max_blk: int):
    """Row b's valid pages: [lo, hi); empty when hi <= lo."""
    lo = lax.div(st_ref[b], bs)
    hi = lax.div(_valid_end(sl_ref, b, bs, max_blk) + bs - 1, bs)
    return lo, hi


def _paged_attn_kernel(bt_ref, sl_ref, st_ref, q_ref, k_hbm, v_hbm, o_ref,
                       k_buf, v_buf, sems, acc_ref, m_ref, l_ref, state,
                       *, bs: int, hkv: int, pages: int, max_blk: int,
                       scale: float):
    b = pl.program_id(0)
    i = pl.program_id(1)
    nrows = pl.num_programs(0)
    nblk = pl.num_programs(1)
    P = pages

    def block_pages(row, blk):
        """Table entries [j0, j1) of block ``blk`` of ``row`` that lie in
        the row's window."""
        lo, hi = _window(sl_ref, st_ref, row, bs, max_blk)
        return lax.max(lo, blk * P), lax.min(hi, blk * P + P)

    def for_pages(row, blk, slot, op):
        """``op`` on the K and V copy of each page of the block's window
        (one loop body, whatever P)."""
        def body(j, carry):
            page = bt_ref[row * max_blk + j]
            for n, (src, buf) in enumerate(((k_hbm, k_buf),
                                            (v_hbm, v_buf))):
                op(pltpu.make_async_copy(src.at[page],
                                         buf.at[slot, j - blk * P],
                                         sems.at[slot, n]))
            return carry
        lax.fori_loop(*block_pages(row, blk), body, 0)

    def start(row, blk, slot):
        for_pages(row, blk, slot, lambda cp: cp.start())

    def wait(row, blk, slot):
        for_pages(row, blk, slot, lambda cp: cp.wait())
        # a page outside the window was not copied: its rows hold stale
        # or uninitialised VMEM, which a zero probability must not meet
        # in the PV product
        def zero(p, carry):
            v_buf[slot, p] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)
            return carry
        j0, j1 = block_pages(row, blk)
        lax.fori_loop(0, j0 - blk * P, zero, 0)
        lax.fori_loop(j1 - blk * P, P, zero, 0)

    def blocks(row):
        """First and last block of ``row`` with a valid page, and whether
        there is one."""
        lo, hi = _window(sl_ref, st_ref, row, bs, max_blk)
        return lax.div(lo, P), lax.div(hi - 1, P), hi > lo

    @pl.when((b == 0) & (i == 0))
    def _first():
        state[0] = 0        # the slot the next block's pages land in
        state[1] = 0        # 1: the copies of that block have started

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    first, last, any_valid = blocks(b)

    @pl.when(any_valid & (i >= first) & (i <= last))
    def _block():
        slot = state[0]

        @pl.when(state[1] == 0)
        def _():
            start(b, i, slot)

        # the next block to walk: this row's next, else the first block
        # of the next row with a valid page
        def later_row(r):
            return lax.while_loop(
                lambda r: (r < nrows)
                & ~blocks(lax.min(r, nrows - 1))[2],
                lambda r: r + 1, r)

        more = i < last
        nrow = lax.select(more, b, later_row(b + 1))
        has_next = nrow < nrows
        nrow = lax.min(nrow, nrows - 1)
        nxt = lax.select(more, i + 1, blocks(nrow)[0])

        @pl.when(has_next)
        def _():
            start(nrow, nxt, 1 - slot)

        state[0] = 1 - slot
        state[1] = has_next.astype(jnp.int32)
        wait(b, i, slot)

        q = q_ref[0]                                   # (H, Dh)
        H, Dh = q.shape
        G = H // hkv
        n = P * bs * hkv
        k = k_buf[slot].reshape(n, Dh)
        v = v_buf[slot].reshape(n, Dh).astype(jnp.float32)
        if k.dtype != q.dtype:
            k = k.astype(q.dtype)

        # column c of the block is token c // hkv's KV head c % hkv
        lo_pos = st_ref[b] - i * (P * bs)
        hi_pos = _valid_end(sl_ref, b, bs, max_blk) - i * (P * bs)
        col = lax.broadcasted_iota(jnp.int32, (H, n), 1)
        row = lax.broadcasted_iota(jnp.int32, (H, n), 0)
        tok, head = _divmod(col, hkv)
        valid = ((tok >= lo_pos) & (tok < hi_pos)
                 & (row >= head * G) & (row < head * G + G))

        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(valid, s * scale, NEG_INF)       # (H, n)
        m_prev = m_ref[...]                            # (H, 1)
        m_new = lax.max(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(valid, lax.exp(s - m_new), 0.0)
        corr = lax.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == nblk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _divmod(x, d: int):
    """Elementwise ``(x // d, x % d)`` of a non-negative int32 vector."""
    if d & (d - 1) == 0:
        shift = d.bit_length() - 1
        return x >> shift, x & (d - 1)
    quo = x // d
    return quo, x - quo * d


def paged_attention_pallas(q, k_pool, v_pool, block_table, seq_lens,
                           start_lens=None, *, interpret=False):
    """q: (B,H,Dh); pools: (nb, bs, Hkv, Dh); block_table: (B, max_blk);
    seq_lens: (B,); start_lens: optional (B,) first valid position
    (sliding window) -> (B, H, Dh).  ``interpret``: True or a
    ``pltpu.InterpretParams`` runs the kernel on the CPU."""
    Dh = q.shape[-1]
    scale = 1.0 / (Dh ** 0.5)
    if start_lens is None:
        start_lens = jnp.zeros_like(seq_lens)
    if Dh % LANES == 0:
        return _paged_attention(q, k_pool, v_pool, block_table, seq_lens,
                                start_lens, scale, interpret)
    # a DMA moves whole lane tiles: widen heads such as MLA's latent
    # R + dr with zero lanes (a copy of the pools) and cut them off the
    # output
    pad = [(0, 0)] * 3 + [(0, -Dh % LANES)]
    out = _paged_attention(jnp.pad(q, pad[1:]), jnp.pad(k_pool, pad),
                           jnp.pad(v_pool, pad), block_table, seq_lens,
                           start_lens, scale, interpret)
    return out[..., :Dh]


def _paged_attention(q, k_pool, v_pool, block_table, seq_lens, start_lens,
                     scale: float, interpret):
    B, H, Dh = q.shape
    nb, bs, Hkv, _ = k_pool.shape
    max_blk = block_table.shape[1]
    P = pages_per_step(bs, Hkv, Dh, k_pool.dtype.itemsize, max_blk)

    kernel = functools.partial(_paged_attn_kernel, bs=bs, hkv=Hkv, pages=P,
                               max_blk=max_blk, scale=scale)
    page_rows = (nb, bs * Hkv, Dh)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, pl.cdiv(max_blk, P)),
        in_specs=[
            pl.BlockSpec((1, H, Dh), lambda b, i, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, Dh), lambda b, i, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, P, bs * Hkv, Dh), k_pool.dtype),
            pltpu.VMEM((2, P, bs * Hkv, Dh), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((H, Dh), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), q.dtype),
        # a block's copies are started one grid step ahead, possibly in
        # the previous row: both axes run in order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(block_table.astype(jnp.int32).reshape(-1),
      seq_lens.astype(jnp.int32), start_lens.astype(jnp.int32), q,
      k_pool.reshape(page_rows), v_pool.reshape(page_rows))
