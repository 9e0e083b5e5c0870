"""Paged KV cache: physical block pools addressed through block tables.

This is the engine's **only** compiled serving-cache representation (the
dense per-slot ring caches in ``repro.models.attention`` remain as the
reference decode semantics, proven equivalent in
tests/test_paged_serving.py).  The host-side twin is
``repro.core.block_log``: the BlockManager/BlockTable decide *which*
physical block a token lands in (all logged/undoable); the device-side
pools live inside the model's paged cache pytree
(``Model.init_paged_cache``) and are attended through
``ops.paged_attention`` — the Pallas kernel on TPU, its jnp oracle on
CPU.

This module owns the host-side glue: packing the per-step paging arrays
(block tables, valid lengths, write destinations) that ride into the
compiled decode step as data, so continuous batching, migration, and
recovery never retrigger compilation.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import ops


def max_blocks_per_seq(max_seq: int, block_size: int) -> int:
    return (max_seq + block_size - 1) // block_size


def walked_pages(seq_lens, block_size: int, window: int = 0) -> int:
    """Pages ``paged_attention`` walks for rows of these valid lengths:
    each row's pages from the one holding its first attended position
    (``seq_len - window`` under a sliding window; 0 is none) to the one
    holding its last; a row of ``seq_len`` 0 walks none."""
    sl = np.asarray(seq_lens, np.int64)
    lo = np.maximum(sl - window, 0) // block_size if window else 0
    hi = -(-sl // block_size)
    return int(np.maximum(hi - lo, 0).sum())


def table_array(tables: Dict[int, "BlockTable"], order: List[int],
                max_blk: int) -> np.ndarray:
    """Pack host-side block tables into the (B, max_blk) device array."""
    out = np.zeros((len(order), max_blk), np.int32)
    for i, seq_id in enumerate(order):
        blocks = tables[seq_id].blocks
        out[i, : len(blocks)] = blocks[:max_blk]
    return out


def build_page_context(decode_reqs, block_tables, *, max_batch: int,
                       max_blk: int, block_size: int,
                       trash_block: int) -> Dict[str, np.ndarray]:
    """The per-step paging arrays for one decode batch.

    For each request in ``decode_reqs`` (occupying ``req.batch_slot``),
    position ``num_tokens - 1`` is where this step's incoming token
    lands; ``seq_lens`` is the valid length including it.  Idle batch
    slots keep seq_len 0 and write into the trash block, so a full-width
    decode step never touches live blocks.
    """
    tables = np.zeros((max_batch, max_blk), np.int32)
    seq_lens = np.zeros((max_batch,), np.int32)
    write_bid = np.full((max_batch,), trash_block, np.int32)
    write_off = np.zeros((max_batch,), np.int32)
    for req in decode_reqs:
        slot = req.batch_slot
        blocks = block_tables[req.req_id].blocks
        tables[slot, : len(blocks)] = blocks[:max_blk]
        wp = req.num_tokens - 1              # position of the new token
        seq_lens[slot] = wp + 1
        write_bid[slot] = blocks[wp // block_size]
        write_off[slot] = wp % block_size
    return {"tables": tables, "seq_lens": seq_lens,
            "write_bid": write_bid, "write_off": write_off}


def build_chunk_context(pieces, block_tables, *, width: int, max_blk: int,
                        block_size: int, trash_block: int):
    """Pack a batched multi-request prefill chunk into paging arrays.

    A chunked prefill step is a decode step over ``width`` *virtual
    slots*: row ``r`` carries one prompt token, its owner's block table,
    ``seq_lens`` = its absolute position + 1 (so causal attention over
    the pool covers the already-installed prefix AND earlier rows of the
    same chunk, whose K/V land in the pool before attention runs), and
    the (block, offset) its own K/V is written to.  Requests of any
    length mix freely in one chunk — raggedness is pure data, so the
    compiled graph never re-specializes.  Rows past the planned tokens
    are idle: seq_len 0, writes into the trash block.

    ``pieces``: objects with ``.req`` (owning Request), ``.start``
    (first position this step), ``.length`` and ``.tokens`` (the full
    token sequence being prefilled).  Returns ``(tokens, page)``.
    """
    tokens = np.zeros((width,), np.int32)
    tables = np.zeros((width, max_blk), np.int32)
    seq_lens = np.zeros((width,), np.int32)
    write_bid = np.full((width,), trash_block, np.int32)
    write_off = np.zeros((width,), np.int32)
    row = 0
    for piece in pieces:
        blocks = block_tables[piece.req.req_id].blocks
        packed = np.asarray(blocks[:max_blk], np.int32)
        for j in range(piece.length):
            pos = piece.start + j
            tokens[row] = piece.tokens[pos]
            tables[row, : len(packed)] = packed
            seq_lens[row] = pos + 1
            write_bid[row] = blocks[pos // block_size]
            write_off[row] = pos % block_size
            row += 1
    assert row <= width, (row, width)
    page = {"tables": tables, "seq_lens": seq_lens,
            "write_bid": write_bid, "write_off": write_off}
    return tokens, page


def page_context_specs(max_batch: int, max_blk: int):
    i32 = jnp.int32
    return {
        "tables": jax.ShapeDtypeStruct((max_batch, max_blk), i32),
        "seq_lens": jax.ShapeDtypeStruct((max_batch,), i32),
        "write_bid": jax.ShapeDtypeStruct((max_batch,), i32),
        "write_off": jax.ShapeDtypeStruct((max_batch,), i32),
    }


def padded_block_ids(blocks: List[int], nblk: int,
                     trash_block: int) -> np.ndarray:
    """A request's block ids padded to the prefill bucket's block count;
    ids past the table point at the trash block (their rows are dead)."""
    out = np.full((nblk,), trash_block, np.int32)
    out[: min(len(blocks), nblk)] = blocks[:nblk]
    return out


class PagedKVCache:
    """Standalone per-layer K/V pools — the unit-test twin of the pools
    inside the engine's paged cache pytree (kept for kernel-level tests
    and ad-hoc experiments; the engine uses ``Model.init_paged_cache``)."""

    def __init__(self, cfg: ModelConfig, num_layers: int, num_blocks: int,
                 block_size: int, dtype=jnp.float32):
        Dh = cfg.resolved_head_dim()
        self.cfg = cfg
        self.block_size = block_size
        self.num_blocks = num_blocks
        shape = (num_layers, num_blocks, block_size, cfg.num_kv_heads, Dh)
        self.k_pool = jnp.zeros(shape, dtype)
        self.v_pool = jnp.zeros(shape, dtype)

    def write_token(self, layer: int, block_id: int, offset: int,
                    k: jnp.ndarray, v: jnp.ndarray) -> None:
        """Write one token's K/V (Hkv, Dh) into (block, offset)."""
        self.k_pool = self.k_pool.at[layer, block_id, offset].set(
            k.astype(self.k_pool.dtype))
        self.v_pool = self.v_pool.at[layer, block_id, offset].set(
            v.astype(self.v_pool.dtype))

    def write_prefill(self, layer: int, block_ids: List[int],
                      k: jnp.ndarray, v: jnp.ndarray) -> None:
        """Write a whole prompt's K/V (S, Hkv, Dh) into its blocks."""
        S = k.shape[0]
        bs = self.block_size
        for j, bid in enumerate(block_ids):
            lo = j * bs
            if lo >= S:
                break
            hi = min(lo + bs, S)
            self.k_pool = self.k_pool.at[layer, bid, : hi - lo].set(
                k[lo:hi].astype(self.k_pool.dtype))
            self.v_pool = self.v_pool.at[layer, bid, : hi - lo].set(
                v[lo:hi].astype(self.v_pool.dtype))

    def attend(self, layer: int, q: jnp.ndarray,
               block_table: jnp.ndarray, seq_lens: jnp.ndarray,
               use_pallas: bool = False) -> jnp.ndarray:
        """Decode attention for one layer.

        q: (B, H, Dh); block_table: (B, max_blk) int32; seq_lens: (B,).
        use_pallas: run the Pallas kernel (interpret mode on CPU).
        """
        return ops.paged_attention(q, self.k_pool[layer],
                                   self.v_pool[layer], block_table,
                                   seq_lens, use_pallas=use_pallas)
