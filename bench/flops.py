"""Operations and bytes the algorithm needs, from the model's sizes.

Counted as the model needs them, not as the program happens to compute:
a token's matrix products over the parameters it uses (its top-k routed
experts, not every expert; the output head only where its logits are
used), and attention over the keys actually valid for it.
"""
from __future__ import annotations

from typing import Iterable, Sequence, Tuple

from bench.model import Shape


def matmul_params(s: Shape) -> int:
    """Parameters one token multiplies with in one layer."""
    D, H, Hkv, dh = s.d_model, s.heads, s.kv_heads, s.head_dim
    attn = D * H * dh * 2 + D * Hkv * dh * 2          # wq, wo, wk, wv
    if s.moe:
        ffn = (D * s.experts                          # router
               + s.top_k * 3 * D * s.expert_d_ff      # routed SwiGLU
               + 3 * D * s.shared_d_ff)               # shared SwiGLU
    else:
        ffn = 3 * D * s.d_ff
    return attn + ffn


def attention_flops(s: Shape, ctx: int) -> int:
    """One token attending over ``ctx`` keys in one layer: QK^T and PV."""
    return 4 * ctx * s.heads * s.head_dim


def token_flops(s: Shape, ctx: int, head: bool) -> int:
    """Forward FLOPs of one token at context length ``ctx`` (itself
    included), with the output head when its logits are used."""
    per_layer = 2 * matmul_params(s) + attention_flops(s, ctx)
    return s.layers * per_layer + (2 * s.d_model * s.vocab if head else 0)


def prefill_flops(s: Shape, start: int, stop: int) -> int:
    """Forward FLOPs of prompt positions [start, stop) (each at context
    position + 1), without the output head: the sum of
    :func:`token_flops` over them, in closed form."""
    n = stop - start
    ctx_sum = (start + 1 + stop) * n // 2
    return s.layers * (2 * matmul_params(s) * n
                       + 4 * s.heads * s.head_dim * ctx_sum)


def paged_attention_call(s: Shape, seqs: Iterable[Sequence[int]],
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
