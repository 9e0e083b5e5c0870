"""Operations the algorithm needs, from the model's sizes.

Counted as the model needs them, not as the program happens to compute:
a token's matrix products over the parameters it uses (its top-k routed
experts, not every expert; the output head only where its logits are
used), and attention over the keys actually valid for it.  Each layer's
share comes from the architecture module (``arch``,
``bench/reference/<name>.py``): ``matmul_params(s, i)`` of layer ``i``
and ``attention_flops(s, ctx)``, which is linear in ``ctx`` (a count per
key); the bytes of one paged-attention call are its ``attention_call``.
"""
from __future__ import annotations

import functools

from bench.model import Shape


@functools.lru_cache(maxsize=None)
def _matmul_params(arch, s: Shape) -> int:
    """Parameters one token multiplies with, over all layers."""
    return sum(arch.matmul_params(s, i) for i in range(s.layers))


def token_flops(arch, s: Shape, ctx: int, head: bool) -> int:
    """Forward FLOPs of one token at context length ``ctx`` (itself
    included), with the output head when its logits are used."""
    return (2 * _matmul_params(arch, s)
            + s.layers * arch.attention_flops(s, ctx)
            + (2 * s.d_model * s.vocab if head else 0))


def prefill_flops(arch, s: Shape, start: int, stop: int) -> int:
    """Forward FLOPs of prompt positions [start, stop) (each at context
    position + 1), without the output head: the sum of
    :func:`token_flops` over them, in closed form."""
    n = stop - start
    ctx_sum = (start + 1 + stop) * n // 2
    return (2 * _matmul_params(arch, s) * n
            + s.layers * arch.attention_flops(s, ctx_sum))
