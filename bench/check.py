"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, the plain
reference of the configuration (``bench/reference/<name>.py``) runs once
over each sampled request: its prompt followed by the tokens the program
served, teacher-forced.  At every position that produced a served token
it reads the gap by which the served token's logit lies below the
reference's best logit there.  Greedy decoding serves the argmax, so a
sound program serves tokens whose gap is only rounding; a wrong layer,
cache or runtime opens it.  The configuration's ``check`` names the
numbers held to limits (the widest or the mean gap, and how many tokens
were checked, over the whole sample or over the requests served before
the fault and after the revive apart: ``bench.harness._checks``).

The reference makes its weights again from the seed, one layer at a time
(``bench.weights``), in float32 (``highest`` matmul precision), and holds
the hidden states of every sampled request between layers.

The control (``python bench/control.py``) runs the same reference with
its matrix products rounded to float8 and reads the gap of the token
that float8 puts first, at the same positions.
"""
from __future__ import annotations

import functools
import importlib
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.model import Shape
from bench.reference.common import mm, rms_norm


def reference_module(name: str):
    return importlib.import_module(f"bench.reference.{name}")


@functools.partial(jax.jit, static_argnames=("s", "dtype"))
def _layer_weights(key, layer, s, dtype):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                  W.layer_weights(key, s, layer, dtype))


def _padded(n: int, to: int = 128) -> int:
    return -(-n // to) * to


class Served:
    """One sampled request: ``tokens`` = prompt + served output; the
    served tokens start at ``n_prompt``; ``live`` lists the experts the
    program could route to while it served them (None: all)."""

    def __init__(self, tokens: Sequence[int], n_prompt: int, live=None):
        if not 0 < n_prompt < len(tokens):
            raise ValueError("a sampled request needs a prompt and at "
                             "least one served token")
        self.tokens = list(tokens)
        self.n_prompt = n_prompt
        self.live = live

    @property
    def rows(self) -> np.ndarray:
        """Positions whose logits chose a served token."""
        return np.arange(self.n_prompt - 1, len(self.tokens) - 1)

    @property
    def served(self) -> np.ndarray:
        return np.asarray(self.tokens[self.n_prompt:], np.int32)


def final_hidden(seed: int, s: Shape, ref_name: str, seqs: List[Served],
                 precision: str = "f32", dtype=jnp.bfloat16,
                 length: int = 0) -> list:
    """Normed last hidden state at each sequence's served rows, after the
    reference's forward pass in ``precision``.  Every sequence is padded
    to ``length`` (at least its own), so one compiled program serves
    every run of a configuration."""
    key = W.seed_key(seed)
    T = _padded(max([length] + [len(q.tokens) for q in seqs]))
    positions = jnp.arange(T, dtype=jnp.int32)
    emb = W.embed(key, s, dtype)
    xs = []
    for q in seqs:
        ids = np.zeros((T,), np.int32)
        ids[: len(q.tokens)] = q.tokens
        xs.append(emb[jnp.asarray(ids)].astype(jnp.float32))
    del emb
    lives = [jnp.ones((max(s.experts, 1),), bool) if q.live is None
             else jnp.asarray(q.live) for q in seqs]
    layer = _layer_fn(ref_name, s, precision)
    with jax.default_matmul_precision("highest"):
        for li in range(s.layers):
            w = _layer_weights(key, li, s, dtype)
            xs = [layer(w, x, positions, live=lv)
                  for x, lv in zip(xs, lives)]
            del w
        ones = jnp.ones((s.d_model,), jnp.float32)
        return [rms_norm(x[jnp.asarray(_rows_padded(q))], ones, s.eps)
                for x, q in zip(xs, seqs)]


@functools.lru_cache(maxsize=None)
def _layer_fn(ref_name: str, s: Shape, precision: str):
    return jax.jit(functools.partial(reference_module(ref_name).layer, s=s,
                                     precision=precision))


def _rows_padded(q: Served) -> np.ndarray:
    """``q.rows`` padded to a multiple of 128 with its last row, so the
    programs that read them compile for a few sizes only (the padding
    is cut off again: :func:`_unpad`)."""
    idx = np.full((_padded(len(q.rows)),), q.rows[-1])
    idx[: len(q.rows)] = q.rows
    return idx


def _unpad(gap, q: Served) -> np.ndarray:
    return np.asarray(gap, np.float64)[: len(q.rows)]


@jax.jit
def _served_gap(h, head, served):
    logits = mm(h, head)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return jnp.max(logits, axis=-1) - got


@jax.jit
def _control_gap(h32, h8, head):
    ref = mm(h32, head)
    pick = jnp.argmax(mm(h8, head, "f8"), axis=-1)
    got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - got


def _head(seed: int, s: Shape, dtype):
    return W.lm_head(W.seed_key(seed), s, dtype).astype(jnp.float32)


def served_gaps(seed: int, s: Shape, ref_name: str, seqs: List[Served],
                dtype=jnp.bfloat16, length: int = 0) -> List[np.ndarray]:
    """Per sequence, reference best logit minus the served token's
    logit at each served position (>= 0).  ``dtype`` is the one the
    weights are served in; the reference computes in float32 from the
    same rounded values."""
    hs = final_hidden(seed, s, ref_name, seqs, "f32", dtype, length)
    head = _head(seed, s, dtype)
    out = []
    for h, q in zip(hs, seqs):
        served = np.zeros((h.shape[0],), np.int32)
        served[: len(q.served)] = q.served
        out.append(_unpad(_served_gap(h, head, jnp.asarray(served)), q))
    return out


def control_gaps(seed: int, s: Shape, ref_name: str, seqs: List[Served],
                 dtype=jnp.bfloat16, length: int = 0) -> List[np.ndarray]:
    """Per sequence, the float32 reference's best logit minus its logit
    for the token the float8 reference puts first, at the same served
    positions (the same prompts and served tokens are fed to both)."""
    h32 = final_hidden(seed, s, ref_name, seqs, "f32", dtype, length)
    h8 = final_hidden(seed, s, ref_name, seqs, "f8", dtype, length)
    head = _head(seed, s, dtype)
    return [_unpad(_control_gap(a, b, head), q)
            for a, b, q in zip(h32, h8, seqs)]
