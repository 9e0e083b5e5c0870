"""Fused MoE dispatch → grouped SwiGLU FFN → weighted combine kernel.

The dense-scatter MoE path (``moe.dispatch_compute_combine``) round-trips
an ``(E_local, cap, D)`` capacity buffer through HBM four times: scatter
tokens in, read for the expert FFN, write the FFN output, gather back out
— plus an ``(N, D)`` unsort scatter.  At prefill shapes that buffer is
``capacity_factor`` × the token payload and dominates the MoE roofline.

This kernel keeps the whole pipeline on-chip.  A single ``argsort`` over
expert ids (done in jnp by :func:`moe_group_tokens` — sorting is cheap,
it is the D-wide data movement that hurts) produces, per capacity slot:

  * ``tok_idx (E_local, cap) int32`` — which token row fills the slot
  * ``wgt     (E_local, cap) f32``   — its combine weight (0 = empty slot)

The kernel then runs the ``expert_ffn`` tiling (grid ``(E, cap/Cb,
F/Fb)``, f innermost, (Cb, D) accumulator resident in VMEM, 128-aligned
MXU tiles) but instead of reading a pre-scattered capacity buffer it

  1. **gathers** the x rows for its (expert, slot-block) tile straight
     from the token array at ``f == 0`` (rows stay in VMEM scratch for
     the whole F sweep),
  2. computes ``silu(x@gate) * (x@up) @ down`` tile by tile, and
  3. **scatter-combines** ``wgt * acc`` into the output token rows at
     the last f step.

TPU grids execute sequentially over non-parallel dimensions, so the
read-modify-write combine into ``y`` is race-free; a token selected by k
experts receives its k partial sums across k distinct grid steps.  HBM
sees x once, y once, and two (E·cap) int32/f32 tables — no (E, cap, D)
buffer, no unsort pass.

The two tables are scalar-prefetched whole into SMEM (the kernel only
reads them as scalars).  x and y ride in VMEM as float32: their rows are
addressed by a runtime token index, which Mosaic allows only on 32-bit
arrays.

Current limitation (documented, not enforced): x and y ride in whole-
array VMEM block specs, so very large prefill chunks should be split by
the caller (the distributed path already chunks at
``MAX_GATHERED_TOKENS``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.models.moe import group_by_expert


def f_block(F: int, pref: int) -> int:
    """FFN-axis block for a (D, F) weight page: the whole axis when it
    fits ``pref``, else the largest multiple of 128 up to ``pref`` that
    divides F (qwen2-moe's 1408 = 11 x 128 takes 128), so the expert bank
    is never padded (a per-call copy of every weight); ``pref`` with
    padding only when no such divisor exists."""
    if F <= pref:
        return F
    for fb in range(pref - pref % 128, 0, -128):
        if F % fb == 0:
            return fb
    return pref


def moe_group_tokens(phys, alive, weights, *, expert_offset, e_local: int,
                     cap: int):
    """Single sort pass: routing outputs -> per-expert slot tables.

    phys/alive/weights: (T, k); expert_offset may be traced (EP rank *
    e_local inside shard_map).  Returns (tok_idx (E,cap) i32 row into the
    flat token array, wgt (E,cap) f32; empty slots have wgt == 0 and
    tok_idx == 0).
    """
    T, k = phys.shape
    N = T * k
    e_id = phys.reshape(N) - expert_offset
    ok = (e_id >= 0) & (e_id < e_local) & alive.reshape(N)
    order, scatter_e, scatter_p = group_by_expert(e_id, ok, e_local, cap)
    tok = (jnp.arange(N, dtype=jnp.int32) // k)[order]
    w = weights.reshape(N).astype(jnp.float32)[order]
    tok_idx = jnp.zeros((e_local, cap), jnp.int32).at[
        scatter_e, scatter_p].set(tok, mode="drop")
    wgt = jnp.zeros((e_local, cap), jnp.float32).at[
        scatter_e, scatter_p].set(w, mode="drop")
    return tok_idx, wgt


def _moe_fused_kernel(tok_ref, wgt_ref, x_ref, g_ref, u_ref, d_ref, y_ref,
                      xs_ref, acc_ref, *, cb: int):
    # tok_ref/wgt_ref are the whole (E, Cp) slot tables, scalar-prefetched
    # into SMEM: every access below is a scalar read
    e = pl.program_id(0)
    c = pl.program_id(1)
    f = pl.program_id(2)
    nf = pl.num_programs(2)
    c0 = c * cb

    @pl.when((e == 0) & (c == 0) & (f == 0))
    def _zero_out():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(f == 0)
    def _gather():
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def body(i, _):
            t = tok_ref[e, c0 + i]
            live = wgt_ref[e, c0 + i] != 0.0
            row = x_ref[pl.ds(t, 1), :]
            xs_ref[pl.ds(i, 1), :] = jnp.where(live, row, 0.0)
            return 0

        jax.lax.fori_loop(0, cb, body, 0)

    x = xs_ref[...].astype(g_ref.dtype)               # (Cb, D)
    g = g_ref[0]                                      # (D, Fb)
    u = u_ref[0]
    d = d_ref[0]                                      # (Fb, D)
    h = jax.nn.silu(jnp.dot(x, g, preferred_element_type=jnp.float32))
    h = h * jnp.dot(x, u, preferred_element_type=jnp.float32)
    acc_ref[...] += jnp.dot(h.astype(x.dtype), d,
                            preferred_element_type=jnp.float32)

    @pl.when(f == nf - 1)
    def _combine():
        def body(i, _):
            w = wgt_ref[e, c0 + i]

            @pl.when(w != 0.0)
            def _():
                t = tok_ref[e, c0 + i]
                y_ref[pl.ds(t, 1), :] += w * acc_ref[pl.ds(i, 1), :]

            return 0

        jax.lax.fori_loop(0, cb, body, 0)


def moe_fused_pallas(x, gate_w, up_w, down_w, weights, phys, alive, *,
                     cap: int, expert_offset=0, e_local: int,
                     block_c: int = 128, block_f: int = 256,
                     interpret: bool = False):
    """Fused dispatch->FFN->combine over local expert slots.

    x: (T, D); gate/up: (E_local, D, F); down: (E_local, F, D);
    weights (T,k) f32, phys (T,k) i32 physical slot ids, alive (T,k) bool.
    Returns y (T, D) = sum_k w * expert_{phys}(x) restricted to slots in
    [expert_offset, expert_offset + e_local); out-of-capacity / foreign /
    lost-expert copies contribute zero (same semantics as the dense path).
    """
    T, D = x.shape
    E = gate_w.shape[0]
    assert E == e_local, (E, e_local)
    F = gate_w.shape[-1]
    tok_idx, wgt = moe_group_tokens(
        phys, alive, weights, expert_offset=expert_offset,
        e_local=e_local, cap=cap)

    Cb = min(block_c, cap)
    Fb = f_block(F, block_f)
    Cp = ((cap + Cb - 1) // Cb) * Cb
    Fp = ((F + Fb - 1) // Fb) * Fb
    if Cp != cap:
        tok_idx = jnp.pad(tok_idx, ((0, 0), (0, Cp - cap)))
        wgt = jnp.pad(wgt, ((0, 0), (0, Cp - cap)))
    if Fp != F:
        gate_w = jnp.pad(gate_w, ((0, 0), (0, 0), (0, Fp - F)))
        up_w = jnp.pad(up_w, ((0, 0), (0, 0), (0, Fp - F)))
        down_w = jnp.pad(down_w, ((0, 0), (0, Fp - F), (0, 0)))

    kernel = functools.partial(_moe_fused_kernel, cb=Cb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                        # tok_idx, wgt
        grid=(E, Cp // Cb, Fp // Fb),
        in_specs=[
            pl.BlockSpec((T, D), lambda e, c, f, tk, wg: (0, 0)),  # x whole
            pl.BlockSpec((1, D, Fb), lambda e, c, f, tk, wg: (e, 0, f)),
            pl.BlockSpec((1, D, Fb), lambda e, c, f, tk, wg: (e, 0, f)),
            pl.BlockSpec((1, Fb, D), lambda e, c, f, tk, wg: (e, f, 0)),
        ],
        out_specs=pl.BlockSpec((T, D), lambda e, c, f, tk, wg: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Cb, D), jnp.float32),
            pltpu.VMEM((Cb, D), jnp.float32),
        ],
    )
    y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(tok_idx, wgt, x.astype(jnp.float32), gate_w, up_w, down_w)
    return y.astype(x.dtype)
