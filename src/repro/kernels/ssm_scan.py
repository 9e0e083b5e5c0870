"""Chunked Mamba selective-scan kernel.

The recurrence h_t = exp(dt_t·A)·h_{t-1} + dt_t·B_t·u_t is sequential in
time but embarrassingly parallel over (batch, channel).  TPU-native
layout: grid = (B, d_inner/db, S/Sc) with the chunk axis innermost and
*sequential*; the (db, N) state lives in VMEM scratch and is carried
across chunks, so HBM traffic is exactly one read of u/dt/B/C and one
write of y — the memory-bound optimum (N=16 keeps the state tiny).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_scan_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref,
                     h_ref, *, chunk: int):
    cblk = pl.program_id(2)
    nchunk = pl.num_programs(2)

    @pl.when(cblk == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = a_ref[...].astype(jnp.float32)                # (db, N)

    def body(t, _):
        u_t = u_ref[0, t, :].astype(jnp.float32)      # (db,)
        dt_t = dt_ref[0, t, :].astype(jnp.float32)    # (db,)
        b_t = b_ref[0, t, :].astype(jnp.float32)      # (N,)
        c_t = c_ref[0, t, :].astype(jnp.float32)      # (N,)
        dA = jnp.exp(dt_t[:, None] * A)               # (db, N)
        h = h_ref[...] * dA + (dt_t * u_t)[:, None] * b_t[None, :]
        h_ref[...] = h
        y_ref[0, t, :] = jnp.sum(h * c_t[None, :], axis=1).astype(
            y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, chunk, body, 0)

    @pl.when(cblk == nchunk - 1)
    def _emit_state():
        hout_ref[0] = h_ref[...].astype(hout_ref.dtype)


def ssm_scan_pallas(u, dt, A, B_ssm, C_ssm, *, block_d: int = 256,
                    chunk: int = 64, interpret: bool = False):
    """u/dt: (B,S,d); A: (d,N); B/C: (B,S,N) -> (y (B,S,d), h (B,d,N))."""
    Bsz, S, d = u.shape
    N = A.shape[1]
    db = min(block_d, d)
    Sc = min(chunk, S)
    assert d % db == 0 and S % Sc == 0, (d, db, S, Sc)

    kernel = functools.partial(_ssm_scan_kernel, chunk=Sc)
    y, h = pl.pallas_call(
        kernel,
        grid=(Bsz, d // db, S // Sc),
        in_specs=[
            pl.BlockSpec((1, Sc, db), lambda b, dd, c: (b, c, dd)),
            pl.BlockSpec((1, Sc, db), lambda b, dd, c: (b, c, dd)),
            pl.BlockSpec((db, N), lambda b, dd, c: (dd, 0)),
            pl.BlockSpec((1, Sc, N), lambda b, dd, c: (b, c, 0)),
            pl.BlockSpec((1, Sc, N), lambda b, dd, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Sc, db), lambda b, dd, c: (b, c, dd)),
            pl.BlockSpec((1, db, N), lambda b, dd, c: (b, dd, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, S, d), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, d, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((db, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(u, dt, A, B_ssm, C_ssm)
    return y, h
