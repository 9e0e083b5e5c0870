"""Decode megakernel: paged attention + router + MoE in one launch.

The steady-state decode step is the hot path every ReviveMoE recovery
event returns to.  The composed step runs, per attention+MoE block, a
chain of kernels with HBM round-trips between them:

  paged_attention -> (B, H*Dh) out -> wo matmul -> residual -> rms_norm
  -> router matmul -> top_k -> replica select -> sort pre-pass ->
  fused MoE dispatch/FFN/combine -> shared-expert FFN -> residual

This kernel fuses the whole chain into **one** ``pallas_call`` per
block.  A single flat sequential grid runs five phases (TPU grids with
``arbitrary`` semantics execute in order, so cross-phase scratch carries
are race-free).  Only the *activations* — (B, D) residual/``h2`` tiles
and the (B, H*Dh) attention output — stay whole in VMEM (decode B is
small); every weight matrix with a ``d_model`` axis streams through the
kernel one D-page at a time, so deployment hidden sizes
(deepseek_v3/kimi_k2-class D = 7168) never have to fit a weight's full
D extent on chip:

  * **attention** (steps ``[0, B*max_blk)``): the paged-attention online
    softmax of ``kernels.paged_attention`` — page ``j`` of row ``b`` is
    gathered per row via the scalar-prefetched block table (the grid
    pipeline revolves these KV page buffers, i.e. the DMA for row
    ``b``'s next page overlaps the current page's compute); each row's
    normalized (H, Dh) output lands in a VMEM scratch tile.
  * **project** (``nd = D/block_d`` steps): the post-attention
    projection, one D-page per step — ``y[:, dp] = x[:, dp] +
    o @ w_post[:, dp]`` with the (H*Dh, block_d) weight page streamed
    (and double-buffered) by the pipeline; a running sum of squares
    accumulates for the norm.
  * **route** (``nd`` steps): RMS norm one D-page at a time (the sum of
    squares is already complete), router logits accumulated over
    (block_d, E) router pages; the last page finishes with the masked
    softmax, iterative top-k (k argmax passes — decode-shaped, k <= 8),
    replica selection from the MoERuntime arrays, and the per-expert
    slot tables built by a sequential scan (decode batches are small
    enough that the sort pre-pass of ``moe_fused`` degenerates to this
    O(B*k) scan).
  * **shared experts** (``ns * 2*nd`` steps, skipped when the config has
    none): the dense shared-expert SwiGLU folded into the launch — for
    each shared F-block, ``nd`` contraction steps accumulate the hidden
    over streamed (block_d, Fs_b) weight pages, then ``nd`` output
    steps scatter ``act @ w_down[f, dp]`` pages back into the resident
    ``y`` tile.
  * **MoE** (``E * nf * 2*nd`` steps): the grouped-SwiGLU expert
    pipeline of ``kernels.moe_fused`` with the same D-paging — gather
    rows from the resident ``h2`` tile at each expert's first step,
    ``nd`` contraction steps per F-block over streamed (1, block_d, Fb)
    gate/up pages, ``nd`` output steps over (1, Fb, block_d) down
    pages, and a weighted scatter-combine into ``y`` on the expert's
    last step.

Everything mutable by recovery — block tables, seq lens, window starts,
``expert_offset`` and the MoERuntime ``l2p``/``replica_count``/
``expert_mask`` — rides in as scalar-prefetch or tensor *data*, so
``fail_rank``/``mask_experts``/migration/chunked prefill never retrigger
compilation.

Remaining limitation: the capacity axis is a single block (decode caps
are small) and VMEM still scales with B * H * Dh for the attention
scratch, so prefill-shaped batches belong to the flash kernel, not this
one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.moe_fused import f_block

NEG_INF = -1e30


def _megastep_kernel(bt_ref, sl_ref, st_ref, off_ref, l2p_ref, rcnt_ref,
                     q_ref, k_ref, v_ref, x_ref, wpost_ref, ln2_ref,
                     router_ref, mask_ref,
                     sgate_ref, sup_ref, sdown_ref,
                     gate_ref, up_ref, down_ref,
                     y_ref, h2_ref,
                     acc_ref, m_ref, l_ref, o_ref, ssq_ref, lg_ref,
                     xs_ref, accm_ref, hg_ref, hu_ref, hgs_ref, hus_ref,
                     yf_ref, h2f_ref,
                     sel_ref, wsel_ref, tok_ref, wgt_ref, cnt_ref, *,
                     bs: int, n_attn: int, nd: int, nf: int, ns: int,
                     cap: int, top_k: int, e_local: int, e_log: int,
                     n_rep: int, scale: float, eps: float, d_model: int,
                     block_d: int):
    # Scalar tables live in SMEM: the scalar-prefetched paging arrays,
    # expert_offset and MoERuntime l2p (flat, n_rep per logical
    # expert) / replica_count, plus the routing scratch sel/wsel (flat
    # B*top_k) and the per-expert slot tables tok/wgt (flat E*cap).
    # Rows addressed by a runtime index (token gathers, the combine)
    # use the f32 VMEM copies yf/h2f of the (B, Dp) activations; y and
    # h2 are written from them.
    t = pl.program_id(0)
    B = y_ref.shape[0]
    attn_steps = B * n_attn
    p0 = attn_steps            # projection phase start
    r0 = p0 + nd               # norm/route phase start
    s0 = r0 + nd               # shared-expert phase start
    m0 = s0 + ns * 2 * nd      # routed-expert phase start

    # ---- phase A: paged-attention online softmax ----------------------
    @pl.when(t < attn_steps)
    def _attention():
        b = t // n_attn
        j = t % n_attn

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        q = q_ref[0].astype(jnp.float32)                  # (H, Da)
        k = k_ref[0].astype(jnp.float32)                  # (bs, Hkv, Da)
        v = v_ref[0].astype(jnp.float32)
        H, Da = q.shape
        Hkv = k.shape[1]
        G = H // Hkv

        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        valid = (pos < sl_ref[b]) & (pos >= st_ref[b])    # (1, bs)

        qg = q.reshape(Hkv, G, Da)
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
        pv = jnp.stack(pv_rows).reshape(H, Da)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

        @pl.when(j == n_attn - 1)
        def _finish_row():
            o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)   # (H, Da)
            o_ref[b, :] = o.reshape(H * Da)

    # ---- phase P: post-projection + residual, one D-page per step -----
    @pl.when((t >= p0) & (t < r0))
    def _project():
        dp = t - p0
        o_flat = o_ref[...].astype(x_ref.dtype)           # (B, H*Da)
        proj = jnp.dot(o_flat, wpost_ref[...],
                       preferred_element_type=jnp.float32)  # (B, Db)
        yb = (x_ref[...] + proj.astype(y_ref.dtype)).astype(jnp.float32)
        yf_ref[:, pl.ds(dp * block_d, block_d)] = yb
        sq = jnp.sum(jnp.square(yb), axis=-1, keepdims=True)

        @pl.when(dp == 0)
        def _():
            ssq_ref[...] = sq

        @pl.when(dp != 0)
        def _():
            ssq_ref[...] += sq

    # ---- phase R: norm + router (paged), then top-k + grouping --------
    @pl.when((t >= r0) & (t < s0))
    def _norm_route():
        dr = t - r0
        yb = yf_ref[:, pl.ds(dr * block_d, block_d)]
        rs = jax.lax.rsqrt(ssq_ref[...] / d_model + eps)  # (B, 1)
        h2b = (yb * rs).astype(h2_ref.dtype) * ln2_ref[...]
        h2_ref[:, pl.ds(dr * block_d, block_d)] = h2b
        h2f_ref[:, pl.ds(dr * block_d, block_d)] = h2b.astype(jnp.float32)
        contrib = jnp.dot(h2b, router_ref[...],
                          preferred_element_type=jnp.float32)  # (B, E_log)

        @pl.when(dr == 0)
        def _():
            lg_ref[...] = contrib

        @pl.when(dr != 0)
        def _():
            lg_ref[...] += contrib

        @pl.when(dr == nd - 1)
        def _route():
            logits = jnp.where(mask_ref[...] != 0, lg_ref[...], NEG_INF)
            mx = jnp.max(logits, axis=-1, keepdims=True)
            g = jnp.exp(logits - mx)
            gates = g / jnp.sum(g, axis=-1, keepdims=True)
            iota_e = jax.lax.broadcasted_iota(jnp.int32, (B, e_log), 1)
            remaining = gates
            wsum = jnp.zeros((B, 1), jnp.float32)
            sels, mvs = [], []
            for kk in range(top_k):  # k argmax passes; ties -> lowest id,
                mv = jnp.max(remaining, axis=-1, keepdims=True)  # as top_k
                sk = jnp.min(jnp.where(remaining >= mv, iota_e, e_log),
                             axis=-1, keepdims=True)
                sels.append(sk)
                mvs.append(mv)
                wsum = wsum + mv
                remaining = jnp.where(iota_e == sk, -1.0, remaining)
            wn = [mv / jnp.maximum(wsum, 1e-9) for mv in mvs]
            iota_b = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)

            def _spill(b, _):
                # (B, 1) vector -> row b as an SMEM scalar (a one-hot
                # reduction: exactly one row contributes)
                row = iota_b == b
                for kk in range(top_k):
                    sel_ref[b * top_k + kk] = jnp.sum(
                        jnp.where(row, sels[kk], 0))
                    wsel_ref[b * top_k + kk] = jnp.sum(
                        jnp.where(row, wn[kk], 0.0))
                return 0
            jax.lax.fori_loop(0, B, _spill, 0)

            # per-expert slot tables: the sequential scan is the decode-
            # shaped sort pre-pass (token order == stable-sort order, so
            # drop semantics match moe_group_tokens exactly)
            def _zero_slot(i, _):
                tok_ref[i] = 0
                wgt_ref[i] = 0.0
                return 0
            jax.lax.fori_loop(0, e_local * cap, _zero_slot, 0)

            def _zero(i, _):
                cnt_ref[i] = 0
                return 0
            jax.lax.fori_loop(0, e_local, _zero, 0)

            off = off_ref[0]

            def _group(n, _):
                b = n // top_k
                kk = n % top_k
                s = sel_ref[n]
                w = wsel_ref[n]
                rc = rcnt_ref[s]
                rep = jax.lax.rem(b + kk, jnp.maximum(rc, 1))
                ph = l2p_ref[s * n_rep + rep]
                e = ph - off
                ok = (e >= 0) & (e < e_local) & (rc > 0)
                ec = jnp.clip(e, 0, e_local - 1)
                c = cnt_ref[ec]
                ok = ok & (c < cap)

                @pl.when(ok)
                def _():
                    tok_ref[ec * cap + c] = b
                    wgt_ref[ec * cap + c] = w
                    cnt_ref[ec] = c + 1

                return 0
            jax.lax.fori_loop(0, B * top_k, _group, 0)

    # ---- phase S: shared-expert SwiGLU over h2 (paged weights) --------
    @pl.when((t >= s0) & (t < m0))
    def _shared():
        u = t - s0
        r = jax.lax.rem(u, 2 * nd)
        d = jax.lax.rem(r, nd)
        is_in = r < nd

        @pl.when(is_in)
        def _contract():
            h2b = h2_ref[:, pl.ds(d * block_d, block_d)]
            cg = jnp.dot(h2b, sgate_ref[...],
                         preferred_element_type=jnp.float32)
            cu = jnp.dot(h2b, sup_ref[...],
                         preferred_element_type=jnp.float32)

            @pl.when(d == 0)
            def _():
                hgs_ref[...] = cg
                hus_ref[...] = cu

            @pl.when(d != 0)
            def _():
                hgs_ref[...] += cg
                hus_ref[...] += cu

        @pl.when(jnp.logical_not(is_in))
        def _emit():
            @pl.when(d == 0)
            def _():
                hgs_ref[...] = jax.nn.silu(hgs_ref[...]) * hus_ref[...]

            contrib = jnp.dot(hgs_ref[...].astype(h2_ref.dtype),
                              sdown_ref[...],
                              preferred_element_type=jnp.float32)
            yf_ref[:, pl.ds(d * block_d, block_d)] += contrib

    # ---- phase M: grouped SwiGLU FFN + weighted scatter-combine -------
    @pl.when(t >= m0)
    def _moe():
        u = t - m0
        per_e = nf * 2 * nd
        e = u // per_e
        u2 = jax.lax.rem(u, per_e)
        r = jax.lax.rem(u2, 2 * nd)
        d = jax.lax.rem(r, nd)
        is_in = r < nd

        @pl.when(u2 == 0)
        def _gather():
            accm_ref[...] = jnp.zeros_like(accm_ref)

            def body(i, _):
                tkn = tok_ref[e * cap + i]
                live = wgt_ref[e * cap + i] != 0.0
                row = h2f_ref[pl.ds(tkn, 1), :]
                xs_ref[pl.ds(i, 1), :] = jnp.where(live, row, 0.0)
                return 0
            jax.lax.fori_loop(0, cap, body, 0)

        @pl.when(is_in)
        def _contract():
            xg = xs_ref[:, pl.ds(d * block_d, block_d)].astype(
                gate_ref.dtype)                           # (cap, Db)
            cg = jnp.dot(xg, gate_ref[0],
                         preferred_element_type=jnp.float32)
            cu = jnp.dot(xg, up_ref[0],
                         preferred_element_type=jnp.float32)

            @pl.when(d == 0)
            def _():
                hg_ref[...] = cg
                hu_ref[...] = cu

            @pl.when(d != 0)
            def _():
                hg_ref[...] += cg
                hu_ref[...] += cu

        @pl.when(jnp.logical_not(is_in))
        def _emit():
            @pl.when(d == 0)
            def _():
                hg_ref[...] = jax.nn.silu(hg_ref[...]) * hu_ref[...]

            contrib = jnp.dot(hg_ref[...].astype(down_ref.dtype),
                              down_ref[0],
                              preferred_element_type=jnp.float32)
            accm_ref[:, pl.ds(d * block_d, block_d)] += contrib

        @pl.when(u2 == per_e - 1)
        def _combine():
            def body(i, _):
                w = wgt_ref[e * cap + i]

                @pl.when(w != 0.0)
                def _():
                    tkn = tok_ref[e * cap + i]
                    yf_ref[pl.ds(tkn, 1), :] += w * accm_ref[pl.ds(i, 1), :]

                return 0
            jax.lax.fori_loop(0, cap, body, 0)

    @pl.when(t == pl.num_programs(0) - 1)
    def _emit_y():
        y_ref[...] = yf_ref[...].astype(y_ref.dtype)


def decode_megastep_pallas(q, k_pool, v_pool, block_table, seq_lens,
                           start_lens, x, w_post, ln2_w, router_w, l2p,
                           replica_count, expert_mask, gate_w, up_w,
                           down_w, expert_offset, shared_gate=None,
                           shared_up=None, shared_down=None, *,
                           top_k: int, cap: int, e_local: int,
                           eps: float = 1e-5, block_f: int = 256,
                           block_d: int = 512, interpret: bool = False):
    """One fused attention+MoE decode block step (see module docstring).

    Shapes as :func:`repro.kernels.ref.decode_megastep_ref`; returns
    ``(y (B, D), h2 (B, D))``.  ``shared_gate``/``shared_up`` (D, Fs)
    and ``shared_down`` (Fs, D) are the shared-expert SwiGLU weights
    (None = no shared experts; the phase is statically skipped).  The
    D axis is tiled into ``block_d`` pages: activations stay VMEM-
    resident whole, weights stream one (double-buffered) page per grid
    step.
    """
    B, H, Da = q.shape
    nb, bs, Hkv, _ = k_pool.shape
    n_attn = block_table.shape[1]
    D = x.shape[1]
    E = gate_w.shape[0]
    assert E == e_local, (E, e_local)
    e_log = router_w.shape[1]
    F = gate_w.shape[-1]
    scale = 1.0 / (Da ** 0.5)

    Fb = f_block(F, block_f)
    Fp = ((F + Fb - 1) // Fb) * Fb
    if Fp != F:
        gate_w = jnp.pad(gate_w, ((0, 0), (0, 0), (0, Fp - F)))
        up_w = jnp.pad(up_w, ((0, 0), (0, 0), (0, Fp - F)))
        down_w = jnp.pad(down_w, ((0, 0), (0, Fp - F), (0, 0)))
    nf = Fp // Fb

    Db = min(block_d, D)
    Dp = ((D + Db - 1) // Db) * Db
    nd = Dp // Db
    if Dp != D:
        # zero D-padding is norm-/router-/FFN-neutral: padded x/w_post
        # columns keep y's pad zero (the norm divides by the true D),
        # padded router/gate/up rows contribute nothing, padded down
        # columns write nothing
        pad = Dp - D
        x = jnp.pad(x, ((0, 0), (0, pad)))
        w_post = jnp.pad(w_post, ((0, 0), (0, pad)))
        ln2_w = jnp.pad(ln2_w, ((0, pad),))
        router_w = jnp.pad(router_w, ((0, pad), (0, 0)))
        gate_w = jnp.pad(gate_w, ((0, 0), (0, pad), (0, 0)))
        up_w = jnp.pad(up_w, ((0, 0), (0, pad), (0, 0)))
        down_w = jnp.pad(down_w, ((0, 0), (0, 0), (0, pad)))

    if shared_gate is None:
        ns, Fsb = 0, 8
        shared_gate = jnp.zeros((Db, Fsb), x.dtype)
        shared_up = jnp.zeros((Db, Fsb), x.dtype)
        shared_down = jnp.zeros((Fsb, Db), x.dtype)
    else:
        Fs = shared_gate.shape[1]
        Fsb = f_block(Fs, block_f)
        Fsp = ((Fs + Fsb - 1) // Fsb) * Fsb
        ns = Fsp // Fsb
        shared_gate = jnp.pad(shared_gate,
                              ((0, Dp - D), (0, Fsp - Fs)))
        shared_up = jnp.pad(shared_up, ((0, Dp - D), (0, Fsp - Fs)))
        shared_down = jnp.pad(shared_down,
                              ((0, Fsp - Fs), (0, Dp - D)))

    attn_steps = B * n_attn
    p0 = attn_steps
    r0 = p0 + nd
    s0 = r0 + nd
    m0 = s0 + ns * 2 * nd
    grid = (m0 + E * nf * 2 * nd,)

    def _ab(t):
        ta = jnp.minimum(t, attn_steps - 1)
        return ta // n_attn, ta % n_attn

    def _dp(t):
        return jnp.clip(t - p0, 0, nd - 1)

    def _dr(t):
        return jnp.clip(t - r0, 0, nd - 1)

    def _sfd(t):
        u = jnp.clip(t - s0, 0, max(ns * 2 * nd - 1, 0))
        f = u // (2 * nd)
        r = jax.lax.rem(u, 2 * nd)
        return f, jax.lax.rem(r, nd)

    def _efd(t):
        u = jnp.clip(t - m0, 0, E * nf * 2 * nd - 1)
        per_e = nf * 2 * nd
        e = u // per_e
        u2 = jax.lax.rem(u, per_e)
        f = u2 // (2 * nd)
        r = jax.lax.rem(u2, 2 * nd)
        return e, f, jax.lax.rem(r, nd)

    kernel = functools.partial(
        _megastep_kernel, bs=bs, n_attn=n_attn, nd=nd, nf=nf, ns=ns,
        cap=cap, top_k=top_k, e_local=E, e_log=e_log, scale=scale,
        n_rep=l2p.shape[1], eps=eps, d_model=D, block_d=Db)
    # index maps see the grid index then the six scalar-prefetch refs
    # (bt, sl, st, off, l2p, rcnt); only the KV pages read one (bt)
    def _kv(t, bt, *_):
        b, j = _ab(t)
        return (bt[b, j], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, H, Da), lambda t, *_: (_ab(t)[0], 0, 0)),
            pl.BlockSpec((1, bs, Hkv, Da), _kv),
            pl.BlockSpec((1, bs, Hkv, Da), _kv),
            pl.BlockSpec((B, Db), lambda t, *_: (0, _dp(t))),
            pl.BlockSpec((H * Da, Db), lambda t, *_: (0, _dp(t))),
            pl.BlockSpec((1, Db), lambda t, *_: (0, _dr(t))),
            pl.BlockSpec((Db, e_log), lambda t, *_: (_dr(t), 0)),
            pl.BlockSpec((1, e_log), lambda t, *_: (0, 0)),
            pl.BlockSpec((Db, Fsb), lambda t, *_: (_sfd(t)[1], _sfd(t)[0])),
            pl.BlockSpec((Db, Fsb), lambda t, *_: (_sfd(t)[1], _sfd(t)[0])),
            pl.BlockSpec((Fsb, Db), lambda t, *_: (_sfd(t)[0], _sfd(t)[1])),
            pl.BlockSpec((1, Db, Fb),
                         lambda t, *_: (_efd(t)[0], _efd(t)[2], _efd(t)[1])),
            pl.BlockSpec((1, Db, Fb),
                         lambda t, *_: (_efd(t)[0], _efd(t)[2], _efd(t)[1])),
            pl.BlockSpec((1, Fb, Db),
                         lambda t, *_: (_efd(t)[0], _efd(t)[1], _efd(t)[2])),
        ],
        out_specs=[
            pl.BlockSpec((B, Dp), lambda t, *_: (0, 0)),
            pl.BlockSpec((B, Dp), lambda t, *_: (0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, Da), jnp.float32),    # attention accumulator
            pltpu.VMEM((H, 1), jnp.float32),     # running max
            pltpu.VMEM((H, 1), jnp.float32),     # running denominator
            pltpu.VMEM((B, H * Da), jnp.float32),  # attention outputs
            pltpu.VMEM((B, 1), jnp.float32),     # norm sum of squares
            pltpu.VMEM((B, e_log), jnp.float32),  # router logit accum
            pltpu.VMEM((cap, Dp), jnp.float32),  # gathered expert rows
            pltpu.VMEM((cap, Dp), jnp.float32),  # FFN accumulator
            pltpu.VMEM((cap, Fb), jnp.float32),  # expert gate hidden
            pltpu.VMEM((cap, Fb), jnp.float32),  # expert up hidden
            pltpu.VMEM((B, Fsb), jnp.float32),   # shared gate hidden
            pltpu.VMEM((B, Fsb), jnp.float32),   # shared up hidden
            pltpu.VMEM((B, Dp), jnp.float32),    # block output y
            pltpu.VMEM((B, Dp), jnp.float32),    # h2 rows for gathers
            pltpu.SMEM((B * top_k,), jnp.int32),    # selected logical ids
            pltpu.SMEM((B * top_k,), jnp.float32),  # renormalized weights
            pltpu.SMEM((E * cap,), jnp.int32),   # slot -> token row
            pltpu.SMEM((E * cap,), jnp.float32),  # slot combine weight
            pltpu.SMEM((E,), jnp.int32),         # per-expert fill count
        ],
    )
    y, h2 = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Dp), x.dtype),
                   jax.ShapeDtypeStruct((B, Dp), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      start_lens.astype(jnp.int32),
      jnp.asarray(expert_offset, jnp.int32).reshape(1),
      l2p.astype(jnp.int32).reshape(-1), replica_count.astype(jnp.int32),
      q, k_pool, v_pool, x, w_post, ln2_w.reshape(1, Dp), router_w,
      expert_mask.astype(jnp.int32).reshape(1, e_log),
      shared_gate, shared_up, shared_down, gate_w, up_w, down_w)
    if Dp != D:
        y, h2 = y[:, :D], h2[:, :D]
    return y, h2
