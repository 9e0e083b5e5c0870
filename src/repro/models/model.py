"""Composable model assembly for all assigned architecture families.

``Model`` is a pure-functional wrapper: ``init`` builds a param pytree,
``loss``/``prefill``/``decode_step`` are jit-able functions of it.

Layer stacking: repeated layers are stored stacked on a leading axis and
iterated with ``lax.scan`` (compile time stays O(1) in depth for the 61-96
layer configs).  Heterogeneous-depth families (MoE first-k-dense, Jamba
periods) use one stack per homogeneous group.

Two decode-cache representations:

* **Paged** (``init_paged_cache``/``prefill_paged``/``decode_step_paged``)
  — per-layer block pools addressed through block tables; the serving
  engine's only compiled cache.  Attention state has no batch axis
  (requests own blocks), recurrent SSM state stays per-slot.
* **Ring** (``init_cache``/``prefill``/``decode_step``) — per-slot ring
  buffers (window = sliding_window or max_seq); the reference decode
  semantics used by dry-runs/training-eval and the parity oracle for the
  paged path (tests/test_paged_serving.py).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import ffn as F
from repro.models import mamba as M
from repro.models import moe as MoE
from repro.models.layers import (
    cross_entropy_loss,
    embed_init,
    padded_vocab_size,
    rms_norm,
    split_keys,
    stack_init,
    take_layer,
)


# ---------------------------------------------------------------------------
# block init / forward / decode for one (mixer, ffn) combination
# ---------------------------------------------------------------------------

def _mixer_init(key, cfg: ModelConfig, kind: str, dtype):
    if kind == "attn":
        if cfg.attention_type == "mla":
            return A.mla_init(key, cfg, dtype)
        return A.gqa_init(key, cfg, dtype)
    if kind == "mamba":
        return M.mamba_init(key, cfg, dtype)
    raise ValueError(kind)


def _block_init(key, cfg: ModelConfig, mixer: str, ffn_kind: Optional[str],
                dtype, cross: bool = False):
    D = cfg.d_model
    ks = split_keys(key, 4)
    p: Dict[str, Any] = {
        "ln1": jnp.ones((D,), dtype),
        "mixer": _mixer_init(ks[0], cfg, mixer, dtype),
    }
    if cross:
        p["ln_cross"] = jnp.ones((D,), dtype)
        p["cross"] = A.gqa_init(ks[1], cfg, dtype)
    if ffn_kind == "dense":
        p["ln2"] = jnp.ones((D,), dtype)
        p["ffn"] = F.ffn_init(ks[2], D, cfg.d_ff, cfg.activation, dtype)
    elif ffn_kind == "dense_first":
        p["ln2"] = jnp.ones((D,), dtype)
        p["ffn"] = F.ffn_init(ks[2], D, cfg.moe.dense_d_ff or cfg.d_ff,
                              cfg.activation, dtype)
    elif ffn_kind == "moe":
        p["ln2"] = jnp.ones((D,), dtype)
        p["moe"] = MoE.moe_init(ks[3], cfg, dtype)
    elif ffn_kind is None:
        pass
    else:
        raise ValueError(ffn_kind)
    return p


class Model:
    def __init__(self, cfg: ModelConfig, dtype=jnp.float32,
                 moe_dist=None):
        """moe_dist: optional distributed MoE applier
        (``repro.distributed.collectives.MoEDist``); None = single rank."""
        cfg.validate()
        self.cfg = cfg
        self.dtype = dtype
        self.moe_dist = moe_dist
        self.vpad = padded_vocab_size(cfg)

    # -- structure ---------------------------------------------------------

    def layer_groups(self):
        """(group_name, n_layers, mixer, ffn_kind, cross) per stack."""
        cfg = self.cfg
        if cfg.family == "ssm":
            return [("layers", cfg.num_layers, "mamba", None, False)]
        if cfg.family == "audio":
            return [
                ("enc_layers", cfg.encoder_layers, "attn", "dense", False),
                ("layers", cfg.num_layers, "attn", "dense", True),
            ]
        if cfg.hybrid_period:
            return [("periods", cfg.num_layers // cfg.hybrid_period,
                     "hybrid", None, False)]
        if cfg.moe is not None:
            groups = []
            if cfg.moe.first_k_dense:
                groups.append(("dense_layers", cfg.moe.first_k_dense,
                               "attn", "dense_first", False))
            groups.append(("layers", cfg.num_layers - cfg.moe.first_k_dense,
                           "attn", "moe", False))
            return groups
        # dense / vlm
        return [("layers", cfg.num_layers, "attn", "dense", False)]

    def _period_init(self, key, dtype):
        """One Jamba period: hybrid_period sublayers, attention at
        hybrid_attn_index, MoE on odd sublayers."""
        cfg = self.cfg
        subs = {}
        ks = split_keys(key, cfg.hybrid_period)
        for i in range(cfg.hybrid_period):
            mixer = "attn" if i == cfg.hybrid_attn_index else "mamba"
            ffn_kind = "moe" if (i % cfg.moe.moe_layer_period == 1) else "dense"
            subs[f"sub_{i}"] = _block_init(ks[i], cfg, mixer, ffn_kind, dtype)
        return subs

    # -- init ---------------------------------------------------------------

    def init(self, key) -> Dict[str, Any]:
        cfg, dtype = self.cfg, self.dtype
        ks = split_keys(key, 8)
        params: Dict[str, Any] = {
            "embed": embed_init(ks[0], self.vpad, cfg.d_model, dtype),
            "final_norm": jnp.ones((cfg.d_model,), dtype),
            "lm_head": embed_init(ks[1], self.vpad, cfg.d_model, dtype).T,
        }
        gi = 2
        for name, n, mixer, ffn_kind, cross in self.layer_groups():
            if mixer == "hybrid":
                params[name] = stack_init(
                    ks[gi], n, lambda k: self._period_init(k, dtype))
            else:
                params[name] = stack_init(
                    ks[gi], n,
                    functools.partial(_block_init, cfg=cfg, mixer=mixer,
                                      ffn_kind=ffn_kind, dtype=dtype,
                                      cross=cross))
            gi += 1
        if cfg.family == "audio":
            params["enc_norm"] = jnp.ones((cfg.d_model,), dtype)
        return params

    def param_specs(self):
        key = jax.random.PRNGKey(0)
        return jax.eval_shape(lambda: self.init(key))

    def count_params(self) -> int:
        specs = self.param_specs()
        return sum(int(jnp.prod(jnp.array(l.shape)))
                   for l in jax.tree_util.tree_leaves(specs))

    def default_runtime(self) -> Optional[MoE.MoERuntime]:
        if self.cfg.moe is None:
            return None
        return MoE.default_runtime(self.cfg.moe)

    @property
    def supports_chunked_prefill(self) -> bool:
        """Chunked prefill runs prompt tokens as *virtual decode slots*
        against the paged pools (``decode_step_paged`` over a per-token
        page context), which requires every mixer to be attention (the
        paged cache is then pure pools with no batch axis, so the same
        cache pytree serves any chunk width).  Recurrent mixers (SSM,
        hybrid periods) carry per-slot state that must be threaded
        sequentially — those models keep whole-prompt prefills.  VLM /
        audio prefills embed non-token inputs and are excluded too."""
        if self.cfg.family in ("vlm", "audio"):
            return False
        return all(mixer == "attn"
                   for _, _, mixer, _, _ in self.layer_groups())

    # -- moe application ----------------------------------------------------

    def _moe(self, p, x, runtime, cap):
        """x: (B, S, D) or (B, D)."""
        shape = x.shape
        x2 = x.reshape(-1, shape[-1])
        if self.moe_dist is not None:
            y, aux = self.moe_dist.apply(p, self.cfg, x2, runtime, cap)
        else:
            y, aux = MoE.moe_apply_local(p, self.cfg, x2, runtime, cap=cap)
        y = y + MoE.shared_expert_apply(p, self.cfg, x2)
        return y.reshape(shape), aux

    def _cap(self, n_tokens: int) -> int:
        if self.moe_dist is not None:
            return self.moe_dist.cap_for(n_tokens, self.cfg.moe)
        return MoE.capacity(n_tokens * self.cfg.moe.top_k,
                            MoE.physical_experts(self.cfg.moe),
                            self.cfg.moe.capacity_factor,
                            floor=self.cfg.moe.min_capacity)

    # -- full-sequence block forward -----------------------------------------

    def _block_fwd(self, p, x, positions, *, mixer, ffn_kind, runtime, cap,
                   causal=True, enc_out=None, enc_positions=None,
                   build_cache=False, max_seq=0, paged=False):
        """Returns (x, cache_entry, aux)."""
        cfg = self.cfg
        aux = 0.0
        cache_entry = None
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if mixer == "attn":
            if cfg.attention_type == "mla":
                if build_cache:
                    out, cache_entry = self._mla_fwd_cache(
                        p["mixer"], h, positions, max_seq, paged=paged)
                else:
                    out = A.mla_forward(p["mixer"], cfg, h, positions,
                                        causal=causal,
                                        window=cfg.sliding_window)
            else:
                if build_cache:
                    out, cache_entry = self._gqa_fwd_cache(
                        p["mixer"], h, positions, max_seq, paged=paged)
                else:
                    out = A.gqa_forward(p["mixer"], cfg, h, positions,
                                        causal=causal,
                                        window=cfg.sliding_window)
        elif mixer == "mamba":
            if build_cache:
                out, cache_entry = M.mamba_forward(p["mixer"], cfg, h,
                                                   return_state=True)
            else:
                out = M.mamba_forward(p["mixer"], cfg, h)
        else:
            raise ValueError(mixer)
        x = x + out
        if enc_out is not None and "cross" in p:
            hc = rms_norm(x, p["ln_cross"], cfg.norm_eps)
            x = x + A.gqa_forward(p["cross"], cfg, hc, positions,
                                  causal=False, kv_input=enc_out,
                                  kv_positions=enc_positions, use_rope=False)
        if ffn_kind in ("dense", "dense_first"):
            h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
            x = x + F.ffn_apply(p["ffn"], h2, cfg.activation)
        elif ffn_kind == "moe":
            h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
            y, aux = self._moe(p["moe"], h2, runtime, cap)
            x = x + y
        return x, cache_entry, aux

    def _gqa_fwd_cache(self, p, h, positions, max_seq, paged=False):
        cfg = self.cfg
        out, (k, v) = A.gqa_forward_with_kv(p, cfg, h, positions)
        if paged:
            # raw (B, S, Hkv, Dh), rope applied — ready for pool blocks
            return out, {"k": k, "v": v}
        entry = _ring_from_full(k, v, positions, cfg.sliding_window, max_seq)
        return out, entry

    def _mla_fwd_cache(self, p, h, positions, max_seq, paged=False):
        cfg = self.cfg
        out, (c_kv, k_rope) = A.mla_forward_with_cache(p, cfg, h, positions)
        if paged:
            # fused latent row (B, S, 1, R + dr) matching the pool layout
            ckr = jnp.concatenate([c_kv, k_rope], axis=-1)[:, :, None, :]
            return out, {"ckr": ckr}
        entry = _ring_from_full_mla(c_kv, k_rope, positions,
                                    cfg.sliding_window, max_seq)
        return out, entry

    # -- stack iteration ------------------------------------------------------

    def _run_stack(self, stacked, x, body: Callable, n: int, cache=None):
        """body(p_layer, x, cache_slice) -> (x, cache_entry, aux).

        Returns (x, stacked_cache_entries, total_aux)."""
        if self.cfg.remat:
            body = jax.checkpoint(body)
        if self.cfg.scan_layers and n > 1:
            if cache is not None and self.cfg.decode_cache_carry:
                # §Perf A4: the cache rides the scan carry and is updated
                # in place with DUS — XLA can alias the buffer instead of
                # copying the whole cache through xs/ys every step.
                def carry_body(carry, xs):
                    x, aux, cache_full = carry
                    p, i = xs
                    csl = jax.tree_util.tree_map(
                        lambda c: jax.lax.dynamic_index_in_dim(
                            c, i, 0, keepdims=False), cache_full)
                    x, entry, a = body(p, x, csl)
                    cache_full = jax.tree_util.tree_map(
                        lambda c, e: jax.lax.dynamic_update_index_in_dim(
                            c, e.astype(c.dtype), i, 0), cache_full, entry)
                    return (x, aux + a, cache_full), None
                (x, aux, new_cache), _ = jax.lax.scan(
                    carry_body, (x, 0.0, cache),
                    (stacked, jnp.arange(n)))
                return x, new_cache, aux
            def scan_body(carry, xs):
                x, aux = carry
                if cache is None:
                    p = xs
                    x, entry, a = body(p, x, None)
                else:
                    p, csl = xs
                    x, entry, a = body(p, x, csl)
                return (x, aux + a), entry
            xs = stacked if cache is None else (stacked, cache)
            (x, aux), entries = jax.lax.scan(scan_body, (x, 0.0), xs)
            return x, entries, aux
        # unrolled
        aux = 0.0
        entries = []
        for i in range(n):
            p = take_layer(stacked, i)
            csl = take_layer(cache, i) if cache is not None else None
            x, entry, a = body(p, x, csl)
            aux = aux + a
            entries.append(entry)
        if entries and entries[0] is not None:
            entries = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *entries)
        else:
            entries = None
        return x, entries, aux

    # -- full forward ---------------------------------------------------------

    def _trunk(self, params, x, positions, runtime, *, build_cache=False,
               max_seq=0, enc_out=None, enc_positions=None, paged=False):
        """Run all layer groups. x: (B, S, D). Returns (x, caches, aux)."""
        cfg = self.cfg
        caches: Dict[str, Any] = {}
        total_aux = 0.0
        cap = self._cap(x.shape[0] * x.shape[1]) if cfg.moe else 0

        for name, n, mixer, ffn_kind, cross in self.layer_groups():
            if name == "enc_layers":
                continue  # encoder handled separately
            if mixer == "hybrid":
                def body(p, x, _):
                    return self._period_fwd(p, x, positions, runtime, cap,
                                            build_cache=build_cache,
                                            max_seq=max_seq, paged=paged)
            else:
                def body(p, x, _, _mx=mixer, _fk=ffn_kind, _cr=cross):
                    return self._block_fwd(
                        p, x, positions, mixer=_mx, ffn_kind=_fk,
                        runtime=runtime, cap=cap,
                        enc_out=enc_out if _cr else None,
                        enc_positions=enc_positions if _cr else None,
                        build_cache=build_cache, max_seq=max_seq,
                        paged=paged)
            x, entries, aux = self._run_stack(params[name], x, body, n)
            total_aux += aux
            if build_cache and entries is not None:
                caches[name] = entries
        return x, caches, total_aux

    def _period_fwd(self, p, x, positions, runtime, cap, *, build_cache,
                    max_seq, paged=False):
        """One Jamba period (unrolled heterogeneous sublayers)."""
        cfg = self.cfg
        aux = 0.0
        attn_entry = None
        ssm_entries = []
        for i in range(cfg.hybrid_period):
            mixer = "attn" if i == cfg.hybrid_attn_index else "mamba"
            ffn_kind = "moe" if (i % cfg.moe.moe_layer_period == 1) else "dense"
            x, entry, a = self._block_fwd(
                p[f"sub_{i}"], x, positions, mixer=mixer, ffn_kind=ffn_kind,
                runtime=runtime, cap=cap, build_cache=build_cache,
                max_seq=max_seq, paged=paged)
            aux += a
            if build_cache:
                if mixer == "attn":
                    attn_entry = entry
                else:
                    ssm_entries.append(entry)
        entry = None
        if build_cache:
            ssm = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                         *ssm_entries)
            entry = {"attn": attn_entry, "ssm": ssm}
        return x, entry, aux

    def _encode(self, params, frames, runtime):
        """Audio encoder over precomputed frame embeddings (B, F, D)."""
        cfg = self.cfg
        positions = jnp.arange(frames.shape[1])
        def body(p, x, _):
            return self._block_fwd(p, x, positions, mixer="attn",
                                   ffn_kind="dense", runtime=runtime,
                                   cap=0, causal=False)
        x, _, _ = self._run_stack(params["enc_layers"], frames, body,
                                  cfg.encoder_layers)
        return rms_norm(x, params["enc_norm"], cfg.norm_eps)

    def _embed_inputs(self, params, batch):
        """Family-specific input embedding. Returns (x, positions)."""
        cfg = self.cfg
        if cfg.family == "vlm":
            tok = params["embed"][batch["tokens"]]
            x = jnp.concatenate(
                [batch["patches"].astype(tok.dtype), tok], axis=1)
        else:
            x = params["embed"][batch["tokens"]]
        positions = jnp.arange(x.shape[1])
        return x, positions

    def logits_full(self, params, batch, runtime=None, *,
                    build_cache=False, max_seq=0, paged=False):
        """Full-sequence forward. Returns (logits, caches, aux)."""
        cfg = self.cfg
        runtime = runtime if runtime is not None else self.default_runtime()
        x, positions = self._embed_inputs(params, batch)
        enc_out = enc_positions = None
        if cfg.family == "audio":
            enc_out = self._encode(params, batch["frames"].astype(x.dtype),
                                   runtime)
            enc_positions = jnp.arange(enc_out.shape[1])
        x, caches, aux = self._trunk(params, x, positions, runtime,
                                     build_cache=build_cache, max_seq=max_seq,
                                     enc_out=enc_out,
                                     enc_positions=enc_positions,
                                     paged=paged)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = x @ params["lm_head"]
        if build_cache and cfg.family == "audio":
            # decoder-layer cache = self-attn ring + precomputed cross K/V,
            # scanned together at decode time (leading dim = layer).
            caches["layers"] = {"self": caches["layers"],
                                "cross": self._cross_kv(params, enc_out)}
        return logits, caches, aux

    def _cross_kv(self, params, enc_out):
        """Precompute per-layer cross-attention K/V from encoder output."""
        cfg = self.cfg
        Dh = cfg.resolved_head_dim()
        def one(p):
            k = (enc_out @ p["cross"]["wk"]).reshape(
                enc_out.shape[0], enc_out.shape[1], cfg.num_kv_heads, Dh)
            v = (enc_out @ p["cross"]["wv"]).reshape(
                enc_out.shape[0], enc_out.shape[1], cfg.num_kv_heads, Dh)
            return {"k": k, "v": v}
        return jax.vmap(one)(params["layers"])

    # -- public APIs -----------------------------------------------------------

    def loss(self, params, batch, runtime=None):
        cfg = self.cfg
        logits, _, aux = self.logits_full(params, batch, runtime)
        if cfg.family == "vlm":
            # loss over text positions only (they sit after the patches)
            logits = logits[:, cfg.num_patches:]
        labels = batch["tokens"][:, 1:]
        mask = batch["loss_mask"][:, 1:].astype(jnp.float32)
        ce = cross_entropy_loss(logits[:, :-1], labels, mask, cfg.vocab_size)
        aux_coef = cfg.moe.aux_loss_coef if cfg.moe else 0.0
        nlayers_moe = max(self._num_moe_layers(), 1)
        total = ce + aux_coef * aux / nlayers_moe
        return total, {"ce": ce, "aux": aux}

    def _num_moe_layers(self) -> int:
        cfg = self.cfg
        if cfg.moe is None:
            return 0
        if cfg.hybrid_period:
            per = sum(1 for i in range(cfg.hybrid_period)
                      if i % cfg.moe.moe_layer_period == 1)
            return per * (cfg.num_layers // cfg.hybrid_period)
        return cfg.num_layers - cfg.moe.first_k_dense

    def prefill(self, params, batch, runtime=None, max_seq: int = 0):
        """Prefill: full forward + decode cache. Returns (last_logits, cache).

        max_seq: ring-buffer size for the decode cache (>= prompt len).
        """
        cfg = self.cfg
        S = (batch["tokens"].shape[1] + (cfg.num_patches or 0)
             if cfg.family == "vlm" else batch["tokens"].shape[1])
        max_seq = max_seq or S
        logits, caches, _ = self.logits_full(params, batch, runtime,
                                             build_cache=True,
                                             max_seq=max_seq)
        B = logits.shape[0]
        if "lengths" in batch:
            last = logits[jnp.arange(B), batch["lengths"] - 1]
            pos = batch["lengths"]
        else:
            last = logits[:, -1]
            pos = jnp.full((B,), S, jnp.int32)
        caches["pos"] = pos.astype(jnp.int32)
        return last, caches

    def prefill_paged(self, params, batch, runtime=None):
        """Prefill for the paged serving cache.

        Returns ``(last_logits, raw)`` where ``raw`` mirrors the paged
        cache structure with *raw per-token* leaves: attention layers
        carry (B, S, ...) K/V rows ready to scatter into pool blocks
        (``cache_ops.install_prefill``), non-attention mixers carry their
        final recurrent state (B, ...) for the request's batch slot.
        """
        logits, caches, _ = self.logits_full(params, batch, runtime,
                                             build_cache=True, paged=True)
        B = logits.shape[0]
        if "lengths" in batch:
            last = logits[jnp.arange(B), batch["lengths"] - 1]
        else:
            last = logits[:, -1]
        return last, caches

    def init_paged_cache(self, batch: int, num_blocks: int,
                         block_size: int, dtype=None):
        """Block-pool decode cache — the serving engine's compiled cache.

        Attention layers get per-layer K/V pools with **no batch axis**
        (requests own physical blocks, addressed through block tables);
        non-attention mixers (Mamba state) keep fixed-size per-slot state
        with a batch axis.  The pools carry one extra trailing *trash*
        block (id == ``num_blocks``) that idle batch slots write into, so
        a full decode batch never touches live blocks.
        """
        cfg = self.cfg
        dtype = dtype or self.dtype
        nb = num_blocks + 1  # + trash block
        caches: Dict[str, Any] = {}
        for name, n, mixer, ffn_kind, cross in self.layer_groups():
            if name == "enc_layers":
                continue
            if cross:
                raise ValueError(
                    "paged serving does not support encoder-decoder "
                    "(audio) models")
            if mixer == "hybrid":
                attn_c = _stack_cache(
                    lambda: A.gqa_paged_pools(cfg, nb, block_size, dtype), n)
                ssm_c = _stack_cache(
                    lambda: _stack_cache(
                        lambda: M.mamba_init_state(cfg, batch, dtype),
                        cfg.hybrid_period - 1), n)
                caches[name] = {"attn": attn_c, "ssm": ssm_c}
            elif mixer == "mamba":
                caches[name] = _stack_cache(
                    lambda: M.mamba_init_state(cfg, batch, dtype), n)
            elif cfg.attention_type == "mla":
                caches[name] = _stack_cache(
                    lambda: A.mla_paged_pools(cfg, nb, block_size, dtype), n)
            else:
                caches[name] = _stack_cache(
                    lambda: A.gqa_paged_pools(cfg, nb, block_size, dtype), n)
        return caches

    def decode_step_paged(self, params, cache, token, page, runtime=None):
        """One decode step against the paged cache.

        token: (B,) int32; ``page`` carries the per-step paging arrays:
        ``tables`` (B, max_blk) int32 block tables, ``seq_lens`` (B,)
        valid length *including* this step's token, ``write_bid``/
        ``write_off`` (B,) physical destination of the incoming token
        (idle slots point at the trash block with seq_len 0).  Returns
        (logits, new_cache); positions derive from seq_lens, so the
        cache carries no per-slot position state.
        """
        cfg = self.cfg
        runtime = runtime if runtime is not None else self.default_runtime()
        with jax.named_scope("embed"):
            x = params["embed"][token]                   # (B, D)
        B = x.shape[0]
        cap = self._cap(B) if cfg.moe else 0
        new_cache = dict(cache)
        for name, n, mixer, ffn_kind, cross in self.layer_groups():
            if name == "enc_layers":
                continue
            if mixer == "hybrid":
                def body(p, x, csl):
                    return self._period_decode_paged(p, x, csl, page,
                                                     runtime, cap)
            else:
                def body(p, x, csl, _mx=mixer, _fk=ffn_kind):
                    return self._block_decode_paged(
                        p, x, csl, page, runtime, cap,
                        mixer=_mx, ffn_kind=_fk)
            x, entries, _ = self._run_stack(params[name], x, body, n,
                                            cache=cache[name])
            new_cache[name] = entries
        with jax.named_scope("lm_head"):
            x = rms_norm(x, params["final_norm"], cfg.norm_eps)
            logits = x @ params["lm_head"]
        return logits, new_cache

    def _block_decode_paged(self, p, x, csl, page, runtime, cap, *,
                            mixer, ffn_kind):
        from repro.kernels.ops import _on_cpu
        cfg = self.cfg
        if (cfg.decode_impl == "megakernel" and mixer == "attn"
                and ffn_kind == "moe" and self.moe_dist is None):
            return self._block_decode_megastep(p, x, csl, page, runtime,
                                               cap)
        aux = 0.0
        use_pallas = not _on_cpu()
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if mixer == "attn":
            if cfg.attention_type == "mla":
                out, entry = A.mla_decode_paged(p["mixer"], cfg, h, csl,
                                                page, use_pallas=use_pallas)
            else:
                out, entry = A.gqa_decode_paged(p["mixer"], cfg, h, csl,
                                                page, use_pallas=use_pallas)
        else:
            out, entry = M.mamba_decode(p["mixer"], cfg, h, csl)
        x = x + out
        if ffn_kind in ("dense", "dense_first"):
            h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
            with jax.named_scope("ffn"):
                x = x + F.ffn_apply(p["ffn"], h2, cfg.activation)
        elif ffn_kind == "moe":
            h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
            y, aux = self._moe(p["moe"], h2, runtime, cap)
            x = x + y
        return x, entry, aux

    def _block_decode_megastep(self, p, x, csl, page, runtime, cap):
        """One attention+MoE block through ``ops.decode_megastep``: the
        whole attention -> residual -> norm -> route -> expert FFN
        (routed + shared) -> combine chain is a single kernel launch
        (jnp oracle on CPU).
        QKV projection + rope + the pool token write stay outside — they
        are one fused GEMM/scatter shared with the composed path, and
        keeping the write in XLA keeps the §3.3 row-level undo manifest
        valid unchanged.  All paging arrays and MoERuntime tables ride
        in as data: recovery mutations never recompile (§3.4)."""
        from repro.kernels import ops
        from repro.kernels.ops import _on_cpu
        cfg = self.cfg
        use_pallas = not _on_cpu()
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if cfg.attention_type == "mla":
            q, token = A.mla_decode_q_token(p["mixer"], cfg, h, page)
            entry = A.mla_write_token(csl, page, token)
            k_pool = v_pool = entry["ckr"]
            q = q.astype(k_pool.dtype)
            w_post = A.mla_post_matrix(p["mixer"], cfg)
        else:
            q, k, v = A.gqa_decode_qkv(p["mixer"], cfg, h, page)
            entry = A.gqa_write_token(csl, page, k, v)
            k_pool, v_pool = entry["k"], entry["v"]
            w_post = p["mixer"]["wo"]
        starts = A.window_starts(cfg, page["seq_lens"])
        if starts is None:
            starts = jnp.zeros_like(page["seq_lens"])
        moe_p = p["moe"]
        shared = moe_p.get("shared")
        with jax.named_scope("megastep"):
            y, _ = ops.decode_megastep(
                q, k_pool, v_pool, page["tables"], page["seq_lens"], starts,
                x, w_post, p["ln2"], moe_p["router"],
                runtime.logical_to_physical, runtime.replica_count,
                runtime.expert_mask, moe_p["gate"], moe_p["up"],
                moe_p["down"], jnp.int32(0),
                shared["w_gate"] if shared else None,
                shared["w_up"] if shared else None,
                shared["w_down"] if shared else None,
                top_k=cfg.moe.top_k, cap=cap,
                e_local=MoE.physical_experts(cfg.moe), eps=cfg.norm_eps,
                use_pallas=use_pallas)
        return y, entry, 0.0

    def _period_decode_paged(self, p, x, csl, page, runtime, cap):
        cfg = self.cfg
        si = 0
        new_ssm = []
        new_attn = None
        for i in range(cfg.hybrid_period):
            mixer = "attn" if i == cfg.hybrid_attn_index else "mamba"
            ffn_kind = "moe" if (i % cfg.moe.moe_layer_period == 1) else "dense"
            sub_c = csl["attn"] if mixer == "attn" else take_layer(
                csl["ssm"], si)
            x, entry, _ = self._block_decode_paged(
                p[f"sub_{i}"], x, sub_c, page, runtime, cap,
                mixer=mixer, ffn_kind=ffn_kind)
            if mixer == "attn":
                new_attn = entry
            else:
                new_ssm.append(entry)
                si += 1
        ssm = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *new_ssm)
        return x, {"attn": new_attn, "ssm": ssm}, 0.0

    def init_cache(self, batch: int, max_seq: int, dtype=None):
        """Fresh (empty) decode cache — used by the decode dry-runs."""
        cfg = self.cfg
        dtype = dtype or self.dtype
        caches: Dict[str, Any] = {}
        for name, n, mixer, ffn_kind, cross in self.layer_groups():
            if name == "enc_layers":
                continue
            if mixer == "hybrid":
                attn_c = _stack_cache(
                    lambda: A.gqa_init_cache(cfg, batch, max_seq, dtype), n)
                ssm_c = _stack_cache(
                    lambda: _stack_cache(
                        lambda: M.mamba_init_state(cfg, batch, dtype),
                        cfg.hybrid_period - 1), n)
                caches[name] = {"attn": attn_c, "ssm": ssm_c}
            elif mixer == "mamba":
                caches[name] = _stack_cache(
                    lambda: M.mamba_init_state(cfg, batch, dtype), n)
            else:
                if cfg.attention_type == "mla":
                    caches[name] = _stack_cache(
                        lambda: A.mla_init_cache(cfg, batch, max_seq, dtype), n)
                else:
                    caches[name] = _stack_cache(
                        lambda: A.gqa_init_cache(cfg, batch, max_seq, dtype), n)
                if cross:
                    Dh = cfg.resolved_head_dim()
                    kshape = (n, batch, cfg.encoder_seq, cfg.num_kv_heads, Dh)
                    caches[name] = {
                        "self": caches[name],
                        "cross": {"k": jnp.zeros(kshape, dtype),
                                  "v": jnp.zeros(kshape, dtype)},
                    }
        caches["pos"] = jnp.zeros((batch,), jnp.int32)
        return caches

    def decode_step(self, params, cache, token, runtime=None):
        """One decode step. token: (B,) int32. Returns (logits, new_cache)."""
        cfg = self.cfg
        runtime = runtime if runtime is not None else self.default_runtime()
        pos = cache["pos"]
        x = params["embed"][token]                       # (B, D)
        B = x.shape[0]
        cap = self._cap(B) if cfg.moe else 0
        new_cache = dict(cache)
        for name, n, mixer, ffn_kind, cross in self.layer_groups():
            if name == "enc_layers":
                continue
            if mixer == "hybrid":
                def body(p, x, csl):
                    return self._period_decode(p, x, csl, pos, runtime, cap)
            else:
                def body(p, x, csl, _mx=mixer, _fk=ffn_kind, _cr=cross):
                    return self._block_decode(p, x, csl, pos, runtime, cap,
                                              mixer=_mx, ffn_kind=_fk,
                                              cross=_cr, cache=cache)
            x, entries, _ = self._run_stack(params[name], x, body, n,
                                            cache=cache[name])
            new_cache[name] = entries
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = x @ params["lm_head"]
        new_cache["pos"] = pos + 1
        return logits, new_cache

    def _block_decode(self, p, x, csl, pos, runtime, cap, *, mixer, ffn_kind,
                      cross, cache):
        cfg = self.cfg
        aux = 0.0
        self_csl = csl["self"] if cross else csl
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if mixer == "attn":
            if cfg.attention_type == "mla":
                out, entry = A.mla_decode(p["mixer"], cfg, h, self_csl, pos)
            else:
                out, entry = A.gqa_decode(p["mixer"], cfg, h, self_csl, pos)
        else:
            out, entry = M.mamba_decode(p["mixer"], cfg, h, self_csl)
        x = x + out
        if cross and "cross" in p:
            hc = rms_norm(x, p["ln_cross"], cfg.norm_eps)
            ck, cv = csl["cross"]["k"], csl["cross"]["v"]
            valid = jnp.ones((x.shape[0], ck.shape[1]), bool)
            x = x + A.gqa_cross_decode(p["cross"], cfg, hc, ck, cv, valid)
            entry = {"self": entry, "cross": csl["cross"]}
        if ffn_kind in ("dense", "dense_first"):
            h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
            x = x + F.ffn_apply(p["ffn"], h2, cfg.activation)
        elif ffn_kind == "moe":
            h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
            y, aux = self._moe(p["moe"], h2, runtime, cap)
            x = x + y
        return x, entry, aux

    def _period_decode(self, p, x, csl, pos, runtime, cap):
        cfg = self.cfg
        si = 0
        new_ssm = []
        new_attn = None
        for i in range(cfg.hybrid_period):
            mixer = "attn" if i == cfg.hybrid_attn_index else "mamba"
            ffn_kind = "moe" if (i % cfg.moe.moe_layer_period == 1) else "dense"
            sub_c = csl["attn"] if mixer == "attn" else take_layer(
                csl["ssm"], si)
            x, entry, _ = self._block_decode(
                p[f"sub_{i}"], x, sub_c, pos, runtime, cap,
                mixer=mixer, ffn_kind=ffn_kind, cross=False, cache=None)
            if mixer == "attn":
                new_attn = entry
            else:
                new_ssm.append(entry)
                si += 1
        ssm = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *new_ssm)
        return x, {"attn": new_attn, "ssm": ssm}, 0.0


# ---------------------------------------------------------------------------
# cache helpers
# ---------------------------------------------------------------------------

def _stack_cache(make_one, n: int):
    one = make_one()
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), one)


def _ring_from_full(k, v, positions, window, max_seq):
    """Arrange full-prefill K/V (B,S,Hkv,Dh) into a ring cache (B,W,...)."""
    B, S = k.shape[0], k.shape[1]
    W = min(window or max_seq, max_seq)
    if S <= W:
        kc = jnp.zeros((B, W) + k.shape[2:], k.dtype)
        vc = jnp.zeros((B, W) + v.shape[2:], v.dtype)
        slots = positions % W
        kc = kc.at[:, slots].set(k)
        vc = vc.at[:, slots].set(v)
    else:
        tail = positions[S - W:]
        slots = tail % W
        kc = jnp.zeros((B, W) + k.shape[2:], k.dtype).at[:, slots].set(
            k[:, S - W:])
        vc = jnp.zeros((B, W) + v.shape[2:], v.dtype).at[:, slots].set(
            v[:, S - W:])
    return A.GQACache(kc, vc)


def _ring_from_full_mla(c_kv, k_rope, positions, window, max_seq):
    B, S = c_kv.shape[0], c_kv.shape[1]
    W = min(window or max_seq, max_seq)
    if S <= W:
        cc = jnp.zeros((B, W, c_kv.shape[-1]), c_kv.dtype)
        rc = jnp.zeros((B, W, k_rope.shape[-1]), k_rope.dtype)
        slots = positions % W
        cc = cc.at[:, slots].set(c_kv)
        rc = rc.at[:, slots].set(k_rope)
    else:
        tail = positions[S - W:]
        slots = tail % W
        cc = jnp.zeros((B, W, c_kv.shape[-1]), c_kv.dtype).at[:, slots].set(
            c_kv[:, S - W:])
        rc = jnp.zeros((B, W, k_rope.shape[-1]), k_rope.dtype).at[:, slots].set(
            k_rope[:, S - W:])
    return A.MLACache(cc, rc)
