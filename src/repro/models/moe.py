"""Mixture-of-experts layer with ReviveMoE-aware routing.

Design (DESIGN.md §6, §1.4):

* Experts live in **physical slots**: ``E_phys = num_experts +
  num_redundant_experts``.  Redundant slots hold replicas of (by default
  the first R) logical experts — the paper's load-balancing replicas that
  double as fault-tolerance spares (§3.4).
* Routing happens over **logical** expert ids, then a
  :class:`MoERuntime` table maps (logical id, token) -> physical slot.
  ReviveMoE recovery mutates only this table (drop a dead replica, mask a
  lost expert) — a *data* change, never a recompile.  This mirrors the
  paper's "remove failed experts from the logical-to-physical mapping".
* The distributed implementation is ``gather_psum`` (MA-collocated
  analogue): activations are replicated across the EP ('model') axis, each
  EP rank gathers the tokens routed to its local experts, computes, and the
  partial outputs are combined with a psum — the XCCL combine analogue.
  An explicit all-to-all variant (A2E/E2A analogue) lives in
  ``repro.distributed.collectives`` and is selected with
  ``cfg.moe_impl='a2a'``.
* The local dispatch->FFN->combine has two implementations with
  identical semantics: :func:`dispatch_compute_combine` (dense-scatter
  capacity buffer) and :func:`dispatch_compute_combine_fused` (the
  fused Pallas pipeline in ``repro.kernels.moe_fused``), selected by a
  'fused' suffix on ``cfg.moe_impl``.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.layers import dense_init, split_keys

MAX_REPLICAS = 2  # base slot + at most one redundant replica per expert


class MoERuntime(NamedTuple):
    """Host-controlled routing state; mutated by ReviveMoE recovery."""

    logical_to_physical: jnp.ndarray  # (E_log, MAX_REPLICAS) int32
    replica_count: jnp.ndarray        # (E_log,) int32 >= 0 (0 = expert lost)
    expert_mask: jnp.ndarray          # (E_log,) bool; False = masked (§3.4)


def default_runtime(moe: MoEConfig) -> MoERuntime:
    E, R = moe.num_experts, moe.num_redundant_experts
    l2p = jnp.stack(
        [jnp.arange(E, dtype=jnp.int32),
         jnp.where(jnp.arange(E) < R, E + jnp.arange(E), 0).astype(jnp.int32)],
        axis=1,
    )
    count = jnp.where(jnp.arange(E) < R, 2, 1).astype(jnp.int32)
    return MoERuntime(l2p, count, jnp.ones((E,), dtype=bool))


def physical_experts(moe: MoEConfig) -> int:
    return moe.num_experts + moe.num_redundant_experts


def moe_init(key, cfg: ModelConfig, dtype=jnp.float32):
    """Router + physical expert bank. Replica slots start as true copies."""
    moe = cfg.moe
    D, F = cfg.d_model, moe.expert_d_ff
    E_log = moe.num_experts
    R = moe.num_redundant_experts
    ks = split_keys(key, 4)
    gate = jax.vmap(lambda k: dense_init(k, D, F, dtype))(
        jax.random.split(ks[0], E_log))
    up = jax.vmap(lambda k: dense_init(k, D, F, dtype))(
        jax.random.split(ks[1], E_log))
    down = jax.vmap(lambda k: dense_init(k, F, D, dtype))(
        jax.random.split(ks[2], E_log))
    # physical bank: logical experts then replicas of experts [0, R)
    phys_to_logical = jnp.concatenate(
        [jnp.arange(E_log), jnp.arange(R)]).astype(jnp.int32)
    params = {
        "router": dense_init(ks[3], D, E_log, dtype),
        "gate": gate[phys_to_logical],
        "up": up[phys_to_logical],
        "down": down[phys_to_logical],
    }
    if moe.num_shared_experts:
        from repro.models.ffn import ffn_init
        params["shared"] = ffn_init(
            jax.random.fold_in(key, 7), D,
            moe.num_shared_experts * moe.expert_d_ff, "swiglu", dtype)
    return params


def route(router_w, x_flat, runtime: MoERuntime, moe: MoEConfig):
    """Top-k routing over logical experts with the §3.4 failure mask.

    Returns (weights (T,k) f32, sel (T,k) int32 logical ids, aux_loss).
    """
    T = x_flat.shape[0]
    logits = (x_flat @ router_w).astype(jnp.float32)        # (T, E_log)
    logits = jnp.where(runtime.expert_mask[None, :], logits, -jnp.inf)
    gates = jax.nn.softmax(logits, axis=-1)
    weights, sel = jax.lax.top_k(gates, moe.top_k)
    weights = weights / jnp.maximum(
        weights.sum(axis=-1, keepdims=True), 1e-9)
    # GShard load-balance auxiliary loss over healthy experts.
    E = moe.num_experts
    onehot = jax.nn.one_hot(sel, E, dtype=jnp.float32).sum(axis=1)  # (T,E)
    frac_tokens = onehot.mean(axis=0)
    frac_prob = gates.mean(axis=0)
    aux = E * jnp.sum(frac_tokens * frac_prob)
    return weights, sel, aux


def select_replicas(sel, runtime: MoERuntime):
    """Map logical selections to physical slots, balancing over replicas.

    Tokens alternate between replicas of the same logical expert — the
    paper's redundant experts double throughput on hot experts while every
    replica remains a valid recovery target.
    """
    T, k = sel.shape
    count = jnp.maximum(runtime.replica_count[sel], 1)           # (T,k)
    replica = (jnp.arange(T)[:, None] + jnp.arange(k)[None, :]) % count
    phys = jnp.take_along_axis(
        runtime.logical_to_physical[sel], replica[..., None], axis=-1
    )[..., 0]
    # experts with replica_count==0 are fully lost; mask contributions later
    alive = runtime.replica_count[sel] > 0
    return phys.astype(jnp.int32), alive


def capacity(tokens_times_k: int, e_phys: int, cf: float,
             floor: int = 8) -> int:
    c = int(math.ceil(cf * tokens_times_k / max(e_phys, 1)))
    return max(floor, min(tokens_times_k, c))


def group_by_expert(ids, ok, n_groups: int, cap: int):
    """The single sort pass shared by every dispatch implementation.

    ids: (N,) int32 group ids; ok: (N,) bool validity.  Returns
    (order, group, slot): ``order`` sorts the flat copies by group id
    (invalid entries last), ``group``/``slot`` are each sorted element's
    scatter coordinates, with invalid and over-capacity elements mapped
    out of bounds to (n_groups, cap) so ``mode='drop'`` scatters drop
    them.  Drop semantics live here and nowhere else — the dense path,
    the fused kernel's slot tables, and the A2A send/receive legs all
    consume this helper.
    """
    N = ids.shape[0]
    key = jnp.where(ok, ids, n_groups)           # dropped sort last
    order = jnp.argsort(key, stable=True)
    sorted_k = key[order]
    first = jnp.searchsorted(sorted_k, sorted_k, side="left")
    pos = jnp.arange(N, dtype=jnp.int32) - first.astype(jnp.int32)
    keep = (sorted_k < n_groups) & (pos < cap)
    group = jnp.where(keep, sorted_k, n_groups)
    slot = jnp.where(keep, pos, cap)
    return order, group, slot


def experts_compute(gate_w, up_w, down_w, buf):
    """Batched expert FFN. buf: (E_local, C, D) -> (E_local, C, D)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, gate_w))
    h = h * jnp.einsum("ecd,edf->ecf", buf, up_w)
    return jnp.einsum("ecf,efd->ecd", h, down_w)


def dispatch_compute_combine(x_flat, weights, phys, alive,
                             gate_w, up_w, down_w, *,
                             cap: int, expert_offset, e_local: int):
    """Capacity-based dispatch -> expert FFN -> weighted combine.

    Pure local computation over the expert slots
    ``[expert_offset, expert_offset + e_local)``; tokens routed elsewhere
    are dropped locally (they are served by another EP rank, whose partial
    output arrives via the caller's psum/all-to-all).

    x_flat: (T, D); weights/phys/alive: (T, k).
    """
    T, D = x_flat.shape
    k = phys.shape[1]
    N = T * k
    e_id = phys.reshape(N) - expert_offset
    ok = (e_id >= 0) & (e_id < e_local) & alive.reshape(N)
    tok = jnp.arange(N, dtype=jnp.int32) // k
    order, scatter_e, scatter_p = group_by_expert(e_id, ok, e_local, cap)

    buf = jnp.zeros((e_local, cap, D), x_flat.dtype)
    buf = buf.at[scatter_e, scatter_p].set(
        x_flat[tok[order]], mode="drop")

    out_buf = experts_compute(gate_w, up_w, down_w, buf)   # (E_local, C, D)

    y_sorted = out_buf.at[scatter_e, scatter_p].get(
        mode="fill", fill_value=0.0)                        # (N, D)
    y_flat = jnp.zeros((N, D), x_flat.dtype).at[order].set(y_sorted)
    y = (y_flat.reshape(T, k, D)
         * weights[..., None].astype(x_flat.dtype)).sum(axis=1)
    return y


def use_pallas_default() -> bool:
    """Pallas kernels compile natively on TPU; on CPU the jnp fallback is
    the fast path (interpret mode is for parity tests only)."""
    return jax.default_backend() not in ("cpu",)


def dispatch_compute_combine_fused(x_flat, weights, phys, alive,
                                   gate_w, up_w, down_w, *,
                                   cap: int, expert_offset, e_local: int,
                                   use_pallas: Optional[bool] = None):
    """Fused-pipeline twin of :func:`dispatch_compute_combine`.

    One sort pass groups tokens per expert; gather -> grouped SwiGLU FFN
    -> weighted scatter-combine run in a single Pallas kernel (see
    ``repro.kernels.moe_fused``), skipping the dense (E_local, cap, D)
    HBM capacity buffer and the (N, D) unsort of the dense path.
    """
    from repro.kernels import ops
    if use_pallas is None:
        use_pallas = use_pallas_default()
    return ops.moe_dispatch_ffn_combine(
        x_flat, gate_w, up_w, down_w, weights, phys, alive,
        jnp.asarray(expert_offset, jnp.int32),
        cap=cap, e_local=e_local, use_pallas=use_pallas)


def dispatch_fn(cfg: ModelConfig):
    """Local dispatch->FFN->combine implementation selected by
    ``cfg.moe_impl``: dense-scatter or the fused Pallas pipeline."""
    return (dispatch_compute_combine_fused if cfg.moe_fused
            else dispatch_compute_combine)


def moe_apply_local(p, cfg: ModelConfig, x_flat, runtime: MoERuntime, *,
                    cap: int, expert_offset=0, e_local: Optional[int] = None):
    """Single-rank MoE application over local expert slots.

    Shared experts and the router run on the caller side (replicated /
    TP-sharded by GSPMD); this function is what runs inside shard_map for
    the distributed path.  ``cfg.moe_impl`` endings in 'fused' route the
    dispatch->FFN->combine through the fused Pallas pipeline.
    Returns (y (T,D), aux_loss scalar).
    """
    moe = cfg.moe
    e_local = e_local if e_local is not None else physical_experts(moe)
    with jax.named_scope("moe.router"):
        weights, sel, aux = route(p["router"], x_flat, runtime, moe)
        phys, alive = select_replicas(sel, runtime)
    with jax.named_scope("moe.experts"):
        y = dispatch_fn(cfg)(
            x_flat, weights, phys, alive, p["gate"], p["up"], p["down"],
            cap=cap, expert_offset=expert_offset, e_local=e_local)
    return y, aux


def shared_expert_apply(p, cfg: ModelConfig, x):
    if cfg.moe and cfg.moe.num_shared_experts and "shared" in p:
        from repro.models.ffn import ffn_apply
        with jax.named_scope("moe.shared"):
            return ffn_apply(p["shared"], x, "swiglu")
    return 0.0
