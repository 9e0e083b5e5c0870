#!/usr/bin/env python3
"""One-chip smoke run of the serving system's main path on a TPU.

Serves qwen2-moe-a2.7b at its published widths (only the depth is cut;
random weights made from ``--seed``) through the engine builder of
``python -m repro.launch.serve --layers N``, in one process:

  (a) device   — a TPU must be present; platform, kind and count printed.
  (b) kernels  — ``paged_attention``, ``moe_fused`` and the decode
                 megakernel, compiled for the chip at qwen widths, against
                 their jnp oracles in ``repro.kernels.ref`` (float32,
                 highest matmul precision); fails above ``TOL``.  The
                 megakernel runs with the expert runtime of every rank
                 alive and with the runtime a revive serves after losing
                 each rank (dead replicas dropped, lost experts masked).
  (c) default  — collocated engine, 2 DP ranks, composed decode: 8 seeded
                 requests (64-token prompts, 32 new tokens), one mid-step
                 L6 MoE-side device fault (as ``serve.py --inject-fault
                 moe``), revive in place.  Every request must finish and
                 no step graph may compile from the revive on.
  (d) megakernel — the same requests and fault with
                 ``decode_impl="megakernel"``; every request must finish.

Numbers go on earlier lines.  The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Any failed phase raises: the script exits non-zero without that line.

    python3 chip_smoke.py [--seed 0] [--layers 8]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

ARCH = "qwen2-moe-a2.7b"
# max |kernel - oracle| / max |oracle| allowed for a bfloat16 kernel
# against the float32 oracle (bfloat16 keeps 8 bits of mantissa: 2^-8 =
# 0.0039 per rounding, a few roundings deep)
TOL = 2e-2
N_REQUESTS, PROMPT_LEN, NEW_TOKENS = 8, 64, 32
NUM_DP = 2          # collocated DP ranks, each one expert-parallel rank
FAULT_STEP = 12


def _errors(out, ref) -> tuple:
    """(max |out - ref|, that over max |ref|)."""
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    err = float(np.abs(out - ref).max())
    return err, err / max(float(np.abs(ref).max()), 1e-30)


def check_kernels(cfg, seed: int, interpret: bool = False) -> dict:
    """Phase (b): each kernel at ``cfg``'s widths against its oracle.
    Returns {name: relative max error}; raises above ``TOL``."""
    import jax
    import jax.numpy as jnp

    from repro.core.expert_map import ExpertMap
    from repro.kernels import ref
    from repro.kernels.decode_megakernel import decode_megastep_pallas
    from repro.kernels.moe_fused import moe_fused_pallas
    from repro.kernels.paged_attention import paged_attention_pallas
    from repro.models.moe import capacity, physical_experts

    moe = cfg.moe
    D, H, Hkv = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    Dh = cfg.resolved_head_dim()
    E, E_log, K, F = (physical_experts(moe), moe.num_experts, moe.top_k,
                      moe.expert_d_ff)
    Fs = moe.num_shared_experts * F
    bs, max_blk, nb = 16, 8, 64
    bf = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    def normal(shape, scale=1.0):
        return (jax.random.normal(next(keys), shape) * scale).astype(bf)

    oracles = {}

    def oracle(fn, *args, **kw):
        f32 = [a.astype(jnp.float32) if a is not None
               and a.dtype == bf else a for a in args]
        key = (fn, tuple(sorted(kw.items())))
        if key not in oracles:
            oracles[key] = jax.jit(lambda *a: fn(*a, **kw))
        with jax.default_matmul_precision("highest"):
            return oracles[key](*f32)

    errs = {}

    def record(name, out, want):
        abs_err, errs[name] = _errors(out, want)
        print(f"kernel {name}: max_abs_err={abs_err!r} "
              f"max_rel_err={errs[name]!r} (tol {TOL})", flush=True)

    # paged attention at the decode (B=4) and chunk (B=32) widths
    k_pool, v_pool = normal((nb, bs, Hkv, Dh)), normal((nb, bs, Hkv, Dh))
    for B in (4, 32):
        q = normal((B, H, Dh))
        bt = jax.random.randint(next(keys), (B, max_blk), 0, nb)
        sl = jax.random.randint(next(keys), (B,), 1, max_blk * bs + 1)
        out = jax.jit(lambda *a: paged_attention_pallas(
            *a, interpret=interpret))(q, k_pool, v_pool, bt, sl)
        record(f"paged_attention[B={B}]", out,
               oracle(ref.paged_attention_ref, q, k_pool, v_pool, bt, sl))

    # fused MoE dispatch -> grouped FFN -> combine over the whole bank
    T = 32
    cap = capacity(T * K, E, moe.capacity_factor, moe.min_capacity)
    gate, up = normal((E, D, F), D ** -0.5), normal((E, D, F), D ** -0.5)
    down = normal((E, F, D), F ** -0.5)
    x = normal((T, D))
    w = jax.nn.softmax(jax.random.normal(next(keys), (T, K)), axis=-1)
    phys = jax.random.randint(next(keys), (T, K), 0, E)
    alive = jax.random.bernoulli(next(keys), 0.9, (T, K))
    out = jax.jit(lambda *a: moe_fused_pallas(
        *a, cap=cap, e_local=E, interpret=interpret))(
            x, gate, up, down, w, phys, alive)
    record(f"moe_fused[T={T}]", out,
           oracle(ref.moe_fused_ref, x, gate, up, down, w, phys, alive,
                  cap=cap, e_local=E))

    # decode megakernel: one attention+MoE block (shared experts too),
    # under the expert runtime of every rank alive and under the one a
    # revive serves after losing rank r: its slots dead (and zeroed in
    # the bank), the logical-to-physical map down to the live replicas,
    # the experts with none left masked.  Each token's input is built to
    # prefer K distinct live experts by a wide margin (token b includes
    # replicated expert b when it lives), so the bf16 kernel and the f32
    # oracle route alike.
    B = 4
    cap = capacity(B * K, E, moe.capacity_factor, moe.min_capacity)
    router = normal((D, E_log), D ** -0.5)
    q = normal((B, H, Dh))
    bt = jax.random.randint(next(keys), (B, max_blk), 0, nb)
    sl = jax.random.randint(next(keys), (B,), 1, max_blk * bs + 1)
    st = jnp.zeros((B,), jnp.int32)
    w_post = normal((H * Dh, D), (H * Dh) ** -0.5)
    ln2 = jnp.ones((D,), bf)
    s_gate, s_up = normal((D, Fs), D ** -0.5), normal((D, Fs), D ** -0.5)
    s_down = normal((Fs, D), Fs ** -0.5)
    kw = dict(top_k=K, cap=cap, e_local=E, eps=cfg.norm_eps)
    mega = jax.jit(lambda *a: decode_megastep_pallas(
        *a, interpret=interpret, **kw))
    rng = np.random.default_rng(seed)
    for lost in (None,) + tuple(range(NUM_DP)):
        emap = ExpertMap(moe, NUM_DP)
        bank = (gate, up, down)
        if lost is not None:
            emap.fail_rank(lost)
            emap.mask_experts(emap.fully_lost())
            dead = np.zeros((E, 1, 1), bool)
            dead[emap.rank_slots(lost)] = True
            bank = tuple(jnp.where(dead, jnp.zeros((), bf), w) for w in bank)
        rt = emap.runtime()
        live = np.flatnonzero(np.asarray(rt.expert_mask))
        mix = np.zeros((B, E_log), np.float32)
        for b in range(B):
            pref = [b % moe.num_redundant_experts] if (
                moe.num_redundant_experts and b % moe.num_redundant_experts
                in live) else []
            pref += list(rng.choice([e for e in live if e not in pref],
                                    K - len(pref), replace=False))
            mix[b, pref] = 1.0 - 0.02 * np.arange(K)
        x = (4.0 * D ** 0.5 * jnp.asarray(mix)
             @ router.astype(jnp.float32).T).astype(bf)
        args = (q, k_pool, v_pool, bt, sl, st, x, w_post, ln2, router,
                rt.logical_to_physical, rt.replica_count, rt.expert_mask,
                *bank, jnp.int32(0), s_gate, s_up, s_down)
        y, h2 = mega(*args)
        y_ref, h2_ref = oracle(ref.decode_megastep_ref, *args, **kw)
        state = "all ranks" if lost is None else f"rank {lost} lost"
        record(f"decode_megastep[B={B}, {state}].y", y, y_ref)
        record(f"decode_megastep[B={B}, {state}].h2", h2, h2_ref)
    bad = {k: v for k, v in errs.items() if not v <= TOL}
    if bad:
        raise AssertionError(f"kernels above tolerance {TOL}: {bad}")
    return errs


def fresh_compiles(eng, since: int) -> int:
    """Step graphs the engine compiled (not found precompiled) from its
    ``since``-th graph-cache lookup on, however fast the compile was."""
    return sum(t.source != "precompiled"
               for t in eng.graph_cache.timings[since:])


def serve_through_fault(cfg, seed: int, decode_impl: str,
                        workdir=None) -> dict:
    """Phases (c)/(d): build the engine, serve the seeded requests
    through one mid-step MoE-side device fault, revive in place.  Raises
    unless every request finishes, the revive found its graph precompiled
    and no step graph compiles from the revive's step on."""
    from repro.launch.serve import engine_config, schedule_fault
    from repro.serving.engine import InferenceEngine
    from repro.serving.request import RequestState

    t0 = time.perf_counter()
    ec = engine_config(cfg, mode="collocated", num_dp=NUM_DP,
                       decode_impl=decode_impl, seed=seed, workdir=workdir)
    eng = InferenceEngine(cfg, ec)
    build_s = time.perf_counter() - t0
    it = eng.init_timings
    compile_s = (it.get("compile", 0.0)
                 + it.get("precompile_failure_scenarios", 0.0))
    print(f"[{decode_impl}] engine built in {build_s!r} s; compile "
          f"{compile_s!r} s apart from the rest; init timings "
          f"{ {k: v for k, v in sorted(it.items())} }", flush=True)

    rng = np.random.default_rng(seed)
    reqs = [eng.submit([int(t) for t in rng.integers(0, cfg.vocab_size,
                                                     PROMPT_LEN)],
                       NEW_TOKENS) for _ in range(N_REQUESTS)]
    pid = schedule_fault(eng, "moe", FAULT_STEP, ec.mode, ec.num_dp)
    since = None
    t0 = time.perf_counter()
    while eng.unfinished and eng.step_no < 2000:
        before = len(eng.graph_cache.timings)
        eng.step()
        if since is None and eng.reports:
            since = before          # the step the revive ran in
    serve_s = time.perf_counter() - t0
    done = sum(r.state is RequestState.FINISHED
               and len(r.output_tokens) == NEW_TOKENS for r in reqs)
    print(f"[{decode_impl}] finished {done}/{len(reqs)} requests in "
          f"{eng.step_no} steps, {serve_s!r} s", flush=True)
    if done != len(reqs):
        raise AssertionError(f"[{decode_impl}] only {done}/{len(reqs)} "
                             f"requests finished")
    if len(eng.reports) != 1 or since is None:
        raise AssertionError(f"[{decode_impl}] expected one revive, got "
                             f"{len(eng.reports)}")
    rep = eng.reports[0]
    fresh = fresh_compiles(eng, since)
    print(f"[{decode_impl}] recovery: scenario={rep.scenario} "
          f"device={pid} compile_source={rep.compile_source} "
          f"stall_s={rep.total_s!r} fresh_graph_compiles_after_revive="
          f"{fresh} timings="
          f"{ {k: v for k, v in sorted(rep.timings.items())} }", flush=True)
    if rep.compile_source != "precompiled" or fresh != 0:
        raise AssertionError(f"[{decode_impl}] {fresh} step graphs "
                             f"compiled from the revive on (revive graph "
                             f"{rep.compile_source})")
    tokens = [list(r.output_tokens) for r in reqs]
    del eng, reqs, rep
    gc.collect()
    return {"tokens": tokens, "compile_s": compile_s}


def _peak_bytes(dev) -> tuple:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use"), stats.get("bytes_limit")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--layers", type=int, default=8,
                    help="depth kept of the published 24 layers")
    args = ap.parse_args(argv)

    import jax

    # (a) device
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this script runs only on the "
              "chip", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.serve import model_config
    from repro.models.model import Model

    full = get_config(ARCH)
    cfg = model_config(ARCH, args.layers)
    n_params = Model(cfg).count_params()
    print(f"config: {ARCH} {full.source} at published widths (d_model "
          f"{cfg.d_model}, {cfg.num_heads} heads x {cfg.resolved_head_dim()}"
          f", {cfg.num_kv_heads} kv heads, {cfg.moe.num_experts} routed "
          f"experts + {cfg.moe.num_redundant_experts} redundant slots, "
          f"top-{cfg.moe.top_k}, expert_d_ff {cfg.moe.expert_d_ff}, "
          f"{cfg.moe.num_shared_experts} shared, vocab {cfg.vocab_size}); "
          f"depth cut {full.num_layers} -> {cfg.num_layers} layers; "
          f"{n_params} parameters, bfloat16", flush=True)

    # (b) kernels vs oracles
    check_kernels(cfg, args.seed)

    # (c) default path through a device fault and revive
    base = serve_through_fault(cfg, args.seed, "composed")
    peak, limit = _peak_bytes(dev)
    print(f"[composed] peak_bytes_in_use={peak} bytes_limit={limit}",
          flush=True)
    if peak is not None and limit is not None and not peak < limit:
        raise AssertionError("peak device memory reached the limit")

    # (d) megakernel path
    mega = serve_through_fault(cfg, args.seed, "megakernel")
    same = sum(a == b for ta, tb in zip(base["tokens"], mega["tokens"])
               for a, b in zip(ta, tb))
    total = sum(len(t) for t in base["tokens"])
    print(f"[megakernel] tokens identical to composed: {same}/{total} = "
          f"{same / total!r}", flush=True)
    peak, limit = _peak_bytes(dev)
    print(f"peak_bytes_in_use={peak} bytes_limit={limit} (whole run)",
          flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
