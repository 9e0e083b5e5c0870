"""MoE steady-state hot-path benchmark: dense-scatter vs fused pipeline,
plus the decode-step megakernel vs the composed kernel chain.

ReviveMoE's recovery races against the per-step MoE latency (§3.4 keeps
the compiled MoE graph alive across failures precisely so the steady
state stays fast), so this benchmark tracks the one number every future
kernel PR has to beat: time per MoE layer application for decode- and
prefill-shaped batches.

Two sections:

  * **MoE layer** — ``dense`` (``moe.dispatch_compute_combine``: argsort
    + scatter into an (E, cap, D) capacity buffer, batched einsum FFN,
    gather + unsort) vs ``fused`` (``ops.moe_dispatch_ffn_combine``: one
    sort pass to slot tables, then gather -> grouped SwiGLU ->
    scatter-combine in a single kernel).
  * **Decode step** — the ``composed`` chain one attention+MoE block
    runs per decode step (paged attention -> output projection ->
    residual -> norm -> router top-k -> replica select -> fused MoE)
    vs ``ops.decode_megastep``, which fuses the whole chain into one
    kernel launch.  On CPU both sides are jnp (one XLA jit each), so
    the numbers measure op-boundary overhead only; on TPU the megastep
    replaces a multi-kernel chain with one ``pallas_call``.

Every row carries ``metric_us`` — the number the CI trajectory gate
(``benchmarks/trajectory.py check``) compares against the best prior
record.  Results append to ``BENCH_moe_hotpath.json`` at the repo root —
machine-readable so later PRs diff against the trajectory.
"""
from __future__ import annotations

import os
import time
from typing import Dict, List

ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
BENCH_PATH = os.path.join(ROOT, "BENCH_moe_hotpath.json")

# (name, kind, T, E_local, top_k, D, F) — CPU-sized; on TPU scale these
# up to serving shapes (decode_32k: T=128, kimi: E=384/ep, D=7168).
SWEEP = [
    ("decode_b8", "decode", 8, 8, 2, 256, 512),
    ("decode_b32", "decode", 32, 16, 2, 256, 512),
    ("decode_b128", "decode", 128, 32, 4, 256, 512),
    ("prefill_1k", "prefill", 1024, 8, 2, 256, 512),
    ("prefill_2k", "prefill", 2048, 16, 2, 256, 512),
]

# (name, B, max_blk, block_size, H, Hkv, Dh, E, top_k, D, F, Fs) — one
# attention+MoE block at decode shapes (CPU-sized; see SWEEP note).
# Fs > 0 adds the shared-expert SwiGLU the megakernel folds in-kernel.
# ``megastep_deploy`` is the deployment-shape row: deepseek_v3-class
# d_model=7168, where the D-blocked megakernel pages weights through
# VMEM instead of resident tiles (on CPU both sides are jnp, so the row
# tracks op-boundary overhead at real hidden sizes).
DECODE_STEP_SWEEP = [
    ("megastep_b8", 8, 8, 16, 8, 2, 64, 8, 2, 256, 512, 0),
    ("megastep_b32", 32, 8, 16, 8, 2, 64, 16, 2, 256, 512, 0),
    ("megastep_b128", 128, 16, 16, 8, 2, 64, 32, 4, 256, 512, 0),
    ("megastep_deploy", 8, 8, 16, 16, 2, 64, 8, 2, 7168, 512, 512),
]


def _time_fn(fn, *args, iters: int = 5, warmup: int = 2) -> float:
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def run(quick: bool = False, use_pallas: bool = None,
        iters: int = 5) -> List[Dict]:
    """``iters``: timing repetitions per shape (best-of).  The CI gate
    passes a higher count — on small shared machines the best-of
    converges to the true minimum despite scheduling noise."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.models.moe import capacity, dispatch_compute_combine

    if use_pallas is None:
        # interpret-mode Pallas is a correctness tool, not a benchmark;
        # CPU numbers compare the two jnp formulations instead
        use_pallas = jax.default_backend() not in ("cpu",)

    sweep = SWEEP[:3] if quick else SWEEP
    dense = jax.jit(dispatch_compute_combine,
                    static_argnames=("cap", "e_local"))
    rows = []
    for name, kind, T, E, k, D, F in sweep:
        ks = jax.random.split(jax.random.fold_in(
            jax.random.PRNGKey(7), T * E), 7)
        x = jax.random.normal(ks[0], (T, D)) * 0.1
        g = jax.random.normal(ks[1], (E, D, F)) * 0.05
        u = jax.random.normal(ks[2], (E, D, F)) * 0.05
        d = jax.random.normal(ks[3], (E, F, D)) * 0.05
        phys = jax.random.randint(ks[4], (T, k), 0, E)
        w = jax.nn.softmax(jax.random.normal(ks[5], (T, k)), -1)
        alive = jnp.ones((T, k), bool)
        cap = capacity(T * k, E, 1.25)
        off = jnp.int32(0)

        t_dense = _time_fn(
            lambda: dense(x, w, phys, alive, g, u, d, cap=cap,
                          expert_offset=off, e_local=E), iters=iters)
        t_fused = _time_fn(
            lambda: ops.moe_dispatch_ffn_combine(
                x, g, u, d, w, phys, alive, off, cap=cap, e_local=E,
                use_pallas=use_pallas), iters=iters)
        rows.append({
            "name": name, "kind": kind, "T": T, "E": E, "top_k": k,
            "D": D, "F": F, "cap": cap,
            "dense_us": t_dense * 1e6, "fused_us": t_fused * 1e6,
            "metric_us": t_fused * 1e6,
            "speedup": t_dense / max(t_fused, 1e-12),
            "backend": jax.default_backend(), "use_pallas": use_pallas,
        })
    rows.extend(run_decode_step(quick=quick, use_pallas=use_pallas,
                                iters=iters))
    rows.extend(run_spec_decode(quick=quick, iters=iters))
    rows.extend(run_engine_overlap(quick=quick, iters=iters))
    return rows


def run_decode_step(quick: bool = False, use_pallas: bool = None,
                    iters: int = 5) -> List[Dict]:
    """Decode-step section: composed attention->router->MoE chain vs the
    fused ``ops.decode_megastep`` (both jit'd whole, so on CPU the
    comparison isolates op-boundary overhead; on TPU it is one
    ``pallas_call`` vs the kernel chain)."""
    import functools

    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.models.moe import capacity

    if use_pallas is None:
        use_pallas = jax.default_backend() not in ("cpu",)
    # quick keeps the smallest shape plus the deployment-shape row (the
    # one the D-blocking work exists for), so CI gates both
    sweep = ([DECODE_STEP_SWEEP[0], DECODE_STEP_SWEEP[-1]] if quick
             else DECODE_STEP_SWEEP)
    rows = []
    for name, B, max_blk, bs, H, Hkv, Dh, E, k, D, F, Fs in sweep:
        nb = max_blk * B + 1
        ks = jax.random.split(jax.random.fold_in(
            jax.random.PRNGKey(11), B * E), 14)
        q = jax.random.normal(ks[0], (B, H, Dh)) * 0.3
        k_pool = jax.random.normal(ks[1], (nb, bs, Hkv, Dh)) * 0.3
        v_pool = jax.random.normal(ks[2], (nb, bs, Hkv, Dh)) * 0.3
        bt = jax.random.randint(ks[3], (B, max_blk), 0, nb)
        sl = jax.random.randint(ks[4], (B,), 1, max_blk * bs + 1)
        st = jnp.zeros((B,), jnp.int32)
        x = jax.random.normal(ks[5], (B, D)) * 0.2
        w_post = jax.random.normal(ks[6], (H * Dh, D)) * 0.1
        ln2 = jnp.ones((D,))
        router_w = jax.random.normal(ks[7], (D, E)) * 0.2
        l2p = jnp.stack([jnp.arange(E, dtype=jnp.int32),
                         jnp.zeros((E,), jnp.int32)], axis=1)
        rcnt = jnp.ones((E,), jnp.int32)
        mask = jnp.ones((E,), bool)
        g = jax.random.normal(ks[8], (E, D, F)) * 0.05
        u = jax.random.normal(ks[9], (E, D, F)) * 0.05
        d = jax.random.normal(ks[10], (E, F, D)) * 0.05
        if Fs:
            sg = jax.random.normal(ks[11], (D, Fs)) * 0.05
            su = jax.random.normal(ks[12], (D, Fs)) * 0.05
            sd = jax.random.normal(ks[13], (Fs, D)) * 0.05
        else:
            sg = su = sd = None
        cap = capacity(B * k, E, 1.25)
        off = jnp.int32(0)

        @functools.partial(jax.jit, static_argnames=())
        def composed(q, k_pool, v_pool, bt, sl, st, x, w_post, ln2,
                     router_w, rcnt, l2p, mask, g, u, d, off):
            o = ops.paged_attention(q, k_pool, v_pool, bt, sl, st,
                                    use_pallas=use_pallas)
            x2 = x + o.reshape(B, -1).astype(x.dtype) @ w_post
            xf = x2.astype(jnp.float32)
            var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
            h2 = (xf * jax.lax.rsqrt(var + 1e-5)).astype(x2.dtype) * ln2
            logits = (h2 @ router_w).astype(jnp.float32)
            logits = jnp.where(mask[None, :], logits, -jnp.inf)
            gates = jax.nn.softmax(logits, axis=-1)
            w, sel = jax.lax.top_k(gates, k)
            w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
            count = jnp.maximum(rcnt[sel], 1)
            rep = (jnp.arange(B)[:, None] + jnp.arange(k)[None, :]) % count
            phys = jnp.take_along_axis(l2p[sel], rep[..., None],
                                       axis=-1)[..., 0]
            alive = rcnt[sel] > 0
            y = ops.moe_dispatch_ffn_combine(
                h2, g, u, d, w, phys.astype(jnp.int32), alive, off,
                cap=cap, e_local=E, use_pallas=use_pallas)
            out = x2 + y
            if Fs:
                # the separate shared-expert launch the megakernel folds
                out = out + (jax.nn.silu(h2 @ sg) * (h2 @ su)) @ sd
            return out

        args = (q, k_pool, v_pool, bt, sl, st, x, w_post, ln2, router_w,
                rcnt, l2p, mask, g, u, d, off)
        t_comp = _time_fn(lambda: composed(*args), iters=iters)
        t_mega = _time_fn(lambda: ops.decode_megastep(
            q, k_pool, v_pool, bt, sl, st, x, w_post, ln2, router_w,
            l2p, rcnt, mask, g, u, d, off, sg, su, sd,
            top_k=k, cap=cap, e_local=E,
            use_pallas=use_pallas)[0], iters=iters)
        rows.append({
            "name": name, "kind": "decode_step", "T": B, "E": E,
            "top_k": k, "D": D, "F": F, "cap": cap, "F_shared": Fs,
            "composed_us": t_comp * 1e6, "mega_us": t_mega * 1e6,
            "metric_us": t_mega * 1e6,
            "speedup": t_comp / max(t_mega, 1e-12),
            "backend": jax.default_backend(), "use_pallas": use_pallas,
        })
    return rows


def run_spec_decode(quick: bool = False, iters: int = 5) -> List[Dict]:
    """Speculative-decode efficiency row: a small collocated engine
    serves a repetitive trace with self-speculation on (windows ride
    the compiled chunk graph), and the row records microseconds per
    emitted token (the gate metric) next to accepted tokens per
    speculative step and the planned-window-width histogram — the
    speculation-efficiency surface, not just latency.

    Serve repetitions are capped at min(iters, 3): one engine serve is
    seconds-long, so best-of-12 timing would dominate the gate job; the
    cap is recorded in the row as ``serves``.
    """
    import shutil
    import tempfile

    import jax
    from repro.configs import get_smoke_config
    from repro.serving.engine import EngineConfig, InferenceEngine
    from repro.serving.sampling import SamplingParams

    cfg = get_smoke_config("qwen2-moe-a2.7b")
    workdir = tempfile.mkdtemp(prefix="bench_spec_decode_")
    ec = EngineConfig(mode="collocated", num_dp=1, max_batch=4,
                      max_seq=96, block_size=8, num_blocks=96,
                      workdir=workdir, spec_window=6,
                      sampling=SamplingParams(temperature=0.0, seed=3))
    eng = InferenceEngine(cfg, ec)
    # repetitive trace: the n-gram proposer drafts from recurrence, so
    # this measures the accept path, not the empty-proposal fallback
    prompts = [[5, 9, 2, 7] * 5, [3, 1] * 8, [4, 4, 8] * 6, [2, 6] * 9]

    def serve():
        reqs = [eng.submit(list(p), 24) for p in prompts]
        t0 = time.perf_counter()
        eng.run(max_steps=600)
        dt = time.perf_counter() - t0
        assert all(r.state.value == "finished" for r in reqs)
        return dt, sum(len(r.output_tokens) for r in reqs)

    serve()                          # warmup: compiles off the clock
    serves = 1 if quick else min(iters, 3)
    best_us = float("inf")
    for _ in range(serves):
        dt, toks = serve()
        best_us = min(best_us, dt / max(toks, 1) * 1e6)
    stats = eng.prefill_stats()
    hist = eng.spec_histogram()
    shutil.rmtree(workdir, ignore_errors=True)
    windows = max(stats["spec_windows"], 1)
    return [{
        "name": "spec_decode_greedy", "kind": "spec_decode",
        "T": len(prompts), "metric_us": best_us,
        "accepted_per_step": stats["spec_emitted"] / windows,
        "spec_windows": stats["spec_windows"],
        "spec_drafts": stats["spec_drafts"],
        "spec_accepted": stats["spec_accepted"],
        "spec_emitted": stats["spec_emitted"],
        "window_hist": {str(g): n for g, n in sorted(hist.items())},
        "serves": serves,
        "backend": jax.default_backend(),
        # the engine picks its kernels per-backend; tag the row like the
        # kernel rows so the gate's row filter keeps it comparable
        "use_pallas": jax.default_backend() not in ("cpu",),
    }]


def run_engine_overlap(quick: bool = False, iters: int = 5) -> List[Dict]:
    """Engine rows: lockstep vs the async pipelined engine on the same
    workload.  ``metric_us`` is wall-clock time per emitted token
    (engine TPOT); each row also records ``host_share`` — the share of
    the engine steps' wall time the host spent not waiting on a logits
    readback (``telemetry.summary()``) — which the overlap pipeline
    exists to reduce.  The two modes' token streams are
    asserted identical (overlap is a schedule change, not a sampling
    change).

    Serve repetitions are capped like the spec row (engine serves are
    seconds-long).
    """
    import shutil
    import tempfile

    import jax
    from repro.configs import get_smoke_config
    from repro.serving.engine import EngineConfig, InferenceEngine
    from repro.serving.sampling import SamplingParams

    cfg = get_smoke_config("qwen2-moe-a2.7b")
    prompts = [[5, 9, 2, 7] * 4, [3, 1, 6] * 5, [4, 8] * 7,
               [2, 6, 1, 9] * 3]
    rows = []
    streams: Dict[str, List[List[int]]] = {}
    # one workdir for both modes: the jax persistent-cache dir is
    # process-global, and sharing it lets overlap reuse lockstep's
    # compiled graphs (overlap adds only the predict epilogue)
    workdir = tempfile.mkdtemp(prefix="bench_engine_")
    for mode in ("lockstep", "overlap"):
        ec = EngineConfig(mode="collocated", num_dp=1, max_batch=4,
                          max_seq=96, block_size=8, num_blocks=96,
                          workdir=workdir, overlap=(mode == "overlap"),
                          sampling=SamplingParams(temperature=0.0,
                                                  seed=3))
        eng = InferenceEngine(cfg, ec)

        def serve():
            reqs = [eng.submit(list(p), 24) for p in prompts]
            t0 = time.perf_counter()
            eng.run(max_steps=800)
            dt = time.perf_counter() - t0
            assert all(r.state.value == "finished" for r in reqs)
            return (dt, sum(len(r.output_tokens) for r in reqs),
                    [list(r.output_tokens) for r in reqs])

        serve()                      # warmup: compiles off the clock
        warm = eng.telemetry.n_steps  # share measured on warm serves only
        serves = 1 if quick else min(iters, 3)
        best_us = float("inf")
        toks = None
        for _ in range(serves):
            dt, n, toks = serve()
            best_us = min(best_us, dt / max(n, 1) * 1e6)
        streams[mode] = toks
        row = {
            "name": f"engine_{mode}", "kind": "engine",
            "T": len(prompts), "metric_us": best_us,
            "host_share": round(
                eng.telemetry.summary(since=warm)["host_share"], 4),
            "serves": serves,
            "backend": jax.default_backend(),
            "use_pallas": jax.default_backend() not in ("cpu",),
        }
        if mode == "overlap":
            row["overlap"] = eng.overlap_stats()
        rows.append(row)
    shutil.rmtree(workdir, ignore_errors=True)
    assert streams["lockstep"] == streams["overlap"], \
        "overlap engine diverged from lockstep token streams"
    return rows


def print_table(rows: List[Dict]) -> None:
    impl = "pallas" if rows and rows[0]["use_pallas"] else "jnp fallback"
    backend = rows[0]["backend"] if rows else "?"
    layer = [r for r in rows if "fused_us" in r]
    step = [r for r in rows if "mega_us" in r]
    spec = [r for r in rows if "accepted_per_step" in r]
    engine = [r for r in rows if r.get("kind") == "engine"]
    if layer:
        print(f"\n# MoE hot path: dense-scatter vs fused ({impl}, "
              f"backend={backend})")
        print(f"{'shape':12s} {'kind':8s} {'T':>6s} {'E':>4s} {'k':>3s} "
              f"{'cap':>5s} {'dense us':>10s} {'fused us':>10s} "
              f"{'speedup':>8s}")
        for r in layer:
            print(f"{r['name']:12s} {r['kind']:8s} {r['T']:6d} {r['E']:4d} "
                  f"{r['top_k']:3d} {r['cap']:5d} {r['dense_us']:10.0f} "
                  f"{r['fused_us']:10.0f} {r['speedup']:7.2f}x")
    if step:
        print(f"\n# Decode step: composed chain vs megakernel ({impl}, "
              f"backend={backend})")
        print(f"{'shape':12s} {'kind':11s} {'B':>6s} {'E':>4s} {'k':>3s} "
              f"{'cap':>5s} {'composed us':>12s} {'mega us':>10s} "
              f"{'speedup':>8s}")
        for r in step:
            print(f"{r['name']:12s} {r['kind']:11s} {r['T']:6d} "
                  f"{r['E']:4d} {r['top_k']:3d} {r['cap']:5d} "
                  f"{r['composed_us']:12.0f} {r['mega_us']:10.0f} "
                  f"{r['speedup']:7.2f}x")
    if spec:
        print(f"\n# Speculative decode (engine, greedy, "
              f"backend={backend})")
        print(f"{'shape':18s} {'us/token':>10s} {'acc/step':>9s} "
              f"{'windows':>8s} {'drafts':>7s} {'accepted':>9s} "
              f"{'window hist':>20s}")
        for r in spec:
            hist = ",".join(f"{g}:{n}" for g, n in
                            sorted(r["window_hist"].items()))
            print(f"{r['name']:18s} {r['metric_us']:10.0f} "
                  f"{r['accepted_per_step']:9.2f} "
                  f"{r['spec_windows']:8d} {r['spec_drafts']:7d} "
                  f"{r['spec_accepted']:9d} {hist:>20s}")
    if engine:
        print(f"\n# Engine: lockstep vs async pipelined "
              f"(token-identical, backend={backend})")
        print(f"{'mode':18s} {'us/token':>10s} {'host':>9s} "
              f"{'planned ahead':>14s} {'replans':>8s}")
        for r in engine:
            ov = r.get("overlap", {})
            pa = str(ov.get("planned_ahead", "—"))
            rp = str(ov.get("replans", "—"))
            print(f"{r['name']:18s} {r['metric_us']:10.0f} "
                  f"{r['host_share']:8.1%} {pa:>14s} {rp:>8s}")


def save_json(rows: List[Dict], path: str = BENCH_PATH, *,
              quick: bool = False) -> None:
    """Append this run to the perf trajectory (list of run records).

    ``quick`` is recorded so reduced sweeps are never mistaken for the
    full-sweep records future PRs must beat.
    """
    from benchmarks.trajectory import append_record, machine_id
    append_record(path, {
        "benchmark": "moe_hotpath",
        "unix_time": time.time(),
        "quick": quick,
        "machine": machine_id(),
        "rows": rows,
    })


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--pallas", action="store_true",
                    help="force the Pallas kernel (interpret mode on CPU)")
    args = ap.parse_args()
    rs = run(quick=args.quick, use_pallas=True if args.pallas else None)
    print_table(rs)
    save_json(rs, quick=args.quick)
    print(f"\nappended to {BENCH_PATH}")
