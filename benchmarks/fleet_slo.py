"""Fleet-level Figure-5 analogue: recovery policy vs client SLO.

Three fleets serve the *same* Poisson arrival trace and take the *same*
injected MoE device fault on instance 0; the only difference is the
recovery policy the arbiter is forced to use:

* ``revive``  — ReviveMoE in-place recovery (paper's contribution),
* ``restart`` — drain-and-restart of the wounded instance (baseline),
* ``spare``   — live migration onto a pre-warmed standby (FailSafe-style
  KV-block streaming; the wounded instance's reachable executors ship
  their residents' live blocks, only dead-device requests re-prefill).

A no-fault run provides the TTFT reference.  The figure of merit is p99
TTFT *degradation* vs that baseline.  Two extra sections stress the
parts a single-fault trace cannot:

* ``compound`` — correlated / multi-fault traces (two devices of the
  same comm domain, and a second instance faulting while the first is
  still recovering), with the arbiter left free to choose per fault.
* ``prefix_sweep`` — migration cost vs prompt length: KV-block streaming
  is ~flat in the prefix (a block copy), token-replay re-prefill grows
  linearly with it; both paths are asserted token-exact.

Every run appends to ``BENCH_fleet_slo.json`` via benchmarks.trajectory.
"""
from __future__ import annotations

import dataclasses
import os
import tempfile
import time
from typing import Dict, List

import numpy as np

from benchmarks.fleet_harness import (fleet_cfg as _cfg,
                                      fleet_ecfg as _ecfg,
                                      percentile as _percentile,
                                      run_fleet as _run_fleet)
from repro.fleet import PoissonTraffic, build_fleet
from repro.serving.engine import EngineConfig

BENCH_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_fleet_slo.json")


# correlated / multi-fault traces (ROADMAP follow-up b): the arbiter is
# left free to choose per fault.  pids: 0-1 attention, 2-3 MoE.
COMPOUND_TRACES = {
    # two devices in the same comm domain (one host/switch takes both):
    # the MoE rank at step 10, then an attention rank of the *same*
    # instance two steps later — mid-recovery of the first
    "double_fault_same_domain": [
        {"iid": 0, "step": 10, "pid": 3, "component": "moe"},
        {"iid": 0, "step": 12, "pid": 1, "component": "attn"},
    ],
    # a second instance faults while the fleet is still absorbing the
    # first instance's recovery
    "fault_during_recovery": [
        {"iid": 0, "step": 10, "pid": 3, "component": "moe"},
        {"iid": 1, "step": 11, "pid": 1, "component": "attn"},
    ],
}


def _sweep_engines(workdir: str):
    """Two weight-identical engines sharing one compile cache: the
    migration source and target of the prefix sweep."""
    from repro.serving.engine import InferenceEngine
    cfg = _cfg()
    max_seq = 320
    ecfg = EngineConfig(mode="collocated", num_dp=1, max_batch=2,
                        max_seq=max_seq, block_size=16, num_blocks=48,
                        workdir=workdir)
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0,
                                     min_capacity=64))
    src = InferenceEngine(cfg, dataclasses.replace(ecfg))
    tgt = InferenceEngine(cfg, dataclasses.replace(ecfg))
    return cfg, src, tgt


def prefix_sweep(workdir: str, quick: bool = False) -> Dict:
    """Migration cost vs prompt length: KV-block streaming vs re-prefill.

    For each prefix length P, a request generated to mid-stream on the
    source engine is migrated to the target both ways; the measured cost
    is admission + the target's first step (decode-only when streamed, a
    P-token prefill when replayed).  Both paths must continue the exact
    token stream (position-seeded sampling), asserted per point.
    """
    prefixes = [16, 128] if quick else [16, 64, 128, 256]
    reps = 2 if quick else 3
    cfg, src, tgt = _sweep_engines(workdir)
    rng = np.random.default_rng(17)

    def migrate_once(prompt: List[int], stream: bool) -> Dict:
        req = src.submit(prompt, max_new_tokens=6)
        for _ in range(40):              # chunked prefill: step to mid-gen
            src.step()
            if len(req.output_tokens) >= 2:
                break
        assert len(req.output_tokens) >= 2, "must be mid-generation"
        pre_tokens = list(req.output_tokens)
        exported = src.export_live_requests(with_kv=True)
        (req2, kv), = exported
        assert req2 is req
        if not stream:
            kv = None
        # time to the next token on the target: one decode step when
        # streamed vs a chunked P-token re-prefill when replayed
        t0 = time.perf_counter()
        tgt.admit(req, kv=kv)
        for _ in range(40):
            tgt.step()
            if len(req.output_tokens) > len(pre_tokens):
                break
        dt = time.perf_counter() - t0
        assert len(req.output_tokens) == len(pre_tokens) + 1
        tgt.run(max_steps=60)
        assert req.state.value == "finished"
        return {"s": dt, "tokens": list(req.output_tokens)}

    def prompt_for(P: int) -> List[int]:
        return list(rng.integers(0, cfg.vocab_size, P))

    # warm every prefill bucket + the install/decode graphs off-clock
    for P in prefixes:
        migrate_once(prompt_for(P), stream=True)
        migrate_once(prompt_for(P), stream=False)

    points = []
    for P in prefixes:
        stream_runs, replay_runs = [], []
        for _ in range(reps):
            prompt = prompt_for(P)
            s = migrate_once(prompt, stream=True)
            r = migrate_once(prompt, stream=False)
            # parity: KV-stream and re-prefill continue identical tokens
            # (same prompt, position-seeded sampling — the token stream
            # must be independent of the migration mechanism)
            assert s["tokens"] == r["tokens"], (P, s["tokens"], r["tokens"])
            stream_runs.append(s["s"])
            replay_runs.append(r["s"])
        points.append({"prefix": P,
                       "stream_s": round(min(stream_runs), 5),
                       "replay_s": round(min(replay_runs), 5)})
    lo, hi = points[0], points[-1]
    stream_growth = hi["stream_s"] - lo["stream_s"]
    replay_growth = hi["replay_s"] - lo["replay_s"]
    return {
        "block_size": 16,
        "points": points,
        "stream_growth_s": round(stream_growth, 5),
        "replay_growth_s": round(replay_growth, 5),
        # streamed takeover must not inherit re-prefill's linear term
        "stream_flat_vs_replay_linear": bool(
            replay_growth > 0
            and stream_growth < 0.5 * replay_growth),
    }


def admission_bench(workdir: str, quick: bool = False) -> Dict:
    """Continuous-batching admission pipeline vs the one-prefill-per-step
    baseline, on the production-shaped workload it exists for: mixed
    long/short prompts, 80% opening with one shared system prompt.

    Both fleets serve the identical arrival trace; the only differences
    are ``EngineConfig.admission`` ('chunked' = token-budget chunked
    prefills + shared-prefix block cache, 'serial' = legacy whole-prompt,
    one per step) and prefix-affinity routing (chunked only).  Reported:
    p50/p99 TTFT and prefill tokens computed vs skipped via the cache.
    """
    n_requests = 18 if quick else 36
    rate = 40.0
    out: Dict = {"n_requests": n_requests, "rate_per_s": rate,
                 "shared_fraction": 0.8, "modes": {}}

    def _traffic():
        return PoissonTraffic(rate, _cfg().vocab_size,
                              prompt_len=(8, 40), max_new_tokens=10,
                              seed=23, limit=n_requests,
                              shared_prefix_len=24, shared_fraction=0.8)

    for mode in ("serial", "chunked"):
        wd = os.path.join(workdir, f"adm_{mode}")
        ecfg = dataclasses.replace(_ecfg(wd), admission=mode,
                                   prefill_chunk=16, workdir=wd)
        # warm the per-mode compile cache + checkpoint off the clock
        warm = build_fleet(_cfg(), dataclasses.replace(ecfg), instances=2,
                           traffic=PoissonTraffic(
                               rate, _cfg().vocab_size, prompt_len=(8, 40),
                               max_new_tokens=4, seed=5, limit=2,
                               shared_prefix_len=24, shared_fraction=0.8))
        warm.run(max_ticks=400)
        fleet = build_fleet(_cfg(), dataclasses.replace(ecfg), instances=2,
                            traffic=_traffic(),
                            prefix_affinity=(mode == "chunked"))
        t0 = time.perf_counter()
        fleet.run(max_ticks=4000)
        ttfts = fleet.ttfts()
        stats: Dict[str, int] = {}
        for inst in fleet.instances.values():
            for k, v in inst.engine.prefill_stats().items():
                stats[k] = stats.get(k, 0) + v
        done = len(fleet.requests) - fleet.unfinished
        out["modes"][mode] = {
            "finished": done, "n": len(fleet.requests),
            "p50_ttft_s": _percentile(ttfts, 50),
            "p99_ttft_s": _percentile(ttfts, 99),
            "virtual_makespan_s": round(fleet.now_s, 3),
            "wall_s": round(time.perf_counter() - t0, 3),
            "prefill_tokens_computed": stats.get(
                "prefill_tokens_computed", 0),
            "prefill_tokens_cached": stats.get("prefill_tokens_cached", 0),
        }
    ch, se = out["modes"]["chunked"], out["modes"]["serial"]
    out["p99_ttft_improvement_s"] = round(
        se["p99_ttft_s"] - ch["p99_ttft_s"], 4)
    out["prefill_tokens_saved"] = ch["prefill_tokens_cached"]
    out["chunked_beats_serial_p99"] = bool(
        ch["p99_ttft_s"] < se["p99_ttft_s"])
    # deterministic regression gates (CI runs --quick): every request
    # finished in both modes, and the prefix-heavy trace actually hit
    # the shared-prefix cache — a silent cache regression fails here
    assert ch["finished"] == ch["n"] and se["finished"] == se["n"], out
    assert ch["prefill_tokens_cached"] > 0, \
        "prefix-heavy trace produced zero shared-prefix cache hits"
    assert ch["prefill_tokens_computed"] < se["prefill_tokens_computed"], \
        "shared-prefix cache saved no prefill compute vs serial"
    return out


def frontend_bench(workdir: str, quick: bool = False,
                   slo_s: float = 30.0) -> Dict:
    """Wall-clock serving through the real HTTP front end: sustained
    req/s at a fixed p99 completion-latency SLO.

    Unlike the virtual-clock sections above, this measures the whole
    serving stack end to end — asyncio HTTP, SSE-free JSON completions,
    the fleet driver thread, and the async pipelined engine — with a
    closed-loop client pool hammering ``POST /v1/completions``.  The
    p99 bound is deliberately loose (CI boxes vary); the hard gates are
    that every request finishes and the SLO holds at the achieved rate.
    """
    import asyncio
    import http.client
    import json as _json
    import threading

    from repro.serving.frontend import ServingFrontend

    n_requests = 8 if quick else 24
    concurrency = 4 if quick else 6
    max_tokens = 8 if quick else 12
    ecfg = dataclasses.replace(_ecfg(workdir), overlap=True)
    fleet = build_fleet(_cfg(), ecfg, instances=2)
    fe = ServingFrontend(fleet, port=0)
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def _serve():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(fe.start())
        started.set()
        loop.run_forever()

    th = threading.Thread(target=_serve, daemon=True)
    th.start()
    assert started.wait(120), "front end failed to start"
    rng = np.random.default_rng(11)
    prompts = [list(map(int, rng.integers(0, _cfg().vocab_size, 10)))
               for _ in range(n_requests + concurrency)]

    def one(prompt: List[int]) -> float:
        conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                          timeout=600)
        try:
            t0 = time.perf_counter()
            conn.request("POST", "/v1/completions",
                         body=_json.dumps({
                             "prompt": prompt, "max_tokens": max_tokens,
                             "eos_token": None}),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = _json.loads(resp.read())
            assert resp.status == 200, body
            toks = body["choices"][0]["tokens"]
            assert len(toks) == max_tokens, (len(toks), body)
            return time.perf_counter() - t0
        finally:
            conn.close()

    # warm the compile caches + http path off the clock
    one(prompts[-1])
    lats: List[float] = []
    lock = threading.Lock()
    queue = list(range(n_requests))

    def worker():
        while True:
            with lock:
                if not queue:
                    return
                i = queue.pop()
            dt = one(prompts[i])
            with lock:
                lats.append(dt)

    t0 = time.perf_counter()
    workers = [threading.Thread(target=worker)
               for _ in range(concurrency)]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    wall = time.perf_counter() - t0
    shares = [inst.engine.telemetry.summary()["host_share"]
              for inst in fleet.instances.values()
              if inst.state.value != "dead"]
    asyncio.run_coroutine_threadsafe(fe.stop(), loop).result(timeout=30)
    loop.call_soon_threadsafe(loop.stop)
    th.join(timeout=10)
    assert len(lats) == n_requests
    p99 = _percentile(lats, 99)
    out = {
        "n": n_requests, "concurrency": concurrency,
        "max_tokens": max_tokens,
        "wall_s": round(wall, 3),
        "req_per_s": round(n_requests / wall, 3),
        "tokens_per_s": round(n_requests * max_tokens / wall, 2),
        "p50_latency_s": _percentile(lats, 50),
        "p99_latency_s": p99,
        "slo_s": slo_s,
        "p99_within_slo": bool(p99 <= slo_s),
        "host_share": round(float(np.mean(shares)), 4),
    }
    assert out["p99_within_slo"], out
    return out


def run(quick: bool = False) -> Dict:
    n_requests = 24 if quick else 48
    rate = 60.0          # open-loop: arrivals do not wait for recovery
    workdir = tempfile.mkdtemp(prefix="bench_fleet_slo_")
    out: Dict = {"unix_time": time.time(), "quick": quick,
                 "n_requests": n_requests, "rate_per_s": rate,
                 "policies": {}}
    # warmup: populate the shared on-disk compile cache + checkpoint so
    # the first measured fleet isn't charged for cold compiles
    _run_fleet(workdir, None, 2, rate)
    base = _run_fleet(workdir, None, n_requests, rate)
    out["baseline"] = base
    for policy in ("revive", "restart", "spare"):
        res = _run_fleet(workdir, policy, n_requests, rate)
        res["p99_degradation_s"] = round(
            res["p99_ttft_s"] - base["p99_ttft_s"], 4)
        res["p50_degradation_s"] = round(
            res["p50_ttft_s"] - base["p50_ttft_s"], 4)
        out["policies"][policy] = res
    out["revive_beats_restart"] = bool(
        out["policies"]["revive"]["p99_degradation_s"]
        < out["policies"]["restart"]["p99_degradation_s"])
    # compound failures: arbiter free, one warm spare available
    out["compound"] = {}
    for name, faults in COMPOUND_TRACES.items():
        res = _run_fleet(workdir, None, n_requests, rate,
                         faults=faults, spares=1)
        res["p99_degradation_s"] = round(
            res["p99_ttft_s"] - base["p99_ttft_s"], 4)
        res["all_finished"] = bool(res["finished"] == res["n"])
        out["compound"][name] = res
    out["prefix_sweep"] = prefix_sweep(
        tempfile.mkdtemp(prefix="bench_prefix_sweep_"), quick=quick)
    out["admission"] = admission_bench(
        tempfile.mkdtemp(prefix="bench_admission_"), quick=quick)
    out["frontend"] = frontend_bench(
        tempfile.mkdtemp(prefix="bench_frontend_"), quick=quick)
    return out


def save_json(out: Dict, path: str = BENCH_PATH) -> None:
    from benchmarks.trajectory import append_record
    slim = {k: v for k, v in out.items()}
    # the per-tick timelines are large; keep a downsampled copy
    slim["policies"] = {}
    for name, res in out["policies"].items():
        res = dict(res)
        tl = res.pop("goodput_timeline")
        res["goodput_timeline"] = tl[::max(1, len(tl) // 48)]
        slim["policies"][name] = res
    base = dict(slim["baseline"] if "baseline" in out else {})
    base.pop("goodput_timeline", None)
    slim["baseline"] = base
    if "compound" in out:
        slim["compound"] = {}
        for name, res in out["compound"].items():
            res = dict(res)
            res.pop("goodput_timeline", None)
            slim["compound"][name] = res
    append_record(path, slim)


def print_table(out: Dict) -> None:
    print("\n# Fleet SLO: recovery policy vs p50/p99 TTFT "
          "(same fault, same arrival trace)")
    base = out["baseline"]
    print(f"  open-loop Poisson {out['rate_per_s']:.0f} req/s, "
          f"{out['n_requests']} requests, 3 instances")
    print(f"  {'policy':10s} {'done':>7s} {'p50 TTFT':>10s} "
          f"{'p99 TTFT':>10s} {'p99 degr.':>10s} {'makespan':>9s}")
    print(f"  {'no-fault':10s} {base['finished']:3d}/{base['n']:<3d} "
          f"{base['p50_ttft_s'] * 1e3:8.0f}ms "
          f"{base['p99_ttft_s'] * 1e3:8.0f}ms {'—':>10s} "
          f"{base['virtual_makespan_s']:7.2f}s")
    for name, res in out["policies"].items():
        print(f"  {name:10s} {res['finished']:3d}/{res['n']:<3d} "
              f"{res['p50_ttft_s'] * 1e3:8.0f}ms "
              f"{res['p99_ttft_s'] * 1e3:8.0f}ms "
              f"{res['p99_degradation_s'] * 1e3:8.0f}ms "
              f"{res['virtual_makespan_s']:7.2f}s")
    verdict = "yes" if out["revive_beats_restart"] else "NO (!)"
    print(f"  revive beats restart on p99 TTFT degradation: {verdict}")
    for name, res in out["policies"].items():
        for line in res["arbiter_log"]:
            print(f"    [{name}] {line}")
    if "compound" in out:
        print("\n# Compound failures (arbiter free, 1 warm spare)")
        for name, res in out["compound"].items():
            print(f"  {name:26s} {res['finished']:3d}/{res['n']:<3d} "
                  f"p99 degr {res['p99_degradation_s'] * 1e3:7.0f}ms")
            for line in res["arbiter_log"]:
                print(f"    {line}")
    if "prefix_sweep" in out:
        sw = out["prefix_sweep"]
        print("\n# Migration cost vs prefix length "
              "(KV-block stream vs re-prefill, token-exact both ways)")
        print(f"  {'prefix':>7s} {'stream':>10s} {'re-prefill':>11s}")
        for pt in sw["points"]:
            print(f"  {pt['prefix']:7d} {pt['stream_s'] * 1e3:8.1f}ms "
                  f"{pt['replay_s'] * 1e3:9.1f}ms")
        flag = "yes" if sw["stream_flat_vs_replay_linear"] else "NO (!)"
        print(f"  stream ~flat while re-prefill grows with prefix: {flag}")
    if "admission" in out:
        adm = out["admission"]
        print("\n# Admission pipeline: chunked token-budget + prefix "
              "cache vs one-prefill-per-step\n"
              f"  mixed 8/40-token prompts, "
              f"{adm['shared_fraction'] * 100:.0f}% shared system prompt, "
              f"{adm['n_requests']} requests @ {adm['rate_per_s']:.0f}/s")
        print(f"  {'mode':10s} {'done':>7s} {'p50 TTFT':>10s} "
              f"{'p99 TTFT':>10s} {'prefill tok':>12s} {'cached':>8s}")
        for name, res in adm["modes"].items():
            print(f"  {name:10s} {res['finished']:3d}/{res['n']:<3d} "
                  f"{res['p50_ttft_s'] * 1e3:8.0f}ms "
                  f"{res['p99_ttft_s'] * 1e3:8.0f}ms "
                  f"{res['prefill_tokens_computed']:12d} "
                  f"{res['prefill_tokens_cached']:8d}")
        verdict = "yes" if adm["chunked_beats_serial_p99"] else "NO (!)"
        print(f"  chunked admission beats serial on p99 TTFT: {verdict} "
              f"({adm['p99_ttft_improvement_s'] * 1e3:+.0f}ms, "
              f"{adm['prefill_tokens_saved']} prefill tokens saved)")
    if "frontend" in out:
        fr = out["frontend"]
        print("\n# HTTP front end, wall clock (closed loop, async "
              "pipelined engine)")
        print(f"  {fr['n']} requests x {fr['max_tokens']} tokens @ "
              f"concurrency {fr['concurrency']}: "
              f"{fr['req_per_s']:.2f} req/s "
              f"({fr['tokens_per_s']:.1f} tok/s) in {fr['wall_s']:.1f}s")
        ok = "yes" if fr["p99_within_slo"] else "NO (!)"
        print(f"  p50 {fr['p50_latency_s'] * 1e3:.0f}ms  "
              f"p99 {fr['p99_latency_s'] * 1e3:.0f}ms  "
              f"(SLO {fr['slo_s']:.0f}s: {ok})  "
              f"host share {fr['host_share'] * 100:.1f}%")


if __name__ == "__main__":
    import sys
    out = run(quick="--quick" in sys.argv[1:])
    print_table(out)
    save_json(out)
    print(f"\nappended to {BENCH_PATH}")
