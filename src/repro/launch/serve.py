"""Serving launcher: FlowServe instance(s) with ReviveMoE recovery.

Single instance (smoke-size config, the CPU/CI default):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-moe-a2.7b \
      --mode disaggregated --requests 8 --inject-fault moe

The registered config at its published widths, depth cut to N layers
(``--layers``; what ``chip_smoke.py`` serves on one TPU):
  PYTHONPATH=src python -m repro.launch.serve --layers 8 --mode collocated \
      --requests 8 --inject-fault moe

Fleet mode — N instances + K hot spares behind the cluster router, with
restart-vs-revive-vs-spare arbitration and optional full-instance loss:
  PYTHONPATH=src python -m repro.launch.serve --fleet 3 --spares 1 \
      --requests 24 --inject-fault moe --lose-instance 1
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
from typing import Optional

import numpy as np

from repro.paths import REPO_ROOT


def model_config(arch: str, layers: Optional[int] = None):
    """The smoke config when ``layers`` is None (CI, tests); otherwise the
    registered config at its published widths with only the depth cut to
    ``layers``."""
    from repro.configs import get_config, get_smoke_config
    if layers is None:
        return get_smoke_config(arch)
    cfg = dataclasses.replace(get_config(arch), num_layers=layers)
    cfg.validate()
    return cfg


def default_workdir(cfg, seed: int) -> str:
    """Weights directory inside the checkout, keyed by the config (name
    and depth) and the seed, so weights of one config are never restored
    into another."""
    return os.path.join(REPO_ROOT / ".work",
                        f"{cfg.name}-L{cfg.num_layers}-seed{seed}")


def engine_config(cfg, *, mode: str = "collocated", num_dp: int = 2,
                  num_moe: int = 2, decode_impl: Optional[str] = None,
                  overlap: bool = False, workdir: Optional[str] = None,
                  seed: int = 0):
    """The serving engine's configuration, shared by this launcher and
    ``chip_smoke.py``."""
    from repro.serving.engine import EngineConfig
    return EngineConfig(mode=mode, num_dp=num_dp, num_moe=num_moe,
                        max_batch=4, max_seq=128, block_size=16,
                        num_blocks=256, seed=seed,
                        workdir=workdir or default_workdir(cfg, seed),
                        decode_impl=decode_impl, overlap=overlap)


def schedule_fault(eng, component: str, step: int, mode: str,
                   num_dp: int) -> int:
    """Schedule one mid-step L6 HBM-ECC fault on the device ``--inject-fault``
    names: the first MoE rank (disaggregated) or DP rank 1.  Returns the
    physical id."""
    from repro.core.fault_codes import ErrorType, Severity
    pid = (num_dp if component == "moe" and mode == "disaggregated"
           else 1)
    eng.injector.schedule(step, pid, severity=Severity.L6,
                          error_type=ErrorType.HBM_ECC, component=component,
                          mid_step=True)
    return pid


def _run_fleet(args, cfg) -> int:
    from repro.fleet import PoissonTraffic, build_fleet

    ec = engine_config(cfg, mode=args.mode, num_dp=args.num_dp,
                       num_moe=args.num_moe, decode_impl=args.decode_impl,
                       overlap=args.overlap, workdir=args.workdir)
    if args.http is not None:
        # HTTP mode: arrivals come from clients, not a synthetic trace
        fleet = build_fleet(cfg, ec, instances=args.fleet,
                            spares=args.spares,
                            force_policy=args.force_policy,
                            replenish_spares=args.replenish_spares,
                            kv_stream=not args.no_kv_stream)
        from repro.serving.frontend import serve_http
        serve_http(fleet, host=args.http_host, port=args.http)
        return 0
    traffic = PoissonTraffic(args.rate, cfg.vocab_size, prompt_len=12,
                             max_new_tokens=args.max_new, seed=0,
                             limit=args.requests)
    print(f"building fleet: {args.fleet} x [{args.arch} {args.mode} "
          f"{args.num_dp}DP+{args.num_moe if cfg.moe else 0}MoE] + "
          f"{args.spares} spare(s)")
    fleet = build_fleet(cfg, ec, instances=args.fleet,
                        spares=args.spares,
                        force_policy=args.force_policy, traffic=traffic,
                        replenish_spares=args.replenish_spares,
                        kv_stream=not args.no_kv_stream)
    if args.inject_fault:
        pid = schedule_fault(fleet.instances[0].engine, args.inject_fault,
                             args.fault_step, args.mode, args.num_dp)
        print(f"scheduled {args.inject_fault} device fault on instance 0 "
              f"pid {pid} at engine step {args.fault_step}")
    lost = False
    for _ in range(4000):
        fleet.tick()
        if (args.lose_instance is not None and not lost
                and fleet.ticks == 2 * args.fault_step):
            print(f"injecting full loss of instance {args.lose_instance}")
            fleet.lose_instance(args.lose_instance)
            lost = True
        if traffic.exhausted and fleet.requests and not fleet.unfinished:
            break
    done = sum(r.state.value == "finished" for r in fleet.requests)
    ttfts = sorted(fleet.ttfts())
    print(f"\nfinished {done}/{len(fleet.requests)} requests in "
          f"{fleet.ticks} ticks ({fleet.now_s:.2f}s virtual)")
    if ttfts:
        print(f"TTFT p50={ttfts[len(ttfts) // 2] * 1e3:.0f}ms "
              f"max={ttfts[-1] * 1e3:.0f}ms")
    for line in fleet.log:
        print(" ", line)
    return 0 if done == len(fleet.requests) else 1


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-moe-a2.7b")
    ap.add_argument("--mode", default="disaggregated",
                    choices=["collocated", "disaggregated"])
    ap.add_argument("--num-dp", type=int, default=2)
    ap.add_argument("--num-moe", type=int, default=2)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--inject-fault", default=None,
                    choices=[None, "attn", "moe"])
    ap.add_argument("--fault-step", type=int, default=5)
    ap.add_argument("--layers", type=int, default=None, metavar="N",
                    help="serve the registered config at its published "
                    "widths with its depth cut to N layers (default: the "
                    "smoke-size config)")
    ap.add_argument("--workdir", default=None,
                    help="weights directory (default: .work/<config> in "
                    "the checkout)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="run N instances behind the fleet router")
    ap.add_argument("--spares", type=int, default=0, metavar="K",
                    help="pre-warm K hot-spare instances (fleet mode)")
    ap.add_argument("--rate", type=float, default=50.0,
                    help="open-loop Poisson arrival rate (fleet mode)")
    ap.add_argument("--force-policy", default=None,
                    choices=[None, "revive", "restart", "spare"],
                    help="pin the recovery arbiter (fleet mode)")
    ap.add_argument("--lose-instance", type=int, default=None,
                    metavar="IID", help="inject a full-instance loss "
                    "(fleet mode)")
    ap.add_argument("--replenish-spares", action="store_true",
                    help="rebuild consumed standbys in the background "
                    "(fleet mode)")
    ap.add_argument("--decode-impl", default=None,
                    choices=[None, "composed", "megakernel"],
                    help="decode/chunk step implementation (megakernel "
                    "= fused attention+MoE step; default: model config)")
    ap.add_argument("--no-kv-stream", action="store_true",
                    help="force token-replay re-prefill on migration "
                    "(disable KV-block streaming)")
    ap.add_argument("--overlap", action="store_true",
                    help="async pipelined engine: plan step N+1 while "
                    "step N runs on device (token streams stay "
                    "bit-identical to lockstep)")
    ap.add_argument("--http", type=int, default=None, metavar="PORT",
                    help="serve an OpenAI-style HTTP front end "
                    "(/v1/completions with SSE streaming, /health, "
                    "/instances, /control) instead of a synthetic "
                    "request batch; 0 picks a free port")
    ap.add_argument("--http-host", default="127.0.0.1")
    args = ap.parse_args(argv)
    if args.http is not None and args.fleet == 0:
        args.fleet = 1              # the front end drives a FleetRouter

    from repro.serving.engine import InferenceEngine

    cfg = model_config(args.arch, args.layers)
    if args.fleet > 0:
        return _run_fleet(args, cfg)
    ec = engine_config(cfg, mode=args.mode, num_dp=args.num_dp,
                       num_moe=args.num_moe, decode_impl=args.decode_impl,
                       overlap=args.overlap, workdir=args.workdir)
    print(f"building engine: {args.arch} ({args.mode}, "
          f"{args.num_dp} DP + {args.num_moe if cfg.moe else 0} MoE ranks)")
    eng = InferenceEngine(cfg, ec)
    print("init timings:",
          {k: f"{v:.2f}s" for k, v in eng.init_timings.items()})

    rng = np.random.default_rng(0)
    reqs = [eng.submit(list(rng.integers(0, cfg.vocab_size, 12)),
                       args.max_new) for _ in range(args.requests)]

    if args.inject_fault:
        pid = schedule_fault(eng, args.inject_fault, args.fault_step,
                             args.mode, args.num_dp)
        print(f"scheduled {args.inject_fault} fault on device {pid} "
              f"at step {args.fault_step}")

    eng.run(max_steps=500)
    done = sum(r.state.value == "finished" for r in reqs)
    print(f"finished {done}/{len(reqs)} requests in {eng.step_no} steps")
    ttfts = sorted(r.ttft_s for r in reqs if r.ttft_s is not None)
    if ttfts:
        # single-engine mode has no virtual clock: wall TTFT is the metric
        print(f"TTFT p50={ttfts[len(ttfts) // 2] * 1e3:.0f}ms "
              f"max={ttfts[-1] * 1e3:.0f}ms")
    for rep in eng.reports:
        print("RECOVERY:", rep.summary())
        for a in rep.actions:
            print("   -", a)
    return 0 if done == len(reqs) else 1


if __name__ == "__main__":
    sys.exit(main())
