"""The run loop: one cell, one seed, one process.

Set-up builds the serving engine of the cell's configuration with the
benchmark's weights, then serves the cell's traffic from ``-warmup_s``
so that every program the window drives has compiled and the queue is in
its steady state when the window opens at 0.  The window drives the
engine's own API (``submit`` and ``step``, lockstep as the program
defaults) for ``seconds``; after each ``step()`` the loop reads the
growth of every request's committed output on the host clock.  The run
ends at the window's end with no drain.  Then the memory peak is read,
the program's state is freed, and the plain reference checks a sample
of the served requests (``bench.check``).

Metrics are found by name: ``bench/metrics/<name>.py`` has ``read(run)``,
which returns a number or None (nothing to read: the metric is left out).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from functools import cached_property
from typing import Dict, List, Optional

import numpy as np

from bench import flops as FL
from bench.model import (BENCH, Shape, load_config, load_json,
                         reference_module)
from bench.spans import Spans
from bench.traffic.source import Arrival, load_mix, make_source

ROOT = BENCH.parent
TRACE_AT = 0.2              # traced window opens at this share of the run
TRACE_S = 4.0               # ... and lasts this long, or ends 1 s before
                            # a fault


class NoDevice(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def load_benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer
    metrics (a metric without ``workloads`` goes to every cell that
    reports the end-to-end metric it moves)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def compile_cache() -> None:
    """JAX's persistent compilation cache where the program keeps it:
    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it), else
    ``.jax_cache/`` at the root of the checkout, so the benchmark's own
    programs (weights, reference) are cached beside the program's from
    the first compile of the run on."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices(platform: str, chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} {platform} device(s); "
                       f"JAX found {len(devs)} {devs[0].platform}")
    return devs[:chips]


# -- records ------------------------------------------------------------------

@dataclass
class ReqRec:
    arrival: Arrival
    req: object                      # the program's Request
    submit_t: float
    n_prompt: int
    left_waiting_t: Optional[float] = None
    token_t: List[float] = field(default_factory=list)
    done_t: Optional[float] = None
    prefill_seen: int = 0


@dataclass
class StepRec:
    t0: float
    t1: float
    requests_served: int = 0             # requests given a token
    tokens: int = 0                      # output tokens
    prefill_tokens: int = 0              # prompt positions computed
    flops: int = 0
    # (dp rank, "decode" | "chunk") -> that call's rows, one list of
    # valid lengths per sequence
    attn_rows: Dict[tuple, List[List[int]]] = field(default_factory=dict)
    traced: bool = False


@dataclass
class Run:
    """What a metric reader sees."""
    cell: dict
    cfg: dict
    mix: dict
    shape: Shape
    seconds: float
    peaks: dict
    slots: int
    setup_s: float = 0.0
    init_timings: Dict[str, float] = field(default_factory=dict)
    reqs: List[ReqRec] = field(default_factory=list)
    steps: List[StepRec] = field(default_factory=list)
    fault_step: Optional[StepRec] = None
    revive_s: Optional[float] = None           # RecoveryReport.total_s
    trace: Optional[object] = None             # bench.trace.Summary

    @cached_property
    def arch(self):
        """The configuration's architecture module."""
        return reference_module(self.cfg["reference"])

    def in_window(self, t: float) -> bool:
        return 0.0 <= t < self.seconds

    def window_steps(self) -> List[StepRec]:
        return [s for s in self.steps
                if s.t0 >= 0.0 and s.t1 <= self.seconds]


# -- the run ----------------------------------------------------------------------

def _patch_init(params):
    """Hand the benchmark's weights to the engine in place of its own
    random init (``serving.engine._jitted_init``); returns the undo."""
    import repro.serving.engine as E
    if not hasattr(E, "_jitted_init"):
        raise RuntimeError("serving.engine has no _jitted_init to hand the "
                           "benchmark's weights through")
    orig = E._jitted_init
    E._jitted_init = lambda cfg, dtype: (lambda key: params)
    return lambda: setattr(E, "_jitted_init", orig)


class _Compiles:
    """Compile requests and fresh backend compiles, from JAX's events."""

    def __init__(self):
        import jax
        self.requests = self.backend = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.backend += 1


def _observe(run: Run, live: List[ReqRec], step: StepRec, now: float,
             source) -> None:
    """Book one step: new tokens, queue exits, prefill progress and the
    work the step did, from each live request's host state."""
    from repro.serving.request import RequestState
    arch, s = run.arch, run.shape
    for r in list(live):
        req = r.req
        if r.left_waiting_t is None and req.state is not RequestState.WAITING:
            r.left_waiting_t = now
        out = len(req.committed_output)
        new = out - len(r.token_t)
        p0, p1 = r.prefill_seen, req.prefill_pos
        if p1 < p0:                      # re-prefill after a migration
            p0 = 0
        r.prefill_seen = p1
        known = r.n_prompt + len(r.token_t)   # tokens before this step
        from_prefill = p1 > p0 and new > 0
        if p1 > p0:
            if step.traced:
                step.attn_rows.setdefault((req.dp_rank, "chunk"), []).append(
                    list(range(p0 + 1, p1 + 1)))
            step.flops += FL.prefill_flops(arch, s, p0, p1)
            step.prefill_tokens += p1 - p0
            if from_prefill:         # the last row's logits chose a token
                step.flops += 2 * s.d_model * s.vocab
        if new > 0:
            step.requests_served += 1
            step.tokens += new
            k0 = 1 if from_prefill else 0
            ctxs = [known + k0 + j for j in range(new - k0)]
            if step.traced and ctxs:
                step.attn_rows.setdefault((req.dp_rank, "decode"),
                                          []).append(ctxs)
            for ctx in ctxs:
                step.flops += FL.token_flops(arch, s, ctx, True)
            r.token_t.extend([now] * new)
        if req.state in (RequestState.FINISHED, RequestState.FAILED):
            r.done_t = now
            live.remove(r)
            source.finished(r.arrival, now)


def serve(run: Run, eng, source, spans: Spans, t_process: float,
          trace_dir: Optional[str], compiles: _Compiles) -> dict:
    """Warm-up and window; returns counters of the window."""
    import jax
    from repro.core.fault_codes import ErrorType, Severity
    fault = run.mix.get("fault")
    fault_at = fault["at"] * run.seconds if fault else None
    trace_at = trace_end = None
    if trace_dir is not None:
        trace_at = TRACE_AT * run.seconds
        trace_end = trace_at + TRACE_S
        if fault_at is not None:
            trace_end = min(trace_end, fault_at - 1.0)
    tracing = traced_done = False
    fault_scheduled = False
    live: List[ReqRec] = []
    clock = time.perf_counter
    origin = clock() + source.warmup_s
    win = {}
    n_reports = len(eng.reports)

    def now():
        return clock() - origin

    while True:
        t = now()
        if t >= run.seconds:
            break
        if "open" not in win and t >= 0.0:
            run.setup_s = origin - t_process
            win["open"] = True
            # name on standard error whatever compiles inside the window
            jax.config.update("jax_log_compiles", True)
            win["graph_timings"] = len(eng.graph_cache.timings)
            win["compile_requests"] = compiles.requests
            win["backend_compiles"] = compiles.backend
        if trace_dir is not None and not tracing and not traced_done \
                and t >= trace_at:
            # the profiler's start and stop are taken off the run's clock
            t_pause = clock()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            spans.tracing = tracing = True
            traced_ann = jax.profiler.TraceAnnotation("bench.traced")
            traced_ann.__enter__()
            origin += clock() - t_pause
            continue
        if tracing and t >= trace_end:
            t_pause = clock()
            traced_ann.__exit__(None, None, None)
            spans.tracing = tracing = False
            jax.profiler.stop_trace()
            traced_done = True
            win["profiler_s"] = clock() - t_pause
            origin += clock() - t_pause
            continue
        due = source.due(t)
        if due:
            with spans.span("submit"):
                for a in due:
                    req = eng.submit(a.prompt, a.max_new)
                    rec = ReqRec(a, req, now(), len(a.prompt))
                    run.reqs.append(rec)
                    live.append(rec)
        if eng.unfinished:
            step = StepRec(0.0, 0.0, traced=tracing)
            if fault and not fault_scheduled and t >= fault_at and any(
                    ex.physical_id == fault["physical_id"]
                    and ex.scheduler.num_requests
                    for ex in eng.dp_executors):
                # a mid-step fault fires in a step its device takes part in
                eng.injector.schedule(
                    eng.step_no + 1, fault["physical_id"],
                    severity=Severity[fault["severity"]],
                    error_type=ErrorType[fault["error"]],
                    component=fault["component"],
                    mid_step=fault["mid_step"])
                fault_scheduled = True
                run.fault_step = step
            with spans.span("engine.step"):
                step.t0 = now()
                eng.step()
                step.t1 = now()
            with spans.span("book"):
                _observe(run, live, step, step.t1, source)
            run.steps.append(step)
            if len(eng.reports) > n_reports:
                run.revive_s = sum(r.total_s for r in eng.reports[n_reports:])
                for rep in eng.reports[n_reports:]:
                    print(f"revive: {rep.scenario} migrated {rep.migrated} "
                          f"compile_source {rep.compile_source} timings "
                          f"{rep.timings}", file=sys.stderr, flush=True)
                n_reports = len(eng.reports)
        else:
            nxt = source.next_at()
            until = run.seconds if nxt is None else min(nxt, run.seconds)
            if trace_dir is not None and not traced_done:
                until = min(until, trace_end if tracing else trace_at)
            with spans.span("wait"):
                time.sleep(max(0.0, min(until - now(), 0.05)))
    jax.config.update("jax_log_compiles", False)
    if tracing:
        traced_ann.__exit__(None, None, None)
        spans.tracing = False
        jax.profiler.stop_trace()
    win["graph_misses"] = sum(
        t.source != "precompiled"
        for t in eng.graph_cache.timings[win.get("graph_timings", 0):])
    win["compile_requests"] = compiles.requests - win.get(
        "compile_requests", 0)
    win["backend_compiles"] = compiles.backend - win.get(
        "backend_compiles", 0)
    win["late_s"] = max((r.submit_t - r.arrival.at for r in run.reqs
                         if run.in_window(r.arrival.at)), default=0.0)
    return win


def sample(run: Run, seed: int, target_tokens: int = 256,
           most: int = 4) -> list:
    """Requests for the reference check, drawn from the seed, in two
    phases.  ``before``: requests that finished before the step in which
    the fault fired (every finished request where the mix has no fault).
    ``after``: requests that left the waiting queue only in a step after
    that one, so that all their work ran under the revived expert set:
    those that finished, and those still in flight at the close on the
    tokens committed so far (greedy tokens, final once committed), so
    that the revived runtime is checked on as many served tokens as the
    window gives it.  In each phase the longest
    comes first, then others drawn from the seed until ``target_tokens``
    served tokens or ``most`` requests.  A request in flight across the
    fault is not sampled: its tokens were served under two expert
    sets."""
    from repro.serving.request import RequestState
    fs = run.fault_step
    phase = {}
    for r in run.reqs:
        n_out = len(r.req.committed_output)
        if not r.token_t or n_out == 0 or r.req.state is RequestState.FAILED:
            continue
        done = r.req.state is RequestState.FINISHED
        if done and (fs is None or (r.done_t is not None
                                    and r.done_t <= fs.t0)):
            phase[id(r)] = "before"
        elif (fs is not None and r.left_waiting_t is not None
              and r.left_waiting_t > fs.t1):
            phase[id(r)] = "after"
    rng = np.random.default_rng([seed, 2])
    picked = []
    for ph in ("before", "after"):
        cands = [r for r in run.reqs if phase.get(id(r)) == ph]
        if not cands:
            continue
        first = max(cands, key=lambda r: (
            r.n_prompt + len(r.req.committed_output), r.submit_t))
        mine = [first]
        rest = [r for r in cands if r is not first]
        for i in rng.permutation(len(rest)):
            if (len(mine) >= most or sum(len(r.req.committed_output)
                                         for r in mine) >= target_tokens):
                break
            mine.append(rest[int(i)])
        picked += [(r, ph) for r in mine]
    return picked


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             bench: Optional[dict] = None, cfg: Optional[dict] = None,
             mix: Optional[dict] = None, platform: str = "tpu",
             t_process: Optional[float] = None,
             control: bool = False) -> dict:
    """One run; returns the result line's object.  ``cfg``/``mix`` stand
    in for the files (tests); ``platform`` is what the cell must run on.
    With ``control`` the float8 control is read on the same sample too
    (``bench/control.py``; the benchmark's own runs never do)."""
    t_process = time.perf_counter() if t_process is None else t_process
    bench = bench or load_benchmark()
    cell = cell_of(bench, cell_name)
    devs = devices(platform, cell["chips"])
    compile_cache()
    import jax
    from repro.models.model import Model
    from repro.serving.engine import InferenceEngine
    from bench import check
    from bench import model as M
    from bench import weights as W

    cfg = cfg or load_config(cell["config"])
    mix = mix or load_mix(cell["traffic"])
    arch = reference_module(cfg["reference"])
    shp = arch.shape(cfg)
    peaks = load_json(BENCH / "peaks.json")["devices"]
    kind = devs[0].device_kind
    if platform == "tpu" and kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    dep = cfg["deployment"]
    run = Run(cell, cfg, mix, shp, float(seconds), peaks.get(kind, {}),
              dep["num_dp"] * dep["max_batch"])
    spans = Spans()
    compiles = _Compiles()
    tmp = tempfile.mkdtemp(prefix="bench-run-")
    phases = {"start": time.perf_counter() - t_process}
    try:
        pcfg = M.program_config(cfg, arch, shp)
        ecfg = M.engine_config(cfg, seed, os.path.join(tmp, "work"))
        model = Model(pcfg, dtype=jax.numpy.dtype(ecfg.dtype))
        params = W.program_params(seed, arch, shp, model.vpad,
                                  jax.numpy.dtype(ecfg.dtype))
        W.check_layout(params, model.param_specs())
        jax.block_until_ready(params)
        phases["weights"] = time.perf_counter() - t_process
        undo = _patch_init(params)
        try:
            eng = InferenceEngine(pcfg, ecfg)
        finally:
            undo()
            del params
        phases["engine"] = time.perf_counter() - t_process
        run.init_timings = dict(eng.init_timings)
        source = make_source(mix, seed, shp.vocab, seconds)
        trace_dir = os.path.join(tmp, "trace") if trace else None
        win = serve(run, eng, source, spans, t_process, trace_dir,
                    compiles)
        stats = devs[0].memory_stats() or {}
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
        attempted = [r for r in run.reqs if run.in_window(r.arrival.at)]
        from repro.serving.request import RequestState
        failed = sum(r.req.state is RequestState.FAILED for r in attempted)
        picked = sample(run, seed)
        lost = (arch.experts_lost_with(shp, mix["fault"]["physical_id"])
                if mix.get("fault") else [])
        seqs = []
        for r, ph in picked:
            live = None
            if ph == "after" and lost:
                live = np.ones((shp.experts,), bool)
                live[lost] = False
            seqs.append((r.req.prompt_tokens
                         + list(r.req.committed_output),
                         r.n_prompt, live, ph))
        del eng, picked
        gc.collect()
        print(f"window: graph-cache misses {win['graph_misses']}, compile "
              f"requests {win['compile_requests']}, backend compiles "
              f"{win['backend_compiles']}, generator late by at most "
              f"{win['late_s']!r} s", file=sys.stderr, flush=True)
        result_trace = None
        if trace_dir is not None:
            from bench import trace as TR
            files = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
                     for f in fs if f.endswith(".xplane.pb")]
            if len(files) != 1:
                raise RuntimeError(f"expected one trace file, got {files}")
            run.trace = TR.summarize(TR.load(files[0]))
            result_trace = run.trace
        # the reference check
        t_ref = time.perf_counter()
        sq = [check.Served(tok, n, live) for tok, n, live, _ in seqs]
        gaps = check.served_gaps(seed, arch, shp, sq,
                                 jax.numpy.dtype(dep["dtype"]),
                                 dep["max_seq"]) if sq else []
        ref_s = time.perf_counter() - t_ref
        faulted = bool(mix.get("fault"))
        checks = _checks(cfg, seqs, gaps, faulted)
        correct = all(c["ok"] for c in checks.values())
        ctrl = None
        if control:
            # the control goes through the same comparison, limits and all
            cg = check.control_gaps(seed, arch, shp, sq,
                                    jax.numpy.dtype(dep["dtype"]),
                                    dep["max_seq"]) if sq else []
            cc = _checks(cfg, seqs, cg, faulted)
            ctrl = {"correct": all(c["ok"] for c in cc.values()),
                    "checks": {k: {"value": c["value"], "limit": c["limit"]}
                               for k, c in cc.items()}}
        metrics = {}
        for m in metrics_of(bench, cell_name, trace):
            v = reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device = {"platform": devs[0].platform, "kind": kind,
                  "count": len(devs), "memory_peak_bytes": int(peak)}
        out = {"correct": bool(correct), "attempted": len(attempted),
               "failed": int(failed), "metrics": metrics, "device": device}
        if result_trace is not None:
            device["busy_s"] = result_trace.busy_s
            device["window_s"] = result_trace.window_s
            ops = sorted(result_trace.op_seconds.items(),
                         key=lambda kv: -kv[1])[:10]
            out["breakdown"] = {"device_ops": [[k, v] for k, v in ops],
                                "idle_gaps": [[k, v] for k, v in
                                              result_trace.gaps[:10]]}
        if control:
            out["control"] = ctrl
        out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                         for k, c in checks.items()}
        print(f"run: setup_s {run.setup_s!r} (after process start: "
              f"{phases}), profiler {win.get('profiler_s')!r} s off the "
              f"clock", file=sys.stderr, flush=True)
        print(f"run: init {run.init_timings}, "
              f"revive {run.revive_s!r} s, reference check {ref_s!r} s "
              f"over {len(sq)} requests, "
              f"memory_stats {stats.get('bytes_limit')} limit",
              file=sys.stderr, flush=True)
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


PHASES = ("before", "after")


def _checks(cfg: dict, seqs: list, gaps: list, faulted: bool) -> dict:
    """Each number the configuration's ``check`` names, with its limit
    and whether it holds: ``max_logit_gap`` (the widest gap over the
    served tokens checked), ``mean_logit_gap`` (their mean) and
    ``min_tokens`` (how many were checked, reported as
    ``tokens_checked``), over every sampled request, or with a suffix
    ``.before`` / ``.after`` over the requests of that phase alone
    (:func:`sample`).  A run whose mix has no fault has no ``after``
    phase, and its ``.after`` numbers are not compared."""
    by = {None: [g for g in gaps]}
    for ph in PHASES:
        by[ph] = [g for g, (*_, p) in zip(gaps, seqs) if p == ph]
    out = {}
    for key, limit in cfg["check"].items():
        stat, _, ph = key.partition(".")
        ph = ph or None
        if ph is not None and ph not in PHASES:
            raise KeyError(f"check {key!r}: no phase {ph!r}")
        if ph == "after" and not faulted:
            continue
        g = np.concatenate(by[ph]) if by[ph] else np.zeros(0)
        if stat == "min_tokens":
            name = "tokens_checked" + key[len(stat):]
            out[name] = {"value": int(len(g)), "limit": limit,
                         "ok": len(g) >= limit}
            continue
        if stat == "max_logit_gap":
            v = float(g.max()) if len(g) else float("inf")
        elif stat == "mean_logit_gap":
            v = float(g.mean()) if len(g) else float("inf")
        else:
            raise KeyError(f"check {key!r}: no number {stat!r}")
        out[key] = {"value": v, "limit": limit, "ok": v <= limit}
    return out


def main(argv=None, t_process: Optional[float] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_process=t_process)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
