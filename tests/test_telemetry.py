"""Step telemetry (``serving/telemetry.py``): one record per engine step
whose rank phases nest under it and whose self times add up to its wall
time; the same spans on the profiler's host plane; compiles charged to
the step and span that caused them; build and revive stages as spans;
the step programs' named scopes in their HLO metadata."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.fault_codes import ErrorType, Severity
from repro.models.model import Model
from repro.serving import telemetry as T
from repro.serving.engine import (EngineConfig, InferenceEngine,
                                  _chunk_closure, _decode_closure)
from repro.serving.kvcache import max_blocks_per_seq, page_context_specs

RANK_PHASES = (T.PLAN, T.LAUNCH, T.READBACK, T.SAMPLE, T.COMMIT)
PROMPTS = [[5, 9, 2, 7] * 5, [3, 1] * 8, [4, 8, 6] * 4]


def _engine(tmp_path, sub="e", **over):
    cfg = get_smoke_config(over.pop("arch", "qwen2-moe-a2.7b"))
    ec = dict(mode="collocated", num_dp=2, max_batch=2, max_seq=96,
              block_size=8, num_blocks=64, workdir=str(tmp_path / sub),
              precompile_failure_scenarios=False)
    ec.update(over)
    return InferenceEngine(cfg, EngineConfig(**ec))


def _check_record(rec, spans):
    """The step span encloses every other span of the step, each span's
    parent chain ends at it, and the self times sum to the wall time."""
    by_name = {}
    for name, parent, t0, t1 in spans:
        by_name.setdefault(name, []).append((parent, t0, t1))
    [(parent, s0, s1)] = by_name[T.STEP]
    assert parent is None
    assert rec.wall_s == pytest.approx((s1 - s0) * 1e-9)
    for name, parent, t0, t1 in spans:
        if name == T.STEP:
            continue
        assert s0 <= t0 <= t1 <= s1, name
        seen = set()
        while parent != T.STEP:
            assert parent in by_name and parent not in seen, (name, parent)
            seen.add(parent)
            parent = by_name[parent][0][0]
    for name in RANK_PHASES:
        for parent, _, _ in by_name.get(name, []):
            assert parent == T.STEP, (name, parent)
    assert sum(rec.self_s.values()) == pytest.approx(rec.wall_s, rel=0.01)


# -- the span itself -----------------------------------------------------------


def test_span_self_time_parent_and_aggregate():
    tel = T.Telemetry()
    with tel.step(3):
        with tel.span("a", rank=0):
            with tel.span("a.b", rank=0):
                pass
            with tel.span("a.b", rank=0):
                pass
        with tel.span("c"):
            pass
    assert tel.n_steps == 1
    rec = tel.records[-1]
    assert rec.step == 3 and rec.index == 0
    parents = {(n, p) for n, p, _, _ in tel.last_spans}
    assert parents == {("a.b", "a"), ("a", T.STEP), ("c", T.STEP),
                       (T.STEP, None)}
    assert sum(rec.self_s.values()) == pytest.approx(rec.wall_s)
    agg = tel.summary()["spans"]
    assert agg["a.b"]["count"] == 2
    assert agg["a"]["self_s"] <= agg["a"]["total_s"]
    # a span outside any step feeds the aggregate only
    with tel.span("outside"):
        pass
    assert tel.n_steps == 1 and "outside" not in tel.records[-1].self_s
    summ = tel.summary()
    assert summ["spans"]["outside"]["count"] == 1
    # the last step's layout: spans in start order from the step's start
    last = summ["last_step"]
    assert last["step"] == 3 and last["compiles"] == 0
    assert [s[:2] for s in last["spans"]] == [
        [T.STEP, None], ["a", T.STEP], ["a.b", "a"], ["a.b", "a"],
        ["c", T.STEP]]
    assert last["spans"][0][2] == 0.0
    assert last["spans"][0][3] == pytest.approx(last["wall_ms"])
    assert all(0.0 <= s[2] and s[2] + s[3] <= last["wall_ms"] + 1e-6
               for s in last["spans"])
    assert T.Telemetry().summary()["last_step"] is None


def test_span_cost_script_reports_each_way(monkeypatch):
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "scripts", "span_cost.py")
    spec = importlib.util.spec_from_file_location("span_cost", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.measure(200, 2)
    for key in ("span_in_step_us", "span_outside_step_us",
                "trace_annotation_us"):
        assert out[key] > 0.0


def test_stage_adds_its_seconds_to_the_sink():
    tel = T.Telemetry()
    sink = {}
    for _ in range(2):
        with tel.stage("repro.build", sink, "engine"):
            pass
    spans = tel.summary()["spans"]
    assert sink["engine"] == pytest.approx(
        spans["repro.build.engine"]["total_s"])


def test_compiles_are_charged_to_the_innermost_span_and_its_step():
    tel = T.Telemetry()
    fresh = jax.jit(lambda a: a * 3 + 17)
    arg = np.ones((13,), np.float32)
    with tel.step(1):
        with tel.span("outer"):
            with tel.span("inner"):
                jax.block_until_ready(fresh(arg))
    with tel.step(2):
        with tel.span("outer"):
            jax.block_until_ready(fresh(arg))     # cached in memory now
    first, second = list(tel.records)[-2:]
    assert first.compiles >= 1
    assert second.compiles == 0
    spans = tel.summary()["spans"]
    assert spans["inner"]["compiles"] == first.compiles
    assert spans["outer"]["compiles"] == 0
    assert spans[T.STEP]["compiles"] == 0


def test_summary_since_leaves_out_earlier_steps():
    tel = T.Telemetry()
    for i in range(3):
        with tel.step(i):
            with tel.span(T.READBACK):
                pass
    s = tel.summary(since=2)
    assert s["steps"] == 1
    assert 0.0 <= s["host_share"] <= 1.0
    assert T.READBACK in s["phase_ms"]


@pytest.mark.parametrize("window,walked", [(0, (11, 6)), (6, (4, 6))],
                         ids=["full", "sliding"])
def test_paged_attn_counter_sums_walked_and_table_pages(window, walked):
    """A launch counts the pages its rows' valid windows span (the pages
    the paged-attention kernel walks) and the pages its tables hold;
    ``summary()`` sums both over the steps it covers."""
    from types import SimpleNamespace as NS

    from repro.serving.executor import DPExecutor
    from repro.serving.kvcache import build_chunk_context, build_page_context
    from repro.serving.sampling import SamplingParams
    cfg = get_smoke_config("qwen2-moe-a2.7b").with_sliding_window(window)
    tel = T.Telemetry()
    ex = DPExecutor(physical_id=0, dp_rank=0, model=Model(cfg), max_batch=4,
                    max_seq=32, num_blocks=16, block_size=4,
                    sampling=SamplingParams(), telemetry=tel)
    assert ex.max_blk == 8
    tables = {1: NS(blocks=[3, 4, 5]), 2: NS(blocks=list(range(7, 15)))}
    dec = build_page_context(
        [NS(req_id=1, batch_slot=1, num_tokens=10),
         NS(req_id=2, batch_slot=3, num_tokens=32)], tables, max_batch=4,
        max_blk=8, block_size=4, trash_block=16)
    _, chunk = build_chunk_context(
        [NS(req=NS(req_id=2), start=5, length=3, tokens=list(range(32)))],
        tables, width=4, max_blk=8, block_size=4, trash_block=16)
    assert list(dec["seq_lens"]) == [0, 10, 0, 32]
    assert list(chunk["seq_lens"]) == [6, 7, 8, 0]
    for page, n in zip((dec, chunk), walked):
        # the pages holding any position of [seq_len - window, seq_len)
        spans = sum(len({pos // 4 for pos in range(
            max(sl - window, 0) if window else 0, sl)})
            for sl in page["seq_lens"])
        assert spans == n
    with tel.step(0):
        ex._count_pages(dec)
    with tel.step(1):
        ex._count_pages(chunk)
    ex._count_pages(chunk)              # outside a step: not counted
    assert tel.summary()["paged_attn"] == {
        "pages_walked": sum(walked), "pages_in_tables": 64}
    assert tel.summary(since=1)["paged_attn"] == {
        "pages_walked": walked[1], "pages_in_tables": 32}


# -- in the engine -------------------------------------------------------------


@pytest.mark.parametrize("overlap", [False, True],
                         ids=["lockstep", "overlap"])
def test_each_step_gives_one_record_with_nested_phases(tmp_path, overlap):
    eng = _engine(tmp_path, overlap=overlap, dtype="float32")
    tel = eng.telemetry
    reqs = [eng.submit(list(p), 8) for p in PROMPTS]
    n0 = tel.n_steps
    names = set()
    for i in range(10):
        eng.step()
        assert tel.n_steps == n0 + i + 1
        rec = tel.records[-1]
        assert rec.step == eng.step_no
        _check_record(rec, tel.last_spans)
        names |= set(rec.self_s)
    assert set(RANK_PHASES) | {T.STEP, T.CONTROL, T.HEALTH,
                               T.UNDO_CAPTURE} <= names
    assert {T.LAUNCH_CHUNK, T.LAUNCH_DECODE} <= names
    eng.run(max_steps=200)
    assert all(r.state.value == "finished" for r in reqs)
    summ = tel.summary()
    assert 0.0 < summ["host_share"] < 1.0
    assert summ["steps"] == tel.n_steps
    pa = summ["paged_attn"]
    assert 0 < pa["pages_walked"] < pa["pages_in_tables"]


def test_spans_land_on_the_profiler_host_plane_in_order(tmp_path):
    eng = _engine(tmp_path)
    for p in PROMPTS:
        eng.submit(list(p), 16)
    for _ in range(3):                  # compiles before the trace
        eng.step()
    d = str(tmp_path / "trace")
    with jax.profiler.trace(d):
        eng.step()
        eng.step()
    [path] = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    events.append((e.name, dict(e.stats), e.start_ns,
                                   e.start_ns + e.duration_ns))
    steps = sorted((ev for ev in events if ev[0] == T.STEP),
                   key=lambda ev: ev[2])
    assert [ev[1]["step"] for ev in steps] == [eng.step_no - 1, eng.step_no]
    recs = list(eng.telemetry.records)[-2:]
    for (_, _, s0, s1), rec in zip(steps, recs):
        inside = [ev for ev in events if ev[0] != T.STEP
                  and s0 <= ev[2] and ev[3] <= s1]
        assert {ev[0] for ev in inside} | {T.STEP} == set(rec.self_s)
        for rank in (0, 1):
            mine = sorted((ev for ev in inside
                           if ev[1].get("rank") == rank
                           and ev[0] in RANK_PHASES), key=lambda ev: ev[2])
            order = [ev[0] for ev in mine]
            assert order[0] == T.PLAN and order[-1] == T.COMMIT, order
            assert order.index(T.LAUNCH) < order.index(T.READBACK) \
                < order.index(T.SAMPLE), order
        for name, _, c0, c1 in inside:
            if name == T.UNDO_CAPTURE:
                assert any(p == T.PLAN and p0 <= c0 and c1 <= p1
                           for p, _, p0, p1 in inside)


def test_step_onto_a_new_shape_has_its_compiles(tmp_path):
    # a pool size no other test uses, so every eager program of this
    # engine compiles here first
    eng = _engine(tmp_path, num_dp=1, num_blocks=45)
    tel = eng.telemetry
    first = eng.submit([5, 9, 2, 7] * 4, 24)
    while first.prefill_pos < 16 or len(first.output_tokens) < 3:
        eng.step()
    eng.step()
    before = tel.records[-1]
    assert before.compiles == 0

    def by_span():
        return {k: a["compiles"] for k, a in tel.summary()["spans"].items()}

    # a chunk beside the running decode: the undo capture gathers the
    # rows of both launches, a manifest length not seen yet
    eng.submit([3, 1, 4] * 4, 4)
    was = by_span()
    eng.step()
    at = tel.records[-1]
    assert at.compiles > 0
    added = {k: n - was.get(k, 0) for k, n in by_span().items()
             if n > was.get(k, 0)}
    assert T.UNDO_CAPTURE in added
    assert at.compiles == sum(added.values())
    eng.step()
    assert tel.records[-1].compiles == 0


def test_build_and_revive_stages_are_spans_and_registration_is_once(
        tmp_path):
    eng = _engine(tmp_path)
    tel = eng.telemetry
    assert sum(t is tel for t in T.registered()) == 1
    spans = tel.summary()["spans"]
    for key, secs in eng.init_timings.items():
        if key in ("read_cache", "compile"):
            continue                     # the CompileTiming's split
        assert spans[f"{T.BUILD}.{key}"]["total_s"] == pytest.approx(secs)
    for p in PROMPTS:
        eng.submit(list(p), 12)
    eng.injector.schedule(3, 1, severity=Severity.L6,
                          error_type=ErrorType.HBM_ECC, component="moe",
                          mid_step=True)
    eng.run(max_steps=200)
    [rep] = eng.reports
    spans = tel.summary()["spans"]
    assert spans[T.REVIVE]["count"] == 1
    assert rep.total_s <= spans[T.REVIVE]["total_s"] * 1.01
    for key in ("xccl", "distributed_groups"):
        assert spans[f"{T.REVIVE}.{key}"]["total_s"] == pytest.approx(
            rep.timings[key])
    # a restart rebuilds into the same telemetry, registered once
    eng.full_reinit()
    assert eng.telemetry is tel
    assert sum(t is tel for t in T.registered()) == 1
    assert tel.summary()["spans"][T.BUILD]["count"] == 2


def test_instances_serve_the_telemetry_and_graph_cache(tmp_path):
    from repro.fleet.builder import build_fleet
    from repro.serving.frontend import ServingFrontend
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    router = build_fleet(cfg, EngineConfig(
        mode="collocated", num_dp=1, max_batch=2, max_seq=64,
        block_size=8, num_blocks=32, workdir=str(tmp_path / "f"),
        precompile_failure_scenarios=False), instances=1)
    eng = next(iter(router.instances.values())).engine
    eng.submit(list(PROMPTS[0]), 4)
    for _ in range(3):
        eng.step()
    [row] = ServingFrontend(router)._instances()["instances"]
    last = row["telemetry"]["last_step"]
    assert last["step"] == eng.step_no
    names = [s[0] for s in last["spans"]]
    assert names[0] == T.STEP and {T.PLAN, T.READBACK} <= set(names)
    gc_ = row["graph_cache"]
    assert gc_["compiles"] == len(eng.graph_cache.timings)
    assert gc_["hits"] == eng.graph_cache.hits > 0


# -- named scopes in the step programs -------------------------------------------

ATTN = {"embed", "attn.qkv", "attn.kv_write", "attn.paged", "attn.out",
        "lm_head"}
MOE = {"moe.router", "moe.experts", "moe.shared"}
SCOPE_CASES = [
    ("qwen2-moe-a2.7b", "composed", "decode", ATTN | MOE),
    ("qwen2-moe-a2.7b", "composed", "chunk", ATTN | MOE),
    ("internlm2-20b", "composed", "decode", ATTN | {"ffn"}),
    ("internlm2-20b", "composed", "chunk", ATTN | {"ffn"}),
    ("qwen2-moe-a2.7b", "megakernel", "decode",
     {"embed", "attn.qkv", "attn.kv_write", "megastep", "lm_head"}),
]


@pytest.mark.parametrize(
    "arch,impl,phase,want", SCOPE_CASES,
    ids=[f"{a.split('-')[0]}-{i}-{p}" for a, i, p, _ in SCOPE_CASES])
def test_step_program_hlo_carries_the_scopes(arch, impl, phase, want):
    import dataclasses
    cfg = dataclasses.replace(get_smoke_config(arch), decode_impl=impl)
    model = Model(cfg, dtype=jnp.float32)
    width = 4 if phase == "decode" else 8

    def specs(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                           jnp.result_type(x)), tree)

    args = (model.param_specs(),
            jax.eval_shape(lambda: model.init_paged_cache(4, 24, 8)),
            jax.ShapeDtypeStruct((width,), jnp.int32),
            page_context_specs(width, max_blocks_per_seq(64, 8)),
            specs(model.default_runtime()))
    closure = _decode_closure if phase == "decode" else _chunk_closure
    hlo = jax.jit(closure(model, 0)).lower(*args).compile().as_text()
    op_names = re.findall(r'op_name="([^"]+)"', hlo)
    assert op_names and all(n.startswith(f"jit({phase}_v0)")
                            for n in op_names if n.startswith("jit("))
    scopes = {part for n in op_names for part in n.split("/")}
    assert want <= scopes, want - scopes
