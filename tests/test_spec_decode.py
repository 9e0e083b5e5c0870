"""Multi-token self-speculative decode (PR 8 tentpole).

The speculation window rides the compiled chunk graph (a chunked-
prefill step already IS a fixed-width decode over per-token page
contexts), the n-gram proposer self-drafts from the sequence, and
``spec_verify`` accepts a draft only when it equals the seeded
sampler's output at that position — so the emitted stream must be
token-identical to plain decode, the rejected rows' pool writes must
roll back bit-exact (§3.3 row-level undo), and faults mid-window must
replay to the plain path's stream with zero fresh compiles.
"""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.serving.engine import EngineConfig, InferenceEngine, _Ctx
from repro.serving.sampling import SamplingParams, sample, spec_verify
from repro.serving.scheduler import ngram_propose

# repetitive traces: the n-gram proposer drafts from recurrence, so
# these prompts make speculation windows (and acceptances) happen
PAT_A = [5, 9, 2, 7]
PAT_B = [3, 1]


def _prompts():
    return [PAT_A * 5, PAT_B * 8]


def _engine(tmp_path, sub, *, spec_window=0, temperature=0.0,
            num_dp=1, decode_impl=None, **over):
    cfg = get_smoke_config(over.pop("arch", "qwen2-moe-a2.7b"))
    cfg_fn = over.pop("cfg_fn", None)
    if cfg_fn:
        cfg = cfg_fn(cfg)
    # speculation (chunk graph) vs plain decode is compared token for
    # token: float32 weights
    over.setdefault("dtype", "float32")
    ec = EngineConfig(mode="collocated", num_dp=num_dp, max_batch=2,
                      max_seq=over.pop("max_seq", 96), block_size=8,
                      num_blocks=64, workdir=str(tmp_path / sub),
                      decode_impl=decode_impl, spec_window=spec_window,
                      sampling=SamplingParams(temperature=temperature,
                                              top_p=0.9, seed=3), **over)
    return cfg, InferenceEngine(cfg, ec)


def _serve(eng, prompts, max_new=24):
    reqs = [eng.submit(list(p), max_new) for p in prompts]
    eng.run(max_steps=400)
    assert all(r.state.value == "finished" for r in reqs), \
        [r.state for r in reqs]
    return [list(r.output_tokens) for r in reqs]


# -- unit: proposer + deterministic accept/reject ---------------------------


def test_ngram_propose():
    # final bigram (2, 7) last recurred at index 2: propose what followed
    toks = [5, 9, 2, 7, 5, 9, 2, 7]
    assert ngram_propose(toks, 3) == (5, 9, 2)
    assert ngram_propose(toks, 1) == (5,)
    # no recurrence / too short / no budget -> no drafts
    assert ngram_propose([1, 2, 3, 4, 5], 3) == ()
    assert ngram_propose([1, 2], 3) == ()
    assert ngram_propose(toks, 0) == ()
    # most recent occurrence wins
    assert ngram_propose([1, 2, 9, 1, 2, 8, 1, 2], 2) == (8, 1)


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_spec_verify_matches_sequential_sampling(temperature):
    """Every emitted token equals what the seeded sampler produces at
    that sequence position, the accepted prefix equals the drafts, and
    emission stops exactly at the first mismatch."""
    rng = np.random.default_rng(0)
    params = SamplingParams(temperature=temperature, top_p=0.9, seed=7)
    g, V, base = 5, 64, 40
    logits = rng.normal(size=(g, V)) * 3.0
    targets = [int(sample(logits[r][None], params, step=base + r)[0])
               for r in range(g)]
    # drafts agreeing for 2 rows then diverging
    drafts = [targets[0], targets[1], (targets[2] + 1) % V, 0]
    toks, accepted = spec_verify(logits, drafts, params, start_step=base)
    assert accepted == 2
    assert list(toks) == targets[:3]
    # fully accepted window: all g - 1 drafts match -> g tokens emitted
    toks, accepted = spec_verify(logits, targets[:g - 1], params,
                                 start_step=base)
    assert accepted == g - 1
    assert list(toks) == targets
    # immediate mismatch -> plain-decode behaviour (1 token)
    toks, accepted = spec_verify(logits, [(targets[0] + 1) % V], params,
                                 start_step=base)
    assert accepted == 0
    assert list(toks) == targets[:1]


# -- engine: token-exactness vs greedy non-speculative ----------------------


def _windowed(cfg):
    return dataclasses.replace(cfg, sliding_window=6)


SPEC_ARCHS = [
    ("qwen2-moe-a2.7b", None),       # GQA + MoE + shared experts
    ("deepseek-v3", None),           # MLA + MoE + first-k-dense
    ("qwen2-moe-a2.7b", _windowed),  # GQA + sliding window
]


@pytest.mark.parametrize("arch,cfg_fn", SPEC_ARCHS,
                         ids=["gqa_moe", "mla_moe", "windowed"])
def test_spec_token_exact_vs_greedy(tmp_path, arch, cfg_fn):
    _, base = _engine(tmp_path, "base", arch=arch, cfg_fn=cfg_fn)
    want = _serve(base, _prompts())
    _, eng = _engine(tmp_path, "spec", arch=arch, cfg_fn=cfg_fn,
                     spec_window=6)
    got = _serve(eng, _prompts())
    assert got == want
    stats = eng.prefill_stats()
    assert stats["spec_windows"] > 0          # speculation actually ran
    assert stats["spec_emitted"] >= stats["spec_windows"]
    hist = eng.spec_histogram()
    assert sum(hist.values()) == stats["spec_windows"]
    assert all(2 <= g <= 6 for g in hist)


def test_spec_token_exact_megakernel(tmp_path):
    """Speculation through the fused megakernel chunk path emits the
    same stream as plain composed decode."""
    _, base = _engine(tmp_path, "base")
    want = _serve(base, _prompts())
    _, eng = _engine(tmp_path, "mega_spec", decode_impl="megakernel",
                     spec_window=6)
    got = _serve(eng, _prompts())
    assert got == want
    assert eng.prefill_stats()["spec_windows"] > 0


# -- rejected-window pool-row rollback --------------------------------------


def test_spec_rejected_rows_rollback_bitexact(tmp_path):
    """Rows written for rejected drafts are restored bit-exact from the
    plan-time write-set capture; the committed row 0 write stands."""
    from repro.serving.cache_ops import capture_pool_rows
    _, eng = _engine(tmp_path, "rb", spec_window=6)
    req = eng.submit(PAT_A * 5, 24)
    ex = eng.dp_executors[0]
    ctx = _Ctx(eng)
    checked = False
    for step in range(60):
        if req.state.value == "finished":
            break
        plan = ex.plan()
        win = next((w for w in plan.spec if w.req is req), None)
        pre = None
        if win is not None:
            bs = ex.block_size
            table = ex.scheduler.block_tables[req.req_id].blocks
            pos = range(win.start, win.start + win.length)
            bids = np.asarray([table[p // bs] for p in pos], np.int32)
            offs = np.asarray([p % bs for p in pos], np.int32)
            pre = capture_pool_rows(ex.cache, ex.paged_axes, bids, offs)
            pre_rows = [None if r is None else np.asarray(r)
                        for r in pre["rows"]]
        n_before = req.num_tokens
        ex.compute(ctx, step)
        ex.commit()
        if win is None:
            continue
        emitted = req.num_tokens - n_before
        assert emitted >= 1
        post = capture_pool_rows(ex.cache, ex.paged_axes, bids, offs)
        changed_row0 = False
        for a, b, ax in zip(pre_rows, post["rows"], ex.paged_axes):
            if ax is not None:
                continue
            b = np.asarray(b)
            # rejected rows: bit-identical to the pre-step pool
            np.testing.assert_array_equal(b[:, emitted:], a[:, emitted:])
            if not np.array_equal(b[:, 0], a[:, 0]):
                changed_row0 = True
        # the window's committed write (last token's KV row) happened
        assert changed_row0
        if emitted < win.length:
            checked = True
    assert checked, "no speculation window was ever partially rejected"


# -- faults mid-window ------------------------------------------------------


def test_spec_fault_midwindow_replay_parity(tmp_path):
    """A mid-step L6 fault while speculation windows are in flight rolls
    back and replays to exactly the stream the non-speculative engine
    produces under the identical fault."""
    from repro.core.fault_codes import ErrorType, Severity

    def fault_run(sub, spec):
        _, eng = _engine(tmp_path, sub, num_dp=2, spec_window=spec)
        eng.injector.schedule(3, 1, severity=Severity.L6,
                              error_type=ErrorType.HBM_ECC,
                              component="attn", mid_step=True)
        out = _serve(eng, _prompts())
        surviving = [ex for ex in eng.dp_executors if ex.alive]
        assert surviving and all(
            ex.block_manager.num_allocated == 0 for ex in surviving)
        return out, eng

    want, _ = fault_run("fault_plain", 0)
    got, eng = fault_run("fault_spec", 6)
    assert got == want
    assert eng.prefill_stats()["spec_windows"] > 0


def test_spec_failrank_mask_zero_recompile(tmp_path):
    """fail_rank + mask_experts while speculating are pure MoERuntime
    data edits: the spec windows keep flowing through the precompiled
    chunk graph and the cache never sees a fresh compile."""
    cfg, eng = _engine(tmp_path, "zc", num_dp=2, spec_window=6,
                       precompile_failure_scenarios=False)

    _serve(eng, [PAT_A * 4], max_new=8)
    n0 = len(eng.graph_cache.timings)
    eng.expert_map.fail_rank(1)
    eng.expert_map.mask_experts(
        [e for e in range(cfg.moe.num_experts)
         if not any(s not in set(eng.expert_map.rank_slots(1))
                    for s in eng.expert_map.replicas_of(e))])
    eng.runtime = eng.expert_map.runtime()
    _serve(eng, [PAT_B * 10], max_new=12)
    assert len(eng.graph_cache.timings) == n0   # no miss compiled
    assert eng.prefill_stats()["spec_windows"] > 0


# -- carry-over (f): decode-grown + imported block registration -------------


def test_prefix_cache_registers_decode_grown_blocks(tmp_path):
    """A multi-turn follow-up whose prompt embeds a finished request's
    prompt + outputs hits the cache past the original prompt: blocks
    filled by decode register at fill time, not just prefilled ones."""
    _, eng = _engine(tmp_path, "grown")
    rng = np.random.default_rng(5)
    prompt = list(rng.integers(0, 2048, 16))            # 2 full blocks
    r0 = eng.submit(prompt, 16)
    eng.run(max_steps=200)
    assert r0.state.value == "finished"
    bm = eng.dp_executors[0].block_manager
    # prompt-only registration would publish 2 blocks; decode growth
    # publishes every full block below the KV-complete bound (31 -> 3)
    assert bm.num_cached >= 3

    follow = list(r0.prompt_tokens) + list(r0.output_tokens[:12])  # 28
    eng.submit(follow, 2)
    eng.run(max_steps=200)
    stats = eng.prefill_stats()
    # >= 3 blocks (24 tokens) served from cache: past the prompt's 16
    assert stats["prefill_tokens_cached"] >= 24


def test_prefix_cache_registers_imported_blocks():
    """KV-stream-imported requests register their installed blocks on
    the target immediately — a migrated conversation is shareable there
    without re-prefill."""
    import jax
    from repro.models.model import Model
    from repro.serving.executor import DPExecutor
    from repro.serving.request import Request
    import jax.numpy as jnp

    cfg = get_smoke_config("internlm2-20b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    class Ctx:
        runtime = model.default_runtime()

        def __init__(self):
            self.params = params

        def decode_fn(self, params, cache, tokens, page, runtime):
            page = {k: jnp.asarray(v) for k, v in page.items()}
            return model.decode_step_paged(params, cache,
                                           jnp.asarray(tokens), page,
                                           runtime)

        def chunk_fn(self):
            return self.decode_fn

    def executor(rank):
        return DPExecutor(physical_id=rank, dp_rank=rank, model=model,
                          max_batch=2, max_seq=32, num_blocks=16,
                          block_size=4, sampling=SamplingParams())

    ex = executor(0)
    ctx = Ctx()
    req = Request([7, 1, 7, 1, 7, 1], 8)
    ex.scheduler.add_request(req)
    for step in range(4):
        ex.plan()
        ex.compute(ctx, step)
        ex.commit()
    kv = ex.export_kv_blocks(req)
    assert kv is not None

    tgt = executor(1)
    assert tgt.block_manager.num_cached == 0
    assert tgt.import_kv_blocks(req, kv)
    # full blocks below valid_len registered on the importing manager
    assert tgt.block_manager.num_cached == (req.num_tokens - 1) // 4
    tgt.scheduler.check_consistent()
