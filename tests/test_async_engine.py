"""Async pipelined engine (host/device overlap + async readback).

The overlap pipeline plans step N+1 against the *predicted* post-N
state while step N runs on device, samples on-device with the same
position-seeded uniforms the host sampler uses, and commits one step
late off a ring of in-flight D2H copies.  The contract under test:

* the emitted token stream is bit-identical to lockstep — any
  temperature, spec on or off, every attention architecture;
* a fault while a step is in flight replays to lockstep's exact
  stream (the pending step's readback predates the fault, so its
  outcome commits; everything uncommitted rolls back via §3.3);
* a mispredicted plan (speculation accept-count miss) reconciles
  through the lockstep commit path and replans — never a wrong token;
* the vectorized position-seeded sampler stays bit-equal to the
  per-row ``np.random.default_rng`` reference it replaced.
"""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.fault_codes import ErrorType, Severity
from repro.serving.engine import EngineConfig, InferenceEngine
from repro.serving.sampling import SamplingParams, seeded_uniforms

PAT_A = [5, 9, 2, 7]
PAT_B = [3, 1]


def _prompts():
    return [PAT_A * 5, PAT_B * 8]


def _engine(tmp_path, sub, *, overlap=False, spec_window=0,
            temperature=0.0, num_dp=1, **over):
    cfg = get_smoke_config(over.pop("arch", "qwen2-moe-a2.7b"))
    cfg_fn = over.pop("cfg_fn", None)
    if cfg_fn:
        cfg = cfg_fn(cfg)
    # overlap and fault replay are compared token for token with lockstep:
    # float32 weights
    over.setdefault("dtype", "float32")
    ec = EngineConfig(mode="collocated", num_dp=num_dp, max_batch=2,
                      max_seq=96, block_size=8, num_blocks=64,
                      workdir=str(tmp_path / sub), overlap=overlap,
                      spec_window=spec_window,
                      sampling=SamplingParams(temperature=temperature,
                                              top_p=0.9, seed=3), **over)
    return cfg, InferenceEngine(cfg, ec)


def _serve(eng, prompts, max_new=24):
    reqs = [eng.submit(list(p), max_new) for p in prompts]
    eng.run(max_steps=400)
    assert all(r.state.value == "finished" for r in reqs), \
        [r.state for r in reqs]
    return [list(r.output_tokens) for r in reqs]


# -- config validation ------------------------------------------------------


def test_overlap_requires_row_undo_and_chunked_admission(tmp_path):
    with pytest.raises(ValueError, match="pool_undo"):
        EngineConfig(workdir=str(tmp_path), overlap=True,
                     pool_undo="snapshot")
    with pytest.raises(ValueError, match="admission"):
        EngineConfig(workdir=str(tmp_path), overlap=True,
                     admission="serial")


# -- token exactness vs lockstep --------------------------------------------


def _windowed(cfg):
    return dataclasses.replace(cfg, sliding_window=6)


ARCHS = [
    ("qwen2-moe-a2.7b", None),       # GQA + MoE + shared experts
    ("deepseek-v3", None),           # MLA + MoE + first-k-dense
    ("qwen2-moe-a2.7b", _windowed),  # GQA + sliding window
]


@pytest.mark.parametrize("arch,cfg_fn", ARCHS,
                         ids=["gqa_moe", "mla_moe", "windowed"])
def test_overlap_token_exact_vs_lockstep(tmp_path, arch, cfg_fn):
    _, base = _engine(tmp_path, "base", arch=arch, cfg_fn=cfg_fn)
    want = _serve(base, _prompts())
    _, eng = _engine(tmp_path, "ov", arch=arch, cfg_fn=cfg_fn,
                     overlap=True)
    got = _serve(eng, _prompts())
    assert got == want
    st = eng.overlap_stats()
    assert st["planned_ahead"] > 0        # the pipeline actually piped
    assert st["replans"] == 0             # greedy device argmax is exact
    assert eng.host_gap_fraction() < 1.0


@pytest.mark.parametrize("temperature", [0.3, 0.8])
def test_overlap_token_exact_any_temperature(tmp_path, temperature):
    """The device epilogue samples with the same position-seeded
    uniforms as the host sampler; a last-ULP divergence may cost a
    replan but never a different token."""
    _, base = _engine(tmp_path, "base", temperature=temperature)
    want = _serve(base, _prompts())
    _, eng = _engine(tmp_path, "ov", temperature=temperature,
                     overlap=True)
    got = _serve(eng, _prompts())
    assert got == want
    assert eng.overlap_stats()["planned_ahead"] > 0


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_overlap_with_spec_decode_reconciles(tmp_path, temperature):
    """Speculation makes per-step emit counts unpredictable at plan
    time: the stacked plan-ahead step gets unwound (pool rows restored
    newest-first) and the true outcome committed via the lockstep
    path — the mispredicted-plan reconcile case, still token-exact."""
    _, base = _engine(tmp_path, "base", spec_window=6,
                      temperature=temperature)
    want = _serve(base, _prompts())
    _, eng = _engine(tmp_path, "ov", spec_window=6,
                     temperature=temperature, overlap=True)
    got = _serve(eng, _prompts())
    assert got == want
    st = eng.overlap_stats()
    assert st["planned_ahead"] > 0
    assert st["replans"] >= 1             # accept-count misses happened
    assert eng.prefill_stats()["spec_windows"] > 0


# -- fault while a step is in flight ----------------------------------------


def test_fault_mid_overlap_replays_to_lockstep_stream(tmp_path):
    """Device fault with a step in flight: the pending step's outcome
    commits (its readback predates the fault), §3.3 rolls back the
    rest, and migration + position-seeded replay reproduce lockstep's
    exact stream — recovery included."""
    def serve(sub, overlap):
        _, eng = _engine(tmp_path, sub, num_dp=2, temperature=0.7,
                         overlap=overlap)
        eng.injector.schedule(3, 1, severity=Severity.L6,
                              error_type=ErrorType.HBM_ECC,
                              component="attn", mid_step=True)
        out = _serve(eng, _prompts())
        assert eng.reports, "fault never recovered"
        return out, eng

    want, _ = serve("lock", overlap=False)
    got, eng = serve("ov", overlap=True)
    assert got == want
    assert eng.overlap_stats()["planned_ahead"] > 0


# -- vectorized position-seeded sampler regression --------------------------


def test_seeded_uniforms_match_reference_generator():
    """The batched PCG64/SeedSequence replication must stay bit-equal
    to the per-row ``default_rng`` construction it replaced — this is
    what makes every token a pure function of (seed, prefix,
    position) across executors, instances, and replays."""
    rng = np.random.default_rng(0)
    for seed in (0, 1, 3, 17, 2 ** 31 - 1):
        steps = np.concatenate([
            np.arange(0, 40, dtype=np.int64),
            rng.integers(0, 100_000, 64).astype(np.int64),
        ])
        got = seeded_uniforms(seed, steps)
        base = seed * 1_000_003
        want = np.asarray([
            np.random.default_rng(base + int(s)).random()
            for s in steps])
        np.testing.assert_array_equal(got, want)
