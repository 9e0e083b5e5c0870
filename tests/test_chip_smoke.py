"""``chip_smoke.py`` rehearsed on the CPU at smoke size.

The script serves the registered config on a TPU; here each of its
phases runs against the smoke-size config (kernels in interpret mode,
the engine on its CPU path), and its entry point must refuse to report
a result when no TPU is present.
"""
import importlib.util
import json
import os

import pytest

from repro.configs import get_smoke_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert not any(line.startswith("{") for line in out.splitlines())
    with pytest.raises(json.JSONDecodeError):
        json.loads(out.strip().splitlines()[-1])


def test_kernels_match_oracles(smoke):
    errs = smoke.check_kernels(get_smoke_config("qwen2-moe-a2.7b"), seed=0,
                               interpret=True)
    # paged attention x2, moe_fused, megakernel (y, h2) x 3 runtimes
    assert len(errs) == 9 and all(e <= smoke.TOL for e in errs.values())


def test_fresh_compile_counter_counts_every_cache_miss(smoke):
    """A miss counts however fast it compiled (a persistent-cache hit can
    take well under 10 ms); a precompiled lookup never counts."""
    from types import SimpleNamespace

    from repro.core.graph_cache import CompileTiming
    timings = [CompileTiming("cached", 0.0, 0.0), CompileTiming(
        "precompiled", 0.0, 0.0), CompileTiming("cold", 1e-4, 1e-4)]
    eng = SimpleNamespace(graph_cache=SimpleNamespace(timings=timings))
    assert smoke.fresh_compiles(eng, 0) == 2
    assert smoke.fresh_compiles(eng, 1) == 1
    assert smoke.fresh_compiles(eng, 3) == 0


def test_serves_through_fault_both_decode_paths(smoke, tmp_path):
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    runs = {impl: smoke.serve_through_fault(cfg, 0, impl,
                                            workdir=str(tmp_path))
            for impl in ("composed", "megakernel")}
    for run in runs.values():
        assert [len(t) for t in run["tokens"]] == \
            [smoke.NEW_TOKENS] * smoke.N_REQUESTS
