"""Whole runs at smoke size on the CPU: the references agree with the
engine, and a configuration, a traffic mix, a generator and a metric
added as files alone are found by name."""
import json
import shutil
import subprocess
import sys
import uuid

from bench import harness
from bench.harness import ROOT
from bench.model import BENCH
from bench.tests import cpu_run, smoke

EXACT = 1e-4        # float32 on both sides, same weights: rounding only


def test_qwen_matches_reference_before_and_after_revive(monkeypatch):
    out = cpu_run.run(smoke.QWEN, monkeypatch, seconds=10.0)
    c = out["checks"]
    assert c["mean_logit_gap.before"]["value"] <= EXACT
    assert c["mean_logit_gap.after"]["value"] <= EXACT
    assert c["tokens_checked.after"]["value"] > 0
    assert out["metrics"]["output_tok_s.chat"]["value"] > 0
    assert out["attempted"] > 0 and out["failed"] == 0


def test_internlm2_matches_reference(monkeypatch):
    out = cpu_run.run(smoke.INTERNLM, monkeypatch, seconds=3.0)
    assert out["checks"]["max_logit_gap"]["value"] <= EXACT
    assert out["checks"]["tokens_checked"]["value"] > 0
    assert out["metrics"]["output_tok_s.batch"]["value"] > 0


def test_cell_added_as_files_alone_is_found(monkeypatch):
    tag = "t" + uuid.uuid4().hex[:10]
    files = [BENCH / "configs" / f"{tag}.json",
             BENCH / "traffic" / f"{tag}.json",
             BENCH / "traffic" / "kinds" / f"{tag}.py",
             BENCH / "metrics" / f"{tag}.py"]
    try:
        cfg = smoke.config("internlm2-20b.l10")
        cfg["name"] = tag
        files[0].write_text(json.dumps(cfg))
        mix = smoke.mix("batch")
        mix["kind"] = tag
        files[1].write_text(json.dumps(mix))
        files[2].write_text(
            "from bench.traffic.kinds.closed_loop import make  # noqa\n")
        files[3].write_text("def read(run):\n    return len(run.reqs)\n")
        bench = harness.load_benchmark()
        bench["configs"].append({"name": tag, "source": "x",
                                 "file": f"bench/configs/{tag}.json",
                                 "reduced": [], "why": "x"})
        bench["workloads"] = [{"name": tag, "config": tag, "traffic": tag,
                               "chips": 1, "why": "x"}]
        bench["end_to_end"].append({"name": tag, "unit": "requests",
                                    "better": "higher", "bound": 0.25,
                                    "source": "host_clock"})
        monkeypatch.setattr(harness, "load_benchmark", lambda: bench)
        out = harness.run_cell(tag, 5, 2.0, False, platform="cpu")
        assert out["metrics"][tag]["value"] > 0
        assert "setup_s" in out["metrics"]
    finally:
        for f in files:
            f.unlink(missing_ok=True)
        shutil.rmtree(BENCH / "traffic" / "kinds" / "__pycache__",
                      ignore_errors=True)


def test_no_tpu_means_no_result():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    import os
    env["HOME"] = os.environ.get("HOME", "/tmp")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         smoke.QWEN, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "tpu" in p.stderr
