"""BENCHMARK.json and the files it names: allowed characters, the keys
each entry may have, and every name resolving to a file of its own."""
import json
import re

import pytest

from bench.harness import ROOT, metrics_of
from bench.model import BENCH, Shape, load_config, reference_module

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")


@pytest.fixture(scope="module")
def bench():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    assert len(bench["command"]) <= 32
    for word in bench["command"]:
        assert LINE.match(word)
    assert (ROOT / bench["command"][1]).is_file()
    assert bench["command"][1].startswith(tuple(p + "/"
                                                for p in bench["paths"]))


def test_names_and_units(bench):
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[section]:
            assert NAME.match(e["name"]), e["name"]
            names.append((section in ("end_to_end", "per_layer"), e["name"]))
    metric_names = [n for m, n in names if m]
    assert len(metric_names) == len(set(metric_names))
    for e in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(e["unit"]), e["unit"]
        assert e["better"] in ("lower", "higher")
    for e in bench["workloads"]:
        assert NAME.match(e["config"]) and NAME.match(e["traffic"])
        assert LINE.match(e["why"])
    for e in bench["per_layer"]:
        assert LINE.match(e["layer"])
    assert len(json.dumps(bench)) <= 64 * 1024


def test_entry_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k)
            assert not k.endswith(("_dim", "_rank", "_size")), k
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_name_has_its_file(bench):
    for c in bench["configs"]:
        with open(ROOT / c["file"]) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert (BENCH / "reference" / f"{cfg['reference']}.py").is_file()
    configs = {c["name"] for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        assert w["config"] in configs
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(bench["workloads"])
    assert configs == {w["config"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]


# what the shared code calls in an architecture module
# (bench/reference/__init__.py)
ARCH_EXPORTS = ("shape", "program_config", "layer_leaves", "program_params",
                "matmul_params", "attention_flops", "attention_call",
                "experts_lost_with", "layer")


def test_every_reference_module_exports_the_architecture(bench):
    for c in bench["configs"]:
        cfg = load_config(c["name"])
        arch = reference_module(cfg["reference"])
        missing = [n for n in ARCH_EXPORTS if not callable(
            getattr(arch, n, None))]
        assert not missing, (cfg["reference"], missing)
        s = arch.shape(cfg)
        assert isinstance(s, Shape) and s.layers >= 1
        for i in range(s.layers):
            assert arch.layer_leaves(s, i)
            assert arch.matmul_params(s, i) > 0
        assert arch.attention_flops(s, 2) == 2 * arch.attention_flops(s, 1)
        assert all(0 <= e < s.experts
                   for e in arch.experts_lost_with(s, 0))


def test_each_cell_reports_what_it_must(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
    for cell in cells:
        mine = {m["name"] for m in metrics_of(bench, cell, False)}
        assert "setup_s" in mine and len(mine) >= 2
        assert metrics_of(bench, cell, True)


def test_per_layer_metrics_move_a_metric_their_cells_report(bench):
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        for cell in m.get("workloads", []):
            reported = {e["name"] for e in metrics_of(bench, cell, False)}
            assert m["moves"] in reported, (m["name"], cell)


def test_one_layer_name_per_layer(bench):
    by_prefix = {}
    for m in bench["per_layer"]:
        by_prefix.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    for names in by_prefix.values():
        assert len(names) == 1


def test_four_chip_cells_within_half(bench):
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
