"""Cells at a size the CPU runs in seconds, for the tests: the same
configuration and traffic files, with the model's widths and depth, the
engine's sizes and the traffic's lengths cut down."""
from __future__ import annotations

import copy

from bench.harness import load_benchmark
from bench.model import load_config
from bench.traffic.source import load_mix

QWEN = "qwen2moe.chat_fault"
INTERNLM = "internlm2.batch"

_MODEL = {"hidden_size": 512, "num_attention_heads": 4, "num_hidden_layers": 2,
          "vocab_size": 512}
_MOE = {"num_key_value_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 128, "shared_expert_intermediate_size": 128}
_DENSE = {"num_key_value_heads": 2, "intermediate_size": 512}
_DEPLOY = {"max_batch": 4, "max_seq": 160, "num_blocks": 96}
# The check at smoke size: the configuration's own numbers, with limits
# for this size.  A gap limit set, as the cells' are, between the readings
# of the bfloat16 program (widest gap 0, 0 and 0.0060 on seeds 99, 5 and
# 2**33 + 11, internlm2 at smoke size, CPU) and of its float8 control
# (0.115, 0.123, 0.222): the cells' limits are read off logits of another
# scale.  A few served tokens, where a loaded CPU serves a short window.
SMOKE_GAP = 0.03
SMOKE_MIN_TOKENS = 4


def config(name: str) -> dict:
    cfg = copy.deepcopy(load_config(name))
    cfg.update(_MODEL)
    cfg.update(_MOE if cfg.get("num_experts") else _DENSE)
    cfg["deployment"].update(_DEPLOY)
    if cfg.get("num_experts"):
        cfg["deployment"]["redundant_experts"] = 2
    cfg["check"] = {k: SMOKE_MIN_TOKENS if k.startswith("min_tokens")
                    else SMOKE_GAP for k in cfg["check"]}
    return cfg


def mix(name: str) -> dict:
    m = copy.deepcopy(load_mix(name))
    m["warmup_s"] = 2.0
    m["prompt"] = {"median": 24, "sigma": 0.6, "min": 8, "max": 96}
    m["output"] = {"median": 8, "sigma": 0.5, "min": 4, "max": 32}
    if m["kind"] == "poisson_open":
        # well inside what a loaded CPU serves, so requests that arrive
        # after the revive still finish inside a short window
        m["rate"] = 3.0
    else:
        m["clients"], m["stagger_s"] = 8, 0.1
    return m


def cell(cell_name: str):
    """(bench, cfg, mix) of a cell at smoke size."""
    bench = load_benchmark()
    w = next(w for w in bench["workloads"] if w["name"] == cell_name)
    return bench, config(w["config"]), mix(w["traffic"])
