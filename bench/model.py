"""A configuration file of the benchmark, read into the sizes the yardstick
uses (:class:`Shape`) and into the program's own configuration objects.

A configuration is ``bench/configs/<name>.json``: the published
``config.json`` keys (depth cut, listed in ``reduced``), a ``deployment``
group with the engine's sizes, and the name of its plain reference in
``bench/reference/``.  Sizes come from the file, and every implementation
choice of the program keeps its default, with one exception the file
states in ``deployment``: ``drop_tokens: false`` pins drop-free MoE
routing, where the published model drops no token and the program's
default capacity would (a run that serves another model than the file
states is no sound run to compare).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Optional

BENCH = Path(__file__).resolve().parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    path = BENCH / "configs" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration file {path}")
    return load_json(path)


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the yardstick computes with (weights, FLOPs, reference)."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    eps: float
    rope_theta: float
    d_ff: int = 0                  # dense FFN width (0: every layer MoE)
    experts: int = 0               # routed experts (0: dense model)
    top_k: int = 0
    expert_d_ff: int = 0
    shared_d_ff: int = 0           # all shared experts together
    redundant: int = 0             # replica slots (deployment)
    ep_ranks: int = 0              # ranks the expert slots are split over

    @property
    def moe(self) -> bool:
        return self.experts > 0

    @property
    def slots(self) -> int:
        return self.experts + self.redundant

    def slot_expert(self) -> list:
        """Logical expert held by each physical slot: slot s < experts
        holds expert s, the replica slots hold experts 0..redundant-1."""
        return list(range(self.experts)) + list(range(self.redundant))

    def experts_lost_with(self, rank: int) -> list:
        """Experts with no slot left once EP rank ``rank`` is gone."""
        per = self.slots // self.ep_ranks
        dead = set(range(rank * per, (rank + 1) * per))
        alive = {e for s, e in enumerate(self.slot_expert()) if s not in dead}
        return [e for e in range(self.experts) if e not in alive]


def shape(cfg: dict) -> Shape:
    dep = cfg["deployment"]
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    kw = dict(layers=cfg["num_hidden_layers"], d_model=D, heads=H,
              kv_heads=cfg["num_key_value_heads"],
              head_dim=cfg.get("head_dim", D // H),
              vocab=cfg["vocab_size"], eps=float(cfg["rms_norm_eps"]),
              rope_theta=float(cfg["rope_theta"]))
    if cfg.get("num_experts"):
        kw.update(experts=cfg["num_experts"],
                  top_k=cfg["num_experts_per_tok"],
                  expert_d_ff=cfg["moe_intermediate_size"],
                  shared_d_ff=cfg.get("shared_expert_intermediate_size", 0),
                  redundant=dep.get("redundant_experts", 0),
                  ep_ranks=dep["num_dp"])
    else:
        kw.update(d_ff=cfg["intermediate_size"])
    return Shape(**kw)


def program_config(cfg: dict, shp: Optional[Shape] = None):
    """The program's ``ModelConfig``: its registered architecture with the
    file's sizes put in; implementation choices keep their defaults."""
    from repro.configs import get_config
    shp = shp or shape(cfg)
    base = get_config(cfg["arch"])
    kw = dict(num_layers=shp.layers, d_model=shp.d_model,
              num_heads=shp.heads, num_kv_heads=shp.kv_heads,
              head_dim=shp.head_dim, vocab_size=shp.vocab,
              norm_eps=shp.eps, rope_theta=shp.rope_theta)
    if shp.moe:
        if shp.shared_d_ff % shp.expert_d_ff:
            raise ValueError("shared expert width is not a whole number "
                             "of expert widths")
        moe = dict(num_experts=shp.experts, top_k=shp.top_k,
                   expert_d_ff=shp.expert_d_ff,
                   num_shared_experts=shp.shared_d_ff // shp.expert_d_ff,
                   num_redundant_experts=shp.redundant)
        if cfg["deployment"].get("drop_tokens") is False:
            # the one choice a file may pin: routing as the published
            # model routes, every token to its top-k experts (capacity =
            # tokens x top-k, so no slot can overflow)
            moe["capacity_factor"] = float(shp.slots)
        kw["moe"] = dataclasses.replace(base.moe, **moe)
    else:
        kw["d_ff"] = shp.d_ff
    out = dataclasses.replace(base, **kw)
    out.validate()
    return out


def engine_config(cfg: dict, seed: int, workdir: str):
    """The engine's deployment sizes from the file; everything else (MoE
    and decode paths, overlap, admission, speculation) as the program
    sets it by default."""
    from repro.serving.engine import EngineConfig
    dep = cfg["deployment"]
    return EngineConfig(mode="collocated", num_dp=dep["num_dp"],
                        max_batch=dep["max_batch"], max_seq=dep["max_seq"],
                        block_size=dep["block_size"],
                        num_blocks=dep["num_blocks"], dtype=dep["dtype"],
                        seed=seed % (2 ** 31), workdir=workdir)
