"""A configuration file of the benchmark, read into the sizes the yardstick
uses (:class:`Shape`) and into the program's own configuration objects.

A configuration is ``bench/configs/<name>.json``: the published
``config.json`` keys (depth cut, listed in ``reduced``), a ``deployment``
group with the engine's sizes, and the name of its architecture module in
``bench/reference/`` (``reference``), which reads the sizes, lays out
the weights, counts the operations and holds the plain reference.
Sizes come from the file, and every implementation choice of the
program keeps its default, with one exception the file states in
``deployment``: ``drop_tokens: false`` pins drop-free MoE routing, where
the published model drops no token and the program's default capacity
would (a run that serves another model than the file states is no sound
run to compare).
"""
from __future__ import annotations

import dataclasses
import importlib
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


def reference_module(name: str):
    """The architecture module ``bench/reference/<name>.py`` that a
    configuration file names under ``reference``: the one place that
    describes the architecture to the benchmark (``bench.reference``)."""
    return importlib.import_module(f"bench.reference.{name}")


@dataclasses.dataclass(frozen=True, kw_only=True)
class Shape:
    """The sizes the yardstick computes with (weights, FLOPs, reference):
    the fields the shared code reads.  An architecture module subclasses
    it with the sizes of its own layers."""
    layers: int
    d_model: int
    vocab: int
    eps: float
    experts: int = 0               # routed experts (0: dense model)

    @property
    def moe(self) -> bool:
        return self.experts > 0


def program_config(cfg: dict, arch, shp: Optional[Shape] = None):
    """The program's ``ModelConfig``: its registered architecture with the
    file's sizes put in by the architecture module; implementation
    choices keep their defaults."""
    from repro.configs import get_config
    out = arch.program_config(cfg, shp or arch.shape(cfg),
                              get_config(cfg["arch"]))
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
