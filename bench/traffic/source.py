"""What the run loop asks of a traffic generator.

A mix is ``bench/traffic/<mix>.json``; its ``kind`` names the generator
``bench/traffic/kinds/<kind>.py``, whose ``make(mix, seed, vocab,
seconds)`` returns a :class:`Source`.  Times are seconds on the run's
clock, whose zero is the opening of the measured window; warm-up traffic
is due at negative times.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

from bench.model import BENCH, load_json


@dataclass(frozen=True)
class Arrival:
    at: float                   # due time on the run's clock
    prompt: List[int]
    max_new: int
    client: Optional[int] = None


class Source:
    warmup_s: float = 0.0

    def due(self, now: float) -> List[Arrival]:
        """Requests due at or before ``now`` not handed out yet."""
        raise NotImplementedError

    def next_at(self) -> Optional[float]:
        """When the next request falls due, if it is known."""
        return None

    def finished(self, arrival: Arrival, now: float) -> None:
        """The request made from ``arrival`` has its last token."""


def token_rng(seed: int) -> np.random.Generator:
    """The generator of prompt token ids for a seed (apart from the one
    that orders lengths and arrivals)."""
    return np.random.default_rng([seed, 1])


def load_mix(name: str) -> dict:
    path = BENCH / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {path}")
    return load_json(path)


def make_source(mix: dict, seed: int, vocab: int, seconds: float) -> Source:
    kind = mix["kind"]
    if not (BENCH / "traffic" / "kinds" / f"{kind}.py").is_file():
        raise FileNotFoundError(f"no generator for traffic kind {kind!r}")
    mod = importlib.import_module(f"bench.traffic.kinds.{kind}")
    return mod.make(mix, seed, vocab, seconds)
