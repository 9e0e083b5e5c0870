"""Open-loop Poisson arrivals with lognormal prompt and output lengths.

The arithmetic follows the program's ``fleet.traffic.PoissonTraffic``
(exponential gaps at ``rate``; a lognormal length has its median at
exp(mu), log-space sigma, clipped), copied here so that a change to the
program cannot change the yardstick.  One difference: the work is the
same for every seed.  The warm-up and the window each get
``round(rate * length)`` requests, whose gaps and lengths are the
distribution's quantiles at evenly spaced levels, stretched to fill
that stretch of time.  The seed draws their order: gaps, prompt lengths
and output lengths are each dealt into rounds of about ``deal`` values
that span the distribution (:func:`dealt`), and the seed shuffles each
round and the order of the rounds; it also draws the token ids.  So
which requests are in flight when the window's fault fires, and which
write sets the revive rolls back, is the seed's, while every stretch of
the window gets a like share of long and short requests: with the
order shuffled freely, above the knee the work the engine completed in
a 51 s window moved by a quarter from seed to seed, while two runs of
one seed agreed within 2 %.

Mix keys: ``rate`` (requests/s), ``warmup_s``, ``deal``, ``prompt`` and
``output`` (``median``, ``sigma``, ``min``, ``max``).
"""
from __future__ import annotations

from statistics import NormalDist
from typing import List

import numpy as np

from bench.traffic.source import Arrival, Source, token_rng


def lognormal_set(n: int, spec: dict) -> np.ndarray:
    """``n`` lengths at the lognormal's quantiles (i + 0.5) / n, clipped."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z)).astype(int)
    return np.clip(x, spec["min"], spec["max"])


def dealt(values: np.ndarray, size: int, rng: np.random.Generator):
    """``values`` (ascending) dealt into ``len(values) // size`` rounds
    (at least one), round j holding values j, j + rounds, ..., so each
    spans the whole range; each round shuffled and the rounds in a
    shuffled order, concatenated."""
    rounds = max(1, len(values) // size)
    out = [rng.permutation(values[j::rounds]) for j in range(rounds)]
    return np.concatenate([out[j] for j in rng.permutation(rounds)])


def exponential_set(n: int, rate: float) -> np.ndarray:
    """``n`` gaps at the exponential's quantiles (i + 0.5) / n."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


class PoissonOpen(Source):
    def __init__(self, mix: dict, seed: int, vocab: int, seconds: float):
        self.warmup_s = float(mix["warmup_s"])
        rate = float(mix["rate"])
        deal = int(mix["deal"])
        rng = np.random.default_rng(seed)
        toks = token_rng(seed)
        self._arrivals: List[Arrival] = []
        for start, span in ((-self.warmup_s, self.warmup_s),
                            (0.0, float(seconds))):
            n = int(round(rate * span))
            if n == 0:
                continue
            gaps = dealt(exponential_set(n + 1, rate), deal, rng)
            # n arrivals inside [start, start + span): the (n+1)-th gap
            # closes the stretch
            at = start + span * np.cumsum(gaps)[:n] / gaps.sum()
            prompts = dealt(lognormal_set(n, mix["prompt"]), deal, rng)
            outputs = dealt(lognormal_set(n, mix["output"]), deal, rng)
            self._arrivals += [
                Arrival(float(t), toks.integers(0, vocab, int(p)).tolist(),
                        int(o))
                for t, p, o in zip(at, prompts, outputs)]
        self._next = 0

    def due(self, now: float) -> List[Arrival]:
        out = []
        while (self._next < len(self._arrivals)
               and self._arrivals[self._next].at <= now):
            out.append(self._arrivals[self._next])
            self._next += 1
        return out

    def next_at(self):
        if self._next < len(self._arrivals):
            return self._arrivals[self._next].at
        return None


def make(mix: dict, seed: int, vocab: int, seconds: float) -> Source:
    return PoissonOpen(mix, seed, vocab, seconds)
