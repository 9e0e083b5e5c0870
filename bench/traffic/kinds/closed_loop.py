"""A closed loop of ``clients``: each sends its next request when the last
one has its final token.

Every seed gets the same set of lengths, in an order the seed draws.  The
set is the lognormal's quantiles at ``pool`` levels, dealt into rounds of
``clients`` lengths so that each round spans the whole distribution
(round j holds the quantiles j, j + rounds, j + 2 rounds, ...).  The seed
shuffles the lengths inside each round and the order of the rounds, for
prompts and outputs apart (so it also draws their pairing), and draws the
token ids; the rounds are handed out in turn, round and round.  With one
free shuffle of the whole set, which long prompts happened to be in
flight during a 51 s window moved output tokens/s by a third from seed
to seed, while two runs of one seed agreed within 5 %.  Client ``i``
sends its first request at ``-warmup_s + i * stagger_s``, so the
measured window does not open on every client prefilling at once.

Mix keys: ``clients``, ``warmup_s``, ``stagger_s``, ``prompt`` and
``output`` (``median``, ``sigma``, ``min``, ``max``), and ``pool``, the
number of lengths in the set (a multiple of ``clients``, about the
requests one run sends).
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench.traffic.kinds.poisson_open import dealt, lognormal_set
from bench.traffic.source import Arrival, Source, token_rng


class ClosedLoop(Source):
    def __init__(self, mix: dict, seed: int, vocab: int, seconds: float):
        self.warmup_s = float(mix["warmup_s"])
        n, clients = int(mix["pool"]), int(mix["clients"])
        if n % clients:
            raise ValueError(f"a pool of {n} does not deal into rounds of "
                             f"{clients}")
        order = np.random.default_rng(seed)
        self._prompts = dealt(lognormal_set(n, mix["prompt"]), clients,
                              order)
        self._outputs = dealt(lognormal_set(n, mix["output"]), clients,
                              order)
        self._toks = token_rng(seed)
        self._vocab = vocab
        self._taken = 0
        stagger = float(mix["stagger_s"])
        self._pending = [(-self.warmup_s + i * stagger, i)
                         for i in range(clients)]

    def _arrival(self, at: float, client: int) -> Arrival:
        i = self._taken % len(self._prompts)
        p = int(self._prompts[i])
        o = int(self._outputs[i])
        self._taken += 1
        return Arrival(at, self._toks.integers(0, self._vocab, p).tolist(),
                       o, client)

    def due(self, now: float) -> List[Arrival]:
        ready = sorted(x for x in self._pending if x[0] <= now)
        self._pending = [x for x in self._pending if x[0] > now]
        return [self._arrival(at, c) for at, c in ready]

    def next_at(self):
        return min((at for at, _ in self._pending), default=None)

    def finished(self, arrival: Arrival, now: float) -> None:
        self._pending.append((now, arrival.client))


def make(mix: dict, seed: int, vocab: int, seconds: float) -> Source:
    return ClosedLoop(mix, seed, vocab, seconds)
