"""Open loop: independent users whose requests arrive as a Poisson
process at ``rate`` per second, whether or not earlier ones are done.

The run's ``seconds`` and ``rate`` fix the number of requests,
``round(rate * seconds)``.  Their inter-arrival gaps are that many
mid-quantiles of the exponential distribution of mean ``1 / rate``,
scaled to sum to the window, and their sizes mid-quantiles of the
length mixes: the seed permutes both, so every seed offers the same
work at the same mean rate, in another order.

Mix keys: ``rate`` (requests per second), ``prompt_mix``,
``output_mix``, ``slots`` and ``max_len``.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from traffic_common import (Request, check_fits, rng_for, stratified,
                            tokens, warmup_requests)


class OpenPoisson:
    closed = False

    def __init__(self, mix: dict, seed: int, vocab: int, chunk: int,
                 seconds: float):
        check_fits(mix, chunk)
        self.mix = mix
        self.vocab, self.chunk = vocab, chunk
        rate = float(mix["rate"])
        n = max(1, int(round(rate * seconds)))
        grng = rng_for(seed, 1)
        u = (np.arange(n) + 0.5) / n
        gaps = grng.permutation(-np.log1p(-u) / rate)
        gaps *= seconds * (n - 0.5) / n / gaps.sum()
        due = np.cumsum(gaps) - gaps[0] / 2
        lrng = rng_for(seed, 2)
        p_len = stratified(mix["prompt_mix"], n, lrng)
        o_len = stratified(mix["output_mix"], n, lrng)
        trng = rng_for(seed, 3)
        self.requests = [Request(i, tokens(trng, p_len[i], vocab),
                                 int(o_len[i]), due=float(due[i]))
                         for i in range(n)]
        self._next = 0

    def warmup(self) -> List[Request]:
        return warmup_requests(self.mix, self.vocab, self.chunk, [])

    def initial(self) -> List[Request]:
        return []

    def due(self, now: float) -> List[Request]:
        """Requests due by ``now`` (seconds after the window opened)
        that have not been handed out yet."""
        out = []
        while self._next < len(self.requests) and \
                self.requests[self._next].due <= now:
            out.append(self.requests[self._next])
            self._next += 1
        return out

    def next_due(self) -> Optional[float]:
        if self._next < len(self.requests):
            return self.requests[self._next].due
        return None


def build(mix: dict, seed: int, vocab: int, chunk: int, seconds: float
          ) -> OpenPoisson:
    return OpenPoisson(mix, seed, vocab, chunk, seconds)
