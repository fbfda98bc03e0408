"""Closed loop: ``clients`` callers, each of which sends its next request
the moment its previous one completes, drawn in order from a backlog
made from the seed.

The backlog is made of blocks of ``clients`` consecutive requests.
Every block holds the same ``clients`` requests' sizes: the mixes'
``clients`` mid-quantiles, prompt, output and prefix each paired by one
fixed shuffle, in an order that the seed draws anew for each block.  The
first wave, one request per client, and every later block then do the
same work whatever the seed, so a window that uses a few blocks does
too; the seed changes which client gets which request, and the tokens.

Mix keys: ``clients``, ``backlog`` (requests drawn, a multiple of
``clients``; a run that uses them all starts over from the first), ``prompt_mix`` and ``output_mix``
(length mixes, see ``traffic_common``), optional ``n_prefixes`` and
``prefix_len`` (each prompt is one of ``n_prefixes`` shared prefixes of
``prefix_len`` tokens, then its own tail drawn from ``prompt_mix``),
``slots`` and ``max_len`` (the scheduler the mix is sized for).
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from traffic_common import (Request, check_fits, mix_quantile, rng_for,
                            tokens, warmup_requests)


class ClosedBacklog:
    closed = True

    def __init__(self, mix: dict, seed: int, vocab: int, chunk: int,
                 seconds: float):
        check_fits(mix, chunk)
        self.mix = mix
        self.clients = int(mix["clients"])
        n = int(mix["backlog"])
        if n % self.clients:
            raise ValueError(f"backlog {n} is not a whole number of blocks"
                             f" of {self.clients} clients")
        self.vocab, self.chunk = vocab, chunk
        n_pre = int(mix.get("n_prefixes", 0))
        prng = rng_for(seed, 1)
        self.prefixes = [tokens(prng, int(mix["prefix_len"]), vocab)
                         for _ in range(n_pre)]
        c = self.clients
        u = (np.arange(c) + 0.5) / c
        pair = rng_for(0, 4)                # the same pairing for every seed
        block = np.stack([
            mix_quantile(mix["prompt_mix"], u),
            mix_quantile(mix["output_mix"], u)[pair.permutation(c)],
            mix_quantile([[1.0, 0, max(n_pre - 1, 0)]], u)[
                pair.permutation(c)]], axis=1)
        lrng = rng_for(seed, 2)
        p_len, o_len, pick = np.concatenate(
            [block[lrng.permutation(c)] for _ in range(n // c)]).T
        trng = rng_for(seed, 3)
        self.backlog: List[Request] = []
        for i in range(n):
            prompt = tokens(trng, p_len[i], vocab)
            if n_pre:
                prompt = np.concatenate([self.prefixes[int(pick[i])], prompt])
            self.backlog.append(Request(i, prompt, int(o_len[i])))
        self._next = 0

    def warmup(self) -> List[Request]:
        return warmup_requests(self.mix, self.vocab, self.chunk,
                               self.prefixes)

    def next_request(self, client: int) -> Request:
        r = self.backlog[self._next % len(self.backlog)]
        self._next += 1
        return Request(r.index, r.prompt, r.max_new, client)

    def initial(self) -> List[Request]:
        """One request per client, sent during set-up."""
        return [self.next_request(c) for c in range(self.clients)]

    def due(self, now: float) -> List[Request]:
        return []

    def next_due(self) -> Optional[float]:
        return None


def build(mix: dict, seed: int, vocab: int, chunk: int, seconds: float
          ) -> ClosedBacklog:
    return ClosedBacklog(mix, seed, vocab, chunk, seconds)
