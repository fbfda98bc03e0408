"""What the traffic generators share: the request record, seeded length
mixes drawn so that every seed gets the same multiset of sizes, and
prompt tokens.

A length mix is a list of weighted uniform components
``[[weight, lo, hi], ...]`` (inclusive bounds), the form
``repro.serving.workload`` uses.  ``stratified`` takes the ``n``
mid-quantiles of the mix, so the set of sizes is a function of the mix
and ``n`` alone; the seed only permutes their order.  Two seeds then
differ in which request comes when, never in how much work there is.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np


@dataclasses.dataclass
class Request:
    """One request as a client sends it."""
    index: int                       # position in the generator's order
    prompt: np.ndarray               # int32 token ids
    max_new: int                     # tokens to generate, the first included
    client: int = 0
    due: Optional[float] = None      # open loop: seconds after window start


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of one seed (any size of seed)."""
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), *stream])


def mix_quantile(mix: Sequence[Sequence[float]], u: np.ndarray
                 ) -> np.ndarray:
    """Inverse CDF of a mix of weighted uniform integer components."""
    w = np.array([c[0] for c in mix], np.float64)
    cum = np.concatenate([[0.0], np.cumsum(w / w.sum())])
    out = np.empty(u.shape, np.int64)
    for i, (_, lo, hi) in enumerate(mix):
        sel = (u >= cum[i]) & (u < cum[i + 1]) if i < len(mix) - 1 \
            else (u >= cum[i])
        v = (u[sel] - cum[i]) / (cum[i + 1] - cum[i])   # uniform in [0, 1)
        out[sel] = lo + np.minimum(np.floor(v * (hi - lo + 1)), hi - lo)
    return out


def stratified(mix: Sequence[Sequence[float]], n: int,
               rng: np.random.Generator) -> np.ndarray:
    """``n`` sizes: the mix's mid-quantiles, in an order drawn by ``rng``."""
    u = (np.arange(n) + 0.5) / n
    return rng.permutation(mix_quantile(mix, u))


def mix_max(mix: Sequence[Sequence[float]]) -> int:
    return int(max(c[2] for c in mix))


def tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    """``n`` prompt ids drawn uniformly from ``[1, vocab)``."""
    return rng.integers(1, vocab, size=int(n)).astype(np.int32)


def check_fits(traffic: dict, headroom: int) -> None:
    """Every request of the mix must fit the slot's ``max_len`` buffer
    with the scheduler's headroom (one decode chunk)."""
    longest = int(traffic.get("prefix_len", 0)) + \
        mix_max(traffic["prompt_mix"]) + mix_max(traffic["output_mix"])
    if longest + headroom > int(traffic["max_len"]):
        raise ValueError(f"longest request {longest} + headroom {headroom}"
                         f" exceeds max_len {traffic['max_len']}")


def warmup_requests(traffic: dict, vocab: int, chunk: int,
                    prefixes: List[np.ndarray]) -> List[Request]:
    """Requests that take every shape the window uses through admission,
    a decode chunk and retirement: one per prompt-mix component at its
    longest, and, with shared prefixes, a second request on the same
    prefix (admission over adopted pages)."""
    rng = rng_for(0, 99)
    out = []
    for c in traffic["prompt_mix"]:
        prompt = tokens(rng, int(c[2]), vocab)
        if prefixes:
            prompt = np.concatenate([prefixes[0], prompt])
        out.append(Request(len(out), prompt, chunk + 1))
    if prefixes:
        tail = tokens(rng, int(traffic["prompt_mix"][0][1]), vocab)
        out.append(Request(len(out), np.concatenate([prefixes[0], tail]),
                           chunk + 1))
    return out
