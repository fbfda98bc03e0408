"""Shared stream-identity / parity harness for the serving suites.

Stream identity is THE serving invariant: every serving-layer feature —
chunked admission, prefix-sharing CoW, spill/resume tiering, mesh
sharding, speculative decoding — must change WALL-CLOCK only, never a
token.  Before PR 10 each suite re-implemented the same scaffolding
(family fixtures, layout specs, the scheduler driver, the stream
comparison); this module is the single copy they all import, so a new
serving feature gets its {family} x {layout} parity matrix by calling
:func:`stream_parity_case` with one kwargs delta instead of cloning a
hundred lines.

Building blocks:

* :func:`family` — cached ``(cfg, api, params)`` per model family.  One
  build per pytest process, shared across every suite that imports it.
* :func:`layout_spec` — ``kind`` string -> LayoutSpec (None for dense).
* :func:`serve_streams` — the canonical scheduler driver: submit
  prompts (optionally staggered), run to completion, return the token
  streams (+ the scheduler, for stats assertions).
* :func:`stream_parity_case` — the matrix runner: serve the SAME
  prompts under a baseline and a variant scheduler configuration and
  assert token-identical streams.
* :func:`assert_read_slot_matches_merged` — the ``merged()``-oracle
  check: a slot's ``read_slot`` row must equal the dense-logical oracle
  for every field, every layout (int8: both sides dequantize the same
  stored values).

Deliberately NOT a conftest: plain importable module (pytest's default
prepend import mode puts ``tests/`` on ``sys.path``), so helpers stay
grep-able and usable from scripts.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.config import get_config, reduced
from repro.models import layouts as LT
from repro.models.api import build_decode, build_model
from repro.serving.scheduler import SlotScheduler
from repro.serving.session import Session

PAGE = 16

# family -> (registry arch, config overrides).  "lm" is the small dense
# GQA model; "lm_mqa" the 1-KV-head reduction the tiering/CoW suites use
# (MQA exercises the kv-head-replicated layout paths).
FAMILY_ARCHS: Dict[str, Tuple[str, Dict]] = {
    "tconst": ("tconst_41m", {}),
    "tlin": ("tconst_41m", {"attention_mode": "tlin"}),
    "lm": ("smollm_360m", {}),
    "lm_mqa": ("llama3_405b", {}),
    "encdec": ("whisper_small", {}),
}


@functools.lru_cache(maxsize=None)
def family(name: str):
    """(cfg, api, params) for a named family — built once per process
    and shared by every suite that imports this module."""
    arch, kw = FAMILY_ARCHS[name]
    cfg = reduced(get_config(arch), dtype="float32", **kw)
    api = build_model(cfg)
    return cfg, api, api.init(jax.random.PRNGKey(0))


def layout_spec(kind: str, page_size: int = PAGE,
                pool_pages: Optional[int] = 24):
    """LayoutSpec for a matrix ``kind`` string; dense -> None (the
    build_decode default)."""
    if kind == "dense":
        return None
    return LT.LayoutSpec(kind=kind, page_size=page_size,
                         pool_pages=pool_pages)


def extras_for(cfg, seed: int = 9):
    """Per-session extras a family's prefill needs (encdec: audio)."""
    if not cfg.is_encdec:
        return None
    rng = np.random.RandomState(seed)
    return {"audio_feats": rng.randn(
        cfg.encoder_seq, cfg.frontend_dim).astype(np.float32)}


def make_prompts(cfg, lens: Sequence[int], seed: int = 3) -> List:
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size, size=n).astype(np.int32)
            for n in lens]


def shared_prompts(cfg, n: int, common_len: int = 48, tail_len: int = 8,
                   seed: int = 0) -> List:
    """n prompts sharing a page-aligned common prefix, distinct equal-
    length tails (equal lengths keep prefill bitwise-reproducible, so
    greedy parity with solo runs is exact)."""
    rng = np.random.RandomState(seed)
    common = rng.randint(1, cfg.vocab_size,
                         size=common_len).astype(np.int32)
    return [np.concatenate([common, rng.randint(
        1, cfg.vocab_size, size=tail_len).astype(np.int32)])
        for _ in range(n)]


def serve_streams(cfg, params, prompts, spec=None, *, gen: int = 6,
                  stagger: bool = True, slots: int = 2,
                  max_len: int = 128, chunk_size: int = 4,
                  prefill_chunk: Optional[int] = None,
                  session_kw: Optional[Dict] = None,
                  mesh=None, **sched_kw):
    """The canonical scheduler driver: submit every prompt (stepping
    once between submissions when ``stagger``, so slots sit at mixed
    resync phases), run to completion, return (streams, scheduler)."""
    sched = SlotScheduler(build_decode(cfg, spec, mesh=mesh), params,
                          slots=slots, max_len=max_len,
                          chunk_size=chunk_size,
                          prefill_chunk=prefill_chunk, **sched_kw)
    sessions = []
    for p in prompts:
        sessions.append(sched.submit(Session(
            p, max_new_tokens=gen, extras=extras_for(cfg),
            **(session_kw or {}))))
        if stagger:
            sched.step()
    sched.run()
    return [s.tokens for s in sessions], sched


def assert_streams_equal(ref, got, label: str = "") -> None:
    """Token-identical streams, with a per-session diff on failure."""
    assert len(ref) == len(got), \
        f"{label}: {len(ref)} vs {len(got)} sessions"
    for i, (r, g) in enumerate(zip(ref, got)):
        assert r == g, (f"{label}: session {i} stream diverged\n"
                        f"  ref: {r}\n  got: {g}")


def stream_parity_case(family_name: str, kind: str, *,
                       variant_kw: Dict, base_kw: Optional[Dict] = None,
                       prompt_lens: Sequence[int] = (21, 34, 17),
                       spec=None, seed: int = 3, label: str = "",
                       **common_kw):
    """The {family} x {layout} matrix runner: serve the same prompts
    under ``base_kw`` (default: the plain scheduler) and ``variant_kw``
    and assert the streams are token-identical.  Returns (streams,
    variant scheduler) for follow-up stats assertions."""
    cfg, api, params = family(family_name)
    prompts = make_prompts(cfg, prompt_lens, seed)
    spec = layout_spec(kind) if spec is None and kind != "dense" else spec
    ref, _ = serve_streams(cfg, params, prompts, spec,
                           **{**common_kw, **(base_kw or {})})
    out, sched = serve_streams(cfg, params, prompts, spec,
                               **{**common_kw, **variant_kw})
    assert_streams_equal(ref, out,
                         label or f"{family_name}/{kind}")
    return out, sched


def assert_read_slot_matches_merged(state, slot: int = 0) -> None:
    """``read_slot`` must equal the ``merged()`` dense-logical oracle's
    row for every field (int8 layouts: both sides dequantize the same
    stored values, so the comparison is still exact)."""
    row = jax.jit(state.read_slot)(np.int32(slot))
    oracle = state.merged()
    for f, v in row.items():
        ref = jax.lax.dynamic_slice_in_dim(oracle[f], slot, 1,
                                           state.axes[f])
        np.testing.assert_allclose(np.asarray(v), np.asarray(ref),
                                   rtol=0, atol=0,
                                   err_msg=f"read_slot({f}) != oracle")


def walk_eqns(jaxpr):
    """Every equation of ``jaxpr`` and, depth first, of the jaxprs nested
    in its equations' params (scan / cond / while / pjit bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        stack = list(eqn.params.values())
        while stack:
            x = stack.pop()
            if isinstance(x, ClosedJaxpr):
                x = x.jaxpr
            if isinstance(x, Jaxpr):
                yield from walk_eqns(x)
            elif isinstance(x, (list, tuple)):
                stack.extend(x)
