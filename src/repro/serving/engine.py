"""Batched generation engine on top of the session/scheduler serving API.

The serving stack has three layers:

* ``repro.models.api.DecodeAPI`` — the per-model decode protocol.  Its
  ``step`` fuses the TConst W_og-boundary resync ON DEVICE through the
  batched compacted ``sync_rows`` (ALL boundary rows' bookkeeping is
  gathered in one dispatch, resynced at the bucketed pending count, and
  the fresh KV written back through the layout — non-boundary rows are
  never computed), and ``decode_chunk`` scans it so a chunk of k tokens
  is ONE dispatch with zero per-token host round-trips.  The physical
  cache representation is a pluggable ``repro.models.layouts`` backend
  (dense / paged / int8 / paged_int8) that the decode kernels consume
  LAYOUT-NATIVELY via per-field KVViews: paged pools are walked through
  the page table in-kernel, int8 dequant rides the QK/AV loops, and no
  step materialises the dense ``slots x max_len`` logical cache.
* ``repro.serving.scheduler.SlotScheduler`` + ``repro.serving.session``
  — continuous batching: per-request sessions with their own prompt
  lengths / sampling params / EOS ids / streaming callbacks, admitted
  and evicted mid-flight into a fixed-shape slotted batch (paged
  layout: admission/eviction is page-map surgery).
* :class:`Engine` (this module) — the thin uniform-batch wrapper kept
  for benchmarks and examples: same-length prompts in, ``(B, n)`` ids
  out.  ``generate(record_stats=False)`` uses the chunked zero-sync
  path; ``record_stats=True`` switches to the instrumented step-at-a-
  time reference path that times cache hits and misses separately —
  the amortized-O(1) schedule of §4 (``W_og - 1`` constant-time hits,
  then ONE linear-time miss) for the Fig 8 latency split.

Cache accounting (``cache_bytes``) reads the ``DecodeState`` kv /
bookkeeping partition in its PHYSICAL layout — paged pools and int8
scales report their true bytes, and the id buffer and counters are
excluded by construction, not by name-matching.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.api import ModelAPI, build_decode, decode_chunk, spec_chunk


@dataclasses.dataclass
class StepStats:
    kind: str      # "prefill" | "hit" | "miss" | "chunk" | "admit" | "spill"
    seconds: float
    tokens: int = 1        # tokens produced by this entry (chunks: many)
    # True when this entry's wall-clock includes the one-time jit compile
    # of its dispatch (first chunk of a shape, first prefill of a prompt
    # length, ...).  Throughput aggregation must exclude these entries
    # (or medianize) — BENCH_inference.json numbers do.
    compiled: bool = False
    # "admit" entries: prompt positions the admission actually FORWARDED
    # (chunked KV-conditioned prefill: the unshared tail padded to the
    # chunk grid; one-shot prefill: the whole prompt; tier-store restore:
    # ZERO — the whole point) — the tail-only compute accounting asserted
    # in tests/test_prefill_chunked.py and recorded under
    # "chunked_prefill" in BENCH_inference.json.
    forward_tokens: Optional[int] = None
    # "admit" entries: where the slot state came from — "cold" (prefill
    # forward), "resume" (a spilled session's pinned tier-store snapshot
    # restored into a free slot), or "store" (content-addressed admission
    # cache hit: a known prompt's post-prefill state restored, zero
    # forward compute).  None for non-admit kinds.
    source: Optional[str] = None
    # ``time.perf_counter()`` when the entry's span began ("admit": when
    # the scheduler began choosing the session, before its plan).
    start: Optional[float] = None
    # "admit" entries: the session admitted; ``queued_s`` is the start
    # less the session's ``submit_wall`` (first admissions only, not
    # resumes); ``total_s`` the whole admission, from ``start`` until
    # the first token is delivered and the slot is live (``seconds``
    # is its prefill alone).
    sid: Optional[int] = None
    queued_s: Optional[float] = None
    total_s: Optional[float] = None
    # "chunk" entries (scheduler): slots masked out of the dispatch —
    # empty or fork-paused — and so frozen for the whole chunk.  Known
    # on the host, no device sync; rows that hit EOS inside the chunk
    # freeze on the device and are not counted.
    frozen: Optional[int] = None


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """Host span ``serve.<name>`` on the profiler's clock, so it lines up
    with the device trace of any ``jax.profiler`` session; ``args``
    (e.g. ``sid``) ride along as the event's stats.  Costs under a
    microsecond when no profiler runs.  Open it on the host only, never
    inside a jitted function."""
    return jax.profiler.TraceAnnotation("serve." + name, **args)


def tag_compiled(warm: set, kind: str, sig: Any = None) -> bool:
    """True exactly for the first dispatch of each (kind, signature) —
    the one whose wall-clock includes the jit compile.  One rule shared
    by the Engine and the SlotScheduler so the tagging cannot drift."""
    key = (kind, sig)
    fresh = key not in warm
    warm.add(key)
    return fresh


class Engine:
    def __init__(self, api: ModelAPI, params: Any, max_len: int,
                 sample_temperature: float = 0.0, seed: int = 0,
                 layout: Optional[Any] = None,
                 prefill_chunk: Optional[int] = None,
                 mesh: Optional[Any] = None):
        self.api = api
        # prefill_chunk rides on the decode protocol: the Engine's own
        # uniform-batch prefill is one fixed-shape dispatch already, but
        # a SlotScheduler built from this engine's decode inherits the
        # chunked-admission default.  mesh (a jax Mesh or MeshContext)
        # makes the SAME decode path run sharded — see docs/sharding.md.
        self.decode = build_decode(api.cfg, layout,
                                   prefill_chunk=prefill_chunk, mesh=mesh)
        self.params = params
        self.max_len = max_len
        self.temperature = sample_temperature
        self.key = jax.random.PRNGKey(seed)
        self._prefill = jax.jit(
            lambda p, b: self.decode.prefill(p, b, max_len))
        self._step = jax.jit(self.decode.raw_step)     # hit (no sync check)
        self._mask = jax.jit(self.decode.sync_mask)
        self._sync = jax.jit(self.decode.maybe_sync)   # miss (compacted)
        self._chunk = jax.jit(
            functools.partial(decode_chunk, self.decode),
            static_argnames=("n_steps",))
        self.stats: List[StepStats] = []
        self._warm: set = set()    # (kind, shape-signature) seen -> compiled

    def _stat(self, kind: str, seconds: float, sig: Any = None,
              tokens: int = 1) -> None:
        """Record a StepStats entry, tagging the first dispatch of each
        (kind, signature) as ``compiled`` so aggregations can drop the
        one-time jit cost."""
        self.stats.append(StepStats(kind, seconds, tokens=tokens,
                                    compiled=tag_compiled(self._warm, kind,
                                                          sig)))

    def _select(self, logits: jax.Array) -> jax.Array:
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self.key, sub = jax.random.split(self.key)
        return jax.random.categorical(
            sub, logits / self.temperature, axis=-1).astype(jnp.int32)

    # ------------------------------------------------------------------
    def generate(self, batch: Dict[str, Any], n_tokens: int,
                 record_stats: bool = False) -> np.ndarray:
        """batch: prompt inputs (same-length prompts).  Returns
        (B, n_tokens) generated ids."""
        t0 = time.perf_counter()
        logits, state = jax.block_until_ready(
            self._prefill(self.params, batch))
        if record_stats:
            self._stat("prefill", time.perf_counter() - t0,
                       sig=batch["tokens"].shape)
        token = self._select(logits)
        if record_stats:
            return self._generate_instrumented(state, token, n_tokens)
        return self._generate_chunked(state, token, n_tokens)

    def _generate_chunked(self, state, token, n_tokens: int) -> np.ndarray:
        """Fast path: the remaining n_tokens - 1 steps run as ONE jitted
        lax.scan — the compacted resync fires inside the scanned step, so
        there are zero per-token host syncs."""
        B = token.shape[0]
        temps = jnp.full((B,), self.temperature, jnp.float32)
        active = jnp.ones((B,), bool)
        self.key, sub = jax.random.split(self.key)
        toks, state, _ = self._chunk(self.params, state, token, sub, temps,
                                     active, n_steps=n_tokens - 1)
        return np.concatenate(
            [np.asarray(token)[:, None], np.asarray(toks)], axis=1)

    def _generate_instrumented(self, state, token, n_tokens: int
                               ) -> np.ndarray:
        """Reference path: one dispatch per token, resync decided on host,
        so each hit/miss is timed separately (paper Fig 8)."""
        out = [token]
        for _ in range(n_tokens - 1):
            if bool(np.asarray(self._mask(state)).any()):
                t0 = time.perf_counter()
                state = jax.block_until_ready(
                    self._sync(self.params, state))
                self._stat("miss", time.perf_counter() - t0)
            t0 = time.perf_counter()
            logits, state = jax.block_until_ready(
                self._step(self.params, state, token))
            self._stat("hit", time.perf_counter() - t0)
            token = self._select(logits)
            out.append(token)
        return np.stack([np.asarray(t) for t in out], axis=1)

    # ------------------------------------------------------------------
    def generate_speculative(self, batch: Dict[str, Any], n_tokens: int,
                             k: int = 4, drafter: Optional[Any] = None
                             ) -> np.ndarray:
        """Speculative generation, token-identical to :meth:`generate`:
        each round drafts k tokens per row (``drafter``; default: a
        fresh per-row :class:`~repro.serving.speculative.NGramDrafter`)
        and verifies them in ONE ``spec_chunk`` dispatch, committing the
        verify-exact accepted prefix + bonus token.

        GREEDY ONLY: the Engine samples with one SHARED batch key, and a
        shared-key categorical draw at verify position c depends on the
        whole batch's acceptance positions — it cannot be replayed
        exactly.  The scheduler path (per-slot key chains) is exact for
        any temperature; here a ``sample_temperature > 0`` raises.
        Rows that reach ``n_tokens`` freeze (bit-identically) while
        stragglers catch up.  Returns (B, n_tokens) ids; the round
        count of the last call lands in ``self.spec_rounds``."""
        if self.temperature > 0.0:
            raise ValueError(
                "speculative Engine generation is greedy-only: the "
                "shared batch sampling key cannot replay per-row "
                "accepted positions exactly — use the SlotScheduler "
                "(per-slot key chains) for sampled speculative decoding")
        if not self.decode.supports_speculative():
            raise ValueError(
                "this model family cannot decode speculatively "
                "(recurrent state cannot roll back)")
        prompt = np.asarray(batch["tokens"])
        need = prompt.shape[1] + n_tokens + 2 * k + 1
        if need > self.max_len:
            raise ValueError(
                f"prompt {prompt.shape[1]} + n_tokens {n_tokens} + "
                f"speculative headroom {2 * k + 1} exceeds max_len "
                f"{self.max_len}")
        from repro.serving.speculative import NGramDrafter
        logits, state = jax.block_until_ready(
            self._prefill(self.params, batch))
        token = self._select(logits)
        B = token.shape[0]
        if drafter is None:
            drafter = NGramDrafter(B)
        spec = getattr(self, "_spec", None)
        if spec is None:
            spec = jax.jit(functools.partial(spec_chunk, self.decode))
            self._spec = spec
        t_host = np.asarray(token)
        out: List[List[int]] = [[int(t_host[b])] for b in range(B)]
        for b in range(B):
            drafter.admit(b, prompt[b].tolist() + [int(t_host[b])])
        temps = jnp.full((B,), self.temperature, jnp.float32)
        rounds = 0
        while min(len(o) for o in out) < n_tokens:
            active = jnp.asarray(
                np.array([len(o) < n_tokens for o in out]))
            draft = drafter.propose_batch(k)
            t0 = time.perf_counter()
            toks, m, token, state, self.key = spec(
                self.params, state, token, jnp.asarray(draft), self.key,
                temps, active)
            hm = np.asarray(m)
            ht = np.asarray(toks)
            self._stat("spec_chunk", time.perf_counter() - t0,
                       tokens=int(hm.sum()))
            for b in range(B):
                if hm[b]:
                    acc = ht[b, :hm[b]].tolist()
                    out[b].extend(acc)
                    drafter.observe(b, acc)
            rounds += 1
        self.spec_rounds = rounds
        return np.asarray([o[:n_tokens] for o in out], np.int32)

    # ------------------------------------------------------------------
    def time_chunked_decode(self, batch: Dict[str, Any], n_tokens: int
                            ) -> float:
        """Wall-clock seconds of the (n_tokens - 1)-token decode chunk
        alone — ONE dispatch, prefill and compile excluded.  This is the
        per-token quantity that is O(1) in context length for tconst."""
        logits, state = jax.block_until_ready(
            self._prefill(self.params, batch))
        token = self._select(logits)
        B = token.shape[0]
        temps = jnp.full((B,), self.temperature, jnp.float32)
        active = jnp.ones((B,), bool)
        self.key, sub = jax.random.split(self.key)
        args = (self.params, state, token, sub, temps, active)
        jax.block_until_ready(
            self._chunk(*args, n_steps=n_tokens - 1))    # warm-up/compile
        t0 = time.perf_counter()
        jax.block_until_ready(self._chunk(*args, n_steps=n_tokens - 1))
        return time.perf_counter() - t0

    # ------------------------------------------------------------------
    def cache_bytes(self, batch_size: int) -> int:
        """KV-cache footprint at max_len (paper Fig 8g) in the engine's
        physical layout, from the DecodeState kv/bookkeeping partition
        (no allocation)."""
        state = jax.eval_shape(
            lambda: self.decode.init_state(batch_size, self.max_len))
        return state.kv_bytes()
