"""Speculative decoding: the Drafter seam.

The paper's amortized-O(1) cache-hit step is memory-bound — each token
pays a full weight/KV read for one token of arithmetic — so the next
raw-speed multiplier is to propose k tokens cheaply and VERIFY them in
one fixed-shape dispatch (``DecodeAPI.verify_chunk``).  The contract is
verify-exactness: a draft token is accepted iff it equals the token the
sequential decode would have sampled there (``spec_chunk`` replays the
slot's key chain against the verify logits), so speculation can change
wall-clock only — never a stream.  Draft QUALITY therefore only moves
the acceptance rate; a garbage drafter still makes one token of
progress per round (the bonus token IS the sequential sample).

Two drafters ship:

* :class:`NGramDrafter` — self-drafting from the session's own resident
  token window: the continuation after the last previous occurrence of
  the trailing n-gram.  Zero model cost, surprisingly strong on
  repeat-heavy text (code, transcripts, structured output).
* :class:`TConstModelDrafter` — a reduced small-W tconst model
  (Katharopoulos-style small-state recurrence is the motivation: the
  drafter's O(1) cache makes its k steps cheap) with its OWN
  ``DecodeState``, caught up on accepted tokens by forced decode steps
  (bucketed fixed shapes) and rolled forward k greedy steps to propose.
  Exactness never depends on its weights — they may be random.

The scheduler drives the per-slot protocol: ``admit`` (prompt at
admission/resume), ``observe`` (accepted tokens after each verify
round), ``release`` (slot freed / spilled), ``propose_batch`` (one
(slots, k) proposal per round).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

__all__ = ["Drafter", "NGramDrafter", "TConstModelDrafter", "get_drafter"]


class Drafter:
    """Per-slot draft proposer (host-side protocol object).

    Implementations keep whatever per-slot state they need, keyed by
    slot index; the scheduler guarantees ``admit``/``release`` bracket a
    slot's residency and ``observe`` carries exactly the accepted
    (delivered) tokens in stream order — so a drafter's view of slot s
    is always a prefix-faithful copy of the session's token history.
    """

    name = "base"

    def admit(self, slot: int, tokens: Sequence[int]) -> None:
        """Slot ``slot`` begins a residency with token history
        ``tokens`` (prompt + any tokens generated before a spill)."""
        raise NotImplementedError

    def observe(self, slot: int, tokens: Sequence[int]) -> None:
        """Accepted tokens appended to slot ``slot``'s stream."""
        raise NotImplementedError

    def release(self, slot: int) -> None:
        """Slot freed (retire or spill): drop its state."""
        raise NotImplementedError

    def propose_batch(self, k: int) -> np.ndarray:
        """(slots, k) int32 proposals — every slot, every round (empty
        slots propose garbage; the scheduler masks them out)."""
        raise NotImplementedError


class NGramDrafter(Drafter):
    """Self-drafting from the resident window: propose the continuation
    that followed the LAST previous occurrence of the trailing n-gram
    (orders ``3, 2, 1``), falling back to repeating the final token.
    The search window is bounded (``window`` trailing tokens) so a
    round's host cost is O(slots * window)."""

    name = "ngram"

    def __init__(self, slots: int, window: int = 512,
                 orders: Sequence[int] = (3, 2, 1)):
        self.slots = slots
        self.window = window
        self.orders = tuple(orders)
        self._hist: List[Optional[List[int]]] = [None] * slots

    def admit(self, slot: int, tokens: Sequence[int]) -> None:
        self._hist[slot] = [int(t) for t in tokens][-self.window:]

    def observe(self, slot: int, tokens: Sequence[int]) -> None:
        h = self._hist[slot]
        if h is None:
            return
        h.extend(int(t) for t in tokens)
        if len(h) > self.window:
            del h[:len(h) - self.window]

    def release(self, slot: int) -> None:
        self._hist[slot] = None

    def _propose_one(self, h: List[int], k: int) -> List[int]:
        if not h:
            return [0] * k
        for n in self.orders:
            if len(h) <= n:
                continue
            suffix = h[-n:]
            # last previous occurrence of the trailing n-gram (ending
            # strictly before the end, so it has a continuation)
            for i in range(len(h) - n - 1, -1, -1):
                if h[i:i + n] == suffix:
                    cont = h[i + n:i + n + k]
                    if cont:
                        return (cont + [cont[-1]] * k)[:k]
                    break
        return [h[-1]] * k

    def propose_batch(self, k: int) -> np.ndarray:
        out = np.zeros((self.slots, k), np.int32)
        for s, h in enumerate(self._hist):
            if h is not None:
                out[s] = self._propose_one(h, k)
        return out


class TConstModelDrafter(Drafter):
    """Model drafter: a reduced small-W tconst config with its own O(1)
    decode state, one slot per scheduler slot.  Catch-up feeds pending
    tokens (prompt at admit, accepted tokens after each round) through
    FORCED decode steps — bucketed to power-of-two lengths so the
    compile count stays logarithmic — then ``propose_batch`` snapshots
    the state and rolls k greedy steps forward (the snapshot is simply
    not kept, so mispredicted draft steps never corrupt catch-up
    state).  Weights are randomly initialised by default: verify-
    exactness makes draft quality a THROUGHPUT knob, not a correctness
    one, and the harness exploits that to test the machinery without a
    trained checkpoint."""

    name = "tconst"

    def __init__(self, slots: int, vocab: int, max_len: int,
                 seed: int = 0, params: Any = None,
                 cfg: Any = None):
        import jax
        import jax.numpy as jnp
        from repro.config import get_config, reduced
        from repro.models.api import build_decode, build_model
        self.slots = slots
        self.max_len = max_len
        if cfg is None:
            cfg = reduced(get_config("tconst_41m"), dtype="float32",
                          vocab_size=vocab)
        self.cfg = cfg
        self.decode = build_decode(cfg)
        if params is None:
            params = build_model(cfg).init(jax.random.PRNGKey(seed))
        self.params = params
        self.state = self.decode.init_state(slots, max_len)
        self._fresh = self.state
        self._clear_jit = jax.jit(
            lambda st, keep: st.where_rows(keep, self._fresh))
        # host-side pending (not-yet-fed) tokens + fed counts per slot
        self._pending: List[List[int]] = [[] for _ in range(slots)]
        self._fed = np.zeros((slots,), np.int64)
        self._active = np.zeros((slots,), bool)
        self._last = np.zeros((slots,), np.int32)
        self._jits: Dict[int, Any] = {}
        self._draft_jit = jax.jit(self._draft, static_argnames=("k",))
        self._jnp = jnp

    # -- jitted bodies ---------------------------------------------------
    def _catchup(self, params, state, toks, n_valid, active):
        """Force-feed ``toks`` (B, T): step c feeds toks[:, c] for rows
        with c < n_valid; other rows freeze bit-identically."""
        import jax
        jnp = self._jnp

        def body(c, state):
            live = jnp.logical_and(active, c < n_valid)
            _, state = self.decode.step(params, state, toks[:, c], live)
            return state

        return jax.lax.fori_loop(0, toks.shape[1], body, state)

    def _draft(self, params, state, last, k: int):
        """k greedy steps from ``state`` (state is discarded by the
        caller — the snapshot semantics)."""
        import jax
        jnp = self._jnp

        def body(carry, _):
            state, tok = carry
            logits, state = self.decode.step(params, state, tok)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return (state, nxt), nxt

        (_, _), toks = jax.lax.scan(body, (state, last), None, length=k)
        return jnp.moveaxis(toks, 0, 1)

    # -- protocol --------------------------------------------------------
    def admit(self, slot: int, tokens: Sequence[int]) -> None:
        import jax.numpy as jnp
        keep = np.ones((self.slots,), bool)
        keep[slot] = False
        self.state = self._clear_jit(self.state, jnp.asarray(keep))
        self._pending[slot] = [int(t) for t in tokens]
        self._fed[slot] = 0
        self._active[slot] = True

    def observe(self, slot: int, tokens: Sequence[int]) -> None:
        if self._active[slot]:
            self._pending[slot].extend(int(t) for t in tokens)

    def release(self, slot: int) -> None:
        self._pending[slot] = []
        self._fed[slot] = 0
        self._active[slot] = False

    def _flush(self) -> None:
        """Catch every active slot up on its pending tokens, bucketed."""
        import jax.numpy as jnp
        # overflow guard: a slot whose history outgrows the drafter's
        # buffers stops being modelled (repeat-last fallback) — the
        # served model's exactness is unaffected
        for s in range(self.slots):
            if self._active[s] and \
                    self._fed[s] + len(self._pending[s]) > self.max_len - 1:
                self._active[s] = False
                self._pending[s] = []
        longest = max((len(p) for s, p in enumerate(self._pending)
                       if self._active[s]), default=0)
        if not longest:
            return
        T = 1
        while T < longest:
            T *= 2
        toks = np.zeros((self.slots, T), np.int32)
        n_valid = np.zeros((self.slots,), np.int32)
        run = np.zeros((self.slots,), bool)
        for s in range(self.slots):
            if self._active[s] and self._pending[s]:
                p = self._pending[s]
                toks[s, :len(p)] = p
                n_valid[s] = len(p)
                run[s] = True
                self._last[s] = p[-1]
                self._fed[s] += len(p)
                self._pending[s] = []
        import jax
        fn = self._jits.get(T)
        if fn is None:
            fn = jax.jit(self._catchup)
            self._jits[T] = fn
        self.state = fn(self.params, self.state, jnp.asarray(toks),
                        jnp.asarray(n_valid), jnp.asarray(run))

    def propose_batch(self, k: int) -> np.ndarray:
        import jax.numpy as jnp
        self._flush()
        if not self._active.any():
            return np.zeros((self.slots, k), np.int32)
        draft = self._draft_jit(self.params, self.state,
                                jnp.asarray(self._last), k=k)
        out = np.array(draft, np.int32)          # copy: jax arrays are read-only
        out[~self._active] = 0
        return out


def get_drafter(name: str, *, slots: int, vocab: int, max_len: int,
                seed: int = 0) -> Drafter:
    """Factory behind ``serve.py --drafter``."""
    if name == "ngram":
        return NGramDrafter(slots)
    if name == "tconst":
        return TConstModelDrafter(slots, vocab=vocab, max_len=max_len,
                                  seed=seed)
    raise ValueError(f"unknown drafter {name!r} (expected ngram|tconst)")
