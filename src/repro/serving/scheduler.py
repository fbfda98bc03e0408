"""Slot-based continuous-batching scheduler for streaming inference.

The scheduler owns one fixed-shape multi-slot ``DecodeState`` and admits
/ evicts :class:`~repro.serving.session.Session` objects mid-flight:

* **admit** — a free slot is filled by ``prefill_into_slot``: the
  session's prompt (its own length; compiled once per distinct length)
  is prefilled as a single dense row and scattered into the batched
  state.  Running slots are untouched, so a new request joins a
  half-decoded batch without disturbing it.  Under a **paged** cache
  layout the scheduler is also the page allocator: admission assigns
  just enough pool pages to cover the session's prompt + budget (the
  page table is host-side slot surgery), and a session whose pages
  aren't available yet waits in the queue — later queued sessions that
  DO fit are admitted past it (bounded skip-ahead, so the head cannot
  be starved) — so a pool sized well below ``slots * max_len`` serves
  short sessions at a fraction of the dense footprint.
* **prefix sharing (copy-on-write)** — with ``prefix_sharing=True`` the
  scheduler keeps a host-side content-addressed map from page-aligned
  prompt-token chunks to resident pool pages, with per-page refcounts.
  A session whose prompt prefix matches resident pages MAPS them into
  its ``layout__page_table`` instead of re-allocating and re-writing
  them (the admission scatter is masked to the unshared tail), so S
  sessions sharing a system prompt store its KV once.  Pages are
  writable only at refcount 1: before a chunk in which a slot's
  periodic resync may fire (``DecodeAPI.sync_anticipated``), its shared
  pages are FORKED to fresh pool pages (device-side copy, table
  surgery) — token appends never target shared pages by construction
  (only pages wholly inside ``stable_prefix_len`` enter the map).
  ``_release`` decrements refcounts; a page returns to ``free_pages``
  (and leaves the map) only at refcount 0.
* **decode** — all slots advance together in chunks of ``chunk_size``
  tokens.  A chunk is ONE jitted ``lax.scan`` over the fused step: the
  TConst W_og resync fires on device through the compacted row-wise
  ``sync_rows`` (each boundary row synced at batch size 1 — slots do
  not pay for each other's misses), so a chunk performs zero per-token
  host round-trips (one device->host transfer per chunk, for the
  sampled ids).  A slot that samples its session's EOS id sets the
  on-device ``done`` flag and is frozen for the rest of the chunk.
* **retire** — a session that exhausts its budget or hits EOS frees its
  slot at the chunk boundary (the slot's page-table row is retargeted
  at TRASH before the clearing write, so clearing can never land on a
  page another slot still references; pages whose refcount hits 0
  return to the free pool).
* **session tiering (spill / resume)** — with a
  :class:`~repro.serving.tier_store.TierStore` attached, a preempted or
  idle session SPILLS: its entire slot state is snapshotted in the
  physical representation (``DecodeState.snapshot_slot`` — int8 stays
  compressed, paged gathers only the live pages), stored host-side
  under a content digest of the session, and its slot + pool pages are
  freed.  A later admission RESUMES it into ANY free slot with one
  jitted scatter — token-identical to never having left.  With
  ``preempt_chunks=k``, slots holding their residency for >= k chunks
  are spilled round-robin whenever sessions wait, so sessions >> slots
  makes progress fairly.  The same store content-addresses two more
  things by construction: refcount-0 prefix pages RETIRE into it under
  their rolling-hash chunk keys (re-adopted — one page upload — on a
  later admission instead of re-forwarded), and families whose
  admission is a pure function of the prompt (tconst: the O(N) resync)
  cache the post-admission slot snapshot by prompt digest, so a known
  prompt re-admits as an O(1) restore with ZERO forward compute.

Chunk timings are recorded as ``StepStats(kind="chunk")`` entries (and
spills as ``kind="spill"``), admissions as ``StepStats(kind="admit")``
in ``admit_stats`` with ``source`` naming where the slot state came
from ("cold" / "resume" / "store"); entries whose wall-clock includes a
one-time jit compile carry ``compiled=True`` so aggregations
(``benchmarks/bench_inference``) can exclude them.  Each entry carries
its ``start`` on ``time.perf_counter``; an admission's also its
``sid``, queue wait and whole span (``queued_s``, ``total_s``).  The
phases of ``step`` are ``serve.*`` host spans (``engine.span``) and
every jitted program has a stable module name, so a ``jax.profiler``
trace shows what the host was doing while the device idled
(``docs/serving.md``, "Tracing").

Two later additions layer policy on top of this mechanism:

* **pluggable scheduling policy** — the WHICH decisions (admission try
  order, pool-pressure deferral, preemption victims) live in a
  :class:`~repro.serving.policy.SchedulingPolicy`; the scheduler keeps
  the invariants (arrival-order queue, bounded overtake budget counted
  per admission past the oldest waiter — resume-sourced or cold — page
  refcounts, spill correctness) so no policy can starve or corrupt a
  session.  ``clock`` counts completed ``step()`` calls — the
  deterministic time base for SLO deadlines and telemetry
  (:class:`~repro.serving.metrics.ServingTelemetry` attaches via the
  ``telemetry`` argument and observes submit/admit/spill/token/retire).
* **per-session sampling chains** — each slot samples with its own PRNG
  key chain seeded from the session (``Session.seed``, or the scheduler
  seed folded with ``sid``), advanced once per generated token and
  carried across spill/resume, so a session's stream is a pure function
  of the session itself: replaying a workload trace is token-identical
  across runs, slot placements and scheduling policies.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import time
from typing import Any, Deque, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layouts as LT
from repro.models.api import (DecodeAPI, decode_chunk, sample_tokens,
                              spec_chunk)
from repro.serving.engine import StepStats, span, tag_compiled
from repro.serving.metrics import ServingTelemetry
from repro.serving.policy import FifoPolicy, SchedulingPolicy, get_policy
from repro.serving.session import Session
from repro.serving.speculative import Drafter, get_drafter
from repro.serving.tier_store import (Blob, TierStore, flatten_slot_snapshot,
                                      unflatten_slot_snapshot)


def _jit(name: str, fn, **kw):
    """``jax.jit`` of ``fn`` named ``name``: its program is the module
    ``jit_<name>`` in a profile, where a partial or a lambda would read
    ``jit__unknown`` or ``jit__lambda``."""
    fn.__name__ = name
    return jax.jit(fn, **kw)


class SlotScheduler:
    def __init__(self, decode: DecodeAPI, params: Any, slots: int,
                 max_len: int, chunk_size: int = 8, seed: int = 0,
                 prefix_sharing: bool = False,
                 max_head_skips: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 tier_store: Optional[TierStore] = None,
                 preempt_chunks: Optional[int] = None,
                 policy: Union[SchedulingPolicy, str, None] = None,
                 telemetry: Optional[ServingTelemetry] = None,
                 speculate: int = 0,
                 drafter: Union["Drafter", str, None] = None):
        # accept a ModelAPI facade too (duck-typed .decode)
        if not isinstance(decode, DecodeAPI) and hasattr(decode, "decode"):
            decode = decode.decode
        if slots < 1:
            raise ValueError("scheduler needs at least one decode slot")
        if chunk_size < 1:
            raise ValueError("chunk_size must be positive")
        if speculate < 0:
            raise ValueError("speculate must be >= 0 draft tokens")
        if speculate and not decode.supports_speculative():
            raise ValueError(
                "this model family cannot decode speculatively: rolling "
                "back rejected drafts needs state that is a pure function "
                "of a truncation point (recurrent ssm/conv state is not)")
        self.decode = decode
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.chunk_size = chunk_size
        # speculative decoding: one step() = one draft/verify round of
        # up to speculate + 1 tokens per live slot (the headroom both
        # the token buffer and the page reservation must carry)
        self.speculate = int(speculate)
        self._headroom = max(chunk_size, self.speculate + 1)
        self.drafter: Optional[Drafter] = None
        if self.speculate:
            if drafter is None:
                drafter = "ngram"
            if isinstance(drafter, str):
                drafter = get_drafter(drafter, slots=slots,
                                      vocab=decode.cfg.vocab_size,
                                      max_len=max_len, seed=seed)
            self.drafter = drafter
            self._spec = _jit("spec_chunk",
                              functools.partial(spec_chunk, decode))
        # chunked KV-conditioned admission: default rides on the decode
        # protocol (build_decode(prefill_chunk=...)); None = one-shot
        # full-prompt prefill (one compile per distinct prompt length)
        if prefill_chunk is None:
            prefill_chunk = getattr(decode, "prefill_chunk", None)
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError("prefill_chunk must be positive (or None "
                             "for one-shot admission)")
        self.prefill_chunk = prefill_chunk

        self.state = decode.init_state(slots, max_len)
        self.layout = self.state.layout
        # prefilled rows are always dense; slot scatter goes through the
        # batched state's layout (paged: page-map surgery)
        dense_decode = dataclasses.replace(decode, layout=LT.DENSE_SPEC)
        self._empty_row = dense_decode.init_state(1, max_len)
        self._prefill_slot = jax.jit(decode.prefill_into_slot)
        self._chunk = _jit("decode_chunk",
                           functools.partial(decode_chunk, decode),
                           static_argnames=("n_steps",))
        self._clear = _jit("clear_slot",
                           lambda st, slot, row: st.with_slot(slot, row))

        # paged layout: the scheduler owns page assignment.  Start from an
        # all-TRASH table (a real page is writable iff its refcount is 1 —
        # the invariant the pack/scatter and the CoW fork rely on) with
        # every pool page free.  Page accounting only applies when the
        # cache actually HAS paged fields — for caches that are already
        # O(1) (pure tconst) the paged layout stores nothing in pages and
        # admission must not gate on the pool.
        self._paged = isinstance(self.layout, LT.PagedLayout) and \
            self.layout.pages_anything(self.state.kv)
        self.free_pages: List[int] = []
        self._slot_pages: List[List[int]] = [[] for _ in range(slots)]
        self._page_ref = np.zeros((0,), np.int32)
        if self._paged:
            trash = jnp.full((slots, self.layout.pages_per_slot),
                             self.layout.trash, jnp.int32)
            self.state = self.state.with_bookkeeping(**{LT.PAGE_TABLE: trash})
            self.free_pages = list(range(self.layout.pool_pages))
            self._page_ref = np.zeros((self.layout.pool_pages,), np.int32)
            self._fork = _jit(
                "fork_pages", lambda st, src, dst: dataclasses.replace(
                    st, kv=self.layout.fork_pages(st.kv, src, dst)))
        if self.prefill_chunk is not None and self._paged and \
                self.prefill_chunk % self.layout.page != 0:
            raise ValueError(
                f"prefill_chunk {self.prefill_chunk} must be a multiple "
                f"of the page size {self.layout.page} — chunk-granular "
                f"page writes cover whole pages")

        self.prefix_sharing = bool(prefix_sharing) and self._paged
        self._prefix_map: Dict[bytes, int] = {}   # chunk-chain key -> page
        self._page_key: Dict[int, bytes] = {}     # page -> its map key
        # a resyncing model (tconst/tlin) eventually FORKS every page it
        # adopted, so a sharing admission must reserve that headroom up
        # front — otherwise admission could overcommit the pool into a
        # state where no slot can ever fork (LM families never fork)
        self._fork_reserve = self.prefix_sharing and bool(
            np.any(self.decode.sync_anticipated(self.state, 1 << 30)))
        self._key_cache: Dict[int, List[bytes]] = {}   # sid -> chunk keys
        # bounded skip-ahead: how many sessions may be admitted past a
        # page-blocked queue head before admission stops overtaking it
        # (freed pages then necessarily reach the head: eventual FIFO)
        self.max_head_skips = 4 * slots if max_head_skips is None \
            else max_head_skips
        self._head_skips = 0

        # session tiering: host-side content-addressed store + preemption
        if preempt_chunks is not None and preempt_chunks < 1:
            raise ValueError("preempt_chunks must be positive (or None to "
                             "disable preemptive spilling)")
        if preempt_chunks is not None and tier_store is None:
            raise ValueError("preempt_chunks needs a tier_store to spill "
                             "preempted sessions into")
        self.store = tier_store
        self.preempt_chunks = preempt_chunks
        self.spill_stats = {"spills": 0, "resumes": 0, "spilled_bytes": 0,
                            "pages_retired": 0, "pages_readopted": 0,
                            "admit_store_hits": 0, "admit_store_puts": 0}
        # chunks each slot has decoded since its current residency began
        # (admit/resume resets it) — the preemption ripeness clock
        self._slot_chunks = np.zeros((slots,), np.int64)
        if self._paged:
            self._page_axes = {f: self.layout.page_axis(f)
                               for f in self.state.kv
                               if self.layout.page_axis(f) is not None}
        else:
            self._page_axes = {}
        if self.store is not None:
            self._snap = _jit("snapshot_slot",
                              lambda st, slot: st.snapshot_slot(slot))
            self._restore = _jit(
                "restore_slot",
                lambda st, slot, snap: st.restore_slot(slot, snap))
            if self._paged:
                self._gather_pages = _jit(
                    "gather_pages",
                    lambda st, idx: self.layout.gather_pages(st.kv, idx))
                self._scatter_pages = _jit(
                    "scatter_pages",
                    lambda st, idx, contents: dataclasses.replace(
                        st, kv=self.layout.scatter_pages(st.kv, idx,
                                                         contents)))

        # per-slot sampling key chains: row i is the NEXT key of the
        # session in slot i, advanced on device once per live decode
        # step (decode_chunk's per-slot mode) and seeded per session at
        # admission — never from slot position or batch composition.
        self._base_key = jax.random.PRNGKey(seed)
        self.slot_keys = jnp.zeros((slots, 2), jnp.uint32)
        self.last_token = jnp.zeros((slots,), jnp.int32)
        self.temps = np.zeros((slots,), np.float32)
        self.eos = np.full((slots,), -1, np.int32)
        self.active = np.zeros((slots,), bool)
        self.sessions: List[Optional[Session]] = [None] * slots
        self.pending: Deque[Session] = collections.deque()
        self.stats: List[StepStats] = []
        self.admit_stats: List[StepStats] = []
        self._warm: set = set()       # (kind, signature) -> compiled tag

        # policy seam + telemetry + deterministic clock (chunk units)
        if policy is None:
            policy = FifoPolicy()
        elif isinstance(policy, str):
            policy = get_policy(policy)
        self.policy = policy
        self.telemetry = telemetry
        self.clock = 0                # completed step() calls

    # ------------------------------------------------------------------
    def _pages_needed(self, session: Session) -> int:
        need = len(session.prompt) + session.max_new_tokens + self._headroom
        return -(-need // self.layout.page)

    def submit(self, session: Session) -> Session:
        """Queue a session; it is admitted at the next chunk boundary."""
        # decode writes token ids into the slot's fixed (max_len,) buffer;
        # an overflowing write would be silently dropped by the scatter and
        # corrupt the next resync, so reject oversized requests up front
        # (headroom: a session may overshoot its budget by up to one
        # chunk — or one speculate+1 verify round — before it is retired
        # at the boundary, and a verify round WRITES all speculate+1
        # positions before acceptance truncates).
        need = len(session.prompt) + session.max_new_tokens + self._headroom
        if need > self.max_len:
            raise ValueError(
                f"session {session.sid}: prompt {len(session.prompt)} + "
                f"max_new_tokens {session.max_new_tokens} (+ headroom "
                f"{self._headroom}) exceeds max_len {self.max_len}")
        # total-pool capacity check: a session needing more pages than the
        # POOL holds would pass a max_len-only check but could never be
        # admitted, leaving run() to spin on it forever
        if self._paged and \
                self._pages_needed(session) > self.layout.pool_pages:
            raise ValueError(
                f"session {session.sid}: needs {self._pages_needed(session)}"
                f" pages but the paged pool only has "
                f"{self.layout.pool_pages} — it could never be admitted")
        session.submit_clock = self.clock
        session.submit_wall = time.perf_counter()
        self.pending.append(session)
        if self.telemetry is not None:
            self.telemetry.on_submit(session, self.clock)
        return session

    @property
    def n_active(self) -> int:
        return int(self.active.sum())

    def kv_bytes(self) -> int:
        """GLOBAL physical KV bytes — under a sharded pool this is the
        whole-fleet figure, not one shard's buffer (sharded jax Arrays
        report global shapes; regression-tested in
        ``tests/test_sharded_decode.py``)."""
        return self.state.kv_bytes()

    def assigned_kv_bytes(self) -> int:
        """KV bytes the live page tables reference — a prefix-shared
        page is counted once (see ``DecodeState.assigned_kv_bytes``).
        GLOBAL bytes under a sharded pool, identical to the 1-device
        run; telemetry pool-occupancy shares the same guarantee (its
        free/total page counts come from the host-side allocator, which
        tracks logical — global — pages)."""
        return self.state.assigned_kv_bytes()

    def state_bytes(self) -> Dict[str, int]:
        """The slots' state in two counters: ``fixed`` (recurrent
        ssm/conv state and other per-slot buffers whose size does not
        grow with the stream) and ``growing`` (the K/V pages the live
        tables reference); see ``DecodeState.state_bytes``.  Their sum
        is ``assigned_kv_bytes``."""
        return self.state.state_bytes(self.decode._LENGTH_AXES)

    def per_device_kv_bytes(self) -> int:
        """Largest per-device share of the physical KV buffers —
        ≈ ``kv_bytes() / model_shards`` for the head-sharded decode
        layout, equal to ``kv_bytes()`` unmeshed."""
        return self.state.per_device_kv_bytes()

    def page_refcounts(self) -> np.ndarray:
        """Host-side per-page refcounts (copy); all zeros when idle."""
        return self._page_ref.copy()

    def spill_cost(self, slot: int) -> Dict[str, int]:
        """Estimated cost of evicting the session in ``slot``, for
        cost-aware victim selection: ``bytes`` is the snapshot the spill
        would move to the host tier (paged: live pages only — a tconst
        slot's physical KV is O(1)-small; dense LM: the full per-slot
        row), ``readmit`` the bytes a LATER fresh admission of the same
        request would cost — zero for families whose admission is a pure
        function of the prompt (``DecodeAPI.admission_key`` non-None:
        re-admission is an O(1) store restore), else the snapshot again.
        Host-side arithmetic only — no device work."""
        session = self.sessions[slot]
        assert session is not None, "spill_cost needs an occupied slot"
        snap_bytes = 0
        if self._paged:
            live = self._live_pages(session)
            for f, v in self.state.kv.items():
                ax = self._page_axes.get(f)
                if ax is not None:
                    snap_bytes += (v.nbytes // v.shape[ax]) * live
                else:
                    snap_bytes += v.nbytes // self.slots
        else:
            snap_bytes = self.kv_bytes() // self.slots
        pure = self.decode.admission_key(session.prompt,
                                         session.extras) is not None
        readmit = 0 if pure else snap_bytes
        return {"bytes": int(snap_bytes), "readmit": int(readmit),
                "total": int(snap_bytes + readmit)}

    # ------------------------------------------------------------------
    # prefix sharing: content-addressed page map + refcounts
    # ------------------------------------------------------------------
    def _chunk_keys(self, session: Session) -> List[bytes]:
        """Rolling content-addressed keys for the page-aligned prompt
        chunks inside the session's stable prefix.  Key i covers
        ``prompt[:(i+1)*page]`` — KV content at a position is a causal
        function of ALL preceding tokens — salted with a digest of the
        per-request extras (encoder memory / vision inputs feed the
        same KV, so sessions with different extras must never match)."""
        cached = self._key_cache.get(session.sid)
        if cached is not None:
            return cached
        page = self.layout.page
        stable = self.decode.stable_prefix_len(len(session.prompt))
        n = min(stable, len(session.prompt)) // page
        h = hashlib.sha1()
        if session.extras:
            for name in sorted(session.extras):
                h.update(name.encode())
                h.update(np.asarray(session.extras[name]).tobytes())
        prompt = np.ascontiguousarray(session.prompt, np.int32)
        keys = []
        for i in range(n):
            h.update(prompt[i * page:(i + 1) * page].tobytes())
            keys.append(h.copy().digest())
        # prompt/extras are immutable after submit: memoize so a blocked
        # queue doesn't re-hash megabyte extras once per chunk
        self._key_cache[session.sid] = keys
        return keys

    def _register(self, key: bytes, page: int) -> None:
        self._prefix_map[key] = page
        self._page_key[page] = key

    def _unregister(self, page: int) -> None:
        key = self._page_key.pop(page, None)
        if key is not None:
            self._prefix_map.pop(key, None)

    def _set_table_row(self, slot: int, pages: List[int]) -> None:
        self._slot_pages[slot] = list(pages)
        row = np.full((self.layout.pages_per_slot,), self.layout.trash,
                      np.int32)
        row[:len(pages)] = pages
        pt = self.state.bookkeeping[LT.PAGE_TABLE].at[slot].set(
            jnp.asarray(row))
        self.state = self.state.with_bookkeeping(**{LT.PAGE_TABLE: pt})

    # ------------------------------------------------------------------
    # session tiering: spill / resume / retire through the TierStore
    # ------------------------------------------------------------------
    def _store_salt(self) -> bytes:
        """Scheduler-level key salt: snapshot shapes and admission
        numerics depend on max_len, the bound layout and the prefill
        path, so schedulers differing in any of them must never share
        store entries.  (Params identity is NOT hashed — a TierStore
        must not be shared across schedulers serving different
        weights.)"""
        return f"{self.max_len}|{self.layout!r}|{self.prefill_chunk}" \
            .encode()

    def _session_key(self, session: Session) -> bytes:
        """Content digest of a session's CURRENT state: extras + prompt
        + every token generated so far.  Two sessions at the same point
        of the same request share one snapshot entry (pin counts
        nest)."""
        h = hashlib.sha1(b"session\x00" + self._store_salt())
        if session.extras:
            for name in sorted(session.extras):
                h.update(name.encode())
                h.update(np.asarray(session.extras[name]).tobytes())
        h.update(np.ascontiguousarray(session.prompt, np.int32).tobytes())
        h.update(np.asarray(session.tokens, np.int32).tobytes())
        return h.digest()

    def _admission_key(self, session: Session) -> Optional[bytes]:
        """Store key of this request's post-admission slot state, or
        None when the family's admission is not a pure function of the
        prompt ids (``DecodeAPI.admission_key``) or there is no store."""
        if self.store is None:
            return None
        base = self.decode.admission_key(session.prompt, session.extras)
        if base is None:
            return None
        h = hashlib.sha1(b"admit\x00" + self._store_salt())
        h.update(base)
        return h.digest()

    def _live_pages(self, session: Session) -> int:
        """Pages that can hold WRITTEN content for this session right
        now (prompt + generated ids, one page-granule of slack) — the
        honest host-tier size of a paged spill; the pages beyond it in
        the slot's allocation hold nothing a restore needs."""
        need = len(session.prompt) + len(session.tokens) + 1
        return -(-need // self.layout.page)

    def _snapshot_slot_host(self, slot: int, n_keep: Optional[int] = None
                            ) -> Dict[str, Any]:
        """Device snapshot of ``slot`` pulled to host, with paged page
        stacks trimmed to the first ``n_keep`` table entries (the live
        prefix of the slot's allocation)."""
        snap = jax.device_get(self._snap(self.state, np.int32(slot)))
        if self._paged and n_keep is not None:
            n_keep = min(n_keep, len(self._slot_pages[slot]))
            for f, ax in self._page_axes.items():
                snap["kv"][f] = np.take(snap["kv"][f], np.arange(n_keep),
                                        axis=ax)
        return snap

    def _pad_kv_snapshot(self, kv: Dict[str, Any]) -> Dict[str, Any]:
        """Pad trimmed paged page stacks back to pages_per_slot (zeros —
        they scatter onto unwritten pages, masked until written) so the
        jitted restore has ONE fixed shape."""
        out = {}
        pps = self.layout.pages_per_slot if self._paged else 0
        for f, v in kv.items():
            ax = self._page_axes.get(f)
            if ax is not None and v.shape[ax] < pps:
                widths = [(0, 0)] * v.ndim
                widths[ax] = (0, pps - v.shape[ax])
                v = np.pad(np.asarray(v), widths)
            out[f] = jnp.asarray(v)
        return out

    def spill(self, slot: int) -> bytes:
        """Spill the active session in ``slot`` to the tier store:
        snapshot its entire slot state (physical representation — int8
        stays compressed, paged holds only live pages), PIN it under the
        session's content digest, free the slot and its pool pages, and
        re-queue the session.  A later admission restores it into ANY
        free slot, token-identical to never having left.  Returns the
        store key."""
        assert self.store is not None, "spilling needs a tier_store"
        session = self.sessions[slot]
        assert session is not None and not session.done, \
            "can only spill a live session"
        with span("spill", sid=session.sid):
            t0 = time.perf_counter()
            snap = self._snapshot_slot_host(
                slot, self._live_pages(session) if self._paged else None)
            blob = flatten_slot_snapshot(snap, {
                "kind": "session", "sid": session.sid,
                "last_token": int(np.asarray(self.last_token[slot]))})
            key = self._session_key(session)
            self.store.put(key, blob, pin=True)
            self.stats.append(StepStats(
                "spill", time.perf_counter() - t0,
                tokens=len(session.prompt) + len(session.tokens),
                compiled=tag_compiled(self._warm, "spill")))
            session.snap_key = key
            session.spills += 1
            session.slot = None
            # freeze the session's sampling chain at its current position
            # (= len(session.tokens)) so resume continues the exact stream
            session.sample_chain = np.asarray(self.slot_keys[slot])
            self.spill_stats["spills"] += 1
            self.spill_stats["spilled_bytes"] += blob.nbytes
            self._release(slot)
            self.pending.append(session)
            if self.telemetry is not None:
                self.telemetry.on_spill(session, self.clock)
        return key

    def _resume(self, session: Session, slot: int,
                plan: Dict[str, Any], t_plan: float) -> None:
        """Admission path for a spilled session: allocate fresh private
        pages, restore the pinned snapshot into ``slot`` with ONE jitted
        scatter, and unpin.  No prefill, no sampling — the session's
        last sampled token rides in the snapshot meta and decode picks
        up exactly where it left off."""
        blob = self.store.get(session.snap_key)
        assert blob is not None, \
            "pinned session snapshot disappeared from the tier store"
        bk_rows, kv_rows, meta = unflatten_slot_snapshot(blob)
        if self._paged:
            fresh = [self.free_pages.pop() for _ in range(plan["total"])]
            for p in fresh:
                self._page_ref[p] = 1
            self._set_table_row(slot, fresh)
        t0 = time.perf_counter()
        dev = {"bookkeeping": {n: jnp.asarray(np.asarray(v))
                               for n, v in bk_rows.items()},
               "kv": self._pad_kv_snapshot(kv_rows)}
        self.state = self._restore(self.state, np.int32(slot), dev)
        jax.block_until_ready(self.state.kv)
        self.admit_stats.append(StepStats(
            "admit", time.perf_counter() - t0,
            tokens=len(session.prompt) + len(session.tokens),
            compiled=tag_compiled(self._warm, "admit", ("resume",)),
            forward_tokens=0, source="resume", start=t_plan,
            sid=session.sid))
        self.store.unpin(session.snap_key)
        session.snap_key = None
        session.resumes += 1
        self.spill_stats["resumes"] += 1
        self.last_token = self.last_token.at[slot].set(
            np.int32(meta["last_token"]))
        # resume the sampling chain exactly where the spill froze it
        self.slot_keys = self.slot_keys.at[slot].set(
            jnp.asarray(session.sample_chain))
        session.sample_chain = None
        session.slot = slot
        self.sessions[slot] = session
        self.active[slot] = True
        self.temps[slot] = session.temperature
        self.eos[slot] = -1 if session.eos_id is None else session.eos_id
        self._slot_chunks[slot] = 0
        if self.drafter is not None:
            # re-seed the drafter with the full resumed stream
            self.drafter.admit(slot, list(session.prompt) + session.tokens)
        if self.telemetry is not None:
            self.telemetry.on_admit(session, self.clock, "resume")
        self.admit_stats[-1].total_s = time.perf_counter() - t_plan

    def _retire_pages(self, retiring: List) -> None:
        """Refcount-0 prefix pages RETIRE into the tier store instead of
        vanishing with their map entry (the pre-tiering bug): their
        content stays re-adoptable — LRU-evictable, unpinned — under the
        same rolling-hash chunk key, so residency in the memory
        hierarchy, not refcount, decides whether a later admission
        re-forwards the prefix.  ``retiring`` is [(page, key), ...] for
        pages ABOUT to be recycled; the gather runs before anything can
        reallocate them."""
        pps = self.layout.pages_per_slot
        idx = np.full((pps,), self.layout.trash, np.int32)
        for i, (p, _) in enumerate(retiring):
            idx[i] = p
        gathered = jax.device_get(
            self._gather_pages(self.state, jnp.asarray(idx)))
        for i, (_, key) in enumerate(retiring):
            arrays = {f: np.take(v, np.arange(i, i + 1),
                                 axis=self._page_axes[f])
                      for f, v in gathered.items()}
            self.store.put(key, Blob(arrays, {"kind": "page"}))
        self.spill_stats["pages_retired"] += len(retiring)

    def _fetch_restorable(self, keys: List[bytes]) -> List[Blob]:
        """Fetch the planned re-adoptable page blobs; a key that aged
        out between plan and admit just truncates the restorable run —
        the tail goes back to cold prefill (page counts are unchanged:
        restorable pages come from the free pool either way)."""
        blobs: List[Blob] = []
        for k in keys:
            b = self.store.get(k)
            if b is None:
                break
            blobs.append(b)
        return blobs

    def _upload_pages(self, page_ids: List[int],
                      blobs: List[Blob]) -> None:
        """Scatter retired-page content from the store onto freshly
        allocated pool pages (one fixed-arity jitted write) — the
        re-adoption that replaces re-forwarding the prefix."""
        pps = self.layout.pages_per_slot
        idx = np.full((pps,), self.layout.trash, np.int32)
        idx[:len(page_ids)] = page_ids
        contents = {}
        for f, ax in self._page_axes.items():
            stack = np.concatenate(
                [np.asarray(b.arrays[f]) for b in blobs], axis=ax)
            if stack.shape[ax] < pps:
                widths = [(0, 0)] * stack.ndim
                widths[ax] = (0, pps - stack.shape[ax])
                stack = np.pad(stack, widths)
            contents[f] = jnp.asarray(stack)
        self.state = self._scatter_pages(self.state, jnp.asarray(idx),
                                         contents)
        self.spill_stats["pages_readopted"] += len(blobs)

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    def _admission_plan(self, session: Session) -> Optional[Dict[str, Any]]:
        """The pages this admission would take, or None if it must wait
        for the free pool.  With prefix sharing, resident pages matching
        the session's prompt-prefix chunks are adopted instead of drawn
        from the free pool; with a tier store, chunk keys whose pages
        RETIRED are planned for re-adoption (fresh page + content
        upload) and a spilled session / store-hit prompt plans an
        all-fresh restore."""
        resume = session.snap_key is not None
        admit_key = None if resume else self._admission_key(session)
        # a restore scatters the WHOLE slot, so it must own every page
        # privately — no adoption; the store probe must not touch LRU
        admit_hit = admit_key is not None and admit_key in self.store
        if not self._paged:
            return {"total": 0, "adopted": [], "keys": [],
                    "restorable": [], "resume": resume,
                    "admit_key": admit_key, "admit_hit": admit_hit}
        total = self._pages_needed(session)
        keys = [] if (resume or admit_hit or not self.prefix_sharing) \
            else self._chunk_keys(session)
        adopted: List[int] = []
        for key in keys:
            page = self._prefix_map.get(key)
            if page is None:
                break
            adopted.append(page)
        # beyond the resident run, contiguous chunk keys whose pages
        # retired into the store are re-adoptable: they still need a
        # fresh page each (counted in total - adopted), plus an upload
        restorable: List[bytes] = []
        if self.store is not None:
            for key in keys[len(adopted):]:
                if key in self.store:
                    restorable.append(key)
                else:
                    break
        # resyncing models: adopted pages will be forked before this
        # slot's first resync, so their copies count against the pool now
        reserve = len(adopted) if self._fork_reserve else 0
        if total - len(adopted) + reserve > len(self.free_pages):
            return None                # wait for running sessions to retire
        return {"total": total, "adopted": adopted, "keys": keys,
                "restorable": restorable, "resume": resume,
                "admit_key": admit_key, "admit_hit": admit_hit}

    def _admit(self, session: Session, slot: int, plan: Dict[str, Any],
               t_plan: float) -> None:
        """Fill ``slot`` with ``session`` as ``plan`` says; ``t_plan``
        is when choosing it began (``perf_counter``), the start of its
        admission."""
        session.admit_wall = t_plan
        if plan.get("resume"):
            with span("resume"):
                self._resume(session, slot, plan, t_plan)
            return
        with span("admit.plan"):
            admit_blob = None
            if plan.get("admit_hit"):
                # fetch FIRST (nothing else touches the store before this):
                # None means the entry aged out since planning — the plan's
                # all-fresh pages make the cold path below still valid
                admit_blob = self.store.get(plan["admit_key"])
            mask = None
            n_resident = 0
            if self._paged:
                n_adopt = len(plan["adopted"])
                readopt = self._fetch_restorable(plan.get("restorable", [])) \
                    if admit_blob is None else []
                fresh = [self.free_pages.pop()
                         for _ in range(plan["total"] - n_adopt)]
                pages = list(plan["adopted"]) + fresh
                for p in plan["adopted"]:
                    self._page_ref[p] += 1
                for p in fresh:
                    self._page_ref[p] = 1
                self._set_table_row(slot, pages)
                n_rest = len(readopt)
                if n_rest:
                    # upload retired prefix-page content from the store onto
                    # this slot's fresh pages BEFORE the prefill, so the
                    # chunk loop attends it instead of re-forwarding it
                    self._upload_pages(pages[n_adopt:n_adopt + n_rest],
                                       readopt)
                n_resident = n_adopt + n_rest
                if self.prefix_sharing:
                    # register this prompt's freshly written stable pages so
                    # later sessions can adopt them (adopted ones already
                    # are; re-adopted ones re-enter the map resident)
                    for i, key in enumerate(plan["keys"]):
                        if key not in self._prefix_map:
                            self._register(key, pages[i])
                    if n_resident:
                        # tail-only admission write: resident pages hold the
                        # identical (content-addressed) KV already — and CoW
                        # says never write a page with refcount > 1
                        host_mask = np.ones((self.layout.pages_per_slot,),
                                            bool)
                        host_mask[:n_resident] = False
                        mask = jnp.asarray(host_mask)
        resident = n_resident * self.layout.page if self._paged else 0
        chunked = self.prefill_chunk is not None and \
            self.decode.supports_chunked_prefill(session.extras) and \
            self.decode.chunked_prefill_fits(
                len(session.prompt), resident, self.prefill_chunk,
                self.max_len)
        extras_sig = tuple(sorted(
            (k, tuple(np.shape(v))) for k, v in (session.extras or {}).items()))
        t0 = time.perf_counter()
        with span("admit.prefill"):
            if admit_blob is not None:
                # content-addressed admission-cache hit: the whole
                # post-prefill slot state (+ its logits) restores in ONE
                # jitted scatter — the O(N) resync/prefill never runs
                bk_rows, kv_rows, _ = unflatten_slot_snapshot(admit_blob)
                dev = {"bookkeeping": {n: jnp.asarray(np.asarray(v))
                                       for n, v in bk_rows.items()},
                       "kv": self._pad_kv_snapshot(kv_rows)}
                self.state = self._restore(self.state, np.int32(slot), dev)
                logits = jnp.asarray(np.asarray(admit_blob.arrays["logits"]))
                fwd = 0
                sig = ("admit_restore", extras_sig)
                source = "store"
                self.spill_stats["admit_store_hits"] += 1
            elif chunked:
                # KV-conditioned chunked admission: forward compute covers
                # only the unshared tail (adopted pages are attended, not
                # recomputed... except the one chunk the logits need), and
                # every dispatch has a fixed shape — the compile signature
                # is the BUCKET (chunk size x variants), not the prompt
                # length, so K distinct lengths share one compiled set.
                logits, self.state, info = \
                    self.decode.prefill_into_slot_chunked(
                        self.params, self.state, np.int32(slot),
                        session.prompt, extras=session.extras,
                        page_write_mask=mask, resident_len=resident,
                        chunk=self.prefill_chunk)
                fwd = info["forward_tokens"]
                sig = ("chunked", self.prefill_chunk, resident > 0,
                       mask is not None, extras_sig)
                source = "cold"
            else:
                logits, self.state = self._prefill_slot(
                    self.params, self.state, np.int32(slot),
                    jnp.asarray(session.prompt), extras=session.extras,
                    page_write_mask=mask)
                fwd = len(session.prompt)
                # the one-shot prefill retraces on any shape change: prompt
                # length, mask presence, AND extras shapes
                sig = (len(session.prompt), mask is not None, extras_sig)
                source = "cold"
            logits = jax.block_until_ready(logits)
        self._key_cache.pop(session.sid, None)
        stat = StepStats(
            "admit", time.perf_counter() - t0, tokens=len(session.prompt),
            compiled=tag_compiled(self._warm, "admit", sig),
            forward_tokens=fwd, source=source, start=t_plan,
            sid=session.sid, queued_s=t_plan - session.submit_wall)
        self.admit_stats.append(stat)
        if admit_blob is None and plan.get("admit_key") is not None:
            # cacheable cold admission: the just-admitted slot state is a
            # pure function of the prompt — snapshot it (pre-sampling)
            # with its logits so the NEXT admission of this prompt is an
            # O(1) restore.  Unpinned: LRU decides how long it lives.
            with span("admit.store"):
                snap = self._snapshot_slot_host(
                    slot, self._live_pages(session) if self._paged else None)
                blob = flatten_slot_snapshot(snap, {"kind": "admit"})
                blob.arrays["logits"] = np.asarray(logits)
                self.store.put(plan["admit_key"], blob)
                self.spill_stats["admit_store_puts"] += 1
        # per-session sampling chain: seeded from the session (never
        # from slot position / batch composition), advanced once here
        # for the first token and once per live step on device after —
        # so the chain position is always the generated-token count and
        # the stream replays identically across runs and policies.
        with span("admit.first_token"):
            chain = jax.random.PRNGKey(session.seed) \
                if session.seed is not None \
                else jax.random.fold_in(self._base_key, session.sid)
            pair = jax.random.split(chain)
            t0k = sample_tokens(logits[None],
                                jnp.full((1,), session.temperature),
                                pair[1][None])[0]
            self.slot_keys = self.slot_keys.at[slot].set(pair[0])
            self.last_token = self.last_token.at[slot].set(t0k)
        session.slot = slot
        self.sessions[slot] = session
        self.active[slot] = True
        self.temps[slot] = session.temperature
        self.eos[slot] = -1 if session.eos_id is None else session.eos_id
        self._slot_chunks[slot] = 0
        if self.telemetry is not None:
            self.telemetry.on_admit(session, self.clock, source)
        session.deliver([int(t0k)])          # first token: prefill logits
        if self.drafter is not None:
            # the drafter's window = prompt + everything delivered
            self.drafter.admit(slot, list(session.prompt) + session.tokens)
        if self.telemetry is not None:
            self.telemetry.on_tokens(session, len(session.tokens),
                                     self.clock, stat.compiled)
        stat.total_s = time.perf_counter() - t_plan

    def admit_pending(self) -> bool:
        """Admit as many pending sessions as free slots/pages allow.

        The policy proposes the try order (``order_pending``; FIFO for
        the baseline) and may defer admissible non-head sessions
        (``defer_admission``); the scheduler enforces fairness around
        it: EVERY admission of a session other than the arrival-order
        head — skip-ahead past a page-blocked head, policy reordering,
        or a resume-sourced re-admission of a spilled session — counts
        one overtake against ``max_head_skips``, and a spent budget
        forces strict arrival order until the head admits (freed pages
        then necessarily reach it: eventual FIFO, no starvation).  The
        overtake count is per admitted IDENTITY, not queue position —
        position-based accounting (the pre-policy code) undercounts
        once resumes re-enter at the tail and a policy reorders the try
        list.  Returns True if any session was admitted."""
        free = [i for i in range(self.slots) if not self.active[i]]
        admitted = False
        while free and self.pending:
            t_plan = time.perf_counter()
            with span("admit.plan"):
                head = self.pending[0]
                if self._head_skips >= self.max_head_skips:
                    candidates: List[Session] = [head]   # budget spent
                else:
                    candidates = self.policy.order_pending(
                        list(self.pending), self)
                chosen = None
                plan = None
                for cand in candidates:
                    p = self._admission_plan(cand)
                    if p is None:
                        continue           # blocked on pool pages — try next
                    if cand is not head and \
                            self.policy.defer_admission(self, cand, p):
                        continue           # policy holds it back (never head)
                    chosen, plan = cand, p
                    break
            if chosen is None:
                break                  # nothing admissible this round
            for i, s in enumerate(self.pending):
                if s is chosen:        # identity, not __eq__ (ndarrays)
                    del self.pending[i]
                    break
            if chosen is head:
                self._head_skips = 0
            else:
                self._head_skips += 1
            slot = free.pop(0)
            with span("admit", sid=chosen.sid):
                self._admit(chosen, slot, plan, t_plan)
            admitted = True
            if chosen.done:
                self._release(slot)
                free.insert(0, slot)
                if self.telemetry is not None:
                    self.telemetry.on_retire(chosen, self.clock)
        if not self.pending:
            self._head_skips = 0
        return admitted

    # ------------------------------------------------------------------
    # copy-on-write forking (chunk boundary)
    # ------------------------------------------------------------------
    def _cow_before_chunk(self) -> np.ndarray:
        """A page is writable iff refcount == 1.  The only device-side
        writes that can target resident prefix pages are the periodic
        resync's KV rebuild (token appends land beyond the stable
        prefix by construction), so any active slot whose resync may
        fire within the next chunk is made page-private NOW.  A slot
        that cannot fork (no free pages for the copies) is PAUSED for
        this chunk — masked out of the dispatch, frozen bit-identically
        — and retried once retiring sessions free pages.  Admission's
        fork reserve is checked per-admission against the instantaneous
        free pool (commitments are not tracked across slots — e.g. a
        slot's pages become shared only when a LATER session adopts
        them), so pausing is the backstop that keeps in-flight sessions
        alive instead of crashing them.  Returns the (B,) mask of slots
        that actually decode this chunk."""
        run_mask = self.active.copy()
        anticipated = self.decode.sync_anticipated(self.state,
                                                   self._headroom)
        for slot in np.nonzero(self.active)[0]:
            if anticipated[slot] and not self._make_slot_private(int(slot)):
                run_mask[slot] = False
        return run_mask

    def _make_slot_private(self, slot: int) -> bool:
        """Fork the slot's shared pages to fresh ones; True on success,
        False when the free pool cannot back the copies (caller pauses
        the slot — forking later is always still correct)."""
        pages = self._slot_pages[slot]
        shared = [j for j, p in enumerate(pages) if self._page_ref[p] > 1]
        if len(shared) > len(self.free_pages):
            return False
        for p in pages:
            if self._page_ref[p] == 1:
                # sole owner about to rewrite the page: its content may
                # stop matching the registered token prefix — retract it
                self._unregister(p)
        if not shared:
            return True
        fresh = [self.free_pages.pop() for _ in shared]
        pps = self.layout.pages_per_slot
        src = np.full((pps,), self.layout.trash, np.int32)
        dst = np.full((pps,), self.layout.trash, np.int32)
        for k, (j, p_new) in enumerate(zip(shared, fresh)):
            src[k], dst[k] = pages[j], p_new
        self.state = self._fork(self.state, jnp.asarray(src),
                                jnp.asarray(dst))
        for j, p_new in zip(shared, fresh):
            self._page_ref[pages[j]] -= 1
            self._page_ref[p_new] = 1
            pages[j] = p_new
        self._set_table_row(slot, pages)
        return True

    # ------------------------------------------------------------------
    def _release(self, slot: int) -> None:
        with span("release", sid=self.sessions[slot].sid):
            if self.drafter is not None:
                self.drafter.release(slot)
            self.sessions[slot] = None
            self.active[slot] = False
            self.temps[slot] = 0.0
            self.eos[slot] = -1
            if self._paged:
                # retarget the table row at TRASH before the clearing write
                # below, so clearing zeros can never land on a page another
                # slot still references (prefix sharing); then drop refs —
                # a page is recycled (and leaves the prefix map) only at 0
                trash_row = jnp.full((self.layout.pages_per_slot,),
                                     self.layout.trash, jnp.int32)
                pt = self.state.bookkeeping[LT.PAGE_TABLE].at[slot].set(
                    trash_row)
                self.state = self.state.with_bookkeeping(**{LT.PAGE_TABLE: pt})
                retiring = []
                for p in self._slot_pages[slot]:
                    self._page_ref[p] -= 1
                    if self._page_ref[p] == 0:
                        # tiering bugfix: a refcount-0 prefix page used to
                        # leave the content map the moment it recycled —
                        # with a store it retires INTO the tier instead
                        # (gathered below, before anything can reuse it)
                        key = self._page_key.get(p)
                        if self.store is not None and key is not None:
                            retiring.append((p, key))
                        self._unregister(p)
                        self.free_pages.append(p)
                self._slot_pages[slot] = []
                if retiring:
                    self._retire_pages(retiring)
            # clear the slot so stale phase counters can't keep firing the
            # on-device resync for an empty row (paged: the writes land on
            # the trash page — the slot no longer owns real pages)
            self.state = self._clear(self.state, np.int32(slot),
                                     self._empty_row)
            self.last_token = self.last_token.at[slot].set(0)

    # ------------------------------------------------------------------
    def _preempt_for_pending(self) -> int:
        """Preemption: when sessions still wait after admission (blocked
        on slots OR pool pages), active sessions that have decoded at
        least ``preempt_chunks`` chunks this residency are spill
        CANDIDATES — the policy picks the victims (baseline: longest-
        resident first; the SLO policy: cheapest by ``spill_cost``),
        one per waiter.  A fresh residency always decodes >=
        preempt_chunks before it can be preempted again, so every
        rotation makes progress and the oversubscribed queue drains
        fairly regardless of the victim order."""
        ripe = [s for s in range(self.slots)
                if self.active[s]
                and self._slot_chunks[s] >= self.preempt_chunks]
        n = min(len(ripe), len(self.pending))
        if not n:
            return 0
        victims = self.policy.select_victims(self, ripe, n)[:n]
        for s in victims:
            self.spill(int(s))
        return len(victims)

    def _tick_telemetry(self) -> None:
        if self.telemetry is None:
            return
        self.telemetry.on_tick(
            self.clock, self.n_active, len(self.pending),
            len(self.free_pages) if self._paged else None,
            self.layout.pool_pages if self._paged else None)

    def step(self) -> bool:
        """Admit pending sessions, then decode ONE chunk for the active
        slots (a single dispatch; slots paused for copy-on-write fork
        headroom are masked out, frozen bit-identically).  With a tier
        store and ``preempt_chunks`` set, slots are preemptively spilled
        for waiting sessions first.  Each call advances ``clock`` by one
        — the deterministic time base for SLO deadlines and telemetry.
        Returns False when no progress was made — nothing admitted and
        nothing could decode."""
        with span("step"):
            return self._step()

    def _step(self) -> bool:
        self.clock += 1
        admitted = self.admit_pending()
        if self.store is not None and self.preempt_chunks is not None \
                and self.pending:
            if self._preempt_for_pending():
                admitted = self.admit_pending() or admitted
        if not self.active.any():
            self._tick_telemetry()
            return admitted
        if self.prefix_sharing:
            with span("cow"):
                run_mask = self._cow_before_chunk()
        else:
            run_mask = self.active
        if not run_mask.any():
            self._tick_telemetry()
            return admitted            # every active slot fork-paused
        if self.speculate:
            with span("spec"):
                return self._spec_step(run_mask) or admitted
        t0 = time.perf_counter()
        with span("chunk.dispatch"):
            toks, self.state, self.slot_keys = self._chunk(
                self.params, self.state, self.last_token, self.slot_keys,
                jnp.asarray(self.temps), jnp.asarray(run_mask),
                n_steps=self.chunk_size, eos=jnp.asarray(self.eos))
            self.last_token = toks[:, -1]
        with span("chunk.sync"):
            host_toks = np.asarray(toks)     # the ONE host sync per chunk
        compiled = tag_compiled(self._warm, "chunk")
        self.stats.append(StepStats(
            "chunk", time.perf_counter() - t0, tokens=self.chunk_size,
            compiled=compiled, start=t0,
            frozen=self.slots - int(run_mask.sum())))
        with span("deliver"):
            for slot in np.nonzero(run_mask)[0]:
                self._slot_chunks[slot] += 1
                sess = self.sessions[slot]
                before = len(sess.tokens)
                sess.deliver(host_toks[slot])
                if self.telemetry is not None:
                    self.telemetry.on_tokens(sess, len(sess.tokens) - before,
                                             self.clock, compiled)
                if sess.done:
                    self._release(slot)
                    if self.telemetry is not None:
                        self.telemetry.on_retire(sess, self.clock)
        self._tick_telemetry()
        return True

    def _spec_step(self, run_mask: np.ndarray) -> bool:
        """One speculative round for the running slots: the drafter
        proposes k tokens per slot, ONE ``spec_chunk`` dispatch verifies
        them all against the resident KV, and each live slot commits its
        verify-exact accepted prefix + bonus token (1..k+1 tokens).  The
        per-slot key chains advance by exactly the accepted counts, so
        streams stay token-identical to the non-speculative run — the
        acceptance rate moves throughput only (recorded per session via
        ``telemetry.on_spec``)."""
        k = self.speculate
        draft = self.drafter.propose_batch(k)
        t0 = time.perf_counter()
        toks, m, last, self.state, self.slot_keys = self._spec(
            self.params, self.state, self.last_token, jnp.asarray(draft),
            self.slot_keys, jnp.asarray(self.temps),
            jnp.asarray(run_mask), eos=jnp.asarray(self.eos))
        self.last_token = last
        host_toks = np.asarray(toks)         # the ONE host sync per round
        host_m = np.asarray(m)
        compiled = tag_compiled(self._warm, "spec_chunk")
        self.stats.append(StepStats(
            "spec_chunk", time.perf_counter() - t0,
            tokens=int(host_m[np.nonzero(run_mask)[0]].sum()),
            compiled=compiled, forward_tokens=k + 1))
        for slot in np.nonzero(run_mask)[0]:
            self._slot_chunks[slot] += 1
            sess = self.sessions[slot]
            acc = host_toks[slot, :host_m[slot]].tolist()
            before = len(sess.tokens)
            sess.deliver(acc)
            if self.drafter is not None and not sess.done:
                # the drafter tracks STATE CONTENT (committed tokens),
                # even past the delivery budget clip
                self.drafter.observe(slot, acc)
            if self.telemetry is not None:
                self.telemetry.on_tokens(sess, len(sess.tokens) - before,
                                         self.clock, compiled)
                self.telemetry.on_spec(sess, drafted=k,
                                       accepted=int(host_m[slot]) - 1)
            if sess.done:
                self._release(slot)
                if self.telemetry is not None:
                    self.telemetry.on_retire(sess, self.clock)
        self._tick_telemetry()
        return True

    def run(self) -> None:
        """Drive chunks until every submitted session has completed.

        Raises instead of spinning: if nothing could be admitted and
        nothing could decode (every active slot fork-paused, or no
        active slot at all) while work remains, no future chunk can
        ever free pages or slots — busy-looping would never terminate."""
        while True:
            if self.step():
                continue
            if not self.pending and not self.active.any():
                return
            head = self.pending[0] if self.pending else None
            need = self._pages_needed(head) if head and self._paged else 0
            pool = self.layout.pool_pages if self._paged else 0
            raise RuntimeError(
                f"scheduler stuck: {len(self.pending)} pending and "
                f"{self.n_active} fork-paused session(s) with nothing able "
                f"to decode or free resources (head needs {need} pages; "
                f"free {len(self.free_pages)}/{pool}) — the pool/slot "
                f"accounting cannot make progress")
