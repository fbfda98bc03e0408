"""Unified model facade + the decode-side inference protocol.

Two surfaces live here:

* :class:`ModelAPI` — the training facade (init / forward / loss) plus
  thin compatibility wrappers for the legacy decode entry points
  (``init_cache`` / ``prefill`` / ``decode_step`` / ``resync``) used by
  the dry-run launcher and the complexity benchmarks.

* :class:`DecodeAPI` — the serving protocol.  A decode cache is a typed
  :class:`DecodeState` (registered pytree) with an explicit ``kv`` vs
  ``bookkeeping`` partition, so cache-size reporting (paper Fig 8g)
  reads the partition instead of guessing from field names.  The
  *physical* representation of ``kv`` is a pluggable
  :mod:`repro.models.layouts` backend (dense / paged / int8 /
  paged_int8) riding in the pytree aux data; the decode kernels consume
  it LAYOUT-NATIVELY through ``DecodeState.decode_views()`` — per-field
  KVViews carrying the physical buffers + page-table/scale metadata —
  so a paged step walks pages in-kernel and an int8 step fuses the
  dequant, with zero dense densification on the hot path
  (``DecodeState.merged`` survives as the test/parity oracle).  The
  protocol is slot-oriented for continuous batching:

    ``init_state(slots, max_len)``          fixed-shape multi-slot state
    ``prefill_into_slot(params, state, slot, tokens)``
                                            admit one request mid-flight
    ``step(params, state, token, live)``    one batched token, with the
                                            W_og resync fused on-device;
                                            rows outside ``live`` frozen
                                            at every write
    ``sync_mask(state)``                    per-slot (B,) boundary mask
    ``sync_rows(params, state, rows)``      COMPACTED row-wise resync:
                                            gather only the masked rows,
                                            run their O(N) sync at batch
                                            size 1, scatter back — non-
                                            boundary rows are never
                                            computed (amortized O(1)
                                            under staggered batching)

  ``maybe_sync`` is now *derived* (``sync_rows`` over ``sync_mask`` —
  zero pending rows means zero work), replacing PR-1's monolithic
  compute-all-rows-then-select cond.  :func:`decode_chunk` scans
  ``step`` so a k-token decode chunk runs as ONE dispatch with zero
  per-token host syncs, freezing inactive slots and slots whose
  on-device ``done`` flag was set by EOS at each write of the step.
  Implementations exist for the TConst core, the dense LM family, and
  the encoder-decoder.

Every entry point takes/returns plain pytrees so the launchers can jit
them with explicit shardings.  ``input_specs`` produces the
ShapeDtypeStruct stand-ins used by the multi-pod dry-run (no allocation).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, ShapeConfig
from repro.core import tconst as TC
from repro.layers.common import put_rows, take_rows, where_rows
from repro.models import encdec as ED
from repro.models import layouts as LT
from repro.models import lm as LM
from repro.sharding import rules as SH


def _is_tconst(cfg: ModelConfig) -> bool:
    return cfg.attention_mode in ("tconst", "tlin") and \
        cfg.arch_type not in ("ssm", "audio")


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean next-token CE.  logits (B, L, V) f32; targets (B, L) int32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# DecodeState: the typed decode cache
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class DecodeState:
    """Decode-side cache with an explicit kv / bookkeeping partition and a
    pluggable physical layout.

    ``kv`` holds the true KV (and recurrent-state) buffers in the
    PHYSICAL representation chosen by ``layout`` — dense arrays, paged
    pools, or int8 + scales; ``kv_bytes`` (the paper Fig 8g quantity)
    therefore reflects the actual layout.  ``bookkeeping`` holds token-id
    buffers, lengths, per-slot phase counters and the EOS ``done`` mask
    (NOT KV cache), plus layout-owned fields (``layout__*`` prefix, e.g.
    the paged page table) which are hidden from the dense view.
    ``axes`` (static aux data) maps every DENSE field to its batch
    ("slot") axis; ``layout`` (static aux data) translates dense <->
    physical and implements layout-aware slot surgery.  ``mesh`` (static
    aux data, optional) is a :class:`repro.sharding.rules.MeshContext`:
    when set, every slot-surgery path re-pins its outputs to the
    per-field decode shardings (``with_sharding_constraint`` under jit,
    ``device_put`` eagerly), so the SAME code path runs single-device
    (mesh=None: all constraints vanish) and mesh-sharded.
    """

    kv: Dict[str, jax.Array]
    bookkeeping: Dict[str, jax.Array]
    axes: Dict[str, int]
    layout: Any = dataclasses.field(default_factory=LT.DenseLayout)
    mesh: Optional[SH.MeshContext] = None

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten_with_keys(self):
        children = (
            (jax.tree_util.GetAttrKey("kv"), self.kv),
            (jax.tree_util.GetAttrKey("bookkeeping"), self.bookkeeping),
        )
        return children, (tuple(sorted(self.axes.items())), self.layout,
                          self.mesh)

    @classmethod
    def tree_unflatten(cls, aux, children):
        kv, bookkeeping = children
        axes, layout, mesh = aux
        return cls(kv, bookkeeping, dict(axes), layout, mesh)

    # -- construction -------------------------------------------------------
    @classmethod
    def from_dense(cls, cache: Dict[str, Any], kv_keys: Tuple[str, ...],
                   axes: Dict[str, int], layout: Any = None,
                   layout_bk: Optional[Dict[str, Any]] = None
                   ) -> "DecodeState":
        """Wrap a dense logical cache dict, packing kv into ``layout``'s
        physical representation.  ``layout_bk`` carries layout-owned
        bookkeeping (e.g. a live page table) across re-wraps; omitted,
        the layout initialises it fresh."""
        layout = LT.DenseLayout() if layout is None else layout
        dense_kv = {k: v for k, v in cache.items() if k in kv_keys}
        bk = {k: v for k, v in cache.items() if k not in kv_keys}
        if layout_bk is None:
            name = next(iter(sorted(bk)))
            slots = bk[name].shape[axes[name]]
            layout_bk = layout.init_bookkeeping(slots)
        bk.update(layout_bk)
        all_axes = {**{k: axes[k] for k in cache}, **layout.bookkeeping_axes()}
        return cls(layout.pack(dense_kv, bk, all_axes), bk, all_axes, layout)

    def layout_bookkeeping(self) -> Dict[str, Any]:
        return {k: v for k, v in self.bookkeeping.items()
                if k.startswith(LT.LAYOUT_BK_PREFIX)}

    # -- mesh placement -----------------------------------------------------
    def _field_meta(self, name: str, in_kv: bool
                    ) -> Tuple[Optional[int], Optional[int]]:
        """(batch_axis, pool_axis) of one physical field — the inputs
        :func:`repro.sharding.rules.decode_field_spec` needs."""
        if name.startswith(LT.LAYOUT_BK_PREFIX):
            return None, None
        if in_kv:
            if isinstance(self.layout, LT.PagedLayout):
                pool_ax = self.layout.page_axis(name)
                if pool_ax is not None:
                    return None, pool_ax
            return self.layout._axis(name, self.axes), None
        return self.axes.get(name), None

    def field_shardings(self, ctx: SH.MeshContext) -> "DecodeState":
        """Same-structure DecodeState whose leaves are the per-field
        NamedShardings of ``ctx`` — usable directly as a jit
        in/out_shardings pytree.  Works on arrays and on eval_shape
        structs."""
        B = self.slots
        kv = {n: ctx.sharding(n, l.shape, batch=B,
                              baxis=self._field_meta(n, True)[0],
                              pool_axis=self._field_meta(n, True)[1])
              for n, l in self.kv.items()}
        bk = {n: ctx.sharding(n, l.shape, batch=B,
                              baxis=self._field_meta(n, False)[0])
              for n, l in self.bookkeeping.items()}
        return DecodeState(kv, bk, self.axes, self.layout, ctx)

    def _pinned(self, kv: Dict[str, Any], bk: Dict[str, Any]
                ) -> "DecodeState":
        """Build the successor state, re-pinning every field to the
        decode shardings when a mesh is attached (constraint under
        tracing, device_put eagerly).  mesh=None is the identity — the
        single-device path pays nothing."""
        out = DecodeState(kv, bk, self.axes, self.layout, self.mesh)
        ctx = self.mesh
        if ctx is None:
            return out
        sh = out.field_shardings(ctx)
        return DecodeState(
            {n: ctx.apply(v, sh.kv[n]) for n, v in kv.items()},
            {n: ctx.apply(v, sh.bookkeeping[n]) for n, v in bk.items()},
            self.axes, self.layout, ctx)

    def with_mesh(self, mesh) -> "DecodeState":
        """Attach a mesh context (None | Mesh | MeshContext) and place /
        constrain every field onto its decode sharding."""
        ctx = SH.as_mesh_context(mesh)
        if ctx is None:
            if self.mesh is None:
                return self
            return DecodeState(self.kv, self.bookkeeping, self.axes,
                               self.layout)
        staged = DecodeState(self.kv, self.bookkeeping, self.axes,
                             self.layout, ctx)
        return staged._pinned(self.kv, self.bookkeeping)

    # -- KVView: what the decode kernels consume ----------------------------
    def kv_views(self) -> Dict[str, Any]:
        """Per-field :mod:`repro.models.layouts` FieldViews over the
        PHYSICAL kv buffers (+ index/scale metadata) — the decode-kernel
        contract.  Views alias the buffers; no copy, no densification."""
        return self.layout.view(self.kv, self.bookkeeping, self.axes)

    def decode_views(self) -> Dict[str, Any]:
        """The dict the view-native decode kernels take: non-layout
        bookkeeping as plain arrays + kv fields as FieldViews."""
        bk = {k: v for k, v in self.bookkeeping.items()
              if not k.startswith(LT.LAYOUT_BK_PREFIX)}
        return {**bk, **self.kv_views()}

    def absorb(self, views: Dict[str, Any]) -> "DecodeState":
        """Rebuild a DecodeState from an updated ``decode_views`` dict.
        Views alias the physical buffers, so this is pure unwrapping —
        the inverse round-trip of ``merged``/``from_dense`` without the
        pack/unpack compute."""
        kv = LT.absorb_views({k: v for k, v in views.items()
                              if isinstance(v, LT.FieldView)})
        bk = {k: v for k, v in views.items()
              if not isinstance(v, LT.FieldView)}
        bk.update(self.layout_bookkeeping())
        return self._pinned(kv, bk)

    def merged(self) -> Dict[str, Any]:
        """The dense LOGICAL cache dict (layout-owned bookkeeping
        filtered out, kv unpacked/densified).  OFF the decode hot path:
        this is the test/parity ORACLE and the legacy-wrapper surface —
        the kernels themselves consume :meth:`kv_views`."""
        bk = {k: v for k, v in self.bookkeeping.items()
              if not k.startswith(LT.LAYOUT_BK_PREFIX)}
        return {**bk, **self.layout.unpack(self.kv, self.bookkeeping,
                                           self.axes)}

    def dense_shapes(self) -> Dict[str, jax.ShapeDtypeStruct]:
        """Shapes/dtypes of the dense logical kv view, without computing
        the unpack (works on concrete arrays and under tracing)."""
        return jax.eval_shape(
            lambda kv, bk: self.layout.unpack(kv, bk, self.axes),
            self.kv, self.bookkeeping)

    def with_bookkeeping(self, **updates: Any) -> "DecodeState":
        bk = dict(self.bookkeeping)
        bk.update(updates)
        return self._pinned(self.kv, bk)

    # -- accounting ---------------------------------------------------------
    def kv_bytes(self) -> int:
        """KV-cache footprint of the PHYSICAL representation (works on
        real arrays and on ShapeDtypeStructs from ``jax.eval_shape``), so
        paged pools and int8+scales report their true bytes."""
        return sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
                   for l in jax.tree_util.tree_leaves(self.kv))

    def step_view_bytes(self) -> int:
        """HBM bytes a layout-native decode step actually touches —
        assigned pages + table for paged fields, physical buffers
        otherwise.  Host-side (reads the live page table); concrete
        arrays only.  Compare against :meth:`dense_logical_bytes`."""
        return LT.view_touched_bytes(self.kv_views())

    def state_bytes(self, length_axes: Dict[str, int]) -> Dict[str, int]:
        """The slots' state split by kind: ``fixed``, the bytes of the kv
        fields without a length axis (Mamba-2 ssm/conv, TConst's
        windows), constant per slot however long its stream; ``growing``,
        the bytes of the length-axis K/V that the live page tables
        reference (a shared page once; the whole buffer when unpaged).
        ``length_axes`` is the family's (``DecodeAPI._LENGTH_AXES``).
        Host-side; concrete arrays."""
        views = self.kv_views()
        grow = {f: v for f, v in views.items() if f in length_axes}
        fixed = {f: v for f, v in views.items() if f not in length_axes}
        return {"fixed": LT.assigned_kv_bytes(fixed),
                "growing": LT.assigned_kv_bytes(grow)}

    def assigned_kv_bytes(self) -> int:
        """KV bytes the live page tables actually reference: paged
        fields count their unique assigned pages — a prefix-SHARED page
        (mapped by several slots) is stored and counted ONCE — while
        non-paged fields report their physical buffers.  This is the
        prefix-sharing headline: physical cache scaling with *distinct*
        context rather than slot count.  Host-side; concrete arrays.

        GLOBAL-bytes guarantee: sharded jax Arrays report their global
        ``shape``/``nbytes``, so this (and :meth:`kv_bytes`,
        ``spill_cost``, the telemetry occupancy) is the whole-fleet
        number under a mesh, identical to the 1-device run — the
        per-device split is :meth:`per_device_kv_bytes`."""
        return LT.assigned_kv_bytes(self.kv_views())

    def per_device_kv_bytes(self) -> int:
        """Largest per-device share of the PHYSICAL kv buffers: for each
        addressable device, sum the bytes of its local shards, and
        report the max (replicated fields count fully on every device).
        Equals :meth:`kv_bytes` unmeshed; ≈ global / model_shards for
        the head-sharded decode layout.  Host-side; concrete arrays."""
        per: Dict[Any, int] = {}
        for leaf in jax.tree_util.tree_leaves(self.kv):
            shards = getattr(leaf, "addressable_shards", None)
            if shards is None:           # eval_shape struct: global bytes
                return self.kv_bytes()
            for s in shards:
                per[s.device] = per.get(s.device, 0) + s.data.nbytes
        return max(per.values()) if per else 0

    def dense_logical_bytes(self) -> int:
        """Bytes of the dense LOGICAL kv view — what a ``merged()``-based
        step would materialise and read per token (the pre-KVView cost
        model, kept as the benchmark's comparison baseline)."""
        return sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
                   for s in self.dense_shapes().values())

    @property
    def slots(self) -> int:
        name, leaf = next(iter(sorted(self.bookkeeping.items())))
        return leaf.shape[self.axes[name]]

    # -- slot surgery -------------------------------------------------------
    def with_slot(self, slot: jax.Array, row: "DecodeState",
                  page_write_mask: Optional[jax.Array] = None,
                  exclude: Tuple[str, ...] = ()) -> "DecodeState":
        """Scatter a single-row state (batch size 1, dense layout) into
        slot ``slot``.  Bookkeeping is a per-field row write; kv goes
        through the layout (paged: page-map surgery touching only the
        slot's own pages).  ``page_write_mask`` (pages_per_slot,) bool
        restricts the paged write to the UNSHARED tail of the slot's
        page table — the copy-on-write admission contract: a page whose
        content is already resident (prefix sharing, refcount > 1) is
        mapped, never rewritten.  ``exclude`` skips kv fields by base
        name — the chunked prefill streams its length-axis KV in via
        :meth:`write_span` and finalises with everything else."""
        bk = dict(self.bookkeeping)
        for name, src in row.bookkeeping.items():
            if name.startswith(LT.LAYOUT_BK_PREFIX):
                continue
            bk[name] = jax.lax.dynamic_update_slice_in_dim(
                self.bookkeeping[name], src.astype(bk[name].dtype), slot,
                axis=self.axes[name])
        dense_row = row.layout.unpack(row.kv, row.bookkeeping, row.axes)
        kv = self.layout.write_slot(self.kv, self.bookkeeping, slot,
                                    dense_row, self.axes,
                                    page_mask=page_write_mask,
                                    exclude=exclude)
        return self._pinned(kv, bk)

    def read_slot(self, slot: jax.Array) -> Dict[str, Any]:
        """Dense logical kv row (batch size 1) of slot ``slot``, read
        through the layout (paged: gathered via the slot's OWN page-table
        row — adopted prefix-shared pages included; int8: dequantized).
        The KV-conditioned chunked prefill seeds its row cache from this
        so tail chunks attend the resident KV.  Admission path only."""
        return self.layout.read_slot(self.kv, self.bookkeeping, self.axes,
                                     slot)

    def write_span(self, slot: jax.Array, fields: Dict[str, Any],
                   length_axes: Dict[str, int], start: jax.Array,
                   min_page: Optional[jax.Array] = None) -> "DecodeState":
        """Chunk-granular slot write: scatter one prefill chunk's
        positions ``[start, start + C)`` of the given length-axis fields
        (dense logical, batch 1) into the slot through the layout —
        paged layouts write exactly the covered pages of the slot's
        table (entries below ``min_page`` — adopted shared pages — are
        redirected to TRASH), quantizing layouts quantize on write."""
        kv = self.layout.write_span(self.kv, self.bookkeeping, slot, fields,
                                    length_axes, self.axes, start,
                                    min_page=min_page)
        return self._pinned(kv, self.bookkeeping)

    def where_rows(self, rows: jax.Array, other: "DecodeState"
                   ) -> "DecodeState":
        """Per-slot select over the whole state: take self where ``rows``
        (B,) is True, else ``other``.  Reads and writes every buffer, so
        the decode chunk freezes rows at the step's writes instead
        (``DecodeAPI.step``'s ``live``); the speculative round keeps it."""
        bk = {name: where_rows(rows, leaf, other.bookkeeping[name],
                               self.axes[name])
              for name, leaf in self.bookkeeping.items()}
        kv = self.layout.where_rows(rows, self.kv, other.kv,
                                    self.bookkeeping, self.axes)
        return self._pinned(kv, bk)

    # -- slot snapshot / restore (session tiering) --------------------------
    def snapshot_slot(self, slot: jax.Array) -> Dict[str, Dict[str, Any]]:
        """Everything slot ``slot`` owns, in the PHYSICAL representation:
        ``{"bookkeeping": <non-layout rows, batch dim 1>, "kv": <layout
        snapshot>}``.  Dense/int8 kv snapshots are batch-axis row slices
        (int8 stays ``__q``/``__scale`` — compressed on host); paged kv
        snapshots gather exactly the slot's page-table row out of the
        pools.  Layout-owned bookkeeping (the page table itself) is NOT
        captured — a restore binds the snapshot to the destination
        slot's own fresh pages.  Jittable; the scheduler's spill path
        jits it once and ``device_get``s the result."""
        bk = {name: jax.lax.dynamic_slice_in_dim(leaf, slot, 1,
                                                 self.axes[name])
              for name, leaf in self.bookkeeping.items()
              if not name.startswith(LT.LAYOUT_BK_PREFIX)}
        return {"bookkeeping": bk,
                "kv": self.layout.snapshot_slot(self.kv, self.bookkeeping,
                                                self.axes, slot)}

    def restore_slot(self, slot: jax.Array,
                     snap: Dict[str, Dict[str, Any]]) -> "DecodeState":
        """Inverse of :meth:`snapshot_slot` — one jittable scatter of the
        snapshot into slot ``slot`` (ANY slot: the snapshot carries no
        slot identity).  Bit-exact: the snapshot is in the physical
        representation, so nothing is re-quantized or re-paged on the
        way back in.  Paged layouts scatter through the destination
        slot's CURRENT page-table row, which the caller must have
        pointed at exclusively-owned pages first."""
        bk = dict(self.bookkeeping)
        for name, src in snap["bookkeeping"].items():
            bk[name] = jax.lax.dynamic_update_slice_in_dim(
                self.bookkeeping[name], src.astype(bk[name].dtype), slot,
                axis=self.axes[name])
        kv = self.layout.restore_slot(self.kv, self.bookkeeping, self.axes,
                                      slot, snap["kv"])
        return self._pinned(kv, bk)


# ---------------------------------------------------------------------------
# Sampling + chunked decode (zero per-token host syncs)
# ---------------------------------------------------------------------------


def sample_tokens(logits: jax.Array, temperature: jax.Array,
                  key: jax.Array) -> jax.Array:
    """Per-slot sampling.  logits (B, V); temperature (B,) with <= 0
    meaning greedy.  Pure device code — safe inside a scanned step.

    ``key`` may be a single key — one categorical draw over the whole
    batch, so a slot's sample depends on which other slots share the
    batch — or a PER-SLOT key array (B, 2), where each row is sampled
    with its own key and the draw is independent of batch composition
    (the scheduler uses this for replay-identical session streams)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.maximum(temperature, 1e-6)
    scaled = logits / t[:, None]
    if key.ndim == 2:
        sampled = jax.vmap(
            lambda k, row: jax.random.categorical(k, row, axis=-1)
        )(key, scaled).astype(jnp.int32)
    else:
        sampled = jax.random.categorical(
            key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


def decode_chunk(decode: "DecodeAPI", params: Any, state: DecodeState,
                 token: jax.Array, key: jax.Array, temperature: jax.Array,
                 active: jax.Array, n_steps: int,
                 eos: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, DecodeState, jax.Array]:
    """Run ``n_steps`` decode steps as ONE ``lax.scan`` — a single
    dispatch, zero per-token host round-trips.  The W_og resync fires
    inside the scanned step via the compacted row-wise ``sync_rows``
    (see ``DecodeAPI.step``), correct per-slot even when slots sit at
    different phases.

    token: (B,) the token each slot feeds at the first step (its last
    sampled token).  active: (B,) bool; inactive slots are frozen
    bit-identically and keep echoing their input token — frozen at the
    step's writes (``live``), never by a select over the whole state.
    eos: optional (B,) int32 end-of-sequence id per slot (< 0
    disables); a slot that samples its EOS sets the on-device ``done``
    flag in ``state.bookkeeping`` and is frozen for the rest of the
    chunk — the scheduler evicts it at the chunk boundary.  Returns (sampled tokens
    (B, n_steps), state, key).

    key: a single PRNG key (engine path: one split per step, shared
    batch draw) or PER-SLOT keys (B, 2) (scheduler path): each live row
    splits its own key per step and frozen rows do NOT advance, so a
    session's key-chain position is exactly its generated-token count —
    invariant to slot placement, batch composition and spill/resume.
    """
    per_slot = key.ndim == 2

    # the named scopes are op metadata only: they label the profile's
    # ops (step / sample / the per-row selects of the key, token and
    # EOS flag) and change no compiled program
    def body(carry, _):
        state, tok, key = carry
        done = state.bookkeeping["done"]
        live = jnp.logical_and(active, jnp.logical_not(done))
        with jax.named_scope("decode.step"):
            logits, state = decode.step(params, state, tok, live)
        with jax.named_scope("decode.sample"):
            if per_slot:
                pair = jax.vmap(jax.random.split)(key)   # (B, 2, 2)
                nxt_key, sub = pair[:, 0], pair[:, 1]
            else:
                key, sub = jax.random.split(key)
            nxt = sample_tokens(logits, temperature, sub)
        with jax.named_scope("decode.select"):
            if per_slot:
                key = jnp.where(live[:, None], nxt_key, key)
            nxt = jnp.where(live, nxt, tok)
            if eos is not None:
                hit = jnp.logical_and(
                    live, jnp.logical_and(eos >= 0, nxt == eos))
                state = state.with_bookkeeping(
                    done=jnp.logical_or(done, hit))
        return (state, nxt, key), nxt

    (state, _, key), toks = jax.lax.scan(
        body, (state, token, key), None, length=n_steps)
    toks = jnp.moveaxis(toks, 0, 1) if n_steps else \
        jnp.zeros((token.shape[0], 0), jnp.int32)
    return toks, state, key


def speculative_acceptance(feed: jax.Array, samples: jax.Array,
                           budget: jax.Array, live: jax.Array,
                           eos: Optional[jax.Array] = None
                           ) -> Tuple[jax.Array, jax.Array]:
    """The pure acceptance rule of the speculative state machine
    (property-tested in isolation in tests/test_property.py).

    feed (B, C): the verified inputs — last sampled token, then the
    draft.  samples (B, C): the verify-exact samples, ``samples[:, c]``
    drawn from position c's logits with the c-th key of the slot's
    chain.  A draft token is accepted iff it EQUALS the sample the
    sequential decode would have emitted there; the committed count is

        m = min(longest matching draft prefix + 1, budget)

    — the ``+ 1`` is the bonus token sampled from the verify logits at
    the first mismatch (or after a fully-accepted draft), which is why
    ``m >= 1`` for every live row and the loop always progresses.
    ``budget`` (B,) caps acceptance at a family's window boundary
    (samples at positions ``>= budget`` may be garbage — they can only
    inflate the match count, never survive the cap, so they never reach
    a stream).  ``eos`` (B,, < 0 disables) truncates acceptance at the
    first emitted EOS inclusive.  Returns (m (B,) int32 — 0 for
    non-live rows — and hit (B,) bool: EOS inside the accepted
    prefix)."""
    C = feed.shape[1]
    match = (feed[:, 1:] == samples[:, :C - 1]).astype(jnp.int32)
    a = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
    m = jnp.minimum(a + 1, jnp.maximum(budget, 1))
    if eos is not None:
        is_eos = jnp.logical_and(eos[:, None] >= 0,
                                 samples == eos[:, None])
        first = jnp.argmax(is_eos, axis=1).astype(jnp.int32)
        has = jnp.any(is_eos, axis=1)
        m = jnp.where(has, jnp.minimum(m, first + 1), m)
        hit = jnp.logical_and(has, first < m)
    else:
        hit = jnp.zeros_like(live)
    m = jnp.where(live, m, 0).astype(jnp.int32)
    return m, jnp.logical_and(hit, live)


def spec_chunk(decode: "DecodeAPI", params: Any, state: DecodeState,
               token: jax.Array, draft: jax.Array, key: jax.Array,
               temperature: jax.Array, active: jax.Array,
               eos: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array, jax.Array, DecodeState,
                          jax.Array]:
    """One speculative round as ONE dispatch: verify a k-token draft
    per slot against the resident KV (:meth:`DecodeAPI.verify_chunk`),
    accept the longest verify-exact prefix + one bonus token, commit by
    a counter advance, roll back by NOT advancing.  The sampled-token
    contract of :func:`decode_chunk` is preserved EXACTLY: emitted
    tokens, per-slot key-chain positions, ``done`` flags and counters
    all match what ``n_steps=m`` sequential steps would have produced —
    speculation changes wall-clock only, never a stream.

    token (B,): each slot's last sampled token.  draft (B, k): proposed
    continuations.  key: per-slot (B, 2) keys (scheduler path — exact
    for any temperature) or ONE shared key (engine path — each verify
    position would need the shared key's batch-composition-dependent
    draw, so only greedy decoding is exact there; the Engine enforces
    that).  Returns (toks (B, k+1) — positions ``>= m`` are garbage,
    frozen rows echo ``token`` — m (B,) accepted counts, last (B,) the
    new last-sampled token, state, key)."""
    B, k_draft = draft.shape
    C = k_draft + 1
    per_slot = key.ndim == 2
    done0 = state.bookkeeping["done"]
    live = jnp.logical_and(active, jnp.logical_not(done0))
    synced = decode.maybe_sync(params, state)
    feed = jnp.concatenate([token[:, None], draft.astype(jnp.int32)],
                           axis=1)
    logits, verified = decode.verify_chunk(params, synced, feed)

    # the slot's key chain, C steps ahead of time: keys_seq[c] is the
    # chain AFTER c emitted tokens, subs[c] the c-th sampling key —
    # exactly decode_chunk's per-step split sequence
    keys_seq, subs = [key], []
    for _ in range(C):
        if per_slot:
            pair = jax.vmap(jax.random.split)(keys_seq[-1])
            keys_seq.append(pair[:, 0])
            subs.append(pair[:, 1])
        else:
            nxt, sub = jax.random.split(keys_seq[-1])
            keys_seq.append(nxt)
            subs.append(sub)
    s = jnp.stack([sample_tokens(logits[:, c], temperature, subs[c])
                   for c in range(C)], axis=1)               # (B, C)

    m, hit = speculative_acceptance(feed, s, decode.verify_budget(synced),
                                    live, eos)
    new_state = decode.advance_lengths(verified, m)
    new_state = new_state.with_bookkeeping(
        done=jnp.logical_or(done0, hit))
    new_state = new_state.where_rows(live, state)

    if per_slot:
        # each live row's chain advances by exactly its m — invariant to
        # slot placement and batch composition, like decode_chunk
        stack = jnp.stack(keys_seq, axis=0)                  # (C+1, B, 2)
        key = jnp.take_along_axis(stack, m[None, :, None], axis=0)[0]
    else:
        key = keys_seq[-1]
    last = jnp.take_along_axis(s, jnp.maximum(m - 1, 0)[:, None],
                               axis=1)[:, 0]
    last = jnp.where(live, last, token)
    toks = jnp.where(live[:, None], s, token[:, None])
    return toks, m, last, new_state, key


# ---------------------------------------------------------------------------
# DecodeAPI protocol + per-family implementations
# ---------------------------------------------------------------------------


class DecodeAPI:
    """Slot-oriented decode protocol (see module docstring).

    All methods are pure jax functions of their array arguments, so the
    serving layer can jit them (``step`` composes into
    :func:`decode_chunk`'s scan).  The sync surface is row-wise:
    ``sync_mask`` names the boundary rows, ``sync_rows`` syncs exactly
    those rows (compacted — non-masked rows are never computed), and
    ``maybe_sync`` is derived from the two.  ``raw_step`` is the
    un-fused cache-hit step used by the instrumented engine path that
    times hits and misses separately (Fig 8).
    """

    cfg: ModelConfig

    # required surface ------------------------------------------------------
    def init_state(self, slots: int, max_len: int) -> DecodeState:
        raise NotImplementedError

    def prefill(self, params, batch: Dict[str, Any], max_len: int
                ) -> Tuple[jax.Array, DecodeState]:
        """Full-batch prefill (all slots, same-length prompts)."""
        raise NotImplementedError

    def prefill_into_slot(self, params, state: DecodeState, slot: jax.Array,
                          tokens: jax.Array,
                          extras: Optional[Dict[str, Any]] = None,
                          page_write_mask: Optional[jax.Array] = None
                          ) -> Tuple[jax.Array, DecodeState]:
        """Admit one request: prefill prompt ``tokens`` (L,) and scatter
        the resulting row into ``slot``.  Returns (logits (V,), state).

        ``page_write_mask`` (pages_per_slot,) bool is the TAIL-ONLY
        prefill entry for prefix sharing: table entries where the mask
        is False (pages adopted from the prefix map, content already
        resident) are excluded from the paged scatter, so admission
        writes only the unshared tail of the prompt."""
        raise NotImplementedError

    def raw_step(self, params, state: DecodeState, token: jax.Array,
                 live: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, DecodeState]:
        """One cache-hit decode step, NO sync check (instrumentation).
        ``live`` (B,) bool masks every write by row: frozen rows come
        through bit-identical (None: every row steps)."""
        raise NotImplementedError

    # sync surface (identity for models without periodic resync) ------------
    def sync_mask(self, state: DecodeState) -> jax.Array:
        """(B,) bool: rows whose next step must be preceded by the O(N)
        synchronisation."""
        return jnp.zeros((state.slots,), bool)

    def sync_rows(self, params, state: DecodeState, rows: jax.Array
                  ) -> DecodeState:
        """Sync exactly the rows where ``rows`` is True; all other rows
        come through bit-identical AND uncomputed."""
        return state

    def maybe_sync(self, params, state: DecodeState,
                   live: Optional[jax.Array] = None) -> DecodeState:
        """Derived fused sync: ``sync_rows`` over ``sync_mask`` (and over
        ``live`` rows only, when given).  Zero masked rows means zero
        sync work — this is the on-device decision, no host
        round-trip."""
        rows = self.sync_mask(state)
        if live is not None:
            rows = jnp.logical_and(rows, live)
        return self.sync_rows(params, state, rows)

    # chunked KV-conditioned prefill (admission path) ------------------------
    def supports_chunked_prefill(self, extras: Optional[Dict[str, Any]]
                                 = None) -> bool:
        """True when this family (with these per-request extras) can run
        admission through :meth:`prefill_into_slot_chunked`."""
        return False

    def _chunk_resident_start(self, resident_len: int) -> int:
        """Where the chunk loop may start given a resident shared
        prefix.  KV-only families resume after the adopted pages
        (tail-only compute); families carrying RECURRENT state (ssm /
        conv — a function of the full prompt, not reconstructible from
        the adopted KV) must forward from position 0 — adopted pages
        still save the writes (``min_page``) and the bytes, just not
        the tail compute."""
        return resident_len

    def chunked_prefill_fits(self, prompt_len: int, resident_len: int,
                             chunk: int, max_len: int) -> bool:
        """True when the chunk grid over this prompt stays inside the
        ``max_len`` buffers.  The last chunk's padding spills up to
        ``chunk - 1`` positions past the prompt (harmless: overwritten
        by decode appends, masked meanwhile) — but it must not spill
        past ``max_len``, where ``dynamic_update_slice`` would CLAMP the
        write onto earlier, real positions.  The scheduler falls back to
        one-shot admission for the rare prompt this excludes."""
        start0 = min(self._chunk_resident_start(resident_len),
                     (prompt_len - 1) // chunk * chunk)
        n_chunks = -(-(prompt_len - start0) // chunk)
        return start0 + n_chunks * chunk <= max_len

    def prefill_into_slot_chunked(self, params, state: DecodeState,
                                  slot: jax.Array, tokens: jax.Array,
                                  extras: Optional[Dict[str, Any]] = None,
                                  page_write_mask: Optional[jax.Array]
                                  = None, resident_len: int = 0,
                                  chunk: int = 32
                                  ) -> Tuple[jax.Array, DecodeState,
                                             Dict[str, int]]:
        """Chunked, KV-conditioned admission: process the prompt in
        fixed-size chunks of ``chunk`` tokens, each chunk attending
        against the KV already resident for this slot — earlier chunks
        AND, when ``resident_len > 0``, the prefix-shared pages the
        scheduler adopted into the slot's page table — so forward
        compute scales with the *unshared tail* rather than the full
        prompt, and every dispatch has a fixed shape (one compile per
        chunk shape instead of one per prompt length).

        Host-side driver: loops jitted fixed-shape steps (seed → gather
        resident → per-chunk forward + chunk-granular ``write_span`` →
        finalize).  ``resident_len`` must be page-aligned (it is
        ``adopted_pages * page_size`` by construction); when it covers
        the whole prompt, the driver still forwards the final chunk for
        the admission logits but redirects its page writes to TRASH
        (``min_page``) so adopted pages are never written.  Returns
        ``(logits (V,), state, info)`` with ``info['forward_tokens']``
        the number of prompt positions actually forwarded (padded to the
        chunk grid) — the tail-only accounting asserted in tests and
        recorded in ``BENCH_inference.json``.

        Streams are token-identical to the one-shot ``prefill_into_slot``
        admission (float-associativity noise only; int8 layouts within
        the documented quantization tolerance).
        """
        assert self.supports_chunked_prefill(extras), \
            "this family/extras combination requires one-shot admission"
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        L = int(tokens.shape[0])
        assert L >= 1, "cannot admit an empty prompt"
        chunk = int(chunk)
        fns = _chunked_jits(self)
        max_len = self._state_max_len(state)
        # >= one chunk must be forwarded for the admission logits even
        # when the page-aligned resident prefix covers the whole prompt
        start0 = int(min(self._chunk_resident_start(resident_len),
                         (L - 1) // chunk * chunk))
        n_chunks = -(-(L - start0) // chunk)
        buf = np.zeros((n_chunks * chunk,), np.int32)
        buf[:L - start0] = tokens[start0:]
        row = fns["seed"](params, extras, max_len)
        min_page = None
        if resident_len > 0 and isinstance(state.layout, LT.PagedLayout):
            # adopted (refcount > 1) pages are never written, even when
            # the chunk loop recomputes their positions
            min_page = np.int32(resident_len // state.layout.page)
        if start0 > 0:
            # chunks resume mid-prompt: seed the row cache's resident
            # prefix from the slot's adopted pages so they can attend it
            row = fns["gather"](state, slot, row, np.int32(resident_len))
        logits = None
        n_valid = np.int32(L)
        for j in range(n_chunks):
            start = np.int32(start0 + j * chunk)
            ctoks = jnp.asarray(buf[j * chunk:(j + 1) * chunk])[None]
            logits, row, chunk_kv = fns["chunk"](params, row, ctoks, start,
                                                 n_valid)
            if chunk_kv:
                state = fns["span"](state, slot, chunk_kv, start, min_page)
        last_start = start0 + (n_chunks - 1) * chunk
        out = logits[0, (L - 1) - last_start]
        state = fns["finalize"](state, slot, row, np.int32(L))
        return out, state, {"forward_tokens": n_chunks * chunk,
                            "chunks": n_chunks}

    # chunked-prefill hooks (families using the generic driver implement
    # these; TConst overrides the driver itself with the bucketed path) -----
    def _state_max_len(self, state: DecodeState) -> int:
        raise NotImplementedError

    def _chunk_seed_row(self, params, extras, max_len: int
                        ) -> Dict[str, Any]:
        """Fresh dense row cache (batch 1) before any chunk runs."""
        raise NotImplementedError

    def _chunk_fn(self, params, row: Dict[str, Any], tokens: jax.Array,
                  start: jax.Array, n_valid: jax.Array):
        """One fixed-shape chunk forward (``n_valid`` = total prompt
        length, so recurrent-state families can exclude the last chunk's
        padding): returns (logits (1, C, V), updated row, chunk_kv — the
        chunk's length-axis KV)."""
        raise NotImplementedError

    def _chunk_gather_resident(self, state: DecodeState, slot: jax.Array,
                               row: Dict[str, Any], resident_len: jax.Array
                               ) -> Dict[str, Any]:
        """Seed the row cache's positions [0, resident_len) from the
        slot's resident KV (adopted prefix-shared pages included, read
        through the layout) so tail chunks attend it."""
        dense = state.read_slot(slot)
        out = dict(row)
        for f, la in self._LENGTH_AXES.items():
            if f not in row:
                continue
            S = row[f].shape[la]
            keep = (jnp.arange(S) < resident_len).reshape(
                (1,) * la + (S,) + (1,) * (row[f].ndim - la - 1))
            out[f] = jnp.where(keep, dense[f].astype(row[f].dtype), row[f])
        return out

    def _chunk_span_write(self, state: DecodeState, slot: jax.Array,
                          chunk_kv: Dict[str, Any], start: jax.Array,
                          min_page) -> DecodeState:
        return state.write_span(slot, chunk_kv, self._LENGTH_AXES, start,
                                min_page=min_page)

    def _chunk_finalize(self, state: DecodeState, slot: jax.Array,
                        row: Dict[str, Any], n_valid: jax.Array
                        ) -> DecodeState:
        """Write the row's bookkeeping + non-length kv (recurrent state,
        cross KV); the length-axis KV was already streamed in by the
        per-chunk ``write_span`` calls."""
        row = dict(row)
        row["len"] = jnp.full((1,), n_valid, jnp.int32)
        row["done"] = jnp.zeros((1,), bool)
        return state.with_slot(slot, self._row_state(row),
                               exclude=tuple(self._LENGTH_AXES))

    # prefix-sharing surface (host-side hooks for the scheduler) ------------
    def stable_prefix_len(self, prompt_len: int) -> int:
        """Longest prompt prefix whose paged KV is fully written at
        admission AND a pure function of the prompt token ids — only
        pages wholly inside it may enter the prefix-sharing map.  Models
        with a growing positional KV write every prompt position at
        prefill, so the whole prompt is stable."""
        return prompt_len

    def sync_anticipated(self, state: DecodeState, n_steps: int
                         ) -> np.ndarray:
        """Host-side (B,) bool: slots whose periodic O(N) sync MAY fire
        within the next ``n_steps`` decode steps (conservative over-
        approximation is fine — an early copy-on-write fork loses some
        sharing, never correctness).  Models without a periodic sync
        never rewrite resident pages, so nothing is anticipated."""
        return np.zeros((state.slots,), bool)

    # admission caching (session tiering) ------------------------------------
    def admission_key(self, tokens: np.ndarray,
                      extras: Optional[Dict[str, Any]] = None
                      ) -> Optional[bytes]:
        """Content digest under which this request's POST-ADMISSION slot
        state may be stored and re-used, or None when admission is not a
        pure function of (params, prompt ids) — the default.  Families
        whose admission recomputes state that depends only on the prompt
        (the tconst/tlin O(N) resync) return a digest, so a scheduler
        with a tier store turns re-admission of a known prompt into an
        O(1) restore with zero forward compute."""
        return None

    # fused step ------------------------------------------------------------
    def step(self, params, state: DecodeState, token: jax.Array,
             live: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, DecodeState]:
        """maybe_sync + raw_step: the unit scanned by decode_chunk.  Rows
        outside ``live`` (B,) are neither synced nor written: they come
        through bit-identical (None: every row steps)."""
        return self.raw_step(params, self.maybe_sync(params, state, live),
                             token, live)

    # speculative decoding surface (see serving/speculative.py) -------------
    def supports_speculative(self) -> bool:
        """True when this family can verify a drafted chunk and roll
        back by a length-counter decrement alone.  Families carrying
        recurrent state (ssm / conv) cannot: the state after C steps is
        not a function of a truncation point."""
        return False

    def verify_chunk(self, params, state: DecodeState, feed: jax.Array
                     ) -> Tuple[jax.Array, DecodeState]:
        """Score C fed tokens per slot against the resident KV in ONE
        fixed-shape dispatch.  feed (B, C): position c is the token the
        sequential decode would feed at generation offset c (the slot's
        last sampled token, then the draft).  All C keys/values are
        written through the views at the sequential write sites;
        counters are NOT advanced — acceptance of an m-prefix is
        :meth:`advance_lengths` and the rejected suffix becomes stale
        garbage beyond the counter, causally masked and overwritten by
        the next round before it could be attended.  Returns (logits
        (B, C, V), state)."""
        raise NotImplementedError

    def verify_budget(self, state: DecodeState) -> jax.Array:
        """(B,) int32: how many verified tokens each slot may ACCEPT
        this round without overrunning a fixed-size window.  Evaluated
        on the post-sync state; families without a bounded generation
        window are unconstrained."""
        return jnp.full((state.slots,), jnp.int32(2 ** 30))

    def advance_lengths(self, state: DecodeState, m: jax.Array
                        ) -> DecodeState:
        """Commit an accepted m-token prefix (B,) by advancing the
        per-slot length counter — the ONLY state change acceptance
        makes (rollback is the complement: simply not advancing)."""
        return state.with_bookkeeping(len=state.bookkeeping["len"] + m)

    # shared layout wiring (subclasses set the _KV_KEYS / _AXES /
    # _LENGTH_AXES / _QUANT_FIELDS class attributes) -------------------------
    _KV_KEYS: Tuple[str, ...] = ()
    _AXES: Dict[str, int] = {}
    _LENGTH_AXES: Dict[str, int] = {}
    _QUANT_FIELDS: Tuple[str, ...] = ()
    mesh: Optional[SH.MeshContext] = None

    def _mesh_scope(self):
        """Trace-time decode-mesh scope: the per-family step/sync/chunk
        bodies trace inside it, so the kernel dispatch in
        :mod:`repro.kernels.ops` sees the mesh and shard_map-wraps the
        decode / prefill-chunk attention.  mesh=None is a no-op."""
        from repro.kernels import ops
        return ops.decode_mesh_scope(self.mesh)

    def _bind(self, slots: int, max_len: int):
        return LT.bind_layout(self.layout, slots=slots, max_len=max_len,
                              length_axes=self._LENGTH_AXES,
                              quant_fields=self._QUANT_FIELDS,
                              dtype=self.cfg.dtype)

    def _wrap_new(self, cache: Dict[str, Any], max_len: int) -> DecodeState:
        layout = self._bind(cache["done"].shape[0], max_len)
        return DecodeState.from_dense(cache, self._KV_KEYS, self._AXES,
                                      layout).with_mesh(self.mesh)

    def _rewrap(self, state: DecodeState, cache: Dict[str, Any]
                ) -> DecodeState:
        return DecodeState.from_dense(
            cache, self._KV_KEYS, self._AXES, state.layout,
            layout_bk=state.layout_bookkeeping()).with_mesh(self.mesh)

    def _row_state(self, cache: Dict[str, Any]) -> DecodeState:
        """Wrap a batch-1 prefilled row (always dense — the batched
        state's layout does the slot scatter)."""
        return DecodeState.from_dense(cache, self._KV_KEYS, self._AXES)

    def _check_prefill_layout(self, cache: Dict[str, Any], max_len: int
                              ) -> None:
        """Full-batch prefill can't place rows in an under-sized paged
        pool — but only when the cache actually has paged fields."""
        layout = self._bind(cache["done"].shape[0], max_len)
        if isinstance(layout, LT.PagedLayout) and not layout.preallocated \
                and any(f in cache for f, _ in layout.fields):
            raise ValueError(
                "full-batch prefill cannot place rows in an under-sized "
                "paged pool (pool_pages < slots * pages_per_slot); use "
                "the scheduler's page allocator via prefill_into_slot, "
                "or leave pool_pages=None")


# Per-decode jitted chunked-prefill steps.  Keyed by the (frozen,
# value-hashable) DecodeAPI instance, so every scheduler/engine sharing an
# equal config+layout reuses ONE set of compiled chunk shapes — the
# bucketing that collapses prefill compiles from one-per-prompt-length to
# one-per-(chunk-shape x masked-variant).
_CHUNK_JITS: Dict[Any, Dict[str, Any]] = {}


def _mesh_scoped(decode: "DecodeAPI", fn):
    """Run ``fn``'s trace inside the decode-mesh scope (see
    ``DecodeAPI._mesh_scope``); identity when the decode has no mesh."""
    if decode.mesh is None:
        return fn

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with decode._mesh_scope():
            return fn(*args, **kwargs)
    return wrapped


def _chunked_jits(decode: "DecodeAPI") -> Dict[str, Any]:
    # the fns are chunk-size-agnostic (the size arrives via call-time
    # shapes), so normalise prefill_chunk out of the key: an Engine and
    # a scheduler that differ only in the default knob share one set
    # (the key keeps the mesh: a sharded decode compiles its own set)
    key = dataclasses.replace(decode, prefill_chunk=None)
    fns = _CHUNK_JITS.get(key)
    if fns is None:
        if hasattr(key, "_chunk_bucketed"):
            fns = {"bucketed": jax.jit(_mesh_scoped(key,
                                                    key._chunk_bucketed))}
        else:
            fns = {
                "seed": jax.jit(key._chunk_seed_row,
                                static_argnums=(2,)),
                "gather": jax.jit(key._chunk_gather_resident),
                "chunk": jax.jit(_mesh_scoped(key, key._chunk_fn)),
                "span": jax.jit(key._chunk_span_write),
                "finalize": jax.jit(key._chunk_finalize),
            }
        _CHUNK_JITS[key] = fns
    return fns


@dataclasses.dataclass(frozen=True)
class TConstDecode(DecodeAPI):
    """Paper §4 serving: O(1) cache-hit steps, periodic O(N) resync.

    Layout-native: ``raw_step`` hands the kernels ``state.decode_views()``
    — the physical buffers plus index/scale metadata — so the hit step
    never densifies the cache (mode="tlin" keeps the O(N) history KV per
    block, which the paged layouts attend via the in-kernel page-table
    walk).  The resync decision lives ON DEVICE: ``sync_mask`` reads only
    the per-slot ``gen_len`` phase counters, and ``sync_rows`` gathers
    ALL boundary rows' bookkeeping in one dispatch (bucketed — see
    ``tconst.resync_rows_compacted``), reruns their O(N) synchronisation
    at the compacted batch size, and writes the fresh ctx/hist KV back
    THROUGH the layout (paged: page-map surgery on the rows' own pages;
    int8: fresh values quantized on write).  ``resync`` rebuilds that KV
    from the raw token ids, so the sync path reads no KV at all — slots
    admitted at different times stay token-for-token identical to their
    solo runs without paying for each other's misses.
    """

    cfg: ModelConfig
    layout: LT.LayoutSpec = LT.DENSE_SPEC
    prefill_chunk: Optional[int] = None
    mesh: Optional[SH.MeshContext] = None

    _KV_KEYS = TC.KV_KEYS
    _AXES = TC.CACHE_BATCH_AXES
    _LENGTH_AXES = TC.LENGTH_AXES
    _QUANT_FIELDS = TC.QUANT_FIELDS

    @property
    def mode(self) -> str:
        return self.cfg.attention_mode

    def init_state(self, slots: int, max_len: int) -> DecodeState:
        return self._wrap_new(
            TC.init_tconst_cache(self.cfg, slots, max_len, self.mode),
            max_len)

    def prefill(self, params, batch, max_len):
        logits, cache = TC.prefill(params, batch["tokens"], self.cfg,
                                   max_len, mode=self.mode)
        self._check_prefill_layout(cache, max_len)
        return logits, self._wrap_new(cache, max_len)

    def prefill_into_slot(self, params, state, slot, tokens, extras=None,
                          page_write_mask=None):
        max_len = state.bookkeeping["tokens"].shape[1]
        logits, row = TC.prefill(params, tokens[None], self.cfg, max_len,
                                 mode=self.mode)
        return logits[0], state.with_slot(slot, self._row_state(row),
                                          page_write_mask=page_write_mask)

    # chunked admission: the TConst prefill is resync (already a fixed
    # max_len-shaped dispatch) + a generation-window pass, so "chunking"
    # here means BUCKETING — the whole admission becomes one fixed-shape
    # dispatch (prompt padded into the token buffer, window pass padded
    # to W_og with validity masks): ONE compile for every prompt length.
    # Tail-only compute does NOT apply: the paper's resync rebuilds the
    # compressed ctx KV from the full history by construction (content-
    # addressed ctx-KV reuse is the ROADMAP open item).
    def supports_chunked_prefill(self, extras=None):
        return True

    def chunked_prefill_fits(self, prompt_len, resident_len, chunk,
                             max_len):
        return True          # one max_len-shaped dispatch: always fits

    def prefill_into_slot_chunked(self, params, state, slot, tokens,
                                  extras=None, page_write_mask=None,
                                  resident_len=0, chunk=32):
        del extras, resident_len, chunk       # see class comment above
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        L = int(tokens.shape[0])
        max_len = state.bookkeeping["tokens"].shape[1]
        buf = np.zeros((1, max_len), np.int32)
        buf[0, :L] = tokens
        logits, state = _chunked_jits(self)["bucketed"](
            params, state, slot, jnp.asarray(buf),
            jnp.full((1,), L, jnp.int32), page_write_mask)
        return logits, state, {"forward_tokens": max_len, "chunks": 1}

    def _chunk_bucketed(self, params, state, slot, buf, n_valid, mask):
        logits, row = TC.prefill_bucketed(params, buf, n_valid, self.cfg,
                                          mode=self.mode)
        return logits[0], state.with_slot(slot, self._row_state(row),
                                          page_write_mask=mask)

    def stable_prefix_len(self, prompt_len: int) -> int:
        """The trailing 1..W_og prompt tokens live in the dense gen
        window, not the paged history KV, until the first resync — only
        the hist_len prefix is resident in pages at admission."""
        g0 = ((prompt_len - 1) % self.cfg.tconst.w_og) + 1
        return prompt_len - g0

    def admission_key(self, tokens, extras=None):
        """TConst admission is resync + a generation-window pass, both
        pure functions of the prompt ids (``TC.RESYNC_INPUT_KEYS``) —
        the ctx/hist KV carries no sampling or wall-clock state — so the
        admitted slot is content-addressable by prompt digest and a
        shared-history re-admission becomes an O(1) restore instead of
        the O(N) resync (the ROADMAP's content-addressed ctx-KV
        reuse)."""
        if extras:
            return None
        return TC.admission_digest(np.asarray(tokens), self.mode,
                                   self.cfg.tconst.w_og)

    def sync_anticipated(self, state, n_steps):
        """A slot resyncs when gen_len reaches W_og; gen_len grows by at
        most one per decode step, so gen_len + n_steps >= W_og bounds
        every resync the next chunk can fire (EOS-frozen slots are
        excluded — they are evicted at the boundary, never synced)."""
        gen = np.asarray(state.bookkeeping["gen_len"])
        done = np.asarray(state.bookkeeping["done"])
        return np.logical_and(gen + n_steps >= self.cfg.tconst.w_og,
                              np.logical_not(done))

    def raw_step(self, params, state, token, live=None):
        with self._mesh_scope():
            logits, out = TC.decode_step_views(params, state.decode_views(),
                                               token, self.cfg,
                                               mode=self.mode, live=live)
        return logits, state.absorb(out)

    # speculative surface: verify writes into the O(1) gen window; a
    # slot may only ACCEPT up to the window boundary (the resync that
    # follows rebuilds ctx/hist KV from token ids, so accepted tokens
    # recorded in the id buffer survive it; rejected ones beyond
    # gen_len were never recorded)
    def supports_speculative(self):
        return True

    def verify_chunk(self, params, state, feed):
        with self._mesh_scope():
            logits, out = TC.verify_chunk_views(params,
                                                state.decode_views(),
                                                feed, self.cfg,
                                                mode=self.mode)
        return logits, state.absorb(out)

    def verify_budget(self, state):
        return jnp.maximum(
            jnp.int32(self.cfg.tconst.w_og) -
            state.bookkeeping["gen_len"], 0).astype(jnp.int32)

    def advance_lengths(self, state, m):
        return state.with_bookkeeping(
            gen_len=state.bookkeeping["gen_len"] + m)

    def sync_mask(self, state):
        return TC.pending_resync_rows(state.bookkeeping, self.cfg)

    def sync_rows(self, params, state, rows):
        """Layout-aware batched compacted resync (see class docstring):
        ONE gather of the pending rows' bookkeeping, ONE O(N) resync at
        the bucketed pending count, KV written back through the layout.
        Zero pending rows selects the identity branch — this is the
        on-device decision, no host round-trip."""
        cfg = self.cfg
        axes = TC.CACHE_BATCH_AXES

        def factory(kb: int):
            # each bucket's ops carry its own scope (``tconst.resync.k4``:
            # the resync of up to 4 rows) in the profile
            @jax.named_scope(f"tconst.resync.k{kb}")
            def branch(state, idx, sel):
                bk = state.bookkeeping
                row_in = {f: take_rows(bk[f], idx, axes[f])
                          for f in TC.RESYNC_INPUT_KEYS}
                new = TC.resync(params, row_in, cfg, self.mode)
                out_bk = dict(bk)
                views = state.kv_views()
                for f, v in new.items():
                    if f in views:
                        views[f] = views[f].scatter_rows(idx, sel, v)
                    else:
                        old = take_rows(bk[f], idx, axes[f])
                        vals = where_rows(sel, v.astype(bk[f].dtype), old,
                                          axes[f])
                        out_bk[f] = put_rows(bk[f], idx, vals, axes[f])
                return state._pinned(LT.absorb_views(views), out_bk)
            return branch

        return TC.compacted_rows_switch(rows, state, factory)


@dataclasses.dataclass(frozen=True)
class DenseDecode(DecodeAPI):
    """Decoder-only LM family (dense / moe / ssm / hybrid / vlm): a
    conventional growing KV cache (or O(1) recurrent state for ssm),
    no periodic sync.  The max_len-axis K/V buffers support the paged
    and int8 layouts."""

    cfg: ModelConfig
    layout: LT.LayoutSpec = LT.DENSE_SPEC
    prefill_chunk: Optional[int] = None
    mesh: Optional[SH.MeshContext] = None

    _KV_KEYS = LM.KV_KEYS
    _AXES = LM.CACHE_BATCH_AXES
    _LENGTH_AXES = LM.LENGTH_AXES
    _QUANT_FIELDS = LM.QUANT_FIELDS

    def init_state(self, slots: int, max_len: int) -> DecodeState:
        return self._wrap_new(LM.init_kv_cache(self.cfg, slots, max_len),
                              max_len)

    def _max_len(self, state: DecodeState, fallback: int) -> int:
        shapes = state.dense_shapes()
        for key in ("k", "dense_k"):
            if key in shapes:
                return shapes[key].shape[2]
        return fallback                      # pure ssm: no positional buffer

    def prefill(self, params, batch, max_len):
        logits, cache = LM.lm_prefill(
            params, batch["tokens"], self.cfg, max_len,
            vision_embeds=batch.get("vision_embeds"),
            vision_mask=batch.get("vision_mask"))
        self._check_prefill_layout(cache, max_len)
        return logits, self._wrap_new(cache, max_len)

    def prefill_into_slot(self, params, state, slot, tokens, extras=None,
                          page_write_mask=None):
        extras = extras or {}
        max_len = self._max_len(state, tokens.shape[0])
        logits, cache = LM.lm_prefill(
            params, tokens[None], self.cfg, max_len,
            vision_embeds=None if "vision_embeds" not in extras else
            extras["vision_embeds"][None],
            vision_mask=None if "vision_mask" not in extras else
            extras["vision_mask"][None])
        return logits[0], state.with_slot(slot, self._row_state(cache),
                                          page_write_mask=page_write_mask)

    def raw_step(self, params, state, token, live=None):
        with self._mesh_scope():
            logits, out = LM.lm_decode_step_views(params,
                                                  state.decode_views(),
                                                  token, self.cfg,
                                                  live=live)
        return logits, state.absorb(out)

    def supports_speculative(self):
        # recurrent ssm/conv state advances through VERIFIED-BUT-REJECTED
        # tokens and cannot be rolled back by a length decrement
        return not self.cfg.has_recurrent_state

    def verify_chunk(self, params, state, feed):
        with self._mesh_scope():
            logits, out = LM.lm_verify_chunk_views(params,
                                                   state.decode_views(),
                                                   feed, self.cfg)
        return logits, state.absorb(out)

    # chunked admission hooks (generic driver in DecodeAPI) -----------------
    def supports_chunked_prefill(self, extras=None):
        # VLM vision positions depend on a prompt-length-shaped mask (one
        # compile per length regardless) — those admissions stay one-shot
        return not (extras and "vision_embeds" in extras)

    def _chunk_resident_start(self, resident_len):
        # ssm/conv recurrent state is a function of the FULL prompt and
        # cannot be reconstructed from adopted KV pages: recurrent
        # families forward from 0 (adopted pages still save writes/bytes)
        if self.cfg.has_recurrent_state:
            return 0
        return resident_len

    def _state_max_len(self, state):
        return self._max_len(state, 0)

    def _chunk_seed_row(self, params, extras, max_len):
        del params, extras
        return LM.init_kv_cache(self.cfg, 1, max_len)

    def _chunk_fn(self, params, row, tokens, start, n_valid):
        return LM.lm_prefill_chunk(params, row, tokens, start, n_valid,
                                   self.cfg)


@dataclasses.dataclass(frozen=True)
class EncDecDecode(DecodeAPI):
    """Encoder-decoder: per-session encoder memory is pre-projected into
    the per-layer cross K/V cache at admission."""

    cfg: ModelConfig
    layout: LT.LayoutSpec = LT.DENSE_SPEC
    prefill_chunk: Optional[int] = None
    mesh: Optional[SH.MeshContext] = None

    _KV_KEYS = ED.KV_KEYS
    _AXES = ED.CACHE_BATCH_AXES
    _LENGTH_AXES = ED.LENGTH_AXES
    _QUANT_FIELDS = ED.QUANT_FIELDS

    def init_state(self, slots: int, max_len: int) -> DecodeState:
        return self._wrap_new(ED.init_encdec_cache(self.cfg, slots, max_len),
                              max_len)

    def prefill(self, params, batch, max_len):
        logits, cache = ED.encdec_prefill(params, batch["tokens"],
                                          batch["audio_feats"], self.cfg,
                                          max_len)
        self._check_prefill_layout(cache, max_len)
        return logits, self._wrap_new(cache, max_len)

    def prefill_into_slot(self, params, state, slot, tokens, extras=None,
                          page_write_mask=None):
        if not extras or "audio_feats" not in extras:
            raise ValueError(
                "encoder-decoder sessions need extras={'audio_feats': "
                "(T_enc, frontend_dim)} at submission")
        max_len = state.dense_shapes()["k"].shape[2]
        logits, cache = ED.encdec_prefill(
            params, tokens[None], extras["audio_feats"][None], self.cfg,
            max_len)
        return logits[0], state.with_slot(slot, self._row_state(cache),
                                          page_write_mask=page_write_mask)

    def raw_step(self, params, state, token, live=None):
        with self._mesh_scope():
            logits, out = ED.encdec_decode_step_views(params,
                                                      state.decode_views(),
                                                      token, self.cfg,
                                                      live=live)
        return logits, state.absorb(out)

    def supports_speculative(self):
        return True

    def verify_chunk(self, params, state, feed):
        with self._mesh_scope():
            logits, out = ED.encdec_verify_chunk_views(
                params, state.decode_views(), feed, self.cfg)
        return logits, state.absorb(out)

    # chunked admission hooks: the encoder runs ONCE at seed time (fixed
    # encoder_seq shape — one compile), pre-projecting the cross K/V the
    # decoder chunks then attend; only the growing self-attention KV is
    # chunk-written.
    def supports_chunked_prefill(self, extras=None):
        return True

    def _state_max_len(self, state):
        return state.dense_shapes()["k"].shape[2]

    def _chunk_seed_row(self, params, extras, max_len):
        if not extras or "audio_feats" not in extras:
            raise ValueError(
                "encoder-decoder sessions need extras={'audio_feats': "
                "(T_enc, frontend_dim)} at submission")
        return ED.encdec_seed_cache(params, extras["audio_feats"][None],
                                    self.cfg, max_len)

    def _chunk_fn(self, params, row, tokens, start, n_valid):
        return ED.encdec_prefill_chunk(params, row, tokens, start, n_valid,
                                       self.cfg)


def build_decode(cfg: ModelConfig, layout: Any = None,
                 prefill_chunk: Optional[int] = None,
                 mesh: Any = None) -> DecodeAPI:
    """Build the decode protocol for ``cfg`` with a cache layout chosen
    by ``layout`` ("dense" | "paged" | "int8" | "paged_int8" |
    LayoutSpec | None).  ``prefill_chunk`` is the default chunk size for
    chunked KV-conditioned admission (None = one-shot full-prompt
    prefill); the scheduler reads it unless given its own.  ``mesh``
    (None | jax Mesh | MeshContext) makes the decode mesh-native:
    ``init_state`` places its output with ``jax.device_put`` onto the
    per-field decode shardings (see
    :func:`repro.sharding.rules.decode_shardings`), every state-surgery
    path re-pins its results, and the decode / prefill-chunk attention
    runs shard_map-sharded over the model axis."""
    spec = LT.as_spec(layout)
    if prefill_chunk is not None and prefill_chunk < 1:
        raise ValueError("prefill_chunk must be positive (or None for "
                         "one-shot admission)")
    ctx = SH.as_mesh_context(mesh)
    if ctx is not None and cfg.n_kv_heads > 1 and \
            cfg.n_kv_heads % ctx.model_shards != 0:
        # MQA (n_kv_heads == 1) is exempt: its KV replicates over model
        # (nothing to split); a >1 indivisible head count is a
        # mis-sized mesh
        raise ValueError(
            f"model axis ({ctx.model_shards}) must divide the KV heads "
            f"({cfg.n_kv_heads}) for head-sharded decode")
    if _is_tconst(cfg):
        return TConstDecode(cfg, spec, prefill_chunk, ctx)
    if cfg.is_encdec:
        return EncDecDecode(cfg, spec, prefill_chunk, ctx)
    return DenseDecode(cfg, spec, prefill_chunk, ctx)


# ---------------------------------------------------------------------------
# Facade
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ModelAPI:
    cfg: ModelConfig

    # -- parameters ---------------------------------------------------------
    def init(self, key: jax.Array):
        cfg = self.cfg
        if _is_tconst(cfg):
            return TC.init_tconst_lm(key, cfg)
        if cfg.is_encdec:
            return ED.init_encdec(key, cfg)
        return LM.init_lm(key, cfg)

    # -- training -----------------------------------------------------------
    def forward(self, params, batch: Dict[str, Any]
                ) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        tokens = batch["tokens"]
        if _is_tconst(cfg):
            return TC.tconst_forward(params, tokens, cfg,
                                     mode=cfg.attention_mode)
        if cfg.is_encdec:
            return ED.encdec_forward(params, tokens, batch["audio_feats"],
                                     cfg)
        return LM.lm_forward(
            params, tokens, cfg,
            vision_embeds=batch.get("vision_embeds"),
            vision_mask=batch.get("vision_mask"))

    def loss(self, params, batch: Dict[str, Any]
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        logits, aux = self.forward(params, batch)
        tokens = batch["tokens"]
        ce = cross_entropy(logits[:, :-1], tokens[:, 1:])
        total = ce + self.cfg.router_aux_coef * aux
        return total, {"ce": ce, "aux": aux}

    # -- serving (compat wrappers over DecodeAPI; cache is a DecodeState) ---
    @property
    def decode(self) -> DecodeAPI:
        return build_decode(self.cfg)

    def init_cache(self, batch: int, max_len: int) -> DecodeState:
        return self.decode.init_state(batch, max_len)

    def prefill(self, params, batch: Dict[str, Any], max_len: int
                ) -> Tuple[jax.Array, DecodeState]:
        return self.decode.prefill(params, batch, max_len)

    def decode_step(self, params, state: DecodeState, token: jax.Array
                    ) -> Tuple[jax.Array, DecodeState]:
        return self.decode.raw_step(params, state, token)

    def resync(self, params, state: DecodeState) -> DecodeState:
        """TConst periodic global synchronisation — full, all-rows
        (the legacy schedule where every row shares one phase)."""
        cfg = self.cfg
        if _is_tconst(cfg):
            cache = TC.resync(params, state.merged(), cfg,
                              mode=cfg.attention_mode)
            return DecodeState.from_dense(
                cache, TC.KV_KEYS, TC.CACHE_BATCH_AXES, state.layout,
                layout_bk=state.layout_bookkeeping())
        return state

    def needs_resync(self, state: DecodeState) -> jax.Array:
        if _is_tconst(self.cfg):
            return self.decode.sync_mask(state)
        return jnp.zeros((), bool)

    # -- dry-run specs -----------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of this shape
        (assignment: weak-type-correct, shardable, no device allocation)."""
        cfg = self.cfg
        B, L = shape.global_batch, shape.seq_len
        f = jax.ShapeDtypeStruct
        specs: Dict[str, Any] = {"tokens": f((B, L), jnp.int32)}
        if cfg.arch_type == "vlm":
            Tv = cfg.frontend_tokens
            specs["vision_embeds"] = f((B, Tv, cfg.frontend_dim),
                                       jnp.dtype(cfg.dtype))
            specs["vision_mask"] = f((B, L), jnp.bool_)
        if cfg.is_encdec:
            specs["audio_feats"] = f((B, cfg.encoder_seq, cfg.frontend_dim),
                                     jnp.dtype(cfg.dtype))
        return specs

    def cache_specs(self, batch: int, max_len: int) -> DecodeState:
        """ShapeDtypeStructs of the serve cache (eval_shape: no alloc)."""
        return jax.eval_shape(
            lambda: self.init_cache(batch, max_len))


def build_model(cfg: ModelConfig) -> ModelAPI:
    cfg.validate()
    return ModelAPI(cfg)
