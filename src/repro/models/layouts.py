"""Pluggable physical cache layouts behind :class:`repro.models.api.DecodeState`.

The decode kernels (``core/tconst.py``, ``models/lm.py``,
``models/encdec.py``) consume the cache through **KVViews** — per-field
descriptors (:class:`DenseView` / :class:`QuantView` / :class:`PagedView`)
produced by ``CacheLayout.view(kv, bookkeeping, axes)``.  A view holds the
PHYSICAL buffers plus the index/scale metadata needed to read or append
one token *in that representation*: the kernels walk the page table /
apply the per-vector scales themselves, and nothing on the decode hot
path materialises the dense ``slots x max_len`` logical cache.  The dense
logical dict (``DecodeState.merged`` via :meth:`pack`/:meth:`unpack`)
survives only as the test/parity oracle and for O(N) admission paths
(prefill, resync row scatter).

Layouts:

* :class:`DenseLayout`     — physical == logical.
* :class:`PagedLayout`     — every length-axis KV buffer is split into
  fixed-size pages living in one shared pool per field, with a per-slot
  page table in bookkeeping.  The pool can be sized *below*
  ``slots * pages_per_slot`` (short sessions stop paying ``max_len``
  bytes); page assignment is host-side slot surgery in the scheduler —
  admission/eviction touch the page map, never full rows.  With
  ``quant_fields`` set ("paged_int8") the pool pages hold int8 vectors
  and the per-vector float32 scales ride in a parallel scale pool — the
  page metadata — so footprint composes (~4x on top of the pool saving).
* :class:`QuantizedLayout` — int8 KV with per-vector (last-axis) float32
  scales.  Decode kernels fuse the dequantisation into the QK/AV loops
  (Pallas) or read the dequantised values per-field (XLA fallback).
  Symmetric round-to-nearest; requantizing an unchanged entry is
  idempotent, so no drift accumulates across decode steps.

All layouts are frozen (hashable) dataclasses: they ride in the
``DecodeState`` pytree **aux data**, so jitted functions specialise on the
layout exactly like they specialise on shapes.  Views are registered
pytrees, so they ride ``lax.fori_loop`` carries and ``lax.scan`` bodies.

Layout methods take the *dense field axes* map (the model's
``CACHE_BATCH_AXES``) and derive physical axes themselves; layout-owned
bookkeeping fields carry the ``layout__`` prefix so the model-facing dense
view (``DecodeState.merged``) can filter them out.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.layers.common import put_rows, take_rows, where_rows

LAYOUT_BK_PREFIX = "layout__"
PAGE_TABLE = LAYOUT_BK_PREFIX + "page_table"

_QUANT_SUFFIXES = ("__q", "__scale")


def _base_name(field: str) -> str:
    for suffix in _QUANT_SUFFIXES:
        if field.endswith(suffix):
            return field[: -len(suffix)]
    return field


# ---------------------------------------------------------------------------
# Spec (user-facing knob) and binding
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayoutSpec:
    """User-facing layout choice, before shapes are known.

    kind: "dense" | "paged" | "int8" | "paged_int8".
    page_size: tokens per page (paged / paged_int8).
    pool_pages: total pages in the shared pool (paged); None = full
    ``slots * pages_per_slot`` (no saving, but no allocator needed —
    required for the uniform-batch ``prefill`` path).  A smaller pool
    needs the scheduler's page allocator.
    """

    kind: str = "dense"
    page_size: int = 64
    pool_pages: Optional[int] = None

    def __post_init__(self):
        if self.kind not in ("dense", "paged", "int8", "paged_int8"):
            raise ValueError(f"unknown cache layout kind: {self.kind!r}")
        if self.page_size < 1:
            raise ValueError("page_size must be positive")
        if self.pool_pages is not None and self.pool_pages < 1:
            raise ValueError("pool_pages must be positive (or None for "
                             "the full slots * pages_per_slot pool)")


DENSE_SPEC = LayoutSpec()


def as_spec(layout) -> LayoutSpec:
    if layout is None:
        return DENSE_SPEC
    if isinstance(layout, LayoutSpec):
        return layout
    if isinstance(layout, str):
        return LayoutSpec(kind=layout)
    raise TypeError(f"layout must be LayoutSpec | str | None, got {layout!r}")


def bind_layout(spec: LayoutSpec, *, slots: int, max_len: int,
                length_axes: Dict[str, int], quant_fields: Tuple[str, ...],
                dtype: str) -> "CacheLayout":
    """Turn a shape-free spec into a bound (hashable) layout instance."""
    spec = as_spec(spec)
    if spec.kind == "dense":
        return DenseLayout()
    if spec.kind == "int8":
        return QuantizedLayout(fields=tuple(sorted(quant_fields)),
                               dtype=dtype)
    pps = -(-max_len // spec.page_size)
    pool = slots * pps if spec.pool_pages is None else spec.pool_pages
    quant = tuple(sorted(quant_fields)) if spec.kind == "paged_int8" else ()
    return PagedLayout(page=spec.page_size, pool_pages=pool, max_len=max_len,
                       slots=slots,
                       fields=tuple(sorted(length_axes.items())),
                       quant_fields=quant, dtype=dtype)


# ---------------------------------------------------------------------------
# int8 primitives
# ---------------------------------------------------------------------------


def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-vector (last axis) int8 quantization."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    scale = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.round(x.astype(jnp.float32) / scale).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# KVView: per-field physical descriptors the decode kernels consume
# ---------------------------------------------------------------------------


class FieldView:
    """Base class for per-field cache views (see module docstring).

    The per-layer convention: after peeling all leading layer axes with
    :meth:`layer`, the LOGICAL field is (B, S, KV, D) — batch axis 0,
    length axis 1 — and token writes/attends are defined.  ``dense()``
    works at any level and is the oracle escape hatch."""

    def layer(self, i) -> "FieldView":
        raise NotImplementedError

    def set_layer(self, i, sub: "FieldView") -> "FieldView":
        raise NotImplementedError

    def dense(self) -> jax.Array:
        raise NotImplementedError

    def write_token(self, pos: jax.Array, vec: jax.Array,
                    live: Optional[jax.Array] = None) -> "FieldView":
        """Append one (B, KV, D) vector at per-slot position ``pos`` (B,).
        Only valid at the per-layer level.  ``live`` (B,) bool masks the
        write by row: a frozen row writes nothing, so its buffers stay
        bit-identical — the freeze contract of ``decode_chunk``, which
        every layout's append must honour (None: every row writes)."""
        raise NotImplementedError

    def scatter_rows(self, idx: jax.Array, sel: jax.Array,
                     rows: jax.Array) -> "FieldView":
        """Write dense logical ``rows`` (k rows along the batch axis)
        into slots ``idx`` (k,), but only where ``sel`` (k,) is True —
        unselected slots come through bit-identical.  Stacked level."""
        raise NotImplementedError


def frozen_index(idx: jax.Array, live: Optional[jax.Array],
                 size: int) -> jax.Array:
    """Write index of a row-masked scatter: ``idx`` for live rows,
    ``size`` (one past the end) for frozen ones, whose update the
    scatter then drops (``mode="drop"``) — a frozen row's write costs
    nothing and leaves every bit in place.  ``live=None``: all rows."""
    return idx if live is None else jnp.where(live, idx, size)


def _put_selected(arr: jax.Array, idx: jax.Array, sel: jax.Array,
                  rows: jax.Array, axis: int) -> jax.Array:
    old = take_rows(arr, idx, axis)
    vals = where_rows(sel, rows.astype(arr.dtype), old, axis)
    return put_rows(arr, idx, vals, axis)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DenseView(FieldView):
    """Physical == logical: one dense array."""

    data: jax.Array
    batch_axis: int = 0

    def tree_flatten(self):
        return (self.data,), (self.batch_axis,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux[0])

    def layer(self, i):
        return DenseView(jax.lax.dynamic_index_in_dim(
            self.data, i, 0, keepdims=False), max(0, self.batch_axis - 1))

    def set_layer(self, i, sub):
        return DenseView(jax.lax.dynamic_update_index_in_dim(
            self.data, sub.data.astype(self.data.dtype), i, 0),
            self.batch_axis)

    def dense(self):
        return self.data

    def write_token(self, pos, vec, live=None):
        b = jnp.arange(vec.shape[0])
        pos = frozen_index(pos, live, self.data.shape[1])
        return DenseView(self.data.at[b, pos].set(
            vec.astype(self.data.dtype), mode="drop"), self.batch_axis)

    def scatter_rows(self, idx, sel, rows):
        return DenseView(_put_selected(self.data, idx, sel, rows,
                                       self.batch_axis), self.batch_axis)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantView(FieldView):
    """int8 values + per-vector (last axis) float32 scales."""

    q: jax.Array
    scale: jax.Array
    batch_axis: int = 0
    dtype: str = "float32"

    def tree_flatten(self):
        return (self.q, self.scale), (self.batch_axis, self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    def layer(self, i):
        return QuantView(
            jax.lax.dynamic_index_in_dim(self.q, i, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(self.scale, i, 0, keepdims=False),
            max(0, self.batch_axis - 1), self.dtype)

    def set_layer(self, i, sub):
        return QuantView(
            jax.lax.dynamic_update_index_in_dim(self.q, sub.q, i, 0),
            jax.lax.dynamic_update_index_in_dim(self.scale, sub.scale, i, 0),
            self.batch_axis, self.dtype)

    def dense(self):
        return dequantize_int8(self.q, self.scale, jnp.dtype(self.dtype))

    def write_token(self, pos, vec, live=None):
        b = jnp.arange(vec.shape[0])
        pos = frozen_index(pos, live, self.q.shape[1])
        qv, sv = quantize_int8(vec)
        return QuantView(self.q.at[b, pos].set(qv, mode="drop"),
                         self.scale.at[b, pos].set(sv, mode="drop"),
                         self.batch_axis, self.dtype)

    def scatter_rows(self, idx, sel, rows):
        qr, sr = quantize_int8(rows)
        return QuantView(
            _put_selected(self.q, idx, sel, qr, self.batch_axis),
            _put_selected(self.scale, idx, sel, sr, self.batch_axis),
            self.batch_axis, self.dtype)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedView(FieldView):
    """Length-axis field as a shared page pool + per-slot page table.

    ``storage`` is the pool in its element representation — a
    :class:`DenseView` (float pool ``(..., pool+1, page, KV, D)``) or a
    :class:`QuantView` (int8 pool + float32 scale pool, the paged_int8
    composition).  ``lead`` counts the leading layer axes still stacked
    on the pool; the page table (B, pages_per_slot) is shared across
    them.  The decode kernels receive the pool + table directly
    (``repro.kernels.paged_decode_attention``)."""

    storage: FieldView
    page_table: jax.Array
    page: int = 0
    max_len: int = 0
    trash: int = 0
    lead: int = 0

    def tree_flatten(self):
        return (self.storage, self.page_table), \
            (self.page, self.max_len, self.trash, self.lead)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    @property
    def pages_per_slot(self) -> int:
        return self.page_table.shape[-1]

    @property
    def quant(self) -> bool:
        return isinstance(self.storage, QuantView)

    def _pool_children(self):
        if self.quant:
            return (self.storage.q, self.storage.scale)
        return (self.storage.data,)

    def _rebuild(self, pools):
        if self.quant:
            st = QuantView(pools[0], pools[1], self.storage.batch_axis,
                           self.storage.dtype)
        else:
            st = DenseView(pools[0], self.storage.batch_axis)
        return PagedView(st, self.page_table, self.page, self.max_len,
                         self.trash, self.lead)

    def layer(self, i):
        v = self._rebuild(tuple(
            jax.lax.dynamic_index_in_dim(p, i, 0, keepdims=False)
            for p in self._pool_children()))
        return dataclasses.replace(v, lead=self.lead - 1)

    def set_layer(self, i, sub: "PagedView"):
        return self._rebuild(tuple(
            jax.lax.dynamic_update_index_in_dim(p, s, i, 0)
            for p, s in zip(self._pool_children(), sub._pool_children())))

    def dense(self):
        """Gather pages into the dense logical array — ORACLE/debug only
        (this is exactly the densification the kernels avoid)."""
        la = self.lead + 1
        out = []
        for p in self._pool_children():
            g = jnp.take(p, self.page_table, axis=self.lead)
            g = g.reshape(g.shape[:la] + (-1,) + g.shape[la + 2:])
            out.append(jax.lax.slice_in_dim(g, 0, self.max_len, axis=la))
        if self.quant:
            return dequantize_int8(out[0], out[1],
                                   jnp.dtype(self.storage.dtype))
        return out[0]

    def _to_pages(self, x: jax.Array, la: int) -> jax.Array:
        pps = self.pages_per_slot
        pad = pps * self.page - x.shape[la]
        if pad:
            widths = [(0, 0)] * x.ndim
            widths[la] = (0, pad)
            x = jnp.pad(x, widths)
        return x.reshape(x.shape[:la] + (pps, self.page) + x.shape[la + 1:])

    def write_token(self, pos, vec, live=None):
        """Append through the page table: physical page ``pt[b, pos //
        page]``, offset ``pos % page`` — only the owning page is touched
        (a frozen row's page index points past the pool: no page is)."""
        assert self.lead == 0, "write_token needs a per-layer view"
        b = jnp.arange(vec.shape[0])
        pidx = frozen_index(self.page_table[b, pos // self.page], live,
                            self.trash + 1)
        off = pos % self.page
        if self.quant:
            qv, sv = quantize_int8(vec)
            return self._rebuild((
                self.storage.q.at[pidx, off].set(qv, mode="drop"),
                self.storage.scale.at[pidx, off].set(sv, mode="drop")))
        return self._rebuild((self.storage.data.at[pidx, off].set(
            vec.astype(self.storage.data.dtype), mode="drop"),))

    def scatter_rows(self, idx, sel, rows):
        """Write k dense logical rows through the rows' own pages (page-
        map surgery: other slots' pages are never touched)."""
        la = self.lead + 1                      # length axis at this level
        pt_rows = jnp.take(self.page_table, idx, axis=0)     # (k, pps)
        parts = [rows]
        if self.quant:
            parts = list(quantize_int8(rows))
        pools = []
        for pool, vals in zip(self._pool_children(), parts):
            pages = self._to_pages(vals.astype(pool.dtype), la)
            old = jnp.take(pool, pt_rows, axis=self.lead)
            pages = where_rows(sel, pages, old, self.lead)
            ix = (slice(None),) * self.lead + (pt_rows,)
            pools.append(pool.at[ix].set(pages))
        return self._rebuild(tuple(pools))


def absorb_views(views: Dict[str, FieldView]) -> Dict[str, jax.Array]:
    """Inverse of ``CacheLayout.view``: unwrap updated views back into the
    physical ``DecodeState.kv`` dict.  Pure unwrapping — the views alias
    the physical buffers, so there is no repack compute."""
    kv: Dict[str, jax.Array] = {}
    for f, v in views.items():
        st = v.storage if isinstance(v, PagedView) else v
        if isinstance(st, QuantView):
            kv[f + "__q"], kv[f + "__scale"] = st.q, st.scale
        else:
            kv[f] = st.data
    return kv


def _paged_assigned_bytes(v: "PagedView") -> int:
    """Bytes of the UNIQUE assigned pages of one paged field (+ scale
    pages).  ``np.unique`` over the table means a page mapped by several
    slots (prefix sharing) is counted ONCE — the physical truth."""
    pt = np.asarray(v.page_table)
    assigned = int(np.sum(np.unique(pt) != v.trash))
    total = 0
    for pool in v._pool_children():
        per_page = int(np.prod(pool.shape[v.lead + 1:])) * \
            jnp.dtype(pool.dtype).itemsize
        lead = int(np.prod(pool.shape[:v.lead], dtype=np.int64)) \
            if v.lead else 1
        total += lead * assigned * per_page
    return total


def view_touched_bytes(views: Dict[str, FieldView]) -> int:
    """HBM bytes a layout-native decode step actually touches: assigned
    pages (+ scale pages + the table) for paged fields, the physical
    buffers for the rest.  Shared pages (prefix sharing: one page mapped
    by several slots' tables) are counted once.  Host-side accounting
    (reads the page table); used by ``benchmarks/bench_inference``."""
    total = 0
    for v in views.values():
        if isinstance(v, PagedView):
            total += _paged_assigned_bytes(v)
            pt = np.asarray(v.page_table)
            total += pt.size * pt.dtype.itemsize
        else:
            children = (v.q, v.scale) if isinstance(v, QuantView) \
                else (v.data,)
            total += sum(int(np.prod(c.shape)) *
                         jnp.dtype(c.dtype).itemsize for c in children)
    return total


def assigned_kv_bytes(views: Dict[str, FieldView]) -> int:
    """KV bytes actually REFERENCED by the live page tables: paged fields
    count their unique assigned pages (a prefix-shared page is stored —
    and counted — once), non-paged fields their full physical buffers.
    The prefix-sharing headline metric: physical cache that scales with
    *distinct* context, not with slot count."""
    total = 0
    for v in views.values():
        if isinstance(v, PagedView):
            total += _paged_assigned_bytes(v)
        else:
            children = (v.q, v.scale) if isinstance(v, QuantView) \
                else (v.data,)
            total += sum(int(np.prod(c.shape)) *
                         jnp.dtype(c.dtype).itemsize for c in children)
    return total


# ---------------------------------------------------------------------------
# Dense (base: generic pack-through + per-field slot surgery)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DenseLayout:
    """Physical == logical.  Also the base class providing the generic
    per-field slot surgery used by the other layouts' pass-through
    fields."""

    name = "dense"

    # -- logical <-> physical ----------------------------------------------
    def pack(self, dense: Dict[str, Any], bk: Dict[str, Any],
             axes: Dict[str, int]) -> Dict[str, Any]:
        return dict(dense)

    def unpack(self, kv: Dict[str, Any], bk: Dict[str, Any],
               axes: Dict[str, int]) -> Dict[str, Any]:
        return dict(kv)

    # -- KVView -------------------------------------------------------------
    def view(self, kv: Dict[str, Any], bk: Dict[str, Any],
             axes: Dict[str, int]) -> Dict[str, FieldView]:
        return {f: DenseView(v, axes[f]) for f, v in kv.items()}

    # -- layout-owned bookkeeping ------------------------------------------
    def init_bookkeeping(self, slots: int) -> Dict[str, Any]:
        return {}

    def bookkeeping_axes(self) -> Dict[str, int]:
        return {}

    # -- slot surgery on the PHYSICAL representation -----------------------
    def _axis(self, field: str, axes: Dict[str, int]) -> int:
        return axes[_base_name(field)]

    def where_rows(self, rows: jax.Array, new_kv: Dict[str, Any],
                   old_kv: Dict[str, Any], bk: Dict[str, Any],
                   axes: Dict[str, int]) -> Dict[str, Any]:
        return {f: where_rows(rows, new_kv[f], old_kv[f],
                              self._axis(f, axes)) for f in new_kv}

    def write_slot(self, kv: Dict[str, Any], bk: Dict[str, Any],
                   slot: jax.Array, dense_row: Dict[str, Any],
                   axes: Dict[str, int],
                   page_mask: Optional[jax.Array] = None,
                   exclude: Tuple[str, ...] = ()) -> Dict[str, Any]:
        """Scatter a 1-slot dense row into physical slot ``slot``.
        ``page_mask`` is a paged-layout concern (tail-only admission
        writes under prefix sharing) — ignored for non-paged layouts,
        whose slots are exclusively owned by construction.  Fields whose
        base name is in ``exclude`` come through untouched (the chunked
        prefill streams length-axis KV in via :meth:`write_span`, so the
        finalising scatter writes only the remaining fields)."""
        packed = self.pack(dense_row, bk, axes)
        out = {}
        for f, dst in kv.items():
            if _base_name(f) in exclude:
                out[f] = dst
                continue
            src = packed[f].astype(dst.dtype)
            out[f] = jax.lax.dynamic_update_slice_in_dim(
                dst, src, slot, axis=self._axis(f, axes))
        return out

    # -- chunk-granular access (chunked prefill) ----------------------------
    def read_slot(self, kv: Dict[str, Any], bk: Dict[str, Any],
                  axes: Dict[str, int], slot: jax.Array) -> Dict[str, Any]:
        """Dense logical row (batch size 1) of slot ``slot`` — the
        KV-conditioned chunked prefill seeds its row cache from this
        (adopted prefix-shared pages included) so tail chunks attend the
        resident KV.  O(row) memory; an admission-path primitive, never
        on the decode hot path."""
        row = {f: jax.lax.dynamic_slice_in_dim(v, slot, 1,
                                               self._axis(f, axes))
               for f, v in kv.items()}
        return self.unpack(row, bk, axes)

    def write_span(self, kv: Dict[str, Any], bk: Dict[str, Any],
                   slot: jax.Array, fields: Dict[str, Any],
                   length_axes: Dict[str, int], axes: Dict[str, int],
                   start: jax.Array,
                   min_page: Optional[jax.Array] = None) -> Dict[str, Any]:
        """Write one prefill chunk's positions ``[start, start + C)`` of
        the given length-axis ``fields`` (dense logical, batch size 1)
        into slot ``slot`` — the chunk-granular page write.  For
        non-paged layouts this is a positional ``dynamic_update_slice``
        (quantizing layouts quantize the chunk on write); ``min_page``
        only applies to the paged override."""
        packed = self.pack(fields, bk, axes)
        out = dict(kv)
        for f, v in packed.items():
            if f not in kv:
                continue
            dst = kv[f]
            starts = [0] * dst.ndim
            starts[self._axis(f, axes)] = slot
            starts[length_axes[_base_name(f)]] = start
            out[f] = jax.lax.dynamic_update_slice(
                dst, v.astype(dst.dtype), tuple(starts))
        return out

    # -- slot snapshot / restore (session tiering) --------------------------
    def snapshot_slot(self, kv: Dict[str, Any], bk: Dict[str, Any],
                      axes: Dict[str, int],
                      slot: jax.Array) -> Dict[str, Any]:
        """Slot ``slot``'s kv in the PHYSICAL representation (batch dim
        kept at size 1): a plain batch-axis slice per field.  Unlike
        :meth:`read_slot` this never dequantises — an int8 slot
        snapshots as its ``__q``/``__scale`` rows, so the host tier
        holds it compressed and the restore is bit-exact."""
        return {f: jax.lax.dynamic_slice_in_dim(v, slot, 1,
                                                self._axis(f, axes))
                for f, v in kv.items()}

    def restore_slot(self, kv: Dict[str, Any], bk: Dict[str, Any],
                     axes: Dict[str, int], slot: jax.Array,
                     snap: Dict[str, Any]) -> Dict[str, Any]:
        """Exact inverse of :meth:`snapshot_slot`: scatter the physical
        snapshot back into slot ``slot`` (which need not be the slot it
        was taken from)."""
        return {f: jax.lax.dynamic_update_slice_in_dim(
                    dst, snap[f].astype(dst.dtype), slot,
                    axis=self._axis(f, axes))
                for f, dst in kv.items()}


# ---------------------------------------------------------------------------
# int8 with per-vector scales
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class QuantizedLayout(DenseLayout):
    """int8 KV + float32 per-vector scales (``f`` -> ``f__q``/``f__scale``).

    KV bytes shrink ~4x vs float32 (1 byte per element + 4/head_dim
    scale overhead); decode kernels read the int8 buffers through a
    :class:`QuantView` (dequant fused in-kernel on the Pallas path), so
    accuracy is within the symmetric-int8 rounding error (~0.4% of each
    vector's max magnitude per element — the documented tolerance).
    """

    fields: Tuple[str, ...] = ()
    dtype: str = "float32"
    name = "int8"

    def pack(self, dense, bk, axes):
        out = {}
        for f, v in dense.items():
            if f in self.fields:
                out[f + "__q"], out[f + "__scale"] = quantize_int8(v)
            else:
                out[f] = v
        return out

    def unpack(self, kv, bk, axes):
        out = {}
        for f, v in kv.items():
            if f.endswith("__q"):
                base = f[:-3]
                out[base] = dequantize_int8(v, kv[base + "__scale"],
                                            jnp.dtype(self.dtype))
            elif not f.endswith("__scale"):
                out[f] = v
        return out

    def view(self, kv, bk, axes):
        out: Dict[str, FieldView] = {}
        for f, v in kv.items():
            if f.endswith("__q"):
                base = f[:-3]
                out[base] = QuantView(v, kv[base + "__scale"], axes[base],
                                      self.dtype)
            elif not f.endswith("__scale"):
                out[f] = DenseView(v, axes[f])
        return out


# ---------------------------------------------------------------------------
# Paged (optionally with int8 pages: the "paged_int8" composition)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PagedLayout(DenseLayout):
    """Length-axis KV buffers as fixed-size pages in a shared pool.

    For every paged field the dense (..., B, max_len, ...) buffer becomes
    a physical (..., pool_pages + 1, page, ...) pool — the extra page is
    TRASH: unassigned page-table entries point at it, so packs of
    unallocated regions land there and unpacks of them read garbage that
    the kernels' validity masks never touch.  One int32 page table
    ``layout__page_table`` (slots, pages_per_slot) in bookkeeping is
    shared by all paged fields.

    ``quant_fields`` non-empty is the **paged_int8** composition: those
    fields are first quantized (``f__q`` int8 + ``f__scale`` float32,
    per-vector), then any with a length axis is paged — int8 pages in
    the shared pool with the scales riding in a parallel scale pool.
    Quantized fields WITHOUT a length axis (e.g. the tconst ctx/gen
    windows) stay dense int8+scale buffers, as in
    :class:`QuantizedLayout`.

    Constraint (asserted): a paged field's batch axis must immediately
    precede its length axis, so page gather/scatter is a single take /
    indexed set.

    Fields absent from the cache (e.g. ``hist_k`` in pure-tconst mode)
    are skipped, making the layout a no-op for caches that are already
    O(1).
    """

    page: int = 64
    pool_pages: int = 0
    max_len: int = 0
    slots: int = 0
    fields: Tuple[Tuple[str, int], ...] = ()
    quant_fields: Tuple[str, ...] = ()
    dtype: str = "float32"

    @property
    def name(self) -> str:                             # type: ignore[override]
        return "paged_int8" if self.quant_fields else "paged"

    @property
    def pages_per_slot(self) -> int:
        return -(-self.max_len // self.page)

    @property
    def trash(self) -> int:
        return self.pool_pages

    @property
    def preallocated(self) -> bool:
        """Full pool: identity page table works with no allocator."""
        return self.pool_pages >= self.slots * self.pages_per_slot

    def _length_axis(self, field: str) -> Optional[int]:
        base = _base_name(field)
        for f, la in self.fields:
            if f == base:
                return la
        return None

    def pages_anything(self, kv_keys) -> bool:
        """True if any physical kv field is actually stored in pages."""
        return any(self._length_axis(f) is not None for f in kv_keys)

    def _quant_pack(self, dense: Dict[str, Any]) -> Dict[str, Any]:
        out = {}
        for f, v in dense.items():
            if f in self.quant_fields:
                out[f + "__q"], out[f + "__scale"] = quantize_int8(v)
            else:
                out[f] = v
        return out

    # -- bookkeeping --------------------------------------------------------
    def init_bookkeeping(self, slots):
        pps = self.pages_per_slot
        if self.preallocated:
            pt = jnp.arange(slots * pps, dtype=jnp.int32).reshape(slots, pps)
        else:
            pt = jnp.full((slots, pps), self.trash, jnp.int32)
        return {PAGE_TABLE: pt}

    def bookkeeping_axes(self):
        return {PAGE_TABLE: 0}

    # -- paging primitives --------------------------------------------------
    def _to_pages(self, x: jax.Array, la: int) -> jax.Array:
        """(..., B, L, rest) -> (..., B, pps, page, rest)."""
        pps = self.pages_per_slot
        pad = pps * self.page - x.shape[la]
        if pad:
            widths = [(0, 0)] * x.ndim
            widths[la] = (0, pad)
            x = jnp.pad(x, widths)
        return x.reshape(x.shape[:la] + (pps, self.page) + x.shape[la + 1:])

    def pack(self, dense, bk, axes):
        pt = bk[PAGE_TABLE]
        out = {}
        for f, v in self._quant_pack(dense).items():
            la = self._length_axis(f)
            if la is None:
                out[f] = v
                continue
            assert self._axis(f, axes) == la - 1, (f, axes, la)
            pages = self._to_pages(v, la)          # (..., B, pps, page, rest)
            pool_shape = (v.shape[:la - 1] + (self.pool_pages + 1, self.page)
                          + v.shape[la + 1:])
            idx = (slice(None),) * (la - 1) + (pt,)
            out[f] = jnp.zeros(pool_shape, v.dtype).at[idx].set(pages)
        return out

    def unpack(self, kv, bk, axes):
        pt = bk[PAGE_TABLE]
        staged = {}
        for f, v in kv.items():
            la = self._length_axis(f)
            if la is None:
                staged[f] = v
                continue
            gathered = jnp.take(v, pt, axis=la - 1)  # (..., B, pps, page, r)
            merged = gathered.reshape(
                gathered.shape[:la] + (-1,) + gathered.shape[la + 2:])
            staged[f] = jax.lax.slice_in_dim(merged, 0, self.max_len, axis=la)
        out = {}
        for f, v in staged.items():
            if f.endswith("__q"):
                out[f[:-3]] = dequantize_int8(v, staged[f[:-3] + "__scale"],
                                              jnp.dtype(self.dtype))
            elif not f.endswith("__scale"):
                out[f] = v
        return out

    def view(self, kv, bk, axes):
        pt = bk[PAGE_TABLE]
        out: Dict[str, FieldView] = {}
        for f, v in kv.items():
            if f.endswith("__scale"):
                continue
            base = _base_name(f)
            if f.endswith("__q"):
                storage: FieldView = QuantView(v, kv[base + "__scale"],
                                               axes[base], self.dtype)
            else:
                storage = DenseView(v, axes[f])
            la = self._length_axis(f)
            if la is None:
                out[base] = storage
            else:
                out[base] = PagedView(storage, pt, self.page, self.max_len,
                                      self.trash, lead=la - 1)
        return out

    # -- slot surgery -------------------------------------------------------
    def where_rows(self, rows, new_kv, old_kv, bk, axes):
        pt = bk[PAGE_TABLE]
        # slot mask -> page mask over the pool (real pages are uniquely
        # owned; the trash page's pick is arbitrary and its content dead)
        page_rows = jnp.zeros((self.pool_pages + 1,), bool).at[pt].set(
            jnp.broadcast_to(rows[:, None], pt.shape))
        out = {}
        for f in new_kv:
            la = self._length_axis(f)
            if la is None:
                out[f] = where_rows(rows, new_kv[f], old_kv[f],
                                    self._axis(f, axes))
            else:
                out[f] = where_rows(page_rows, new_kv[f], old_kv[f], la - 1)
        return out

    def write_slot(self, kv, bk, slot, dense_row, axes, page_mask=None,
                   exclude=()):
        """Page-map surgery: only the slot's own pages are touched.

        ``page_mask`` (pps,) bool selects which of the slot's table
        entries are written; masked-out entries are redirected to the
        TRASH page, so a prefix-SHARED page (refcount > 1, content
        already resident and correct) is never written by admission —
        the copy-on-write contract's tail-only prefill write.
        ``exclude`` skips fields by base name (chunked prefill: the
        length-axis KV was already streamed in by :meth:`write_span`)."""
        pt_row = jnp.take(bk[PAGE_TABLE], slot, axis=0)      # (pps,)
        if page_mask is not None:
            pt_row = jnp.where(page_mask, pt_row, self.trash)
        packed = self._quant_pack(dense_row)
        out = {}
        for f, dst in kv.items():
            if _base_name(f) in exclude:
                out[f] = dst
                continue
            la = self._length_axis(f)
            src = packed[f].astype(dst.dtype)
            if la is None:
                out[f] = jax.lax.dynamic_update_slice_in_dim(
                    dst, src, slot, axis=self._axis(f, axes))
                continue
            pages = self._to_pages(src, la)       # (..., 1, pps, page, rest)
            pages = jax.lax.index_in_dim(pages, 0, axis=la - 1,
                                         keepdims=False)
            idx = (slice(None),) * (la - 1) + (pt_row,)
            out[f] = dst.at[idx].set(pages)
        return out

    # -- chunk-granular access (chunked prefill) ----------------------------
    def read_slot(self, kv, bk, axes, slot):
        """Dense logical row of slot ``slot``, gathered through its OWN
        page-table row only — other slots' pages are never touched.
        Table entries at TRASH read garbage; the chunked-prefill seeding
        masks everything beyond the resident prefix."""
        pt_row = jnp.take(bk[PAGE_TABLE], slot, axis=0)       # (pps,)
        staged = {}
        for f, v in kv.items():
            la = self._length_axis(f)
            if la is None:
                staged[f] = jax.lax.dynamic_slice_in_dim(
                    v, slot, 1, self._axis(f, axes))
                continue
            g = jnp.take(v, pt_row, axis=la - 1)   # (..., pps, page, rest)
            g = jnp.expand_dims(g, la - 1)         # batch dim of 1
            merged = g.reshape(g.shape[:la] + (-1,) + g.shape[la + 2:])
            staged[f] = jax.lax.slice_in_dim(merged, 0, self.max_len,
                                             axis=la)
        out = {}
        for f, v in staged.items():
            if f.endswith("__q"):
                out[f[:-3]] = dequantize_int8(v, staged[f[:-3] + "__scale"],
                                              jnp.dtype(self.dtype))
            elif not f.endswith("__scale"):
                out[f] = v
        return out

    def write_span(self, kv, bk, slot, fields, length_axes, axes, start,
                   min_page=None):
        """THE chunk-granular page write: a prefill chunk covering
        positions ``[start, start + C)`` — ``start`` page-aligned, ``C``
        a page-size multiple, so the span is exactly ``C // page`` whole
        pages of the slot's table — is scattered onto those pool pages
        (int8 pools quantize on write, scales ride along).  Table
        entries below ``min_page`` (pages ADOPTED from the prefix map,
        refcount > 1) are redirected to TRASH: a chunked admission that
        recomputes part of a resident prefix (e.g. a fully-resident
        prompt still needs one chunk forwarded for its logits) can never
        violate the copy-on-write invariant."""
        pt_row = jnp.take(bk[PAGE_TABLE], slot, axis=0)       # (pps,)
        out = dict(kv)
        for f, v in self._quant_pack(fields).items():
            if f not in kv:
                continue
            dst = kv[f]
            la = self._length_axis(f)
            assert la is not None, \
                (f, "write_span takes length-axis fields only")
            C = v.shape[la]
            assert C % self.page == 0, \
                (f, C, self.page, "chunk must be a page-size multiple")
            m = C // self.page
            first = start // self.page
            pages = jax.lax.dynamic_slice_in_dim(pt_row, first, m)
            if min_page is not None:
                pages = jnp.where(first + jnp.arange(m) >= min_page,
                                  pages, self.trash)
            vv = jax.lax.index_in_dim(v.astype(dst.dtype), 0, axis=la - 1,
                                      keepdims=False)
            vv = vv.reshape(vv.shape[:la - 1] + (m, self.page)
                            + vv.shape[la:])
            idx = (slice(None),) * (la - 1) + (pages,)
            out[f] = dst.at[idx].set(vv)
        return out

    # -- slot snapshot / restore (session tiering) --------------------------
    def page_axis(self, field: str) -> Optional[int]:
        """Physical pool page axis of ``field`` (None for fields that are
        not paged) — where a slot snapshot's gathered page stack lives,
        so the scheduler can trim/pad it on the host."""
        la = self._length_axis(field)
        return None if la is None else la - 1

    def snapshot_slot(self, kv, bk, axes, slot):
        """Physical slot snapshot: paged fields gather EXACTLY the
        slot's page-table row out of the pool — ``(..., pps, page,
        rest)`` page stacks, int8 pools with their scale pools alongside
        — and non-paged fields fall back to the batch-axis row slice.
        Table entries at TRASH gather garbage pages; the host side trims
        the stack to the slot's live page count, and a restore through a
        fresh table row re-masks whatever padding comes back."""
        pt_row = jnp.take(bk[PAGE_TABLE], slot, axis=0)        # (pps,)
        out = {}
        for f, v in kv.items():
            la = self._length_axis(f)
            if la is None:
                out[f] = jax.lax.dynamic_slice_in_dim(
                    v, slot, 1, self._axis(f, axes))
            else:
                out[f] = jnp.take(v, pt_row, axis=la - 1)
        return out

    def restore_slot(self, kv, bk, axes, slot, snap):
        """Scatter a snapshot back through slot ``slot``'s CURRENT
        page-table row (the restoring scheduler assigns fresh,
        exclusively-owned pages first, so no shared page can be hit;
        entries at TRASH make the matching snapshot pages dead
        writes)."""
        pt_row = jnp.take(bk[PAGE_TABLE], slot, axis=0)        # (pps,)
        out = {}
        for f, dst in kv.items():
            la = self._length_axis(f)
            if la is None:
                out[f] = jax.lax.dynamic_update_slice_in_dim(
                    dst, snap[f].astype(dst.dtype), slot,
                    axis=self._axis(f, axes))
            else:
                ix = (slice(None),) * (la - 1) + (pt_row,)
                out[f] = dst.at[ix].set(snap[f].astype(dst.dtype))
        return out

    def gather_pages(self, kv: Dict[str, Any],
                     pages: jax.Array) -> Dict[str, Any]:
        """Pool pages ``pages`` (k,) of every paged field as host-bound
        page stacks — how refcount-0 prefix pages RETIRE into the tier
        store.  Pad ``pages`` with the trash index for a fixed arity
        (the padding gathers garbage the caller drops)."""
        out = {}
        for f, v in kv.items():
            la = self._length_axis(f)
            if la is not None:
                out[f] = jnp.take(v, pages, axis=la - 1)
        return out

    def scatter_pages(self, kv: Dict[str, Any], pages: jax.Array,
                      contents: Dict[str, Any]) -> Dict[str, Any]:
        """Inverse of :meth:`gather_pages`: upload page ``contents``
        onto pool pages ``pages`` (k,) — how retired prefix-page content
        is RE-ADOPTED from the store onto a fresh page during admission.
        Trash-padded entries are dead writes, as in :meth:`fork_pages`."""
        out = dict(kv)
        for f, v in contents.items():
            la = self._length_axis(f)
            assert la is not None, (f, "scatter_pages takes paged fields")
            dst = kv[f]
            ix = (slice(None),) * (la - 1) + (pages,)
            out[f] = dst.at[ix].set(v.astype(dst.dtype))
        return out

    # -- copy-on-write forking ----------------------------------------------
    def fork_pages(self, kv: Dict[str, Any], src: jax.Array,
                   dst: jax.Array) -> Dict[str, Any]:
        """Device-side page fork: copy pool pages ``src`` (k,) onto fresh
        pool pages ``dst`` (k,) for EVERY paged field (int8 pools carry
        their scale pool along).  The scheduler calls this before a slot
        that references shared (refcount > 1) pages can write them — the
        chunk/admission-boundary copy-on-write.  Pad ``src``/``dst``
        with the trash index for a fixed arity (trash -> trash copies
        are dead writes), so the jitted fork compiles once."""
        out = dict(kv)
        for f, pool in kv.items():
            la = self._length_axis(f)
            if la is None:
                continue
            taken = jnp.take(pool, src, axis=la - 1)
            ix = (slice(None),) * (la - 1) + (dst,)
            out[f] = pool.at[ix].set(taken)
        return out
