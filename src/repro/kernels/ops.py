"""Jit'd dispatch wrappers for the Pallas kernels.

Routing policy
--------------
* On TPU (``jax.default_backend() == "tpu"``): Pallas kernels, compiled.
* Elsewhere (this CPU container, and the dry-run which lowers pure XLA):
  - ``repro.kernels.xla_flash`` for big attention (same blocked algorithm,
    plain XLA ops, differentiable);
  - the pure-jnp references for small shapes.
* ``repro.runtime.flags.use_pallas`` + ``pallas_interpret`` force the
  Pallas path in interpret mode (used by the kernel test sweeps).

``flash`` is differentiable everywhere: on the Pallas path the forward
runs the TPU kernel and the backward falls back to the XLA blocked
implementation via ``jax.custom_vjp`` (the production bwd kernel is the
listed follow-up in DESIGN.md).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import runtime
from repro.kernels import ref as REF
from repro.kernels import xla_flash as XF
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_fwd_pallas
from repro.kernels.paged_decode_attention import (
    paged_decode_attention_pallas, paged_decode_attention_xla)
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.kernels.ssd_state_update import ssd_state_update_pallas


def _pallas_enabled() -> bool:
    return runtime.flags.use_pallas or jax.default_backend() == "tpu"


def _interpret() -> bool:
    return runtime.flags.pallas_interpret and jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Decode-mesh scope (mesh-native serving)
#
# The DecodeAPI step/sync/chunk bodies trace inside ``decode_mesh_scope``;
# while the scope is active the decode and prefill-chunk attention below
# shard_map themselves over the mesh: query/output head dims and the KV-head
# dim of the caches split over ``model`` (each shard computes its local
# head slice — per-head attention is embarrassingly parallel, so the body
# needs NO collective; the single psum for the output projection is the
# all-reduce GSPMD inserts at the model-sharded ``wo`` contraction just
# outside), the slot/batch dim splits over the data axes, and the paged
# pool rides in REPLICATED over data + sharded over model, so a sharded
# step never all-gathers the KV pool.  The Pallas page-walk kernel runs
# per-shard on its local head slice; the XLA fallback is unchanged —
# both see ordinary smaller arrays inside the shard_map body.
# ---------------------------------------------------------------------------

_DECODE_MESH: list = [None]


@contextlib.contextmanager
def decode_mesh_scope(mesh):
    """Trace-time scope; accepts None, a jax Mesh, or anything with a
    ``.mesh`` attribute (e.g. ``repro.sharding.rules.MeshContext``)."""
    _DECODE_MESH.append(getattr(mesh, "mesh", mesh))
    try:
        yield
    finally:
        _DECODE_MESH.pop()


def _decode_mesh() -> Optional[Mesh]:
    return _DECODE_MESH[-1]


def _mesh_axes(mesh: Mesh, *, batch: int, heads: Tuple[int, ...]
               ) -> Tuple[Any, Optional[str]]:
    """(data spec entry for the batch dim, model spec entry for head
    dims) — None where the respective sizes don't divide, so partially
    applicable meshes degrade per-axis instead of bailing out."""
    daxes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]
    db = None
    if dsize > 1 and batch % dsize == 0 and batch >= dsize:
        db = daxes if len(daxes) > 1 else daxes[0]
    mb = None
    msize = mesh.shape["model"] if "model" in mesh.axis_names else 1
    if msize > 1 and all(h % msize == 0 and h >= msize for h in heads):
        mb = "model"
    return db, mb


def _shard_mapped(inner, mesh: Mesh, in_specs, out_specs):
    """shard_map with the conventions used here: dict-pytree operands,
    varying-manual-axes checking off (per-shard valid_len/page tables
    are intentionally replicated inside a data shard)."""
    return jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_pallas_diff(q, k, v, q_pos, k_pos, causal, window, softcap,
                       scale):
    return flash_attention_fwd_pallas(
        q, k, v, q_pos, k_pos, causal=causal, window=window,
        softcap=softcap, scale=scale, interpret=_interpret())


def _fp_fwd(q, k, v, q_pos, k_pos, causal, window, softcap, scale):
    o = _flash_pallas_diff(q, k, v, q_pos, k_pos, causal, window, softcap,
                           scale)
    return o, (q, k, v, q_pos, k_pos)


def _fp_bwd(causal, window, softcap, scale, res, do):
    q, k, v, q_pos, k_pos = res
    # backward via the (differentiable) XLA blocked implementation
    _, vjp = jax.vjp(
        lambda q_, k_, v_: XF.flash_attention(
            q_, k_, v_, q_pos, k_pos, window, causal, softcap, 256, 512,
            scale),
        q, k, v)
    dq, dk, dv = vjp(do)
    return dq, dk, dv, None, None


_flash_pallas_diff.defvjp(_fp_fwd, _fp_bwd)


def flash(q: jax.Array, k: jax.Array, v: jax.Array,
          q_pos: jax.Array, k_pos: jax.Array,
          window: "int | jax.Array" = 0, causal: bool = True,
          softcap: float = 0.0, scale: Optional[float] = None
          ) -> jax.Array:
    """Dispatching flash attention (see module docstring); ``scale``
    multiplies the scores (None: ``D ** -0.5``)."""
    static_window = isinstance(window, int)
    if _pallas_enabled() and static_window and \
            q.shape[1] % 128 == 0 and k.shape[1] % 128 == 0:
        return _flash_pallas_diff(q, k, v, q_pos, k_pos, causal, window,
                                  softcap, scale)
    return XF.flash_attention(q, k, v, q_pos, k_pos, window, causal,
                              softcap, 512, 512, scale)


# ---------------------------------------------------------------------------
# Chunked-prefill attention (admission path)
# ---------------------------------------------------------------------------

# route a chunk's score matrix through the blocked flash path above this
# many C x S elements (below it the masked reference sdpa is cheaper)
PREFILL_CHUNK_FLASH_ELEMS = 1 << 22


def _prefill_chunk_attention_impl(q, k, v, q_pos, k_pos, window, softcap,
                                  scale=None):
    if q.shape[1] * k.shape[1] >= PREFILL_CHUNK_FLASH_ELEMS:
        return flash(q, k, v, q_pos, k_pos, window, True, softcap, scale)
    from repro.layers.attention import make_mask, sdpa
    mask = make_mask(q_pos, k_pos, "sliding", window)
    return sdpa(q, k, v, mask, softcap, scale=scale)


def prefill_chunk_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            q_pos: jax.Array, k_pos: jax.Array,
                            window: "int | jax.Array" = 0,
                            softcap: float = 0.0,
                            scale: Optional[float] = None) -> jax.Array:
    """One prefill chunk's C queries against the slot's row cache
    (``scale``: the score scale, None for ``D ** -0.5``).

    q: (B, C, H, D); k/v: (B, S, KV, D) — the row cache with positions
    [0, start + C) written (resident prefix + earlier chunks + this
    chunk).  Garbage beyond is causally dead: every unwritten slot's
    position exceeds every query's.  Causal + optional sliding window
    (``window`` may be a traced per-layer scalar).  Large score matrices
    route through the blocked flash path (Pallas when enabled); small
    shapes use the masked reference sdpa — numerically interchangeable.
    Under a decode-mesh scope the heads split over ``model`` via
    shard_map (chunk rows are batch-1, so the data axes don't apply).
    """
    mesh = _decode_mesh()
    if mesh is not None:
        db, mb = _mesh_axes(mesh, batch=q.shape[0],
                            heads=(q.shape[2], k.shape[2]))
        if db is not None or mb is not None:
            def _pos_spec(p):
                b = db if (p.ndim >= 2 and p.shape[0] == q.shape[0]) \
                    else None
                return P(*((b,) + (None,) * (p.ndim - 1)))
            operands: Dict[str, Any] = dict(q=q, k=k, v=v, q_pos=q_pos,
                                            k_pos=k_pos)
            specs: Dict[str, P] = dict(
                q=P(db, None, mb, None), k=P(db, None, mb, None),
                v=P(db, None, mb, None), q_pos=_pos_spec(q_pos),
                k_pos=_pos_spec(k_pos))
            static_window = isinstance(window, int)
            if not static_window:
                operands["window"] = jnp.asarray(window)
                specs["window"] = P()

            def inner(o):
                w = window if static_window else o["window"]
                return _prefill_chunk_attention_impl(
                    o["q"], o["k"], o["v"], o["q_pos"], o["k_pos"], w,
                    softcap, scale)

            return _shard_mapped(inner, mesh, (specs,),
                                 P(db, None, mb, None))(operands)
    return _prefill_chunk_attention_impl(q, k, v, q_pos, k_pos, window,
                                         softcap, scale)


# ---------------------------------------------------------------------------
# Decode attention (O(1) cache-hit step)
# ---------------------------------------------------------------------------


def _decode_attend_kv_impl(q, k, v, valid_len, softcap):
    if _pallas_enabled() and q.shape[-1] % 8 == 0:
        return decode_attention_pallas(q, k, v, valid_len, softcap=softcap,
                                       interpret=_interpret())
    return REF.decode_reference(q, k, v, valid_len, softcap=softcap)


def decode_attend_kv(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid_len: jax.Array, softcap: float = 0.0
                     ) -> jax.Array:
    """q: (B, H, D); k/v: (B, S, KV, D); valid_len (B,).  Under a
    decode-mesh scope: slots over data, heads over model (shard_map)."""
    mesh = _decode_mesh()
    if mesh is not None:
        db, mb = _mesh_axes(mesh, batch=q.shape[0],
                            heads=(q.shape[1], k.shape[2]))
        if db is not None or mb is not None:
            inner = functools.partial(_decode_attend_kv_impl,
                                      softcap=softcap)
            return _shard_mapped(
                inner, mesh,
                (P(db, mb, None), P(db, None, mb, None),
                 P(db, None, mb, None), P(db)),
                P(db, mb, None))(q, k, v, valid_len)
    return _decode_attend_kv_impl(q, k, v, valid_len, softcap)


def _int8_decode_fused_impl(q, kq, vq, k_scale, v_scale, valid_len,
                            softcap, window, scale=None):
    return decode_attention_pallas(
        q, kq, vq, valid_len, softcap=softcap, window=window,
        k_scale=k_scale, v_scale=v_scale, scale=scale,
        interpret=_interpret())


def int8_decode_fused(q: jax.Array, kq: jax.Array, vq: jax.Array,
                      k_scale: jax.Array, v_scale: jax.Array,
                      valid_len: jax.Array, softcap: float = 0.0,
                      window: int = 0, scale: Optional[float] = None
                      ) -> jax.Array:
    """Fused int8 decode: dequant happens inside the QK/AV loops (1 HBM
    byte per element).  Caller checks :func:`int8_fused_available`.
    Under a decode-mesh scope the int8 pools shard like their parents
    (KV heads over model); the (..., 1) scale dims stay replicated."""
    mesh = _decode_mesh()
    if mesh is not None:
        db, mb = _mesh_axes(mesh, batch=q.shape[0],
                            heads=(q.shape[1], kq.shape[2]))
        if db is not None or mb is not None:
            inner = functools.partial(_int8_decode_fused_impl,
                                      softcap=softcap, window=window,
                                      scale=scale)
            kv_spec = P(db, None, mb, None)
            return _shard_mapped(
                inner, mesh,
                (P(db, mb, None), kv_spec, kv_spec, kv_spec, kv_spec,
                 P(db)),
                P(db, mb, None))(q, kq, vq, k_scale, v_scale, valid_len)
    return _int8_decode_fused_impl(q, kq, vq, k_scale, v_scale, valid_len,
                                   softcap, window, scale)


def int8_fused_available(window) -> bool:
    """The fused int8 kernel needs the Pallas path and a STATIC window
    (it is baked into the kernel); traced per-layer windows fall back to
    the dequantise-then-attend XLA path."""
    return _pallas_enabled() and isinstance(window, int)


# ---------------------------------------------------------------------------
# Paged decode attention (in-kernel page-table walk)
# ---------------------------------------------------------------------------


def _paged_decode_impl(q, pool_k, pool_v, page_table, valid_len, *,
                       softcap, window, k_scale, v_scale, scale=None):
    if _pallas_enabled():
        return paged_decode_attention_pallas(
            q, pool_k, pool_v, page_table, valid_len, softcap=softcap,
            window=window, k_scale=k_scale, v_scale=v_scale, scale=scale,
            interpret=_interpret())
    return paged_decode_attention_xla(
        q, pool_k, pool_v, page_table, valid_len, softcap=softcap,
        window=window, k_scale=k_scale, v_scale=v_scale, scale=scale)


def paged_decode(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                 page_table: jax.Array, valid_len: jax.Array, *,
                 softcap: float = 0.0, window: "int | jax.Array" = 0,
                 k_scale=None, v_scale=None,
                 scale: Optional[float] = None) -> jax.Array:
    """Layout-native paged decode attention: Pallas page-table-walk
    kernel on the Pallas path (compiled on TPU, interpret elsewhere),
    page-at-a-time XLA scan otherwise.  Neither materialises the dense
    (B, max_len, KV, D) logical view.

    Under a decode-mesh scope the step runs inside shard_map: queries
    split (slots over data, heads over model) and each shard walks the
    SAME page table over its LOCAL (pool, page, KV/shards, D) pool
    slice — the pool's page axis stays whole per shard (any slot may
    own any page), so no all-gather of the pool ever appears."""
    mesh = _decode_mesh()
    if mesh is not None:
        db, mb = _mesh_axes(mesh, batch=q.shape[0],
                            heads=(q.shape[1], pool_k.shape[-2]))
        if db is not None or mb is not None:
            pool_spec = P(None, None, mb, None)
            operands: Dict[str, Any] = dict(
                q=q, pool_k=pool_k, pool_v=pool_v, page_table=page_table,
                valid_len=valid_len)
            specs: Dict[str, P] = dict(
                q=P(db, mb, None), pool_k=pool_spec, pool_v=pool_spec,
                page_table=P(db, None), valid_len=P(db))
            static_window = isinstance(window, int)
            if not static_window:
                operands["window"] = jnp.asarray(window)
                specs["window"] = P()
            if k_scale is not None:
                operands["k_scale"] = k_scale
                operands["v_scale"] = v_scale
                specs["k_scale"] = pool_spec
                specs["v_scale"] = pool_spec

            def inner(o):
                return _paged_decode_impl(
                    o["q"], o["pool_k"], o["pool_v"], o["page_table"],
                    o["valid_len"], softcap=softcap,
                    window=window if static_window else o["window"],
                    k_scale=o.get("k_scale"), v_scale=o.get("v_scale"),
                    scale=scale)

            return _shard_mapped(inner, mesh, (specs,),
                                 P(db, mb, None))(operands)
    return _paged_decode_impl(q, pool_k, pool_v, page_table, valid_len,
                              softcap=softcap, window=window,
                              k_scale=k_scale, v_scale=v_scale, scale=scale)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------


def ssd_scan(x, dt, a, b, c, chunk, init_state=None):
    if _pallas_enabled():
        return ssd_scan_pallas(x, dt, a, b, c, chunk, init_state,
                               interpret=_interpret())
    from repro.layers.ssm import ssd_chunked
    return ssd_chunked(x, dt, a, b, c, chunk, init_state)


# ---------------------------------------------------------------------------
# SSD decode step, in place in the state stack
# ---------------------------------------------------------------------------


def _ssd_state_update_impl(stack, i, x, dt, a, b, c, live):
    if _pallas_enabled():
        f32 = jnp.float32
        dec = jnp.exp(dt.astype(f32) * a.astype(f32)[None])
        xdt = x.astype(f32) * dt.astype(f32)[..., None]
        y, stack = ssd_state_update_pallas(stack, i, dec, xdt, b, c, live,
                                           interpret=_interpret())
        return y.astype(x.dtype), stack
    from repro.layers.common import where_rows
    from repro.layers.ssm import ssd_step
    st = jax.lax.dynamic_index_in_dim(stack, i, 0, keepdims=False)
    y, new = ssd_step(st, x, dt, a, b, c)
    if live is not None:
        new = where_rows(live, new, st, 0)
    return y, jax.lax.dynamic_update_index_in_dim(
        stack, new.astype(stack.dtype), i, 0)


def ssd_state_update(stack: jax.Array, i: jax.Array, x: jax.Array,
                     dt: jax.Array, a: jax.Array, b: jax.Array,
                     c: jax.Array, live: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """One recurrent decode step of layer ``i``'s state inside its stack
    (L, B, H, P, N); x: (B, H, P); dt: (B, H); a: (H,); b, c: (B, N);
    ``live`` (B,) bool freezes rows (None: every row steps).  Returns
    (y (B, H, P), the stack with layer i stepped).

    The Pallas path reads and writes the layer's state once, in place;
    the XLA path is ``ssd_step`` on the sliced layer, the per-row
    select and the write-back.  Under a decode-mesh scope the kernel
    runs inside shard_map: slots over data, heads over model (the
    stack's own split, ``repro.sharding.rules``); the XLA path is left
    to the partitioner, as before."""
    mesh = _decode_mesh()
    if mesh is not None and _pallas_enabled():
        db, mb = _mesh_axes(mesh, batch=x.shape[0], heads=(x.shape[1],))
        if db is not None or mb is not None:
            operands: Dict[str, Any] = dict(stack=stack, i=jnp.asarray(i),
                                            x=x, dt=dt, a=a, b=b, c=c)
            specs: Dict[str, P] = dict(
                stack=P(None, db, mb, None, None), i=P(), x=P(db, mb, None),
                dt=P(db, mb), a=P(mb), b=P(db, None), c=P(db, None))
            if live is not None:
                operands["live"] = live
                specs["live"] = P(db)

            def inner(o):
                return _ssd_state_update_impl(
                    o["stack"], o["i"], o["x"], o["dt"], o["a"], o["b"],
                    o["c"], o.get("live"))

            return _shard_mapped(inner, mesh, (specs,),
                                 (P(db, mb, None), specs["stack"]))(operands)
    return _ssd_state_update_impl(stack, i, x, dt, a, b, c, live)
