"""Grouped-query attention with causal / sliding / full / cross variants.

This is the reference (pure-jnp) attention used everywhere by default; the
perf-critical paths can be routed through the Pallas kernels in
``repro.kernels`` via ``repro.runtime.flags.use_pallas``.

All attention in the paper is the same softmax(QK^T/sqrt(d))V primitive with
different connection patterns (paper Fig. 2); we expose that as a ``mask``
argument so the TConstFormer core can compose its four patterns (causal
self, full self, compress cross, restore cross) from one implementation.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.layers.common import Params, dense_init, split_keys

NEG_INF = -2.3819763e38  # large negative, safe in bf16/f32


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_attention(key: jax.Array, cfg: ModelConfig,
                   d_model: Optional[int] = None) -> Params:
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko = split_keys(key, 4)
    return {
        "wq": dense_init(kq, (d, cfg.n_heads, hd), cfg.param_dtype, fan_in=d),
        "wk": dense_init(kk, (d, cfg.n_kv_heads, hd), cfg.param_dtype, fan_in=d),
        "wv": dense_init(kv, (d, cfg.n_kv_heads, hd), cfg.param_dtype, fan_in=d),
        "wo": dense_init(ko, (cfg.n_heads, hd, d), cfg.param_dtype,
                         fan_in=cfg.n_heads * hd),
    }


def qkv_proj(params: Params, xq: jax.Array, xkv: jax.Array,
             dtype: jnp.dtype) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Project queries from xq and keys/values from xkv (same for self-attn)."""
    q = jnp.einsum("bld,dhk->blhk", xq, params["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bshk", xkv, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", xkv, params["wv"].astype(dtype))
    return q, k, v


def out_proj(params: Params, o: jax.Array, dtype: jnp.dtype) -> jax.Array:
    return jnp.einsum("blhk,hkd->bld", o, params["wo"].astype(dtype))


# ---------------------------------------------------------------------------
# Masks
# ---------------------------------------------------------------------------


def make_mask(q_pos: jax.Array, k_pos: jax.Array, mode: str,
              window: "int | jax.Array" = 0) -> Optional[jax.Array]:
    """Boolean (…, Lq, Lk) mask; True = attend.

    mode: "causal" | "sliding" | "full".
    q_pos/k_pos: integer positions, shapes broadcastable to (B, Lq)/(B, Lk)
    or (Lq,)/(Lk,).  ``window`` may be a traced int32 scalar; for mode
    "sliding", window == 0 degrades to plain causal (per-layer patterns).
    """
    if mode == "full":
        return None
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    mask = kp <= qp
    if mode == "sliding":
        w = jnp.asarray(window, jnp.int32)
        weff = jnp.where(w > 0, w, jnp.int32(2**30))
        mask = jnp.logical_and(mask, kp > qp - weff)
    elif mode != "causal":
        raise ValueError(mode)
    return mask


# ---------------------------------------------------------------------------
# Core scaled-dot-product attention (GQA aware)
# ---------------------------------------------------------------------------


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
         mask: Optional[jax.Array] = None,
         logit_softcap: float = 0.0,
         kv_valid: Optional[jax.Array] = None,
         scale: Optional[float] = None) -> jax.Array:
    """q: (B, Lq, H, D); k, v: (B, Lk, KV, D); mask: (B?, Lq, Lk) bool.

    kv_valid: optional (B, Lk) bool marking valid cache slots (decode).
    scale: the score scale (None: ``D ** -0.5``).
    Returns (B, Lq, H, D).
    """
    B, Lq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = D ** -0.5 if scale is None else scale
    qg = q.reshape(B, Lq, KV, G, D)
    logits = jnp.einsum("blkgd,bskd->bklgs", qg.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))          # (B, KV, Lq, G, Lk)
    if logit_softcap > 0.0:
        logits = jnp.tanh(logits / logit_softcap) * logit_softcap

    cm = None                                            # (B, Lq, Lk) bool
    if mask is not None:
        cm = mask if mask.ndim == 3 else jnp.broadcast_to(
            mask[None], (B,) + mask.shape)
    if kv_valid is not None:
        kvm = jnp.broadcast_to(kv_valid[:, None, :], (B, Lq, kv_valid.shape[-1]))
        cm = kvm if cm is None else jnp.logical_and(cm, kvm)

    if cm is None:
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        # masked-safe softmax: fully-masked query rows produce zero output
        # (needed by the TConst context path when history is still empty).
        mm = cm[:, None, :, None, :]
        logits = jnp.where(mm, logits, NEG_INF)
        mx = jnp.max(logits, axis=-1, keepdims=True)
        e = jnp.exp(logits - jax.lax.stop_gradient(mx)) * mm
        probs = e / (jnp.sum(e, axis=-1, keepdims=True) + 1e-30)
    o = jnp.einsum("bklgs,bskd->blkgd", probs, v.astype(jnp.float32))
    return o.reshape(B, Lq, H, D).astype(q.dtype)


def attention_block(params: Params, xq: jax.Array, xkv: jax.Array,
                    mask: Optional[jax.Array],
                    cos_q: Optional[jax.Array] = None,
                    sin_q: Optional[jax.Array] = None,
                    cos_k: Optional[jax.Array] = None,
                    sin_k: Optional[jax.Array] = None,
                    logit_softcap: float = 0.0) -> jax.Array:
    """Full projected attention; RoPE applied when cos/sin given."""
    from repro.layers.rope import apply_rope
    dtype = xq.dtype
    q, k, v = qkv_proj(params, xq, xkv, dtype)
    if cos_q is not None:
        q = apply_rope(q, cos_q, sin_q)
    if cos_k is not None:
        k = apply_rope(k, cos_k, sin_k)
    o = sdpa(q, k, v, mask, logit_softcap)
    return out_proj(params, o, dtype)


# ---------------------------------------------------------------------------
# Decode-step attention against a static cache
# ---------------------------------------------------------------------------


def cross_attend_cached(params: Params, x: jax.Array, k_cache: jax.Array,
                        v_cache: jax.Array, kv_valid: Optional[jax.Array],
                        cos_q: Optional[jax.Array] = None,
                        sin_q: Optional[jax.Array] = None,
                        logit_softcap: float = 0.0) -> jax.Array:
    """Cross-attention against pre-projected (cached) K/V.

    x: (B, Lq, d); k_cache/v_cache: (B, S, KV, D) already RoPE'd at their
    source positions; kv_valid: (B, S) bool.  Used by the TConst decode path
    (queries attend to the static compressed-context KV).
    """
    from repro.layers.rope import apply_rope
    dtype = x.dtype
    q = jnp.einsum("bld,dhk->blhk", x, params["wq"].astype(dtype))
    if cos_q is not None:
        q = apply_rope(q, cos_q, sin_q)
    o = sdpa(q, k_cache.astype(dtype), v_cache.astype(dtype),
             mask=None, logit_softcap=logit_softcap, kv_valid=kv_valid)
    return out_proj(params, o, dtype)


def project_kv(params: Params, x: jax.Array,
               cos: Optional[jax.Array] = None,
               sin: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Project (and RoPE) K/V for caching. x: (B, S, d) -> (B, S, KV, D)."""
    from repro.layers.rope import apply_rope
    dtype = x.dtype
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
    if cos is not None:
        k = apply_rope(k, cos, sin)
    return k, v


def _attend_views(q: jax.Array, k_view, v_view, *,
                  valid_len: Optional[jax.Array] = None,
                  kv_valid: Optional[jax.Array] = None,
                  logit_softcap: float = 0.0,
                  window: "int | jax.Array" = 0,
                  scale: Optional[float] = None) -> jax.Array:
    """Dispatch one-token attention over a per-layer KVView pair
    (``repro.models.layouts``): the kernel consumes the PHYSICAL
    representation.

    * :class:`~repro.models.layouts.PagedView` — in-kernel page-table
      walk (Pallas on the Pallas path, page-at-a-time XLA scan
      otherwise); int8 pools fuse the dequant.  Needs a prefix
      ``valid_len`` (+ optional sliding ``window``).
    * :class:`~repro.models.layouts.QuantView` — fused int8 kernel on
      the Pallas path; dequantise-then-``sdpa`` fallback (XLA fuses the
      scale multiply into the contraction).
    * :class:`~repro.models.layouts.DenseView` — exactly the historic
      dense path (bit-identical to the pre-KVView code).

    q: (B, 1, H, D) RoPE'd queries; ``scale`` the score scale (None:
    ``D ** -0.5``).  Returns (B, 1, H, D).
    """
    from repro.kernels import ops
    from repro.models import layouts as LT
    dtype = q.dtype
    if isinstance(k_view, LT.PagedView):
        assert kv_valid is None and valid_len is not None, \
            "paged attention needs a prefix valid_len"
        if k_view.quant:
            o = ops.paged_decode(
                q[:, 0], k_view.storage.q, v_view.storage.q,
                k_view.page_table, valid_len, softcap=logit_softcap,
                window=window, k_scale=k_view.storage.scale,
                v_scale=v_view.storage.scale, scale=scale)
        else:
            o = ops.paged_decode(
                q[:, 0], k_view.storage.data.astype(dtype),
                v_view.storage.data.astype(dtype), k_view.page_table,
                valid_len, softcap=logit_softcap, window=window,
                scale=scale)
        return o[:, None]
    if isinstance(k_view, LT.QuantView) and valid_len is not None and \
            kv_valid is None and ops.int8_fused_available(window):
        o = ops.int8_decode_fused(q[:, 0], k_view.q, v_view.q,
                                  k_view.scale, v_view.scale, valid_len,
                                  logit_softcap, window, scale)
        return o[:, None]
    k = k_view.dense().astype(dtype)
    v = v_view.dense().astype(dtype)
    if kv_valid is None and valid_len is not None:
        slots = jnp.arange(k.shape[1])[None]                   # (1, S)
        kv_valid = slots < valid_len[:, None]
        w = jnp.asarray(window, jnp.int32)
        weff = jnp.where(w > 0, w, jnp.int32(2 ** 30))
        kv_valid = jnp.logical_and(kv_valid,
                                   slots >= valid_len[:, None] - weff)
    return sdpa(q, k, v, mask=None, logit_softcap=logit_softcap,
                kv_valid=kv_valid, scale=scale)


def decode_attend_view(params: Params, x: jax.Array, k_view, v_view,
                       cache_len: jax.Array,
                       cos_q: Optional[jax.Array] = None,
                       sin_q: Optional[jax.Array] = None,
                       logit_softcap: float = 0.0,
                       window: "int | jax.Array" = 0,
                       live: Optional[jax.Array] = None,
                       scale: Optional[float] = None):
    """Layout-native one-token decode (:func:`decode_attend` over
    KVViews): project q/k/v for the new token, append K/V *through the
    view* (paged: only the owning page is touched; int8: the vector is
    quantized in place), attend over slots ``<= cache_len`` in the
    physical representation.  ``live`` (B,) bool masks the append by
    row (frozen rows write nothing; their output is garbage the caller
    discards); ``scale`` is the score scale (None: ``D ** -0.5``).
    Returns (out (B,1,d), k_view, v_view)."""
    from repro.layers.rope import apply_rope
    dtype = x.dtype
    q, k_new, v_new = qkv_proj(params, x, x, dtype)
    if cos_q is not None:
        q = apply_rope(q, cos_q, sin_q)
        k_new = apply_rope(k_new, cos_q, sin_q)
    k_view = k_view.write_token(cache_len, k_new[:, 0], live)
    v_view = v_view.write_token(cache_len, v_new[:, 0], live)
    o = _attend_views(q, k_view, v_view, valid_len=cache_len + 1,
                      logit_softcap=logit_softcap, window=window,
                      scale=scale)
    return out_proj(params, o, dtype), k_view, v_view


def cross_attend_view(params: Params, x: jax.Array, k_view, v_view,
                      kv_valid: Optional[jax.Array] = None,
                      cos_q: Optional[jax.Array] = None,
                      sin_q: Optional[jax.Array] = None,
                      logit_softcap: float = 0.0,
                      valid_len: Optional[jax.Array] = None,
                      window: "int | jax.Array" = 0) -> jax.Array:
    """Layout-native :func:`cross_attend_cached`: queries attend to
    pre-projected cached K/V read through a KVView pair.  Pass EITHER a
    general (B, S) ``kv_valid`` mask (dense/int8 views only) or a prefix
    ``valid_len`` (any view, required for paged)."""
    from repro.layers.rope import apply_rope
    dtype = x.dtype
    q = jnp.einsum("bld,dhk->blhk", x, params["wq"].astype(dtype))
    if cos_q is not None:
        q = apply_rope(q, cos_q, sin_q)
    o = _attend_views(q, k_view, v_view, valid_len=valid_len,
                      kv_valid=kv_valid, logit_softcap=logit_softcap,
                      window=window)
    return out_proj(params, o, dtype)


def verify_attend_view(params: Params, x: jax.Array, k_view, v_view,
                       kv_valid: Optional[jax.Array] = None,
                       cos_q: Optional[jax.Array] = None,
                       sin_q: Optional[jax.Array] = None,
                       logit_softcap: float = 0.0,
                       valid_len: Optional[jax.Array] = None,
                       window: "int | jax.Array" = 0) -> jax.Array:
    """Multi-query cross-attention over a KVView pair for speculative
    VERIFY: x (B, C, d) — all C draft positions attend the resident KV
    in one dispatch.  Unlike :func:`cross_attend_view` this never takes
    the single-query paged / fused-int8 kernels (they are Lq=1 only);
    every view kind is densified and scored through the masked-safe
    :func:`sdpa`, with ``kv_valid`` (B, S) or a prefix ``valid_len``
    bounding the readable slots exactly as the sequential step would.
    """
    from repro.layers.rope import apply_rope
    dtype = x.dtype
    q = jnp.einsum("bld,dhk->blhk", x, params["wq"].astype(dtype))
    if cos_q is not None:
        q = apply_rope(q, cos_q, sin_q)
    k = k_view.dense().astype(dtype)
    v = v_view.dense().astype(dtype)
    if kv_valid is None and valid_len is not None:
        slots = jnp.arange(k.shape[1])[None]                   # (1, S)
        kv_valid = slots < valid_len[:, None]
        w = jnp.asarray(window, jnp.int32)
        weff = jnp.where(w > 0, w, jnp.int32(2 ** 30))
        kv_valid = jnp.logical_and(kv_valid,
                                   slots >= valid_len[:, None] - weff)
    o = sdpa(q, k, v, mask=None, logit_softcap=logit_softcap,
             kv_valid=kv_valid)
    return out_proj(params, o, dtype)


def decode_attend(params: Params, x: jax.Array, k_cache: jax.Array,
                  v_cache: jax.Array, cache_len: jax.Array,
                  cos_q: Optional[jax.Array] = None,
                  sin_q: Optional[jax.Array] = None,
                  logit_softcap: float = 0.0,
                  window: "int | jax.Array" = 0
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode: x (B, 1, d); cache (B, S, KV, D); cache_len (B,).

    Projects q/k/v for the new token, writes k/v into the cache at
    ``cache_len``, attends over valid slots (optionally sliding-window
    limited), returns (out (B,1,d), new_k_cache, new_v_cache).
    """
    from repro.layers.rope import apply_rope
    dtype = x.dtype
    B, _, _ = x.shape
    S = k_cache.shape[1]
    q, k_new, v_new = qkv_proj(params, x, x, dtype)
    if cos_q is not None:
        q = apply_rope(q, cos_q, sin_q)
        k_new = apply_rope(k_new, cos_q, sin_q)
    # scatter the new K/V into the cache at each sequence's write index.
    # (A one-hot masked rewrite was measured to double decode-step HBM
    # traffic/peak — it reads AND writes the whole cache; scatter touches
    # one slot and updates in place under donation.)
    bidx = jnp.arange(x.shape[0])
    k_cache = k_cache.at[bidx, cache_len].set(
        k_new[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[bidx, cache_len].set(
        v_new[:, 0].astype(v_cache.dtype))
    slots = jnp.arange(S)[None]                                # (1, S)
    valid = slots <= cache_len[:, None]
    w = jnp.asarray(window, jnp.int32)
    weff = jnp.where(w > 0, w, jnp.int32(2**30))
    valid = jnp.logical_and(valid, slots > cache_len[:, None] - weff)
    o = sdpa(q, k_cache.astype(dtype), v_cache.astype(dtype),
             mask=None, logit_softcap=logit_softcap, kv_valid=valid)
    return out_proj(params, o, dtype), k_cache, v_cache
