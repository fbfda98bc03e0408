"""Whisper-style encoder-decoder backbone [arXiv:2212.04356].

Per the assignment carve-out, the mel-spectrogram + conv feature extractor
is a STUB: ``input_specs`` supplies post-conv frame embeddings of shape
(B, encoder_seq, frontend_dim); a learned projector maps them to d_model.

Deviations from the original (documented in DESIGN.md): decoder
self-attention uses RoPE instead of learned absolute positions so that the
assigned decode shapes (32k / 524k) are well-defined; norms are LayerNorm
and FFNs GELU, as in the original.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.layers import attention as A
from repro.layers import embed as E
from repro.layers import rope as R
from repro.layers.common import (Params, embed_init, init_layernorm,
                                 layernorm, split_keys)
from repro.layers.mlp import gelu_mlp, init_gelu_mlp
from repro.kernels.xla_flash import flash_attention

FLASH_THRESHOLD = 2048


def _init_enc_layer(key: jax.Array, cfg: ModelConfig) -> Params:
    ka, kf = split_keys(key, 2)
    return {
        "ln1": init_layernorm(cfg.d_model, cfg.param_dtype),
        "attn": A.init_attention(ka, cfg),
        "ln2": init_layernorm(cfg.d_model, cfg.param_dtype),
        "ffn": init_gelu_mlp(kf, cfg.d_model, cfg.d_ff, cfg.param_dtype),
    }


def _init_dec_layer(key: jax.Array, cfg: ModelConfig) -> Params:
    ka, kc, kf = split_keys(key, 3)
    return {
        "ln1": init_layernorm(cfg.d_model, cfg.param_dtype),
        "attn": A.init_attention(ka, cfg),
        "lnc": init_layernorm(cfg.d_model, cfg.param_dtype),
        "cross": A.init_attention(kc, cfg),
        "ln2": init_layernorm(cfg.d_model, cfg.param_dtype),
        "ffn": init_gelu_mlp(kf, cfg.d_model, cfg.d_ff, cfg.param_dtype),
    }


def init_encdec(key: jax.Array, cfg: ModelConfig) -> Params:
    ke, kp, kenc, kdec = split_keys(key, 4)
    enc_keys = jax.random.split(kenc, cfg.encoder_layers)
    dec_keys = jax.random.split(kdec, cfg.n_layers)
    return {
        "embed": E.init_embed(ke, cfg),
        "enc_pos": embed_init(kp, (cfg.encoder_seq, cfg.d_model),
                              cfg.param_dtype),
        "enc_layers": jax.vmap(lambda k: _init_enc_layer(k, cfg))(enc_keys),
        "enc_norm": init_layernorm(cfg.d_model, cfg.param_dtype),
        "dec_layers": jax.vmap(lambda k: _init_dec_layer(k, cfg))(dec_keys),
        "dec_norm": init_layernorm(cfg.d_model, cfg.param_dtype),
    }


# ---------------------------------------------------------------------------
# Encoder
# ---------------------------------------------------------------------------


def encode(params: Params, audio_feats: jax.Array, cfg: ModelConfig
           ) -> jax.Array:
    """audio_feats: (B, T_enc, frontend_dim) stub conv-frontend output."""
    from repro.sharding.rules import shard_act
    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.norm_eps
    x = E.project_frontend(params["embed"], audio_feats.astype(dtype))
    x = x + params["enc_pos"].astype(dtype)[None, :x.shape[1]]
    x = shard_act(x)

    def body(x, layer):
        x = shard_act(x)
        xn = layernorm(layer["ln1"], x, eps)
        o = A.attention_block(layer["attn"], xn, xn, None)   # bidirectional
        x = x + o
        x = x + gelu_mlp(layer["ffn"], layernorm(layer["ln2"], x, eps))
        return x, None

    body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return layernorm(params["enc_norm"], x, eps)


# ---------------------------------------------------------------------------
# Decoder (teacher-forced)
# ---------------------------------------------------------------------------


def _dec_self_attn(layer: Params, xn: jax.Array, pos: jax.Array,
                   cfg: ModelConfig, cos, sin) -> jax.Array:
    dtype = xn.dtype
    q, k, v = A.qkv_proj(layer["attn"], xn, xn, dtype)
    q = R.apply_rope(q, cos, sin)
    k = R.apply_rope(k, cos, sin)
    L = xn.shape[1]
    window = cfg.sliding_window if cfg.attention_mode == "sliding" else 0
    if L >= FLASH_THRESHOLD:
        o = flash_attention(q, k, v, pos, pos, window, True, 0.0, 512, 512)
    else:
        mode = "sliding" if window else "causal"
        o = A.sdpa(q, k, v, A.make_mask(pos, pos, mode, window))
    return A.out_proj(layer["attn"], o, dtype)


def _cross_attn(layer: Params, xc: jax.Array, memory: jax.Array) -> jax.Array:
    """Decoder->encoder cross-attention; blocked path for long decoders
    (naive logits are (B, H, L_dec, T_enc) — 63 GiB at train_4k x B=256)."""
    L = xc.shape[1]
    if L < FLASH_THRESHOLD:
        return A.attention_block(layer["cross"], xc, memory, None)
    dtype = xc.dtype
    q, k, v = A.qkv_proj(layer["cross"], xc, memory, dtype)
    qp = jnp.arange(L, dtype=jnp.int32)
    kp = jnp.arange(memory.shape[1], dtype=jnp.int32)
    o = flash_attention(q, k, v, qp, kp, 0, False, 0.0, 512, 512)
    return A.out_proj(layer["cross"], o, dtype)


def decode_train(params: Params, tokens: jax.Array, memory: jax.Array,
                 cfg: ModelConfig) -> jax.Array:
    """Teacher-forced decoder. tokens (B, L), memory (B, T_enc, D)."""
    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.norm_eps
    B, L = tokens.shape
    x = E.embed_tokens(params["embed"], tokens, dtype)
    pos = jnp.arange(L, dtype=jnp.int32)
    cos, sin = R.rope_cos_sin(pos, cfg.resolved_head_dim, cfg.rope_theta)

    def body(x, layer):
        from repro.sharding.rules import shard_act
        x = shard_act(x)
        xn = layernorm(layer["ln1"], x, eps)
        x = x + _dec_self_attn(layer, xn, pos, cfg, cos, sin)
        xc = layernorm(layer["lnc"], x, eps)
        x = x + _cross_attn(layer, xc, memory)
        x = x + gelu_mlp(layer["ffn"], layernorm(layer["ln2"], x, eps))
        return x, None

    body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["dec_layers"])
    x = layernorm(params["dec_norm"], x, eps)
    return E.lm_head(params["embed"], x)


def encdec_forward(params: Params, tokens: jax.Array, audio_feats: jax.Array,
                   cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    memory = encode(params, audio_feats, cfg)
    logits = decode_train(params, tokens, memory, cfg)
    return logits, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


# Cache partition for the serving layer (repro.models.api.DecodeState):
# true KV cache vs bookkeeping, and the batch ("slot") axis of each entry.
KV_KEYS = ("k", "v", "cross_k", "cross_v")
CACHE_BATCH_AXES = {"len": 0, "done": 0, "k": 1, "v": 1,
                    "cross_k": 1, "cross_v": 1}

# Cache-layout metadata (repro.models.layouts): the decoder self-attention
# KV grows with max_len (paged); the cross K/V is fixed encoder_seq and
# stays dense.  All four are quantizable.
LENGTH_AXES = {"k": 2, "v": 2}
QUANT_FIELDS = KV_KEYS


def init_encdec_cache(cfg: ModelConfig, batch: int, max_len: int
                      ) -> Dict[str, Any]:
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    n = cfg.n_layers
    return {
        "len": jnp.zeros((batch,), jnp.int32),
        "done": jnp.zeros((batch,), bool),
        "k": jnp.zeros((n, batch, max_len, kv, hd), dt),
        "v": jnp.zeros((n, batch, max_len, kv, hd), dt),
        "cross_k": jnp.zeros((n, batch, cfg.encoder_seq, kv, hd), dt),
        "cross_v": jnp.zeros((n, batch, cfg.encoder_seq, kv, hd), dt),
    }


def encdec_prefill(params: Params, tokens: jax.Array, audio_feats: jax.Array,
                   cfg: ModelConfig, max_len: int
                   ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Encode audio, pre-project per-layer cross K/V, teacher-force the
    prompt through the decoder, fill self-attention caches."""
    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.norm_eps
    B, L = tokens.shape
    memory = encode(params, audio_feats, cfg)
    cache = init_encdec_cache(cfg, B, max_len)
    x = E.embed_tokens(params["embed"], tokens, dtype)
    pos = jnp.arange(L, dtype=jnp.int32)
    cos, sin = R.rope_cos_sin(pos, cfg.resolved_head_dim, cfg.rope_theta)

    def body(x, layer):
        xn = layernorm(layer["ln1"], x, eps)
        q, k, v = A.qkv_proj(layer["attn"], xn, xn, dtype)
        q = R.apply_rope(q, cos, sin)
        k = R.apply_rope(k, cos, sin)
        o = A.sdpa(q, k, v, A.make_mask(pos, pos, "causal")) \
            if L < FLASH_THRESHOLD else flash_attention(
                q, k, v, pos, pos, 0, True, 0.0, 512, 512)
        x = x + A.out_proj(layer["attn"], o, dtype)
        kf = jnp.zeros((B, max_len) + k.shape[2:], dtype)
        vf = jnp.zeros((B, max_len) + v.shape[2:], dtype)
        kf = jax.lax.dynamic_update_slice_in_dim(kf, k, 0, 1)
        vf = jax.lax.dynamic_update_slice_in_dim(vf, v, 0, 1)
        ck, cv = A.project_kv(layer["cross"], memory)
        xc = layernorm(layer["lnc"], x, eps)
        x = x + A.attention_block(layer["cross"], xc, memory, None)
        x = x + gelu_mlp(layer["ffn"], layernorm(layer["ln2"], x, eps))
        return x, {"k": kf, "v": vf, "cross_k": ck, "cross_v": cv}

    x, extras = jax.lax.scan(body, x, params["dec_layers"])
    for key, val in extras.items():
        cache[key] = val
    x = layernorm(params["dec_norm"], x, eps)
    logits = E.lm_head(params["embed"], x[:, -1:])[:, 0]
    cache["len"] = jnp.full((B,), L, jnp.int32)
    return logits, cache


def encdec_seed_cache(params: Params, audio_feats: jax.Array,
                      cfg: ModelConfig, max_len: int) -> Dict[str, Any]:
    """Seed step of the chunked prefill: run the encoder ONCE (fixed
    ``encoder_seq`` shape — one compile regardless of prompt length) and
    pre-project the per-layer cross K/V the decoder chunks attend.  The
    decoder self-attention KV starts empty and is filled chunk by
    chunk."""
    memory = encode(params, audio_feats, cfg)
    cache = init_encdec_cache(cfg, audio_feats.shape[0], max_len)

    def body(_, layer):
        return None, A.project_kv(layer["cross"], memory)

    _, (ck, cv) = jax.lax.scan(body, None, params["dec_layers"])
    cache["cross_k"], cache["cross_v"] = ck, cv
    return cache


def encdec_prefill_chunk(params: Params, row: Dict[str, Any],
                         tokens: jax.Array, start: jax.Array,
                         n_valid: jax.Array, cfg: ModelConfig
                         ) -> Tuple[jax.Array, Dict[str, Any],
                                    Dict[str, Any]]:
    """One fixed-shape chunk of the chunked decoder prefill: the chunk's
    C queries self-attend the row cache's resident positions [0, start)
    plus the chunk (causal, true positions — matches the one-shot
    :func:`encdec_prefill` up to float association) and cross-attend the
    pre-projected encoder memory from :func:`encdec_seed_cache`.
    Returns (logits (B, C, V), row, chunk_kv)."""
    from repro.kernels import ops
    del n_valid              # no recurrent state; padding is causally dead
    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.norm_eps
    B, C = tokens.shape
    x = E.embed_tokens(params["embed"], tokens, dtype)
    pos = start + jnp.arange(C, dtype=jnp.int32)
    cos, sin = R.rope_cos_sin(pos, cfg.resolved_head_dim, cfg.rope_theta)

    def body(x, xs):
        layer, k_row, v_row, ck, cv = xs
        xn = layernorm(layer["ln1"], x, eps)
        q, k, v = A.qkv_proj(layer["attn"], xn, xn, dtype)
        q = R.apply_rope(q, cos, sin)
        k = R.apply_rope(k, cos, sin)
        k_row = jax.lax.dynamic_update_slice_in_dim(
            k_row, k.astype(k_row.dtype), start, axis=1)
        v_row = jax.lax.dynamic_update_slice_in_dim(
            v_row, v.astype(v_row.dtype), start, axis=1)
        kpos = jnp.arange(k_row.shape[1], dtype=jnp.int32)
        # plain causal, like the one-shot prefill's teacher-forced pass
        o = ops.prefill_chunk_attention(q, k_row, v_row, pos, kpos, 0, 0.0)
        x = x + A.out_proj(layer["attn"], o, dtype)
        xc = layernorm(layer["lnc"], x, eps)
        x = x + A.cross_attend_cached(layer["cross"], xc, ck, cv, None)
        x = x + gelu_mlp(layer["ffn"], layernorm(layer["ln2"], x, eps))
        return x, (k_row, v_row, k, v)

    x, (k_rows, v_rows, kc, vc) = jax.lax.scan(
        body, x, (params["dec_layers"], row["k"], row["v"],
                  row["cross_k"], row["cross_v"]))
    row = dict(row)
    row["k"], row["v"] = k_rows, v_rows
    x = layernorm(params["dec_norm"], x, eps)
    logits = E.lm_head(params["embed"], x)
    return logits, row, {"k": kc, "v": vc}


def encdec_decode_step_views(params: Params, cache: Dict[str, Any],
                             token: jax.Array, cfg: ModelConfig,
                             live: Optional[jax.Array] = None
                             ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Layout-native one-token decode: KV names in ``cache`` are
    ``repro.models.layouts`` FieldViews.  The growing decoder KV (paged /
    int8) is appended and attended in its physical representation; the
    fixed-size cross K/V is read through its view (int8-capable).
    ``live`` (B,) bool masks the append and ``len`` by row (None: every
    row steps).  token: (B,) -> (logits (B, V), cache)."""
    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.norm_eps
    x = E.embed_tokens(params["embed"], token[:, None], dtype)
    pos = cache["len"][:, None]
    cos, sin = R.rope_cos_sin(pos, cfg.resolved_head_dim, cfg.rope_theta)
    window = cfg.sliding_window if cfg.attention_mode == "sliding" else 0

    def body(i, carry):
        x, k_all, v_all = carry
        layer = jax.tree_util.tree_map(lambda a: a[i], params["dec_layers"])
        xn = layernorm(layer["ln1"], x, eps)
        out, kv, vv = A.decode_attend_view(
            layer["attn"], xn, k_all.layer(i), v_all.layer(i),
            cache["len"], cos, sin, 0.0, window, live)
        x = x + out
        xc = layernorm(layer["lnc"], x, eps)
        x = x + A.cross_attend_view(
            layer["cross"], xc, cache["cross_k"].layer(i),
            cache["cross_v"].layer(i), None)
        x = x + gelu_mlp(layer["ffn"], layernorm(layer["ln2"], x, eps))
        return x, k_all.set_layer(i, kv), v_all.set_layer(i, vv)

    x, k_all, v_all = jax.lax.fori_loop(
        0, cfg.n_layers, body, (x, cache["k"], cache["v"]))
    cache = dict(cache)
    cache["k"], cache["v"] = k_all, v_all
    x = layernorm(params["dec_norm"], x, eps)
    logits = E.lm_head(params["embed"], x)[:, 0]
    cache["len"] = cache["len"] + (
        1 if live is None else live.astype(jnp.int32))
    return logits, cache


def encdec_verify_chunk_views(params: Params, cache: Dict[str, Any],
                              feed: jax.Array, cfg: ModelConfig
                              ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Speculative VERIFY: score C fed decoder tokens per slot in one
    fixed-shape dispatch (:func:`encdec_decode_step_views` with the
    C-step loop collapsed into one chunk attention per layer).  The
    C keys/values land at decoder positions ``len + c`` through the
    views; ``len`` is NOT advanced — acceptance is a later ``len += m``
    and the rejected suffix is causally masked stale garbage.  The
    frozen cross K/V is read via C-query cross-attention.
    Returns (logits (B, C, V), cache — counters untouched)."""
    from repro.kernels import ops
    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.norm_eps
    B, C = feed.shape
    x = E.embed_tokens(params["embed"], feed, dtype)             # (B, C, D)
    pos = cache["len"][:, None] + \
        jnp.arange(C, dtype=jnp.int32)[None]                     # (B, C)
    cos, sin = R.rope_cos_sin(pos, cfg.resolved_head_dim, cfg.rope_theta)
    window = cfg.sliding_window if cfg.attention_mode == "sliding" else 0

    def body(i, carry):
        x, k_all, v_all = carry
        layer = jax.tree_util.tree_map(lambda a: a[i], params["dec_layers"])
        xn = layernorm(layer["ln1"], x, eps)
        q, k_new, v_new = A.qkv_proj(layer["attn"], xn, xn, dtype)
        q = R.apply_rope(q, cos, sin)
        k_new = R.apply_rope(k_new, cos, sin)
        kv, vv = k_all.layer(i), v_all.layer(i)
        for c in range(C):
            kv = kv.write_token(cache["len"] + c, k_new[:, c])
            vv = vv.write_token(cache["len"] + c, v_new[:, c])
        kd = kv.dense().astype(dtype)
        kpos = jnp.arange(kd.shape[1], dtype=jnp.int32)
        o = ops.prefill_chunk_attention(q, kd, vv.dense().astype(dtype),
                                        pos, kpos, window, 0.0)
        x = x + A.out_proj(layer["attn"], o, dtype)
        xc = layernorm(layer["lnc"], x, eps)
        x = x + A.verify_attend_view(
            layer["cross"], xc, cache["cross_k"].layer(i),
            cache["cross_v"].layer(i), None)
        x = x + gelu_mlp(layer["ffn"], layernorm(layer["ln2"], x, eps))
        return x, k_all.set_layer(i, kv), v_all.set_layer(i, vv)

    x, k_all, v_all = jax.lax.fori_loop(
        0, cfg.n_layers, body, (x, cache["k"], cache["v"]))
    cache = dict(cache)
    cache["k"], cache["v"] = k_all, v_all
    x = layernorm(params["dec_norm"], x, eps)
    logits = E.lm_head(params["embed"], x)                       # (B, C, V)
    return logits, cache


def encdec_decode_step(params: Params, cache: Dict[str, Any],
                       token: jax.Array, cfg: ModelConfig
                       ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Dense-dict one-token decode: legacy entry point / parity oracle."""
    from repro.models import layouts as LT
    views = {k: LT.DenseView(v, CACHE_BATCH_AXES[k]) if k in KV_KEYS else v
             for k, v in cache.items()}
    logits, out = encdec_decode_step_views(params, views, token, cfg)
    return logits, {k: v.dense() if isinstance(v, LT.FieldView) else v
                    for k, v in out.items()}
