"""Decoder-only language model covering the dense / moe / ssm / hybrid /
vlm assigned architectures.

Design notes
------------
* **Scan over layers.**  Homogeneous layers are parameter-stacked (leading
  ``n_scan`` dim) and driven by ``jax.lax.scan`` so the 126-layer llama3
  lowers to a compact HLO.  Per-layer heterogeneity (gemma3's 5 local : 1
  global pattern, hymba's window pattern) is expressed as a *traced* int32
  ``window`` array riding the scan — masks are position arithmetic, so no
  unrolling is needed.  DeepSeek's leading dense layers differ in
  parameter *shape* and are unrolled separately (``dense_layers``).
* **Interleaved kinds.**  A ``layer_pattern`` (granite-4.0-h: Mamba-2
  and attention layers 9:1) keeps one stacked parameter tree and one
  state buffer per kind (``mamba_layers`` with ``ssm``/``conv``,
  ``attention_layers`` with ``k``/``v``) and walks the published order
  as runs of like layers, one loop per run over that kind's stack
  (:func:`layer_runs`), so the program grows with the runs, not the
  layers.  A model of one kind is one run over ``layers``.
* **Attention dispatch.**  Sequences longer than ``FLASH_THRESHOLD`` route
  through the blocked flash implementation (O(L·block) memory); short ones
  use the naive reference.  Both are numerically interchangeable (tested).
* **Remat.**  The scanned layer body is wrapped in ``jax.checkpoint`` for
  training so the dry-run memory analysis reflects a production
  activation-recompute policy.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.layers import attention as A
from repro.layers import embed as E
from repro.layers import rope as R
from repro.layers import ssm as S
from repro.layers.common import (Params, init_rmsnorm, rmsnorm, split_keys,
                                 where_rows)
from repro.layers.mlp import init_swiglu, swiglu
from repro.layers.moe import init_moe, moe_ffn
from repro.kernels.xla_flash import flash_attention

FLASH_THRESHOLD = 2048      # min L_q*L_k elements^(1/2) to use blocked path


# ---------------------------------------------------------------------------
# Layer windows (per-layer sliding window; 0 = full causal)
# ---------------------------------------------------------------------------


def layer_windows(cfg: ModelConfig) -> np.ndarray:
    n = cfg.n_layers
    if cfg.local_global_ratio > 0:
        # gemma3: `ratio` local layers then 1 global, repeating
        period = cfg.local_global_ratio + 1
        w = np.array([0 if (i % period) == cfg.local_global_ratio
                      else cfg.sliding_window for i in range(n)], np.int32)
        return w
    if cfg.attention_mode == "sliding" and cfg.sliding_window > 0:
        return np.full((n,), cfg.sliding_window, np.int32)
    return np.zeros((n,), np.int32)


# ---------------------------------------------------------------------------
# Layer kinds and runs (the published order past the leading dense layers)
# ---------------------------------------------------------------------------


def _n_dense(cfg: ModelConfig) -> int:
    return cfg.first_dense_layers if cfg.is_moe else 0


def stack_name(cfg: ModelConfig, kind: str) -> str:
    """Parameter key of ``kind``'s stacked layers."""
    return f"{kind}_layers" if cfg.layer_pattern else "layers"


def kind_fields(cfg: ModelConfig, kind: str) -> Tuple[str, ...]:
    """Cache fields a layer of ``kind`` reads and writes, each indexed
    by the layer's place in its kind's stack."""
    if kind == "mamba":
        return ("ssm", "conv")
    return ("k", "v") + (("ssm", "conv") if cfg.hybrid_parallel else ())


def kind_counts(cfg: ModelConfig) -> Dict[str, int]:
    """Stacked layers of each kind, in the order the kinds first appear."""
    out: Dict[str, int] = {}
    for kind in cfg.layer_kinds[_n_dense(cfg):]:
        out[kind] = out.get(kind, 0) + 1
    return out


def layer_runs(cfg: ModelConfig) -> Tuple[Tuple[str, int, int], ...]:
    """Maximal runs of like layers in the published order, as ``(kind,
    first index in kind's stack, length)``: granite's first period is
    ``(mamba, 0, 5), (attention, 0, 1), (mamba, 5, 4)``."""
    runs: list = []
    seen: Dict[str, int] = {}
    for kind in cfg.layer_kinds[_n_dense(cfg):]:
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen.get(kind, 0), 1])
        seen[kind] = seen.get(kind, 0) + 1
    return tuple(tuple(r) for r in runs)


def kind_windows(cfg: ModelConfig) -> Dict[str, jax.Array]:
    """Per-layer windows of each kind's stack, in stack order."""
    w = layer_windows(cfg)[_n_dense(cfg):]
    kinds = np.asarray(cfg.layer_kinds[_n_dense(cfg):])
    return {k: jnp.asarray(w[kinds == k]) for k in kind_counts(cfg)}


def _run_slice(tree: Any, start: int, count: int) -> Any:
    """Leading-axis slice of a run's layers (the whole stack as is)."""
    if start == 0 and count == jax.tree_util.tree_leaves(tree)[0].shape[0]:
        return tree
    return jax.tree_util.tree_map(lambda a: a[start:start + count], tree)


def _join(parts: list) -> Any:
    """Concatenate a kind's per-run stacked outputs in stack order."""
    if len(parts) == 1:
        return parts[0]
    return jax.tree_util.tree_map(lambda *a: jnp.concatenate(a), *parts)


def _branch(y: jax.Array, cfg: ModelConfig) -> jax.Array:
    """A mixer or MLP branch before its residual add (granite scales it)."""
    if cfg.residual_multiplier == 1.0:
        return y
    return y * cfg.residual_multiplier


def _rope(x: jax.Array, cos, sin) -> jax.Array:
    return x if cos is None else R.apply_rope(x, cos, sin)


def _head(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    logits = E.lm_head(params["embed"], x, cfg.logit_softcap)
    if cfg.logits_scaling == 1.0:
        return logits
    return logits / cfg.logits_scaling


def _embed(params: Params, tokens: jax.Array, cfg: ModelConfig
           ) -> jax.Array:
    return _scale_embed(
        E.embed_tokens(params["embed"], tokens, jnp.dtype(cfg.dtype)), cfg)


def _scale_embed(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.embedding_multiplier == 1.0:
        return x
    return x * cfg.embedding_multiplier


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _init_layer(key: jax.Array, cfg: ModelConfig, moe: bool,
                kind: str = "attention") -> Params:
    ka, kf, ks = split_keys(key, 3)
    p: Params = {"ln1": init_rmsnorm(cfg.d_model, cfg.param_dtype)}
    if kind == "mamba":
        p["ssm"] = S.init_ssm(ka, cfg)
        if cfg.d_ff == 0:                # mamba2: mixer layers only
            return p
    else:
        p["attn"] = A.init_attention(ka, cfg)
        if cfg.hybrid_parallel:
            p["ssm"] = S.init_ssm(ks, cfg)
    p["ln2"] = init_rmsnorm(cfg.d_model, cfg.param_dtype)
    if moe:
        p["ffn"] = init_moe(kf, cfg)
    else:
        p["ffn"] = init_swiglu(kf, cfg.d_model, cfg.d_ff, cfg.param_dtype)
    return p


def init_lm(key: jax.Array, cfg: ModelConfig) -> Params:
    ke, kd, kl = split_keys(key, 3)
    n_dense = _n_dense(cfg)
    params: Params = {"embed": E.init_embed(ke, cfg)}
    if n_dense:
        dkeys = split_keys(kd, n_dense)
        params["dense_layers"] = [
            _init_layer(k, cfg, moe=False) for k in dkeys]
    counts = kind_counts(cfg)
    kkeys = [kl] if len(counts) == 1 else split_keys(kl, len(counts))
    for kk, (kind, n) in zip(kkeys, counts.items()):
        params[stack_name(cfg, kind)] = jax.vmap(
            lambda k, kind=kind: _init_layer(k, cfg, cfg.is_moe, kind))(
                jax.random.split(kk, n))
    params["final_norm"] = init_rmsnorm(cfg.d_model, cfg.param_dtype)
    return params


# ---------------------------------------------------------------------------
# Shared layer body
# ---------------------------------------------------------------------------


def _self_attention(layer: Params, xn: jax.Array, pos: jax.Array,
                    window: jax.Array, cfg: ModelConfig,
                    cos: jax.Array, sin: jax.Array) -> jax.Array:
    """pos: (L,) SHARED positions (1-D keeps flash masks head/batch-free)."""
    dtype = xn.dtype
    q, k, v = A.qkv_proj(layer["attn"], xn, xn, dtype)
    q = _rope(q, cos, sin)
    k = _rope(k, cos, sin)
    return A.out_proj(layer["attn"], _attend_full(q, k, v, pos, window, cfg),
                      dtype)


def _attend_full(q, k, v, pos, window, cfg: ModelConfig) -> jax.Array:
    """Causal (or windowed) attention over a whole sequence."""
    if q.shape[1] >= FLASH_THRESHOLD:
        return flash_attention(q, k, v, pos, pos, window, True,
                               cfg.logit_softcap, 512, 512, cfg.attn_scale)
    return A.sdpa(q, k, v, A.make_mask(pos, pos, "sliding", window),
                  cfg.logit_softcap, scale=cfg.attn_scale)


def _ffn(layer: Params, xn: jax.Array, cfg: ModelConfig, moe: bool
         ) -> Tuple[jax.Array, jax.Array]:
    if moe:
        return moe_ffn(layer["ffn"], xn, cfg)
    return swiglu(layer["ffn"], xn), jnp.zeros((), jnp.float32)


def _mlp_block(layer: Params, x: jax.Array, cfg: ModelConfig, moe: bool
               ) -> Tuple[jax.Array, jax.Array]:
    """The MLP after a mixer (none in a mixer-only mamba2 layer)."""
    if "ffn" not in layer:
        return x, jnp.zeros((), jnp.float32)
    f, aux = _ffn(layer, rmsnorm(layer["ln2"], x, cfg.norm_eps), cfg, moe)
    return x + _branch(f, cfg), aux


def _layer_fwd(layer: Params, x: jax.Array, pos: jax.Array,
               window: jax.Array, cfg: ModelConfig, moe: bool,
               cos: jax.Array, sin: jax.Array, kind: str = "attention"
               ) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence layer forward. Returns (x, aux)."""
    xn = rmsnorm(layer["ln1"], x, cfg.norm_eps)
    if kind == "mamba":
        out, _ = S.ssm_mixer(layer["ssm"], xn, cfg)
    else:
        out = _self_attention(layer, xn, pos, window, cfg, cos, sin)
        if cfg.hybrid_parallel:
            ssm_out, _ = S.ssm_mixer(layer["ssm"], xn, cfg)
            out = (out + ssm_out) * 0.5      # hymba: mean-fuse parallel heads
    return _mlp_block(layer, x + _branch(out, cfg), cfg, moe)


# ---------------------------------------------------------------------------
# Full forward (training / prefill trunk)
# ---------------------------------------------------------------------------


def embed_inputs(params: Params, tokens: jax.Array, cfg: ModelConfig,
                 vision_embeds: Optional[jax.Array] = None,
                 vision_mask: Optional[jax.Array] = None) -> jax.Array:
    dtype = jnp.dtype(cfg.dtype)
    x = E.embed_tokens(params["embed"], tokens, dtype)
    if vision_embeds is not None:
        pv = E.project_frontend(params["embed"], vision_embeds.astype(dtype))
        Tv = pv.shape[1]
        idx = jnp.clip(jnp.cumsum(vision_mask, axis=1) - 1, 0, Tv - 1)
        gathered = jnp.take_along_axis(pv, idx[..., None], axis=1)
        x = jnp.where(vision_mask[..., None], gathered, x)
    return _scale_embed(x, cfg)


def _rope_tables(cfg: ModelConfig, pos: jax.Array,
                 positions3: Optional[jax.Array]):
    """cos/sin tables at ``pos``, or (None, None) with no positional
    encoding (granite's NoPE attention)."""
    if cfg.position_embedding == "nope":
        return None, None
    hd = cfg.resolved_head_dim
    if cfg.mrope and cfg.mrope_sections:
        p3 = positions3 if positions3 is not None else R.text_positions3(pos)
        return R.mrope_cos_sin(p3, hd, cfg.rope_theta, cfg.mrope_sections)
    return R.rope_cos_sin(pos, hd, cfg.rope_theta)


def lm_forward(params: Params, tokens: jax.Array, cfg: ModelConfig,
               positions3: Optional[jax.Array] = None,
               vision_embeds: Optional[jax.Array] = None,
               vision_mask: Optional[jax.Array] = None,
               remat: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Teacher-forced forward.  tokens: (B, L) -> (logits (B, L, V), aux)."""
    from repro.sharding.rules import shard_act
    B, L = tokens.shape
    x = embed_inputs(params, tokens, cfg, vision_embeds, vision_mask)
    x = shard_act(x)
    pos = jnp.arange(L, dtype=jnp.int32)          # shared 1-D positions
    cos, sin = _rope_tables(cfg, pos, positions3)
    windows = jnp.asarray(layer_windows(cfg))
    aux = jnp.zeros((), jnp.float32)

    for i, layer in enumerate(params.get("dense_layers", [])):
        x, a = _layer_fwd(layer, x, pos, windows[i], cfg, False, cos, sin)
        aux = aux + a

    kwin = kind_windows(cfg)
    for kind, start, n_scan in layer_runs(cfg):
        def body(carry, xs, kind=kind):
            x, aux = carry
            layer, window = xs
            x = shard_act(x)
            x, a = _layer_fwd(layer, x, pos, window, cfg, cfg.is_moe, cos,
                              sin, kind)
            x = shard_act(x)
            return (x, aux + a), None

        scan_xs = _run_slice((params[stack_name(cfg, kind)], kwin[kind]),
                             start, n_scan)
        group = _remat_group(n_scan) if remat else 1
        if remat and group > 1:
            # Nested (sqrt-depth) remat: only n_scan/group boundary
            # activations are saved for the backward pass; each group's
            # inner carries are recomputed from its boundary.  Cuts the
            # 126-layer llama3 saved-activation footprint by ~9x
            # (EXPERIMENTS.md §Perf iteration 2).
            ng = n_scan // group
            grouped = jax.tree_util.tree_map(
                lambda a: a.reshape((ng, group) + a.shape[1:]), scan_xs)

            @jax.checkpoint
            def group_body(carry, xs, body=body):
                # barrier: stop XLA from hoisting f32 converts across the
                # saved boundary stack (measured: it duplicated every
                # saved carry in f32 — §Perf H1 it4)
                carry = jax.lax.optimization_barrier(carry)
                return jax.lax.scan(body, carry, xs)

            (x, aux), _ = jax.lax.scan(group_body, (x, aux), grouped)
        else:
            body_fn = jax.checkpoint(body) if remat else body
            (x, aux), _ = jax.lax.scan(body_fn, (x, aux), scan_xs)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return _head(params, x, cfg), aux


def _remat_group(n: int) -> int:
    """Divisor of n closest to sqrt(n) (1 if n is small)."""
    if n < 16:
        return 1
    target = n ** 0.5
    divs = [d for d in range(2, n) if n % d == 0]
    return min(divs, key=lambda d: abs(d - target)) if divs else 1


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


# Cache partition for the serving layer (repro.models.api.DecodeState):
# true KV/recurrent state (counted in Fig-8g bytes) vs bookkeeping, and the
# batch ("slot") axis of every entry.
KV_KEYS = ("k", "v", "dense_k", "dense_v", "ssm", "conv")
CACHE_BATCH_AXES = {
    "len": 0, "done": 0, "k": 1, "v": 1, "dense_k": 1, "dense_v": 1,
    "ssm": 1, "conv": 1,
}

# Cache-layout metadata (repro.models.layouts): the growing max_len-axis
# KV buffers a PagedLayout pages, and the float KV a QuantizedLayout may
# store as int8 (the ssm recurrent state is mutated every step, so
# requantizing it would accumulate error — it stays dense).
LENGTH_AXES = {"k": 2, "v": 2, "dense_k": 2, "dense_v": 2}
QUANT_FIELDS = ("k", "v", "dense_k", "dense_v")
# Fields a decode step updates in place in their kind's stack: the layer
# loop hands them over whole, with the layer index, and writes back what
# comes out (no slice of the layer, no ``set_layer``).
IN_PLACE_FIELDS = ("ssm",)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int
                  ) -> Dict[str, Any]:
    """Zero cache: growing ``k``/``v`` (layers, batch, max_len, KV, hd)
    over the attention layers, fixed ``ssm``/``conv`` recurrent state
    over the layers with a Mamba-2 mixer; each field's leading axis is
    its kind's stack (:func:`kind_fields`)."""
    n_dense = _n_dense(cfg)
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    cache: Dict[str, Any] = {"len": jnp.zeros((batch,), jnp.int32),
                             "done": jnp.zeros((batch,), bool)}
    n_of = {}
    for kind, n in kind_counts(cfg).items():
        for f in kind_fields(cfg, kind):
            n_of[f] = n
    if cfg.has_growing_kv:
        cache["k"] = jnp.zeros((n_of["k"], batch, max_len, kv, hd), dt)
        cache["v"] = jnp.zeros((n_of["v"], batch, max_len, kv, hd), dt)
        if n_dense:
            cache["dense_k"] = jnp.zeros((n_dense, batch, max_len, kv, hd), dt)
            cache["dense_v"] = jnp.zeros((n_dense, batch, max_len, kv, hd), dt)
    if cfg.has_recurrent_state:
        dims = S.ssm_dims(cfg)
        cache["ssm"] = jnp.zeros(
            (n_of["ssm"], batch, dims.n_heads, dims.head_dim, dims.n_state),
            jnp.float32)
        cache["conv"] = jnp.zeros(
            (n_of["conv"], batch, dims.d_conv - 1, dims.conv_dim), dt)
    return cache


def _layer_decode(layer: Params, x: jax.Array, cache_slice: Dict[str, Any],
                  cache_len: jax.Array, window: jax.Array, cfg: ModelConfig,
                  moe: bool, cos: jax.Array, sin: jax.Array,
                  live: Optional[jax.Array] = None, kind: str = "attention",
                  index: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Per-layer decode over KVViews: ``cache_slice`` holds the per-layer
    ``repro.models.layouts`` FieldViews, so the attention walks the
    physical representation (paged pool / int8) directly, and for the
    :data:`IN_PLACE_FIELDS` the whole stack, stepped at ``index``.
    ``live`` (B,) bool freezes rows at the write: the K/V append is
    masked, and the recurrent state, which a step rewrites whole, is
    selected per row — O(1) a row."""
    from repro.models import layouts as LT
    eps = cfg.norm_eps
    new_slice: Dict[str, Any] = {}
    xn = rmsnorm(layer["ln1"], x, eps)

    def run_ssm():
        conv = cache_slice["conv"].dense()
        out, new = S.ssm_mixer(layer["ssm"], xn, cfg,
                               state={"ssm": cache_slice["ssm"].dense(),
                                      "conv": conv},
                               layer=index, live=live)
        if live is not None:
            new["conv"] = where_rows(live, new["conv"], conv, 0)
        # the recurrent state is never quantized/paged (mutated every
        # step), so a fresh DenseView is the identity re-wrap
        return out, {k: LT.DenseView(new[k], cache_slice[k].batch_axis)
                     for k in new}

    if kind == "mamba":
        out, st = run_ssm()
        new_slice.update(st)
    else:
        out, k_view, v_view = A.decode_attend_view(
            layer["attn"], xn, cache_slice["k"], cache_slice["v"],
            cache_len, cos, sin, cfg.logit_softcap, window, live,
            cfg.attn_scale)
        new_slice["k"], new_slice["v"] = k_view, v_view
        if cfg.hybrid_parallel:
            ssm_out, st = run_ssm()
            new_slice.update(st)
            out = (out + ssm_out) * 0.5
    x, _ = _mlp_block(layer, x + _branch(out, cfg), cfg, moe)
    return x, new_slice


def lm_decode_step_views(params: Params, cache: Dict[str, Any],
                         token: jax.Array, cfg: ModelConfig,
                         positions3: Optional[jax.Array] = None,
                         live: Optional[jax.Array] = None
                         ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Layout-native one-token decode.  ``cache`` maps bookkeeping names
    to plain arrays and KV names to FieldViews; under the paged layout a
    step appends through the page table and attends page-by-page —
    nothing materialises the dense (layers, B, max_len, KV, D) view.
    ``live`` (B,) bool masks every write by row: a frozen row's state
    comes through bit-identical (None: every row steps).
    token: (B,) -> (logits (B, V), cache)."""
    x = _embed(params, token[:, None], cfg)
    pos = cache["len"][:, None]
    cos, sin = _rope_tables(cfg, pos, positions3)
    windows = jnp.asarray(layer_windows(cfg))

    cache = dict(cache)
    for i, layer in enumerate(params.get("dense_layers", [])):
        sl = {"k": cache["dense_k"].layer(i), "v": cache["dense_v"].layer(i)}
        x, new = _layer_decode(layer, x, sl, cache["len"], windows[i], cfg,
                               False, cos, sin, live)
        cache["dense_k"] = cache["dense_k"].set_layer(i, new["k"])
        cache["dense_v"] = cache["dense_v"].set_layer(i, new["v"])

    # fori_loop with the stacked cache VIEWS as CARRY, updated in place —
    # a lax.scan with cache slices as ys would stack a SECOND full cache
    # as its output (measured: ~2x decode peak on llama3-405b decode_32k,
    # EXPERIMENTS.md §Beyond-paper).  Views are registered pytrees, so
    # they ride the carry; ``set_layer`` writes only layer i's slice of
    # the physical buffers.  One loop per run of like layers, over its
    # kind's stack; the scopes name each run's ops in the profile.
    kwin = kind_windows(cfg)
    for kind, start, n in layer_runs(cfg):
        keys = kind_fields(cfg, kind)
        stack, scan_windows = params[stack_name(cfg, kind)], kwin[kind]

        def body(i, carry, kind=kind, keys=keys, stack=stack,
                 scan_windows=scan_windows):
            x, bufs = carry
            layer = jax.tree_util.tree_map(lambda a: a[i], stack)
            slc = {k: bufs[k] if k in IN_PLACE_FIELDS else bufs[k].layer(i)
                   for k in keys}
            x, new = _layer_decode(layer, x, slc, cache["len"],
                                   scan_windows[i], cfg, cfg.is_moe, cos,
                                   sin, live, kind, i)
            bufs = {k: new[k] if k in IN_PLACE_FIELDS
                    else bufs[k].set_layer(i, new[k]) for k in keys}
            return (x, bufs)

        scope = "decode.mamba" if kind == "mamba" else "decode.attn"
        with jax.named_scope(scope):
            x, bufs = jax.lax.fori_loop(
                start, start + n, body, (x, {k: cache[k] for k in keys}))
        for k in keys:
            cache[k] = bufs[k]
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _head(params, x, cfg)[:, 0]
    cache["len"] = cache["len"] + (
        1 if live is None else live.astype(jnp.int32))
    return logits, cache


def lm_decode_step(params: Params, cache: Dict[str, Any], token: jax.Array,
                   cfg: ModelConfig,
                   positions3: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Dense-dict one-token decode: legacy entry point and the parity
    oracle for the layout-native kernels (DenseView dispatch is
    bit-identical to the historic dense path)."""
    from repro.models import layouts as LT
    views = {k: LT.DenseView(v, CACHE_BATCH_AXES[k]) if k in KV_KEYS else v
             for k, v in cache.items()}
    logits, out = lm_decode_step_views(params, views, token, cfg, positions3)
    return logits, {k: v.dense() if isinstance(v, LT.FieldView) else v
                    for k, v in out.items()}


def lm_verify_chunk_views(params: Params, cache: Dict[str, Any],
                          feed: jax.Array, cfg: ModelConfig
                          ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Speculative VERIFY: score C fed tokens per slot in ONE
    fixed-shape dispatch — :func:`lm_decode_step_views` with the
    sequential C-step loop collapsed into a single
    :func:`repro.kernels.ops.prefill_chunk_attention` per layer.

    feed: (B, C) int32 — position c is what the sequential decode would
    feed at ``len + c``.  All C keys/values are written through the
    views at positions ``len + c`` (exactly the sequential write
    sites); ``len`` is NOT advanced — acceptance of an m-prefix is a
    later ``len += m`` and the rejected suffix becomes stale garbage
    beyond ``len``, masked by causality here and overwritten by the
    next round before it could ever be attended.  Recurrent-state
    families (ssm / hybrid) cannot roll back and are excluded by
    :meth:`DenseDecode.supports_speculative`.

    Returns (logits (B, C, V), cache — counters untouched).
    """
    from repro.kernels import ops
    assert not cfg.has_recurrent_state, \
        "recurrent state cannot be rolled back by a length decrement"
    B, C = feed.shape
    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.norm_eps
    x = _embed(params, feed, cfg)                                # (B, C, D)
    pos = cache["len"][:, None] + \
        jnp.arange(C, dtype=jnp.int32)[None]                     # (B, C)
    cos, sin = _rope_tables(cfg, pos, None)
    windows = jnp.asarray(layer_windows(cfg))

    def layer_verify(layer, x, kv, vv, window, moe):
        xn = rmsnorm(layer["ln1"], x, eps)
        q, k_new, v_new = A.qkv_proj(layer["attn"], xn, xn, dtype)
        q = _rope(q, cos, sin)
        k_new = _rope(k_new, cos, sin)
        for c in range(C):
            kv = kv.write_token(cache["len"] + c, k_new[:, c])
            vv = vv.write_token(cache["len"] + c, v_new[:, c])
        kd = kv.dense().astype(dtype)
        kpos = jnp.arange(kd.shape[1], dtype=jnp.int32)
        o = ops.prefill_chunk_attention(q, kd, vv.dense().astype(dtype),
                                        pos, kpos, window,
                                        cfg.logit_softcap, cfg.attn_scale)
        x = x + _branch(A.out_proj(layer["attn"], o, dtype), cfg)
        x, _ = _mlp_block(layer, x, cfg, moe)
        return x, kv, vv

    cache = dict(cache)
    for i, layer in enumerate(params.get("dense_layers", [])):
        x, nk, nv = layer_verify(layer, x, cache["dense_k"].layer(i),
                                 cache["dense_v"].layer(i), windows[i],
                                 False)
        cache["dense_k"] = cache["dense_k"].set_layer(i, nk)
        cache["dense_v"] = cache["dense_v"].set_layer(i, nv)

    scan_windows = kind_windows(cfg)["attention"]
    stack = params[stack_name(cfg, "attention")]

    def body(i, carry):
        x, kb, vb = carry
        layer = jax.tree_util.tree_map(lambda a: a[i], stack)
        x, nk, nv = layer_verify(layer, x, kb.layer(i), vb.layer(i),
                                 scan_windows[i], cfg.is_moe)
        return (x, kb.set_layer(i, nk), vb.set_layer(i, nv))

    x, kb, vb = jax.lax.fori_loop(
        0, kind_counts(cfg)["attention"], body, (x, cache["k"], cache["v"]))
    cache["k"], cache["v"] = kb, vb
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _head(params, x, cfg)                              # (B, C, V)
    return logits, cache


def lm_prefill_chunk(params: Params, row: Dict[str, Any],
                     tokens: jax.Array, start: jax.Array,
                     n_valid: jax.Array, cfg: ModelConfig
                     ) -> Tuple[jax.Array, Dict[str, Any], Dict[str, Any]]:
    """One fixed-shape chunk of the chunked (KV-conditioned) prefill.

    ``row`` is the dense (batch 1) row cache: k/v buffers with positions
    ``[0, start)`` already written — the resident prefix seeded from
    adopted prefix-shared pages plus every earlier chunk — and the
    ssm/conv recurrent state advanced to ``start``.  The chunk's C
    queries attend those resident positions AND the chunk itself
    (causal / per-layer sliding windows, positions are true token
    positions, so the result matches the one-shot :func:`lm_prefill` up
    to float association); its K/V is appended into the row cache and
    also returned per length-axis field for the chunk-granular
    ``write_span`` into the slot's layout.

    tokens: (B, C) int32 (trailing zero padding allowed — padded
    positions sit beyond every real query causally and beyond ``len``
    afterwards, and ``n_valid`` — the TOTAL prompt length — keeps them
    out of the recurrent ssm/conv state); start: traced scalar int32.
    Returns (logits (B, C, V), row, chunk_kv {field: (layers, B, C,
    KV, D)}).
    """
    from repro.kernels import ops
    from repro.sharding.rules import shard_act
    B, C = tokens.shape
    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.norm_eps
    x = shard_act(_embed(params, tokens, cfg))
    pos = start + jnp.arange(C, dtype=jnp.int32)
    cos, sin = _rope_tables(cfg, pos, None)
    windows = jnp.asarray(layer_windows(cfg))
    # real tokens in THIS chunk (the last chunk is zero-padded)
    in_chunk = jnp.clip(n_valid - start, 0, C)
    vl = jnp.broadcast_to(in_chunk, (B,))
    row = dict(row)

    def chunk_layer(layer, x, window, moe, k_row=None, v_row=None,
                    ssm_st=None, kind="attention"):
        xn = rmsnorm(layer["ln1"], x, eps)
        new_st = None
        ssm_out = None
        if ssm_st is not None:
            ssm_out, new_st = S.ssm_mixer(layer["ssm"], xn, cfg,
                                          state=ssm_st, valid_len=vl)
        if kind == "mamba":
            x, _ = _mlp_block(layer, x + _branch(ssm_out, cfg), cfg, moe)
            return x, None, None, None, None, new_st
        q, k, v = A.qkv_proj(layer["attn"], xn, xn, dtype)
        q = _rope(q, cos, sin)
        k = _rope(k, cos, sin)
        k_row = jax.lax.dynamic_update_slice_in_dim(
            k_row, k.astype(k_row.dtype), start, axis=1)
        v_row = jax.lax.dynamic_update_slice_in_dim(
            v_row, v.astype(v_row.dtype), start, axis=1)
        kpos = jnp.arange(k_row.shape[1], dtype=jnp.int32)
        o = ops.prefill_chunk_attention(q, k_row, v_row, pos, kpos,
                                        window, cfg.logit_softcap,
                                        cfg.attn_scale)
        out = A.out_proj(layer["attn"], o, dtype)
        if cfg.hybrid_parallel:
            out = (out + ssm_out) * 0.5
        x, _ = _mlp_block(layer, x + _branch(out, cfg), cfg, moe)
        return x, k_row, v_row, k, v, new_st

    chunk_kv: Dict[str, Any] = {}
    dk_c, dv_c = [], []
    for i, layer in enumerate(params.get("dense_layers", [])):
        x, kr, vr, kc, vc, _ = chunk_layer(
            layer, x, windows[i], False,
            row["dense_k"][i], row["dense_v"][i])
        row["dense_k"] = row["dense_k"].at[i].set(kr)
        row["dense_v"] = row["dense_v"].at[i].set(vr)
        dk_c.append(kc)
        dv_c.append(vc)
    if dk_c:
        chunk_kv["dense_k"] = jnp.stack(dk_c)
        chunk_kv["dense_v"] = jnp.stack(dv_c)

    kwin = kind_windows(cfg)
    parts: Dict[str, list] = {}
    for kind, start_i, n in layer_runs(cfg):
        fields = kind_fields(cfg, kind)
        has_ssm = "ssm" in fields
        xs: Dict[str, Any] = {"layer": params[stack_name(cfg, kind)],
                              "window": kwin[kind]}
        xs.update({f: row[f] for f in fields})
        xs = _run_slice(xs, start_i, n)

        def body(x, xs_i, kind=kind, has_ssm=has_ssm):
            st = {"ssm": xs_i["ssm"], "conv": xs_i["conv"]} if has_ssm \
                else None
            x, kr, vr, kc, vc, new_st = chunk_layer(
                xs_i["layer"], shard_act(x), xs_i["window"], cfg.is_moe,
                xs_i.get("k"), xs_i.get("v"), st, kind)
            ys = {}
            if kind == "attention":
                ys.update(k=kr, v=vr, kc=kc, vc=vc)
            if has_ssm:
                ys.update(ssm=new_st["ssm"], conv=new_st["conv"])
            return x, ys

        x, ys = jax.lax.scan(body, x, xs)
        for f, y in ys.items():
            parts.setdefault(f, []).append(y)
    ys = {f: _join(p) for f, p in parts.items()}
    for f in ("k", "v", "ssm", "conv"):
        if f in ys:
            row[f] = ys[f]
    if "kc" in ys:
        chunk_kv["k"], chunk_kv["v"] = ys["kc"], ys["vc"]
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _head(params, x, cfg)
    return logits, row, chunk_kv


def lm_prefill(params: Params, tokens: jax.Array, cfg: ModelConfig,
               max_len: int,
               positions3: Optional[jax.Array] = None,
               vision_embeds: Optional[jax.Array] = None,
               vision_mask: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Process a prompt, filling the KV cache.  Returns (last logits, cache).

    Implemented as the full forward plus K/V capture (single pass; the
    capture rides the layer scan).
    """
    from repro.sharding.rules import shard_act
    B, L = tokens.shape
    x = embed_inputs(params, tokens, cfg, vision_embeds, vision_mask)
    x = shard_act(x)
    pos = jnp.arange(L, dtype=jnp.int32)          # shared 1-D positions
    cos, sin = _rope_tables(cfg, pos, positions3)
    windows = jnp.asarray(layer_windows(cfg))
    cache = init_kv_cache(cfg, B, max_len)
    eps = cfg.norm_eps
    dtype = jnp.dtype(cfg.dtype)

    def capture_layer(layer, x, window, moe, kind="attention"):
        """Layer fwd that also returns this layer's K/V (and ssm state)."""
        out_extras: Dict[str, Any] = {}
        xn = rmsnorm(layer["ln1"], x, eps)
        if "ssm" in kind_fields(cfg, kind):
            st0 = {"ssm": jnp.zeros_like(cache["ssm"][0]),
                   "conv": jnp.zeros_like(cache["conv"][0])}
            ssm_out, st = S.ssm_mixer(layer["ssm"], xn, cfg, state=st0)
            out_extras["ssm"], out_extras["conv"] = st["ssm"], st["conv"]
        if kind == "mamba":
            x, _ = _mlp_block(layer, x + _branch(ssm_out, cfg), cfg, moe)
            return x, out_extras
        q, k, v = A.qkv_proj(layer["attn"], xn, xn, dtype)
        q = _rope(q, cos, sin)
        k = _rope(k, cos, sin)
        kf = jnp.zeros((B, max_len) + k.shape[2:], dtype)
        vf = jnp.zeros((B, max_len) + v.shape[2:], dtype)
        out_extras["k"] = jax.lax.dynamic_update_slice_in_dim(kf, k, 0, 1)
        out_extras["v"] = jax.lax.dynamic_update_slice_in_dim(vf, v, 0, 1)
        out = A.out_proj(layer["attn"],
                         _attend_full(q, k, v, pos, window, cfg), dtype)
        if cfg.hybrid_parallel:
            out = (out + ssm_out) * 0.5
        x, _ = _mlp_block(layer, x + _branch(out, cfg), cfg, moe)
        return x, out_extras

    for i, layer in enumerate(params.get("dense_layers", [])):
        x, ex = capture_layer(layer, x, windows[i], False)
        cache["dense_k"] = cache["dense_k"].at[i].set(ex["k"])
        cache["dense_v"] = cache["dense_v"].at[i].set(ex["v"])

    kwin = kind_windows(cfg)
    parts: Dict[str, list] = {}
    for kind, start, n in layer_runs(cfg):
        def body(x, xs, kind=kind):
            layer, window = xs
            return capture_layer(layer, shard_act(x), window, cfg.is_moe,
                                 kind)

        x, extras = jax.lax.scan(body, x, _run_slice(
            (params[stack_name(cfg, kind)], kwin[kind]), start, n))
        for key, val in extras.items():
            parts.setdefault(key, []).append(val)
    for key, val in parts.items():
        cache[key] = _join(val)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _head(params, x[:, -1:], cfg)[:, 0]
    cache["len"] = jnp.full((B,), L, jnp.int32)
    return logits, cache
