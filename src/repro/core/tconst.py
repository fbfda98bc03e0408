"""TConstFormer: the paper's contribution as a composable JAX module.

Architecture (paper §3, Fig 1b/2/3).  One TConst block has equivalent depth
``h + 2``; equivalent layer ``i`` owns ONE attention and ONE FFN parameter
set (parameter parity with an (h+2)-layer standard decoder, §6.2.1) which
is reused by every information-flow edge at that depth:

  layer 0      : context COMPRESS  (Q = history tail of length W_oh,
                 K/V = full history, causal)           [Fig 2c]
                 + generation causal self-attention (no cross yet — C_0 is
                 produced at this depth, so the gen window consumes it one
                 layer later; this yields exactly the paper's H+1 cross-
                 attention count, Appendix A.1)
  layers 1..h  : context self-attention over the W_oh slots (causal)
                 + generation causal self-attention
                 + generation cross-attention to C_{i-1}
  layer h+1    : context RESTORE (Q = full history, K/V = C_h) [Fig 2d]
                 (feeds the NEXT stacked block, paper Fig 3)
                 + generation causal self + cross to C_h

Causality: we keep every mask causal, following the paper's principle of
removing only the acausal connections.  RoPE positions are the true token
positions; a compressed slot inherits the position of the history-tail
token that produced it.

Complexity contract (validated in tests/benchmarks):
  cache hit  : (h+1)·D·W_oh + (h+2)·D·W_og²   — O(1) in N     (Eq. 5)
  cache miss : D[2·N·W_oh + …]                 — O(N)          (Eq. 4)
  KV cache   : 2B(h+1)W_oh·d + 2B(h+2)W_og·d  per block — O(1) (Eq. 7)

``mode="tlin"`` enables the prior-work TLinFormer topology: the severed
first-layer pathways from raw history to the generation window are
restored, which makes both the cache and the cache-hit cost O(N) again —
the paper's Fig 1a baseline.
"""
from __future__ import annotations

import functools
import hashlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.layers import attention as A
from repro.layers import embed as E
from repro.layers import rope as R
from repro.layers.common import (Params, init_rmsnorm, put_rows, rmsnorm,
                                 split_keys, take_rows, where_rows)
from repro.layers.mlp import init_swiglu, swiglu
from repro.layers.moe import init_moe, moe_ffn
from repro.models import layouts as LT

# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _init_layer(key: jax.Array, cfg: ModelConfig) -> Params:
    ka, kf = split_keys(key, 2)
    ffn = init_moe(kf, cfg) if cfg.is_moe else \
        init_swiglu(kf, cfg.d_model, cfg.d_ff, cfg.param_dtype)
    return {
        "attn": A.init_attention(ka, cfg),
        "ffn": ffn,
        "ln1": init_rmsnorm(cfg.d_model, cfg.param_dtype),
        "ln2": init_rmsnorm(cfg.d_model, cfg.param_dtype),
    }


def _init_block(key: jax.Array, cfg: ModelConfig) -> Params:
    depth = cfg.tconst.block_depth
    keys = split_keys(key, depth)
    return {"layers": [_init_layer(k, cfg) for k in keys]}


def init_tconst_lm(key: jax.Array, cfg: ModelConfig) -> Params:
    ke, kb = split_keys(key, 2)
    n_blocks = cfg.tconst_blocks
    block_keys = jax.random.split(kb, n_blocks)
    blocks = jax.vmap(lambda k: _init_block(k, cfg))(block_keys)
    return {
        "embed": E.init_embed(ke, cfg),
        "blocks": blocks,                       # leading dim = n_blocks
        "final_norm": init_rmsnorm(cfg.d_model, cfg.param_dtype),
    }


def _ffn_apply(layer: Params, x: jax.Array, cfg: ModelConfig
               ) -> Tuple[jax.Array, jax.Array]:
    if cfg.is_moe:
        y, aux = moe_ffn(layer["ffn"], x, cfg)
        return y, aux
    return swiglu(layer["ffn"], x), jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Context path (compress -> h self-attn -> restore)
# ---------------------------------------------------------------------------


def _rope(pos: jax.Array, cfg: ModelConfig):
    return R.rope_cos_sin(pos, cfg.resolved_head_dim, cfg.rope_theta)


FLASH_MIN_ELEMS = 4 * 1024 * 1024     # route big ctx attentions via flash


def _flash_ctx_attend(li: Params, xq_n: jax.Array, xkv_n: jax.Array,
                      q_pos: jax.Array, k_pos: jax.Array,
                      k_valid: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Blocked (flash) cross-attention for the context path's two O(N)
    hot spots — compress (Fig 2c) and restore (Fig 2d).  Naive sdpa
    materialises (B, KV, Lq, Lk) logits: 2.7+ GiB at 524k context.
    Positions may be per-batch (resync: hist_len differs per row)."""
    from repro.kernels.xla_flash import INVALID_POS, flash_attention
    dtype = xq_n.dtype
    q, k, v = A.qkv_proj(li["attn"], xq_n, xkv_n, dtype)
    cq, sq = _rope(jnp.maximum(q_pos, 0), cfg)
    ck, sk = _rope(jnp.maximum(k_pos, 0), cfg)
    q = R.apply_rope(q, cq, sq)
    k = R.apply_rope(k, ck, sk)
    kp = jnp.where(k_valid, k_pos, INVALID_POS)
    o = flash_attention(q, k, v, q_pos, kp, 0, True, cfg.logit_softcap,
                        256, 1024)
    return A.out_proj(li["attn"], o, dtype)


def context_path(block: Params, hist: jax.Array, hist_pos: jax.Array,
                 hist_valid: jax.Array, tail_pos: jax.Array,
                 tail_valid: jax.Array, cfg: ModelConfig,
                 ) -> Tuple[List[jax.Array], jax.Array, jax.Array]:
    """Run the context path of one block.

    hist: (B, N, D) full history buffer; hist_valid: (B, N) bool;
    tail_pos/tail_valid: (B, W_oh).  Returns (c_states [C_0..C_h] each
    (B, W_oh, D), restored history (B, N, D), aux loss).
    """
    eps = cfg.norm_eps
    h = cfg.tconst.h
    layers = block["layers"]
    B, N, D = hist.shape
    aux = jnp.zeros((), jnp.float32)

    cos_h, sin_h = _rope(hist_pos, cfg)
    cos_t, sin_t = _rope(jnp.maximum(tail_pos, 0), cfg)

    # gather tail tokens from the history buffer
    idx = jnp.clip(tail_pos, 0, N - 1)
    tail_x = jnp.take_along_axis(hist, idx[..., None], axis=1)   # (B,W_oh,D)

    # ---- layer 0: COMPRESS (Fig 2c) --------------------------------------
    l0 = layers[0]
    big = tail_pos.shape[-1] * N >= FLASH_MIN_ELEMS
    if big:
        c = tail_x + _flash_ctx_attend(
            l0, rmsnorm(l0["ln1"], tail_x, eps),
            rmsnorm(l0["ln1"], hist, eps), tail_pos, hist_pos,
            hist_valid, cfg)
    else:
        mask = A.make_mask(tail_pos, hist_pos, "causal")
        mask = jnp.logical_and(mask, hist_valid[:, None, :])
        c = tail_x + A.attention_block(
            l0["attn"], rmsnorm(l0["ln1"], tail_x, eps),
            rmsnorm(l0["ln1"], hist, eps), mask,
            cos_t, sin_t, cos_h, sin_h, cfg.logit_softcap)
    f, a0 = _ffn_apply(l0, rmsnorm(l0["ln2"], c, eps), cfg)
    c = c + f
    aux = aux + a0
    c_states = [c]

    # ---- layers 1..h: context self-attention ------------------------------
    tmask = A.make_mask(tail_pos, tail_pos, "causal")
    tmask = jnp.logical_and(tmask, tail_valid[:, None, :])
    for i in range(1, h + 1):
        li = layers[i]
        cn = rmsnorm(li["ln1"], c, eps)
        c = c + A.attention_block(li["attn"], cn, cn, tmask,
                                  cos_t, sin_t, cos_t, sin_t,
                                  cfg.logit_softcap)
        f, ai = _ffn_apply(li, rmsnorm(li["ln2"], c, eps), cfg)
        c = c + f
        aux = aux + ai
        c_states.append(c)

    # ---- layer h+1: RESTORE (Fig 2d) — feeds the next stacked block -------
    lf = layers[h + 1]
    if big:
        r = hist + _flash_ctx_attend(
            lf, rmsnorm(lf["ln1"], hist, eps),
            rmsnorm(lf["ln1"], c, eps), hist_pos, tail_pos,
            tail_valid, cfg)
    else:
        rmask = A.make_mask(hist_pos, tail_pos, "causal")
        rmask = jnp.logical_and(rmask, tail_valid[:, None, :])
        r = hist + A.attention_block(
            lf["attn"], rmsnorm(lf["ln1"], hist, eps),
            rmsnorm(lf["ln1"], c, eps), rmask,
            cos_h, sin_h, cos_t, sin_t, cfg.logit_softcap)
    f, af = _ffn_apply(lf, rmsnorm(lf["ln2"], r, eps), cfg)
    restored = r + f
    aux = aux + af
    return c_states, restored, aux


# ---------------------------------------------------------------------------
# Generation path (teacher-forced window pass — training / prefill)
# ---------------------------------------------------------------------------


def gen_path(block: Params, hg: jax.Array, gen_pos: jax.Array,
             c_states: List[jax.Array], tail_pos: jax.Array,
             tail_valid: jax.Array, cfg: ModelConfig,
             hist: Optional[jax.Array] = None,
             hist_pos: Optional[jax.Array] = None,
             hist_valid: Optional[jax.Array] = None,
             ) -> Tuple[jax.Array, jax.Array]:
    """Generation-window pass of one block.

    hg: (B, G, D) window activations; c_states from :func:`context_path`.
    When ``hist`` is given (mode="tlin") layer 0 additionally cross-attends
    to the raw history — the TLinFormer pathway the paper severs.
    Returns (hg_out, aux).
    """
    eps = cfg.norm_eps
    h = cfg.tconst.h
    layers = block["layers"]
    aux = jnp.zeros((), jnp.float32)

    cos_g, sin_g = _rope(gen_pos, cfg)
    cos_t, sin_t = _rope(jnp.maximum(tail_pos, 0), cfg)
    gmask = A.make_mask(gen_pos, gen_pos, "causal")

    for i in range(h + 2):
        li = layers[i]
        xn = rmsnorm(li["ln1"], hg, eps)
        out = A.attention_block(li["attn"], xn, xn, gmask,
                                cos_g, sin_g, cos_g, sin_g,
                                cfg.logit_softcap)
        if i >= 1:
            cs = c_states[i - 1]
            cn = rmsnorm(li["ln1"], cs, eps)
            cmask = A.make_mask(gen_pos, tail_pos, "causal")
            cmask = jnp.logical_and(cmask, tail_valid[:, None, :])
            out = out + A.attention_block(
                li["attn"], xn, cn, cmask,
                cos_g, sin_g, cos_t, sin_t, cfg.logit_softcap)
        elif hist is not None:
            # TLinFormer: first-layer direct pathway to raw history
            cos_h, sin_h = _rope(hist_pos, cfg)
            hmask = A.make_mask(gen_pos, hist_pos, "causal")
            hmask = jnp.logical_and(hmask, hist_valid[:, None, :])
            out = out + A.attention_block(
                li["attn"], xn, rmsnorm(li["ln1"], hist, eps), hmask,
                cos_g, sin_g, cos_h, sin_h, cfg.logit_softcap)
        hg = hg + out
        f, ai = _ffn_apply(li, rmsnorm(li["ln2"], hg, eps), cfg)
        hg = hg + f
        aux = aux + ai
    return hg, aux


# ---------------------------------------------------------------------------
# Training forward: sliding-window chunked processing (paper §5.1)
# ---------------------------------------------------------------------------


def tconst_forward(params: Params, tokens: jax.Array, cfg: ModelConfig,
                   mode: str = "tconst") -> Tuple[jax.Array, jax.Array]:
    """Full teacher-forced forward.  tokens: (B, N) with N % W_og == 0.

    Processes the sequence in ``N // W_og`` chunks; chunk j sees chunks
    0..j-1 as (compressed) history.  Returns (logits (B, N, V), aux).
    """
    tc = cfg.tconst
    B, N = tokens.shape
    assert N % tc.w_og == 0, (N, tc.w_og)
    nc = N // tc.w_og
    dtype = jnp.dtype(cfg.dtype)

    from repro.sharding.rules import shard_act
    X = shard_act(E.embed_tokens(params["embed"], tokens, dtype))  # (B,N,D)
    pos = jnp.broadcast_to(jnp.arange(N)[None], (B, N))
    use_tlin = mode == "tlin"

    def chunk_body(_, j):
        hist_valid = pos < j * tc.w_og                           # (B, N)
        tail_pos = j * tc.w_og - tc.w_oh + jnp.arange(tc.w_oh)
        tail_pos = jnp.broadcast_to(tail_pos[None], (B, tc.w_oh))
        tail_valid = tail_pos >= 0
        gen_pos = j * tc.w_og + jnp.arange(tc.w_og)
        gen_pos = jnp.broadcast_to(gen_pos[None], (B, tc.w_og))
        hg0 = jax.lax.dynamic_slice_in_dim(X, j * tc.w_og, tc.w_og, axis=1)

        def block_body(carry, block):
            hist, hg, aux = carry
            c_states, restored, a_ctx = context_path(
                block, hist, pos, hist_valid, tail_pos, tail_valid, cfg)
            hg, a_gen = gen_path(
                block, hg, gen_pos, c_states, tail_pos, tail_valid, cfg,
                hist=hist if use_tlin else None,
                hist_pos=pos if use_tlin else None,
                hist_valid=hist_valid if use_tlin else None)
            return (restored, hg, aux + a_ctx + a_gen), None

        (_, hg, aux), _ = jax.lax.scan(
            block_body, (X, hg0, jnp.zeros((), jnp.float32)),
            params["blocks"])
        hg = rmsnorm(params["final_norm"], hg, cfg.norm_eps)
        logits = E.lm_head(params["embed"], hg, cfg.logit_softcap)
        return None, (logits, aux)

    _, (logits, aux) = jax.lax.scan(chunk_body, None, jnp.arange(nc))
    # logits: (nc, B, W_og, V) -> (B, N, V)
    logits = jnp.moveaxis(logits, 0, 1).reshape(B, N, -1)
    return logits, jnp.sum(aux)


# ---------------------------------------------------------------------------
# Inference: O(1) cache, cache-hit decode step, periodic resync
# ---------------------------------------------------------------------------


def init_tconst_cache(cfg: ModelConfig, batch: int, max_len: int,
                      mode: str = "tconst") -> Dict[str, Any]:
    """The paper's Eq. (7) constant-size cache (+ the raw token id buffer,
    int32, which is not KV-cache and is the only O(N) residue)."""
    tc = cfg.tconst
    nb = cfg.tconst_blocks
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    cache: Dict[str, Any] = {
        "tokens": jnp.zeros((batch, max_len), jnp.int32),
        "hist_len": jnp.zeros((batch,), jnp.int32),
        "gen_len": jnp.zeros((batch,), jnp.int32),
        "done": jnp.zeros((batch,), bool),
        "ctx_k": jnp.zeros((nb, tc.h + 1, batch, tc.w_oh, kv, hd), dt),
        "ctx_v": jnp.zeros((nb, tc.h + 1, batch, tc.w_oh, kv, hd), dt),
        "ctx_valid": jnp.zeros((batch, tc.w_oh), bool),
        "gen_k": jnp.zeros((nb, tc.h + 2, batch, tc.w_og, kv, hd), dt),
        "gen_v": jnp.zeros((nb, tc.h + 2, batch, tc.w_og, kv, hd), dt),
    }
    if mode == "tlin":
        # TLinFormer restores the O(N) first-layer history KV per block.
        cache["hist_k"] = jnp.zeros((nb, batch, max_len, kv, hd), dt)
        cache["hist_v"] = jnp.zeros((nb, batch, max_len, kv, hd), dt)
    return cache


def kv_cache_bytes(cache: Dict[str, Any]) -> int:
    """KV-cache footprint (the quantity in paper Fig 8g)."""
    keys = [k for k in cache if k.endswith("_k") or k.endswith("_v")]
    return sum(cache[k].size * cache[k].dtype.itemsize for k in keys)


# True KV-cache entries vs bookkeeping (token ids, lengths, phase flags) —
# the explicit partition behind :class:`repro.models.api.DecodeState`.
KV_KEYS = ("ctx_k", "ctx_v", "gen_k", "gen_v", "hist_k", "hist_v")

# Batch ("slot") axis of every cache entry, so the serving layer can
# scatter a prefilled row into a slot / select rows at a resync boundary.
CACHE_BATCH_AXES = {
    "tokens": 0, "hist_len": 0, "gen_len": 0, "done": 0, "ctx_valid": 0,
    "ctx_k": 2, "ctx_v": 2, "gen_k": 2, "gen_v": 2,
    "hist_k": 1, "hist_v": 1,
}

# Cache-layout metadata (repro.models.layouts): which KV fields have an
# O(N) length axis that a PagedLayout can split into pages (only the
# TLinFormer history KV — the tconst ctx/gen buffers are already O(1)),
# and which are float KV that a QuantizedLayout may store as int8.
LENGTH_AXES = {"hist_k": 2, "hist_v": 2}
QUANT_FIELDS = KV_KEYS


def needs_resync(cache: Dict[str, Any], cfg: ModelConfig) -> jax.Array:
    """Per-row (B,) bool: the generation window is full, the next decode
    step must be preceded by a global synchronisation."""
    return cache["gen_len"] >= cfg.tconst.w_og


def resync_rows(params: Params, cache: Dict[str, Any], cfg: ModelConfig,
                rows: jax.Array, mode: str = "tconst") -> Dict[str, Any]:
    """Row-selective resync: apply :func:`resync` only to the batch rows
    where ``rows`` is True, leaving the others bit-identical.

    This is what makes the periodic synchronisation correct under
    continuous batching: slots admitted at different times sit at
    different W_og phases, so a boundary crossing in one slot must not
    fold another slot's half-full generation window into history.
    """
    new = resync(params, cache, cfg, mode)
    return {k: where_rows(rows, new[k], cache[k], CACHE_BATCH_AXES[k])
            for k in cache}


def maybe_resync(params: Params, cache: Dict[str, Any], cfg: ModelConfig,
                 mode: str = "tconst") -> Dict[str, Any]:
    """Device-side resync decision (no host round-trip): a ``lax.cond`` on
    the per-row phase counters runs the linear-time synchronisation only
    when some row's generation window is full.  Fusing this into the
    jitted decode step lets a whole decode chunk run as one ``lax.scan``
    with zero per-token host syncs.

    PR-1 reference path: the cond computes the FULL-BATCH resync and
    row-selects, so non-boundary rows are computed then discarded.  The
    serving protocol now uses :func:`resync_rows_compacted` instead;
    this stays as the equivalence oracle for the parity tests.
    """
    rows = needs_resync(cache, cfg)
    return jax.lax.cond(
        jnp.any(rows),
        lambda c: resync_rows(params, c, cfg, rows, mode),
        lambda c: c,
        cache)


def gather_row(cache: Dict[str, Any], i: jax.Array) -> Dict[str, Any]:
    """Extract batch row ``i`` of every cache entry (batch size 1)."""
    return {k: jax.lax.dynamic_slice_in_dim(v, i, 1, CACHE_BATCH_AXES[k])
            for k, v in cache.items()}


def scatter_row(cache: Dict[str, Any], i: jax.Array,
                row: Dict[str, Any]) -> Dict[str, Any]:
    """Write a batch-1 row back into batch row ``i``."""
    return {k: jax.lax.dynamic_update_slice_in_dim(
        cache[k], row[k].astype(cache[k].dtype), i, CACHE_BATCH_AXES[k])
        for k in cache}


def pending_resync_rows(cache: Dict[str, Any], cfg: ModelConfig
                        ) -> jax.Array:
    """(B,) bool: rows that must sync before the next step — the window
    is full AND the slot is not EOS-finished (done rows are frozen by
    the chunk, so syncing them would be wasted O(N) work every step).
    Reads ONLY bookkeeping counters — no KV access, no unpack."""
    return jnp.logical_and(needs_resync(cache, cfg),
                           jnp.logical_not(cache["done"]))


# -- batched compacted resync (one dispatch for all pending rows) -----------

# resync() rebuilds the ctx/hist KV entirely from the raw token buffer, so
# a row-wise resync only ever needs to GATHER these bookkeeping fields —
# never the KV cache itself.
RESYNC_INPUT_KEYS = ("tokens", "hist_len", "gen_len")


def admission_digest(tokens, mode: str, w_og: int) -> bytes:
    """Content key of a TConst POST-ADMISSION slot state.

    ``resync`` (and therefore the bucketed admission prefill) rebuilds
    the ctx/hist KV purely from ``RESYNC_INPUT_KEYS`` — the raw token
    ids plus the deterministic hist/gen split, itself a function of the
    prompt length and ``w_og`` — so for fixed params/config the admitted
    slot (KV *and* bookkeeping) is a pure function of the prompt ids.
    That purity is what makes the ctx/hist KV content-addressable: two
    admissions of the same prompt may share one stored snapshot, and a
    tier-store hit replaces the O(N) resync with an O(1) restore.  The
    digest is salted with ``mode`` (tconst vs tlin caches differ) and
    ``w_og`` (it fixes the split); the caller layers scheduler-level
    salt (layout, max_len) on top."""
    h = hashlib.sha1(f"tconst-admit\x00{mode}\x00{w_og}\x00".encode())
    h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
    return h.digest()


def resync_buckets(batch: int) -> Tuple[int, ...]:
    """Static gather sizes for the compacted resync: 0, powers of two,
    and the full batch.  The pending count is rounded UP to the nearest
    bucket, so at most 2x the pending rows are computed while the number
    of compiled resync variants stays O(log batch)."""
    sizes = {0, batch}
    k = 1
    while k < batch:
        sizes.add(k)
        k *= 2
    return tuple(sorted(sizes))


def compacted_rows_switch(rows: jax.Array, operand: Any, branch_factory):
    """Shared scaffold of the batched compacted resync: sort pending
    rows first, round their count up to a static bucket, and dispatch
    ONE ``lax.switch`` branch.  ``branch_factory(k)`` returns
    ``fn(operand, idx (k,), sel (k,) bool) -> operand`` — ``idx`` are
    the rows to gather (pending first, then padding) and ``sel`` masks
    the padding rows out of the scatter.  Used by the dense-dict oracle
    (:func:`resync_rows_compacted`) and the layout-aware
    ``TConstDecode.sync_rows`` so the bucketing policy lives in exactly
    one place.  Zero pending rows selects the identity branch."""
    buckets = resync_buckets(rows.shape[0])
    order = jnp.argsort(jnp.logical_not(rows))       # pending rows first
    count = jnp.sum(rows)

    def wrap(kb: int):
        if kb == 0:
            return lambda op: op
        branch = branch_factory(kb)
        return lambda op: branch(op, order[:kb], jnp.arange(kb) < count)

    index = jnp.searchsorted(jnp.asarray(buckets), count)
    return jax.lax.switch(index, [wrap(k) for k in buckets], operand)


def resync_rows_compacted(params: Params, cache: Dict[str, Any],
                          cfg: ModelConfig, rows: jax.Array,
                          mode: str = "tconst") -> Dict[str, Any]:
    """Compacted row-wise resync, BATCHED: gather all pending rows in ONE
    dispatch, run ONE O(N) synchronisation at (bucketed) batch size k,
    and scatter the results back — non-pending rows are never computed
    and come through bit-identical.

    This replaces the PR-2 ``lax.while_loop`` that serialized one
    batch-1 resync per pending row (the ROADMAP follow-up: a PARTIALLY
    synchronized batch paid latency linear in its pending count).  The
    pending count is dynamic, so the gather size is rounded up to a
    static bucket (0, 1, 2, 4, ..., B — ``lax.switch`` on the count);
    padding rows are non-pending rows whose results are masked out of
    the scatter, wasting at most 2x the pending compute while keeping
    the dispatch count at exactly one.  Because ``resync`` rebuilds the
    ctx/hist KV from the raw token ids, only the ``RESYNC_INPUT_KEYS``
    bookkeeping rows are gathered — the KV cache is written, never read.

    Zero pending rows selects the identity branch, so this IS the fused
    on-device decision — no outer ``lax.cond`` needed.
    """
    def factory(kb: int):
        def branch(cache, idx, sel):
            row_in = {f: take_rows(cache[f], idx, CACHE_BATCH_AXES[f])
                      for f in RESYNC_INPUT_KEYS}
            new = resync(params, row_in, cfg, mode)
            out = dict(cache)
            for f, v in new.items():
                ax = CACHE_BATCH_AXES[f]
                old = take_rows(cache[f], idx, ax)
                vals = where_rows(sel, v.astype(cache[f].dtype), old, ax)
                out[f] = put_rows(cache[f], idx, vals, ax)
            return out
        return branch

    return compacted_rows_switch(rows, cache, factory)


def resync(params: Params, cache: Dict[str, Any], cfg: ModelConfig,
           mode: str = "tconst") -> Dict[str, Any]:
    """Cache-miss path: global information synchronisation (paper's k-th
    step).  Folds the generation window into history and recomputes the
    compressed context KV from the full token buffer.  Cost O(N)."""
    tc = cfg.tconst
    eps = cfg.norm_eps
    B, max_len = cache["tokens"].shape
    dtype = jnp.dtype(cfg.dtype)

    from repro.sharding.rules import shard_act
    hist_len = cache["hist_len"] + cache["gen_len"]              # (B,)
    X = shard_act(E.embed_tokens(params["embed"], cache["tokens"], dtype))
    pos = jnp.broadcast_to(jnp.arange(max_len)[None], (B, max_len))
    hist_valid = pos < hist_len[:, None]
    tail_pos = hist_len[:, None] - tc.w_oh + jnp.arange(tc.w_oh)[None]
    tail_valid = tail_pos >= 0
    cos_t, sin_t = _rope(jnp.maximum(tail_pos, 0), cfg)
    cos_h, sin_h = _rope(pos, cfg)

    def block_body(hist, block):
        c_states, restored, _ = context_path(
            block, hist, pos, hist_valid, tail_pos, tail_valid, cfg)
        cks, cvs = [], []
        for i in range(1, tc.h + 2):
            li = block["layers"][i]
            cn = rmsnorm(li["ln1"], c_states[i - 1], eps)
            ck, cv = A.project_kv(li["attn"], cn, cos_t, sin_t)
            cks.append(ck)
            cvs.append(cv)
        extras = ()
        if mode == "tlin":
            l0 = block["layers"][0]
            hk, hv = A.project_kv(
                l0["attn"], rmsnorm(l0["ln1"], hist, eps), cos_h, sin_h)
            extras = (hk, hv)
        return restored, (jnp.stack(cks), jnp.stack(cvs)) + extras

    _, outs = jax.lax.scan(block_body, X, params["blocks"])
    cache = dict(cache)
    cache["ctx_k"], cache["ctx_v"] = outs[0], outs[1]
    if mode == "tlin":
        cache["hist_k"], cache["hist_v"] = outs[2], outs[3]
    cache["ctx_valid"] = tail_valid
    cache["hist_len"] = hist_len
    cache["gen_len"] = jnp.zeros_like(cache["gen_len"])
    return cache


def decode_step_views(params: Params, cache: Dict[str, Any],
                      token: jax.Array, cfg: ModelConfig,
                      mode: str = "tconst",
                      live: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Layout-native cache-hit step (paper Eq. 5): strictly O(1) compute
    and memory reads for mode="tconst".  ``cache`` maps bookkeeping names
    to plain arrays and KV names to :mod:`repro.models.layouts`
    FieldViews — the attention consumes the PHYSICAL representation
    (paged pools are walked page-by-page, int8 dequant rides the QK/AV
    loops) and the new token's K/V is appended *through* the views, so
    non-dense layouts never round-trip a dense logical cache.

    token: (B,) int32.  ``live`` (B,) bool masks every write by row: a
    frozen row's gen K/V, token id and ``gen_len`` come through
    bit-identical (None: every row steps).  Returns (logits (B, V),
    updated cache dict with the same view/array structure).
    """
    tc = cfg.tconst
    eps = cfg.norm_eps
    B = token.shape[0]
    dtype = jnp.dtype(cfg.dtype)

    pos = cache["hist_len"] + cache["gen_len"]                   # (B,)
    x = E.embed_tokens(params["embed"], token[:, None], dtype)   # (B,1,D)
    cos_q, sin_q = _rope(pos[:, None], cfg)
    nb = cfg.tconst_blocks
    ctx_k, ctx_v = cache["ctx_k"], cache["ctx_v"]
    use_tlin = mode == "tlin"

    def block_body(ib, carry):
        x, gk, gv = carry
        block = jax.tree_util.tree_map(lambda a: a[ib], params["blocks"])
        ctx_kb, ctx_vb = ctx_k.layer(ib), ctx_v.layer(ib)
        gkb, gvb = gk.layer(ib), gv.layer(ib)
        for i in range(tc.h + 2):
            li = block["layers"][i]
            xn = rmsnorm(li["ln1"], x, eps)
            out, gki, gvi = A.decode_attend_view(
                li["attn"], xn, gkb.layer(i), gvb.layer(i),
                cache["gen_len"], cos_q, sin_q, cfg.logit_softcap,
                live=live)
            gkb = gkb.set_layer(i, gki)
            gvb = gvb.set_layer(i, gvi)
            if i >= 1:
                out = out + A.cross_attend_view(
                    li["attn"], xn, ctx_kb.layer(i - 1),
                    ctx_vb.layer(i - 1), cache["ctx_valid"],
                    cos_q, sin_q, cfg.logit_softcap)
            elif use_tlin:
                # TLinFormer's O(N) history KV: the ONE paged field of
                # this family — attended in its physical layout
                out = out + A.cross_attend_view(
                    li["attn"], xn, cache["hist_k"].layer(ib),
                    cache["hist_v"].layer(ib), None, cos_q, sin_q,
                    cfg.logit_softcap, valid_len=cache["hist_len"])
            x = x + out
            f, _ = _ffn_apply(li, rmsnorm(li["ln2"], x, eps), cfg)
            x = x + f
        return x, gk.set_layer(ib, gkb), gv.set_layer(ib, gvb)

    x, gk, gv = jax.lax.fori_loop(
        0, nb, lambda i, c: block_body(i, c),
        (x, cache["gen_k"], cache["gen_v"]))

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = E.lm_head(params["embed"], x, cfg.logit_softcap)[:, 0]

    cache = dict(cache)
    cache["gen_k"], cache["gen_v"] = gk, gv
    # record the token id into the O(N) id buffer (int32, not KV cache)
    wpos = LT.frozen_index(pos, live, cache["tokens"].shape[1])
    cache["tokens"] = cache["tokens"].at[jnp.arange(B), wpos].set(
        token, mode="drop")
    cache["gen_len"] = cache["gen_len"] + (
        1 if live is None else live.astype(jnp.int32))
    return logits, cache


def _dense_views(cache: Dict[str, Any]) -> Dict[str, Any]:
    return {k: LT.DenseView(v, CACHE_BATCH_AXES[k]) if k in KV_KEYS else v
            for k, v in cache.items()}


def _undense_views(cache: Dict[str, Any]) -> Dict[str, Any]:
    return {k: v.dense() if isinstance(v, LT.FieldView) else v
            for k, v in cache.items()}


def decode_step(params: Params, cache: Dict[str, Any], token: jax.Array,
                cfg: ModelConfig, mode: str = "tconst"
                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Dense-dict cache-hit step: the legacy entry point (launchers,
    benchmarks) and the PARITY ORACLE the layout-native kernels are
    tested against.  Wraps the dense arrays in DenseViews — the
    dense-view dispatch is bit-identical to the historic dense path.

    The caller (or :func:`repro.serving.engine`) must invoke :func:`resync`
    once ``gen_len`` reaches ``W_og`` — the paper's periodic linear-time
    synchronisation.
    """
    logits, out = decode_step_views(params, _dense_views(cache), token,
                                    cfg, mode)
    return logits, _undense_views(out)


def verify_chunk_views(params: Params, cache: Dict[str, Any],
                       feed: jax.Array, cfg: ModelConfig,
                       mode: str = "tconst"
                       ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Speculative VERIFY: score C fed tokens per slot against the
    resident caches in ONE fixed-shape dispatch — the chunked analogue
    of :func:`decode_step_views`, with the C-step python loop replaced
    by :func:`repro.kernels.ops.prefill_chunk_attention` over the gen
    window and C-query cross-attention over the frozen context KV.

    feed: (B, C) int32 — position c is the token the sequential decode
    WOULD feed at generation offset ``gen_len + c`` (the previous
    sample, then the draft).  All C keys/values are written through the
    views at gen slots ``gen_len + c`` (true-position RoPE), exactly
    where the sequential steps would put them; writes past ``W_og``
    fall off the scatter harmlessly and the caller's acceptance budget
    (:meth:`TConstDecode.verify_budget`) never accepts past the window.

    COUNTERS ARE NOT ADVANCED: acceptance of an m-token prefix is a
    later ``gen_len += m`` (``advance_lengths``); rejected suffix
    writes become stale garbage beyond ``gen_len``, masked by the
    slot-causal attention here and overwritten before ever being
    attended by the next round's writes at the same slots.

    Returns (logits (B, C, V) — position c scores the token AFTER
    ``feed[:, c]`` — and the updated cache, same view structure).
    """
    from repro.kernels import ops
    tc = cfg.tconst
    eps = cfg.norm_eps
    B, C = feed.shape
    dtype = jnp.dtype(cfg.dtype)

    pos = cache["hist_len"] + cache["gen_len"]                   # (B,)
    qpos = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None]   # (B, C)
    gpos = cache["gen_len"][:, None] + \
        jnp.arange(C, dtype=jnp.int32)[None]                     # (B, C)
    x = E.embed_tokens(params["embed"], feed, dtype)             # (B, C, D)
    cos_q, sin_q = _rope(qpos, cfg)
    nb = cfg.tconst_blocks
    ctx_k, ctx_v = cache["ctx_k"], cache["ctx_v"]
    use_tlin = mode == "tlin"
    if use_tlin:
        max_len = cache["tokens"].shape[1]
        hist_valid = jnp.arange(max_len)[None] < \
            cache["hist_len"][:, None]                           # (B, N)

    def block_body(ib, carry):
        x, gk, gv = carry
        block = jax.tree_util.tree_map(lambda a: a[ib], params["blocks"])
        ctx_kb, ctx_vb = ctx_k.layer(ib), ctx_v.layer(ib)
        gkb, gvb = gk.layer(ib), gv.layer(ib)
        for i in range(tc.h + 2):
            li = block["layers"][i]
            xn = rmsnorm(li["ln1"], x, eps)
            q, k_new, v_new = A.qkv_proj(li["attn"], xn, xn, dtype)
            q = R.apply_rope(q, cos_q, sin_q)
            k_new = R.apply_rope(k_new, cos_q, sin_q)
            gki, gvi = gkb.layer(i), gvb.layer(i)
            for c in range(C):
                gki = gki.write_token(cache["gen_len"] + c, k_new[:, c])
                gvi = gvi.write_token(cache["gen_len"] + c, v_new[:, c])
            gkb = gkb.set_layer(i, gki)
            gvb = gvb.set_layer(i, gvi)
            w_og = gki.dense().shape[1]
            o = ops.prefill_chunk_attention(
                q, gki.dense().astype(dtype), gvi.dense().astype(dtype),
                gpos, jnp.arange(w_og, dtype=jnp.int32), 0,
                cfg.logit_softcap)
            out = A.out_proj(li["attn"], o, dtype)
            if i >= 1:
                out = out + A.verify_attend_view(
                    li["attn"], xn, ctx_kb.layer(i - 1),
                    ctx_vb.layer(i - 1), cache["ctx_valid"],
                    cos_q, sin_q, cfg.logit_softcap)
            elif use_tlin:
                out = out + A.verify_attend_view(
                    li["attn"], xn, cache["hist_k"].layer(ib),
                    cache["hist_v"].layer(ib), hist_valid,
                    cos_q, sin_q, cfg.logit_softcap)
            x = x + out
            f, _ = _ffn_apply(li, rmsnorm(li["ln2"], x, eps), cfg)
            x = x + f
        return x, gk.set_layer(ib, gkb), gv.set_layer(ib, gvb)

    x, gk, gv = jax.lax.fori_loop(
        0, nb, lambda i, c: block_body(i, c),
        (x, cache["gen_k"], cache["gen_v"]))

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = E.lm_head(params["embed"], x, cfg.logit_softcap)   # (B, C, V)

    cache = dict(cache)
    cache["gen_k"], cache["gen_v"] = gk, gv
    cache["tokens"] = cache["tokens"].at[
        jnp.arange(B)[:, None], qpos].set(feed)
    return logits, cache


def _prefill_window_pass(params: Params, cache: Dict[str, Any],
                         win: jax.Array, gen_pos: jax.Array,
                         cfg: ModelConfig, mode: str
                         ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Teacher-forced generation-window pass shared by :func:`prefill`
    (window = the prompt's trailing 1..W_og tokens, static width W) and
    :func:`prefill_bucketed` (fixed W_og width, trailing padding masked
    by causality now and by ``gen_len`` afterwards).  Fills the leading
    W slots of the per-layer gen KV buffers (W < W_og: the rest stays
    zero).  Returns (hg (B, W, D), (gen_k, gen_v) stacked per block)."""
    tc = cfg.tconst
    eps = cfg.norm_eps
    B, W = win.shape
    dtype = jnp.dtype(cfg.dtype)
    cos_g, sin_g = _rope(gen_pos, cfg)
    hg = E.embed_tokens(params["embed"], win, dtype)
    gmask = A.make_mask(gen_pos, gen_pos, "causal")

    def block_body(hg, xs):
        if mode == "tlin":
            block, ctx_k, ctx_v, hist_k, hist_v = xs
        else:
            block, ctx_k, ctx_v = xs
        new_gk, new_gv = [], []
        for i in range(tc.h + 2):
            li = block["layers"][i]
            xn = rmsnorm(li["ln1"], hg, eps)
            k, v = A.project_kv(li["attn"], xn, cos_g, sin_g)
            q = jnp.einsum("bld,dhk->blhk", xn, li["attn"]["wq"].astype(dtype))
            q = R.apply_rope(q, cos_g, sin_g)
            out = A.out_proj(li["attn"], A.sdpa(
                q, k, v, gmask, cfg.logit_softcap), dtype)
            # store window K/V into slots [0, W)
            if W < tc.w_og:
                gk = jnp.zeros((B, tc.w_og) + k.shape[2:], dtype)
                gv = jnp.zeros((B, tc.w_og) + v.shape[2:], dtype)
                k = jax.lax.dynamic_update_slice_in_dim(gk, k, 0, axis=1)
                v = jax.lax.dynamic_update_slice_in_dim(gv, v, 0, axis=1)
            new_gk.append(k)
            new_gv.append(v)
            if i >= 1:
                out = out + A.cross_attend_cached(
                    li["attn"], xn, ctx_k[i - 1], ctx_v[i - 1],
                    cache["ctx_valid"], cos_g, sin_g, cfg.logit_softcap)
            elif mode == "tlin":
                slots = jnp.arange(hist_k.shape[1])[None]
                hvalid = slots < cache["hist_len"][:, None]
                out = out + A.cross_attend_cached(
                    li["attn"], xn, hist_k, hist_v, hvalid,
                    cos_g, sin_g, cfg.logit_softcap)
            hg = hg + out
            f, _ = _ffn_apply(li, rmsnorm(li["ln2"], hg, eps), cfg)
            hg = hg + f
        return hg, (jnp.stack(new_gk), jnp.stack(new_gv))

    xs = (params["blocks"], cache["ctx_k"], cache["ctx_v"])
    if mode == "tlin":
        xs = xs + (cache["hist_k"], cache["hist_v"])
    return jax.lax.scan(block_body, hg, xs)


def prefill(params: Params, tokens: jax.Array, cfg: ModelConfig,
            max_len: int, mode: str = "tconst"
            ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Process a prompt: resync over the history part, teacher-forced pass
    over the trailing (≤ W_og) generation-window part, fill all caches.

    tokens: (B, N0), N0 static.  Returns (next-token logits (B, V), cache).
    """
    tc = cfg.tconst
    B, n0 = tokens.shape
    g0 = ((n0 - 1) % tc.w_og) + 1            # window part: 1..W_og tokens

    cache = init_tconst_cache(cfg, B, max_len, mode)
    cache["tokens"] = jax.lax.dynamic_update_slice_in_dim(
        cache["tokens"], tokens, 0, axis=1)
    cache["hist_len"] = jnp.full((B,), n0 - g0, jnp.int32)
    cache["gen_len"] = jnp.zeros((B,), jnp.int32)
    cache = resync(params, cache, cfg, mode)     # gen_len folded in (=0)

    win = tokens[:, n0 - g0:]
    gen_pos = (n0 - g0) + jnp.broadcast_to(jnp.arange(g0)[None], (B, g0))
    hg, (gk, gv) = _prefill_window_pass(params, cache, win, gen_pos, cfg,
                                        mode)
    hg = rmsnorm(params["final_norm"], hg, cfg.norm_eps)
    logits = E.lm_head(params["embed"], hg, cfg.logit_softcap)[:, -1]
    cache["gen_k"], cache["gen_v"] = gk, gv
    cache["gen_len"] = jnp.full((B,), g0, jnp.int32)
    return logits, cache


def prefill_bucketed(params: Params, tokens: jax.Array, n_valid: jax.Array,
                     cfg: ModelConfig, mode: str = "tconst"
                     ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Bucketed-shape prefill: ONE compile for every prompt length.

    :func:`prefill` compiles once per distinct prompt length (its token
    argument and teacher-forced window are ``n0``-shaped).  Here the
    prompt arrives already zero-padded into the full ``(B, max_len)``
    token buffer with a TRACED per-row length ``n_valid``, the resync is
    its usual fixed-``max_len`` dispatch, and the generation-window pass
    runs at a fixed ``W_og`` width with validity masking — so the entire
    admission is shape-independent.  Written positions beyond each row's
    window part (``slots >= g0``) hold garbage that ``gen_len`` masks
    out of every later attend, exactly like the unchunked cache.

    tokens: (B, max_len) int32, zeros beyond ``n_valid`` (the resync
    embeds the whole buffer either way, so padding must match the
    unchunked token buffer bit-for-bit).  n_valid: (B,) int32 >= 1.
    Returns (next-token logits (B, V), cache) — stream-identical to
    :func:`prefill` up to float association.
    """
    tc = cfg.tconst
    B, max_len = tokens.shape
    g0 = ((n_valid - 1) % tc.w_og) + 1       # (B,) window part: 1..W_og

    cache = init_tconst_cache(cfg, B, max_len, mode)
    cache["tokens"] = tokens
    cache["hist_len"] = n_valid - g0
    cache["gen_len"] = jnp.zeros((B,), jnp.int32)
    cache = resync(params, cache, cfg, mode)     # fixed-shape O(max_len)

    # teacher-forced generation-window pass at fixed W_og width: row b's
    # window tokens are tokens[hist_len : hist_len + g0]; trailing slots
    # [g0, W_og) are padding whose K/V is never attended (masked by
    # gen_len afterwards, by causality inside this pass).
    win_pos = cache["hist_len"][:, None] + jnp.arange(tc.w_og)[None]
    win = jnp.take_along_axis(tokens, jnp.clip(win_pos, 0, max_len - 1),
                              axis=1)
    hg, (gk, gv) = _prefill_window_pass(params, cache, win, win_pos, cfg,
                                        mode)
    hg = rmsnorm(params["final_norm"], hg, cfg.norm_eps)
    logits = E.lm_head(params["embed"], hg, cfg.logit_softcap)  # (B,W_og,V)
    logits = jnp.take_along_axis(
        logits, (g0 - 1)[:, None, None], axis=1)[:, 0]
    cache["gen_k"], cache["gen_v"] = gk, gv
    cache["gen_len"] = g0
    return logits, cache
