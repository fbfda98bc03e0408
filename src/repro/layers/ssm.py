"""Mamba-2 SSD (state-space duality) layer [arXiv:2405.21060].

Implements the chunked SSD algorithm: within a chunk the recurrence is
computed as masked (decay-weighted) matmuls — MXU-friendly — and across
chunks a ``jax.lax.scan`` carries the (H, P, N) state.  This pure-jnp
implementation is the oracle; ``repro.kernels.ssd_scan`` provides the
Pallas intra-chunk kernel.

Layer layout follows the mamba2 block: in_proj -> (z, x, B, C, dt),
depthwise causal conv over (x, B, C), SSD core, gated norm, out_proj.
Single B/C group (n_groups=1), scalar A per head.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.layers.common import Params, dense_init, split_keys


class SSMDims(NamedTuple):
    d_inner: int
    n_heads: int
    head_dim: int
    n_state: int
    d_conv: int
    conv_dim: int


def ssm_dims(cfg: ModelConfig, d_model: Optional[int] = None) -> SSMDims:
    d = d_model or cfg.d_model
    d_inner = cfg.ssm_expand * d
    head_dim = cfg.ssm_head_dim or 64
    n_heads = cfg.ssm_heads or d_inner // head_dim
    n_state = cfg.ssm_state
    conv_dim = d_inner + 2 * n_state
    return SSMDims(d_inner, n_heads, head_dim, n_state, cfg.ssm_conv, conv_dim)


def init_ssm(key: jax.Array, cfg: ModelConfig,
             d_model: Optional[int] = None) -> Params:
    d = d_model or cfg.d_model
    dims = ssm_dims(cfg, d)
    kin, kconv, kdt, ka, kout, knorm = split_keys(key, 6)
    d_proj = 2 * dims.d_inner + 2 * dims.n_state + dims.n_heads
    dt_bias = jnp.log(jnp.expm1(
        jnp.exp(jax.random.uniform(kdt, (dims.n_heads,), jnp.float32,
                                   jnp.log(1e-3), jnp.log(1e-1)))))
    return {
        "in_proj": dense_init(kin, (d, d_proj), cfg.param_dtype, fan_in=d),
        "conv_w": dense_init(kconv, (dims.d_conv, dims.conv_dim),
                             cfg.param_dtype, fan_in=dims.d_conv),
        "conv_b": jnp.zeros((dims.conv_dim,), cfg.param_dtype),
        "dt_bias": dt_bias.astype(cfg.param_dtype),
        "a_log": jnp.log(jnp.arange(1, dims.n_heads + 1, dtype=jnp.float32)
                         ).astype(cfg.param_dtype),
        "d_skip": jnp.ones((dims.n_heads,), cfg.param_dtype),
        "norm_scale": jnp.ones((dims.d_inner,), cfg.param_dtype),
        "out_proj": dense_init(kout, (dims.d_inner, d), cfg.param_dtype,
                               fan_in=dims.d_inner),
    }


# ---------------------------------------------------------------------------
# SSD core (chunked)
# ---------------------------------------------------------------------------


def _segsum(x: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{j < k <= i} x[..., k].

    x: (..., Q) -> (..., Q, Q) lower-triangular cumulative log-decays.
    """
    Q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, chunk: int,
                init_state: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.

    x: (Bt, L, H, P)   inputs (already multiplied by nothing; dt applied here)
    dt: (Bt, L, H)     positive step sizes
    a: (H,)            negative decay rates (A = -exp(a_log))
    b, c: (Bt, L, N)   input/output projections (single group, broadcast to H)
    Returns (y (Bt, L, H, P), final_state (Bt, H, P, N)).
    """
    Bt, L, H, P = x.shape
    N = b.shape[-1]
    assert L % chunk == 0, (L, chunk)
    nc = L // chunk
    f32 = jnp.float32

    xc = x.astype(f32).reshape(Bt, nc, chunk, H, P)
    dtc = dt.astype(f32).reshape(Bt, nc, chunk, H)
    bc = b.astype(f32).reshape(Bt, nc, chunk, N)
    cc = c.astype(f32).reshape(Bt, nc, chunk, N)

    da = dtc * a.astype(f32)[None, None, None, :]          # (Bt, nc, Q, H) log-decay
    da = jnp.moveaxis(da, -1, 2)                           # (Bt, nc, H, Q)
    seg = _segsum(da)                                      # (Bt, nc, H, Q, Q)
    decay_mat = jnp.exp(seg)

    # intra-chunk (diagonal blocks): y_intra[l] = sum_{s<=l} C_l.B_s decay x_s dt_s
    xdt = xc * dtc[..., None]                              # (Bt,nc,Q,H,P)
    scores = jnp.einsum("bnlm,bnsm->bnls", cc, bc)         # (Bt,nc,Q,Q)
    y_intra = jnp.einsum("bnls,bnhls,bnshp->bnlhp",
                         scores, decay_mat, xdt)

    # chunk-final states: state_n = sum_s decay_to_end * B_s xdt_s
    decay_to_end = jnp.exp(jnp.cumsum(da[..., ::-1], axis=-1)[..., ::-1] - da)
    # decay from step s (exclusive) to end of chunk: (Bt,nc,H,Q)
    states = jnp.einsum("bnsm,bnhs,bnshp->bnhpm", bc, decay_to_end, xdt)

    # inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(jnp.sum(da, axis=-1))            # (Bt, nc, H)
    s0 = (init_state.astype(f32) if init_state is not None
          else jnp.zeros((Bt, H, P, N), f32))

    # scan emits the state BEFORE each chunk; carry ends as the final state
    final, prev_states = jax.lax.scan(
        lambda c, i: ((c * i[1][:, :, None, None] + i[0]), c),
        s0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)          # (Bt,nc,H,P,N)

    # contribution of carried state into each chunk
    decay_from_start = jnp.exp(jnp.cumsum(da, axis=-1))    # (Bt,nc,H,Q)
    y_inter = jnp.einsum("bnlm,bnhl,bnhpm->bnlhp",
                         cc, decay_from_start, prev_states)

    y = (y_intra + y_inter).reshape(Bt, L, H, P)
    return y.astype(x.dtype), final


def ssd_step(state: jax.Array, x: jax.Array, dt: jax.Array, a: jax.Array,
             b: jax.Array, c: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Single-token recurrent step (decode path) — O(1) in sequence length.

    state: (Bt, H, P, N); x: (Bt, H, P); dt: (Bt, H); b, c: (Bt, N).
    """
    f32 = jnp.float32
    dec = jnp.exp(dt.astype(f32) * a.astype(f32)[None])    # (Bt, H)
    xdt = x.astype(f32) * dt.astype(f32)[..., None]        # (Bt, H, P)
    new = state.astype(f32) * dec[:, :, None, None] + \
        jnp.einsum("bhp,bm->bhpm", xdt, b.astype(f32))
    y = jnp.einsum("bhpm,bm->bhp", new, c.astype(f32))
    return y.astype(x.dtype), new


# ---------------------------------------------------------------------------
# Full mamba2 mixer (projections + conv + SSD + gate)
# ---------------------------------------------------------------------------


def _split_proj(z_all: jax.Array, dims: SSMDims):
    di, n = dims.d_inner, dims.n_state
    z = z_all[..., :di]
    xbc = z_all[..., di:di + dims.conv_dim]
    dt = z_all[..., di + dims.conv_dim:]
    return z, xbc, dt


def causal_conv(xbc: jax.Array, w: jax.Array, bias: jax.Array,
                prev: Optional[jax.Array] = None,
                valid_len: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv1d.  xbc: (B, L, C); w: (K, C).

    prev: (B, K-1, C) trailing context from the previous segment (decode).
    valid_len: optional (B,) — only positions ``[0, valid_len)`` are real
    (chunked prefill pads the last chunk): the returned context window
    then ends at ``valid_len`` instead of L, so trailing padding never
    enters the next segment's conv state.
    Returns (out (B, L, C), new_prev (B, K-1, C)).
    """
    K = w.shape[0]
    B, L, C = xbc.shape
    if prev is None:
        prev = jnp.zeros((B, K - 1, C), xbc.dtype)
    xp = jnp.concatenate([prev, xbc], axis=1)              # (B, L+K-1, C)
    out = jnp.zeros((B, L, C), jnp.float32)
    for i in range(K):
        out = out + xp[:, i:i + L].astype(jnp.float32) * \
            w[i].astype(jnp.float32)
    out = jax.nn.silu(out + bias.astype(jnp.float32)).astype(xbc.dtype)
    if valid_len is None:
        return out, xp[:, L:]
    # window of the K-1 inputs preceding position valid_len: xp index j
    # holds segment position j - (K-1), so the window is xp[vl : vl+K-1]
    idx = valid_len[:, None] + jnp.arange(K - 1)[None]     # (B, K-1)
    return out, jnp.take_along_axis(xp, idx[..., None], axis=1)


def ssm_mixer(params: Params, x: jax.Array, cfg: ModelConfig,
              d_model: Optional[int] = None,
              state: Optional[dict] = None,
              valid_len: Optional[jax.Array] = None,
              layer: Optional[jax.Array] = None,
              live: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, Optional[dict]]:
    """Mamba2 mixer. x: (B, L, d). If ``state`` is given (keys: ssm, conv),
    runs in stepwise/streaming mode and returns the updated state.

    valid_len: optional (B,) — positions ``>= valid_len`` are padding
    (the chunked prefill's trailing pad): their ``dt`` is forced to 0,
    which makes the SSD update an exact identity (``exp(0·a) = 1`` decay,
    zero input contribution), and the conv context window ends at
    ``valid_len`` — so the returned state is the state after the REAL
    tokens, bit-for-bit.  Padding rows' outputs are garbage (discarded
    by the caller).

    layer: a single-token step's index into a stacked state: ``state
    ["ssm"]`` is then the whole (layers, B, H, P, N) stack, layer
    ``layer`` is stepped in place for the rows ``live`` (B,) bool marks
    (None: every row) and the whole stack is returned
    (:func:`repro.kernels.ops.ssd_state_update`)."""
    from repro.sharding.rules import shard_act
    dims = ssm_dims(cfg, d_model)
    dtype = x.dtype
    B, L, d = x.shape
    z_all = shard_act(jnp.einsum("bld,dp->blp", x,
                                 params["in_proj"].astype(dtype)))
    z, xbc, dt_raw = _split_proj(z_all, dims)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) +
                         params["dt_bias"].astype(jnp.float32))  # (B, L, H)
    if valid_len is not None:
        dt = jnp.where(jnp.arange(L)[None, :, None] < valid_len[:, None,
                                                                None],
                       dt, 0.0)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))

    prev_conv = state["conv"] if state is not None else None
    xbc, new_conv = causal_conv(xbc, params["conv_w"], params["conv_b"],
                                prev_conv, valid_len=valid_len)
    xs = xbc[..., :dims.d_inner].reshape(B, L, dims.n_heads, dims.head_dim)
    b = xbc[..., dims.d_inner:dims.d_inner + dims.n_state]
    c = xbc[..., dims.d_inner + dims.n_state:]

    if layer is not None:
        from repro.kernels import ops
        assert state is not None and L == 1, "a stacked state steps one token"
        y, new_ssm = ops.ssd_state_update(state["ssm"], layer, xs[:, 0],
                                          dt[:, 0], a, b[:, 0], c[:, 0],
                                          live)
        y = y[:, None]
    elif state is not None and L == 1:
        y, new_ssm = ssd_step(state["ssm"], xs[:, 0], dt[:, 0], a,
                              b[:, 0], c[:, 0])
        y = y[:, None]
    else:
        init = state["ssm"] if state is not None else None
        chunk = min(cfg.ssm_chunk, L)
        while L % chunk != 0:
            chunk //= 2
        y, new_ssm = ssd_chunked(xs, dt, a, b, c, max(1, chunk), init)

    y = y + xs * params["d_skip"].astype(dtype)[None, None, :, None]
    y = y.reshape(B, L, dims.d_inner)

    # gated RMSNorm (mamba2 uses norm(y * silu(z)))
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    g = g * jax.lax.rsqrt(var + 1e-5) * params["norm_scale"].astype(jnp.float32)
    out = jnp.einsum("blp,pd->bld", g.astype(dtype),
                     params["out_proj"].astype(dtype))
    new_state = {"ssm": new_ssm, "conv": new_conv} if state is not None \
        else None
    return out, new_state


def init_ssm_state(cfg: ModelConfig, batch: int,
                   d_model: Optional[int] = None) -> dict:
    dims = ssm_dims(cfg, d_model)
    return {
        "ssm": jnp.zeros((batch, dims.n_heads, dims.head_dim, dims.n_state),
                         jnp.float32),
        "conv": jnp.zeros((batch, dims.d_conv - 1, dims.conv_dim),
                          dtype_from(cfg)),
    }


def dtype_from(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)
