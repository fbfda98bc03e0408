"""Blocked (flash-style) attention in pure XLA with a custom VJP.

Why this exists: the assigned shapes reach 524,288 tokens; a naive
softmax(QK^T)V materialises an O(L_q x L_k) logits tensor, which neither
fits HBM nor passes the dry-run memory analysis.  This implementation
streams K/V in blocks with an online-softmax accumulator (forward) and
recomputes blocks in the backward pass (no O(L^2) residuals) — the same
algorithm the Pallas TPU kernel (`repro.kernels.flash_attention`) uses
with explicit VMEM tiles; this module is its shape-polymorphic oracle and
the path the CPU dry-run lowers.

Masking is positional: callers pass integer ``q_pos``/``k_pos`` arrays.
``causal`` masks ``k_pos > q_pos``; ``window > 0`` additionally masks
``k_pos <= q_pos - window`` (sliding-window attention); invalid K slots
are expressed by setting their ``k_pos`` to ``INVALID_POS`` (never
attended under causal masking).  Fully-masked query rows return zeros.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -2.3819763e38
INVALID_POS = jnp.iinfo(jnp.int32).max // 2


def _pad_to(x: jax.Array, mult: int, axis: int, value=0) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _mask_block(qp: jax.Array, kp: jax.Array, causal: bool,
                window: jax.Array) -> jax.Array:
    """window may be a traced int32 scalar; 0 disables the sliding window
    (so per-layer window patterns can ride through one lax.scan)."""
    m = kp[None, :] != INVALID_POS
    if causal:
        m = jnp.logical_and(m, kp[None, :] <= qp[:, None])
    weff = jnp.where(window > 0, window, jnp.int32(2**30))
    m = jnp.logical_and(m, kp[None, :] > qp[:, None] - weff)
    return m


# ---------------------------------------------------------------------------
# Single-(batched-)head forward / backward over flattened head-batch
# q: (N, Lq, D); k, v: (N, Lk, D); qp: (N, Lq); kp: (N, Lk)
# ---------------------------------------------------------------------------


def _block_mask(qpi, kpj, causal, window):
    """qpi (qb,) or (N, qb); kpj (kb,) or (N, kb) -> (qb, kb) or (N, qb, kb).

    SHARED positions (1-D) are the common case (training/prefill: every
    batch row has positions 0..L-1); keeping the mask head- and batch-free
    lets XLA hoist a few MB instead of tens of GB (EXPERIMENTS.md §Perf).
    """
    if qpi.ndim == 1:
        return _mask_block(qpi, kpj, causal, window)
    return jax.vmap(_mask_block, (0, 0, None, None))(qpi, kpj, causal,
                                                     window)


def _fwd(q, k, v, qp, kp, causal, window, softcap, qb, kb, scale):
    N, Lq, D = q.shape
    Lk = k.shape[1]
    scale = D ** -0.5 if scale is None else scale
    nq, nk = Lq // qb, Lk // kb
    f32 = jnp.float32
    shared = qp.ndim == 1

    qr = q.reshape(N, nq, qb, D)
    qpr = qp.reshape(nq, qb) if shared else qp.reshape(N, nq, qb)
    kr = k.reshape(N, nk, kb, D)
    vr = v.reshape(N, nk, kb, D)
    kpr = kp.reshape(nk, kb) if shared else kp.reshape(N, nk, kb)

    def q_block(carry, inp):
        qi, qpi = inp                 # (N, qb, D), (qb,)|(N, qb)

        def k_block(acc, kin):
            o, l, m = acc
            kj, vj, kpj = kin
            s = jnp.einsum("nqd,nkd->nqk", qi.astype(f32) * scale,
                           kj.astype(f32))
            if softcap > 0.0:
                s = jnp.tanh(s / softcap) * softcap
            mask = _block_mask(qpi, kpj, causal, window)
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(mask, p, 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            o = o * alpha[..., None] + jnp.einsum(
                "nqk,nkd->nqd", p, vj.astype(f32))
            return (o, l, m_new), None

        o0 = jnp.zeros((N, qb, D), f32)
        l0 = jnp.zeros((N, qb), f32)
        m0 = jnp.full((N, qb), NEG_INF, f32)
        (o, l, m), _ = jax.lax.scan(
            k_block, (o0, l0, m0),
            (jnp.moveaxis(kr, 1, 0), jnp.moveaxis(vr, 1, 0),
             kpr if shared else jnp.moveaxis(kpr, 1, 0)))
        o = o / (l[..., None] + 1e-30)
        lse = m + jnp.log(l + 1e-30)
        return carry, (o, lse)

    _, (o, lse) = jax.lax.scan(
        q_block, None,
        (jnp.moveaxis(qr, 1, 0), qpr if shared else jnp.moveaxis(qpr, 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(N, Lq, D)
    lse = jnp.moveaxis(lse, 0, 1).reshape(N, Lq)
    return o.astype(q.dtype), lse


def _bwd(q, k, v, qp, kp, o, lse, do, causal, window, softcap, qb, kb,
         scale):
    N, Lq, D = q.shape
    Lk = k.shape[1]
    scale = D ** -0.5 if scale is None else scale
    nq, nk = Lq // qb, Lk // kb
    f32 = jnp.float32
    shared = qp.ndim == 1

    qr = jnp.moveaxis(q.reshape(N, nq, qb, D), 1, 0)
    qpr = qp.reshape(nq, qb) if shared else \
        jnp.moveaxis(qp.reshape(N, nq, qb), 1, 0)
    dor = jnp.moveaxis(do.reshape(N, nq, qb, D), 1, 0).astype(f32)
    orr = jnp.moveaxis(o.reshape(N, nq, qb, D), 1, 0).astype(f32)
    lser = jnp.moveaxis(lse.reshape(N, nq, qb), 1, 0)
    delta = jnp.sum(dor * orr, axis=-1)                # (nq, N, qb)

    kr = jnp.moveaxis(k.reshape(N, nk, kb, D), 1, 0)
    vr = jnp.moveaxis(v.reshape(N, nk, kb, D), 1, 0)
    kpr = kp.reshape(nk, kb) if shared else \
        jnp.moveaxis(kp.reshape(N, nk, kb), 1, 0)

    def k_block(dq_full, kin):
        kj, vj, kpj = kin                              # (N, kb, D) …

        def q_block(acc, qin):
            dq_full, dkj, dvj = acc
            i, qi, qpi, doi, lsei, di = qin
            s = jnp.einsum("nqd,nkd->nqk", qi.astype(f32) * scale,
                           kj.astype(f32))
            if softcap > 0.0:
                t = jnp.tanh(s / softcap)
                s_capped = t * softcap
                dcap = 1.0 - t * t
            else:
                s_capped = s
                dcap = None
            mask = _block_mask(qpi, kpj, causal, window)
            p = jnp.exp(jnp.where(mask, s_capped, NEG_INF) -
                        lsei[..., None])
            p = jnp.where(mask, p, 0.0)
            dvj = dvj + jnp.einsum("nqk,nqd->nkd", p, doi)
            dp = jnp.einsum("nqd,nkd->nqk", doi, vj.astype(f32))
            ds = p * (dp - di[..., None])
            if dcap is not None:
                ds = ds * dcap
            dq_i = jnp.einsum("nqk,nkd->nqd", ds, kj.astype(f32)) * scale
            dkj = dkj + jnp.einsum("nqk,nqd->nkd", ds,
                                   qi.astype(f32)) * scale
            prev = jax.lax.dynamic_slice_in_dim(dq_full, i * qb, qb, axis=1)
            dq_full = jax.lax.dynamic_update_slice_in_dim(
                dq_full, prev + dq_i, i * qb, axis=1)
            return (dq_full, dkj, dvj), None

        dkj0 = jnp.zeros((N, kb, D), f32)
        dvj0 = jnp.zeros((N, kb, D), f32)
        (dq_full, dkj, dvj), _ = jax.lax.scan(
            q_block, (dq_full, dkj0, dvj0),
            (jnp.arange(nq), qr, qpr, dor, lser, delta))
        return dq_full, (dkj, dvj)

    dq0 = jnp.zeros((N, Lq, D), f32)
    dq, (dk, dv) = jax.lax.scan(k_block, dq0, (kr, vr, kpr))
    dk = jnp.moveaxis(dk, 0, 1).reshape(N, Lk, D)
    dv = jnp.moveaxis(dv, 0, 1).reshape(N, Lk, D)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Public multi-head GQA wrapper with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_pos: jax.Array, k_pos: jax.Array,
                    window: jax.Array | int = 0,
                    causal: bool = True,
                    softcap: float = 0.0, q_block: int = 512,
                    k_block: int = 512,
                    scale: float | None = None) -> jax.Array:
    """Memory-O(L·block) attention.

    q: (B, Lq, H, D); k, v: (B, Lk, KV, D) with H % KV == 0;
    q_pos: (B, Lq) int32; k_pos: (B, Lk) int32 (INVALID_POS = masked slot).
    ``window`` may be a traced int32 scalar (0 = no sliding window).
    ``scale`` multiplies the scores (None: ``D ** -0.5``).
    Returns (B, Lq, H, D).
    """
    o, _ = _flash_fwd_rule(q, k, v, q_pos, k_pos, window, causal, softcap,
                           q_block, k_block, scale)
    return o


def _gqa_flatten(q, k, v, q_pos, k_pos):
    B, Lq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Lq, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, -1, D)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, -1, D)
    if q_pos.ndim == 1:               # shared positions: keep mask tiny
        return qf, kf, vf, q_pos, k_pos
    qpf = jnp.repeat(q_pos, H, axis=0).reshape(B * H, Lq)
    kpf = jnp.repeat(k_pos, H, axis=0).reshape(B * H, -1)
    return qf, kf, vf, qpf, kpf


def _flash_fwd_rule(q, k, v, q_pos, k_pos, window, causal, softcap,
                    q_block, k_block, scale):
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    qb = min(q_block, Lq)
    kb = min(k_block, Lk)
    window = jnp.asarray(window, jnp.int32)
    qf, kf, vf, qpf, kpf = _gqa_flatten(q, k, v, q_pos, k_pos)
    # pad to block multiples; padded K slots get INVALID_POS
    qf = _pad_to(qf, qb, 1)
    qpf = _pad_to(qpf, qb, qpf.ndim - 1)
    kf = _pad_to(kf, kb, 1)
    vf = _pad_to(vf, kb, 1)
    kpf = _pad_to(kpf, kb, kpf.ndim - 1, value=INVALID_POS)
    of, lse = _fwd(qf, kf, vf, qpf, kpf, causal, window, softcap, qb, kb,
                   scale)
    o = of[:, :Lq].reshape(B, H, Lq, D).transpose(0, 2, 1, 3)
    return o, (q, k, v, q_pos, k_pos, window, o, lse[:, :Lq])


def _flash_bwd_rule(causal, softcap, q_block, k_block, scale, res, do):
    q, k, v, q_pos, k_pos, window, o, lse = res
    B, Lq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    Lk = k.shape[1]
    qb = min(q_block, Lq)
    kb = min(k_block, Lk)
    qf, kf, vf, qpf, kpf = _gqa_flatten(q, k, v, q_pos, k_pos)
    dof = do.transpose(0, 2, 1, 3).reshape(B * H, Lq, D)
    of = o.transpose(0, 2, 1, 3).reshape(B * H, Lq, D)
    qf = _pad_to(qf, qb, 1)
    qpf = _pad_to(qpf, qb, qpf.ndim - 1)
    dof = _pad_to(dof, qb, 1)
    of = _pad_to(of, qb, 1)
    lsef = _pad_to(lse, qb, 1)
    kf = _pad_to(kf, kb, 1)
    vf = _pad_to(vf, kb, 1)
    kpf = _pad_to(kpf, kb, kpf.ndim - 1, value=INVALID_POS)
    dqf, dkf, dvf = _bwd(qf, kf, vf, qpf, kpf, of, lsef, dof,
                         causal, window, softcap, qb, kb, scale)
    dq = dqf[:, :Lq].reshape(B, H, Lq, D).transpose(0, 2, 1, 3)
    dk = dkf[:, :Lk].reshape(B, KV, G, Lk, D).sum(axis=2)
    dk = dk.transpose(0, 2, 1, 3)
    dv = dvf[:, :Lk].reshape(B, KV, G, Lk, D).sum(axis=2)
    dv = dv.transpose(0, 2, 1, 3)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None, None)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)
