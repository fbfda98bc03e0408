"""Pallas TPU flash-attention kernel (forward).

TPU adaptation of the paper's cache-miss hot spot — the context-compression
cross-attention (W_oh queries over the full history) — and of ordinary
causal/sliding self-attention.  The GPU-oriented description in the paper
("memory copy bound torch.cat decode") becomes, on TPU, an HBM->VMEM
streaming problem: K/V are streamed through VMEM in MXU-aligned
``block_k`` tiles while an online-softmax accumulator lives in VMEM
scratch across the sequential ``nk`` grid dimension.

Grid: ``(BH, nq, nk)`` — (batch x heads) and query blocks are parallel;
the key-block dimension is sequential ("arbitrary") and owns the scratch
accumulator.  Block shapes are multiples of 128 in the lane dimension so
the ``s = q @ k^T`` and ``p @ v`` contractions map onto the 128x128 MXU.

The backward pass reuses the XLA blocked implementation
(``repro.kernels.xla_flash``) via ``jax.custom_vjp`` in ``ops.py`` — on
real TPUs one would add the dual Pallas bwd kernel; the fwd kernel is the
inference-critical path.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38
INVALID_POS = jnp.iinfo(jnp.int32).max // 2


def _flash_kernel(qp_ref, kp_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, causal: bool, window: int,
                  softcap: float, nk: int, scale: Optional[float]):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                   # (qb, D)
    k = k_ref[0].astype(jnp.float32)                   # (kb, D)
    v = v_ref[0].astype(jnp.float32)                   # (kb, D)
    qp = qp_ref[0]                                     # (qb, 1)
    kp = kp_ref[0]                                     # (1, kb)

    sc = q.shape[-1] ** -0.5 if scale is None else scale
    s = jax.lax.dot_general(q * sc, k,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (qb, kb)
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap

    mask = jnp.broadcast_to(kp != INVALID_POS, s.shape)
    if causal:
        mask = jnp.logical_and(mask, kp <= qp)
    if window > 0:
        mask = jnp.logical_and(mask, kp > qp - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / (l_scr[...] + 1e-30)).astype(o_ref.dtype)


def flash_attention_fwd_pallas(
        q: jax.Array, k: jax.Array, v: jax.Array,
        q_pos: jax.Array, k_pos: jax.Array, *,
        causal: bool = True, window: int = 0, softcap: float = 0.0,
        block_q: int = 256, block_k: int = 512,
        scale: Optional[float] = None,
        interpret: bool = False) -> jax.Array:
    """q: (B, Lq, H, D); k/v: (B, Lk, KV, D); positions (B, Lq)/(B, Lk);
    ``scale`` multiplies the scores (None: ``D ** -0.5``).

    Static ``window`` (the Pallas kernel specialises per layer type; the
    dynamic-window path is served by ``xla_flash``).  Returns (B, Lq, H, D).
    """
    B, Lq, H, D = q.shape
    Lk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qb = min(block_q, Lq)
    kb = min(block_k, Lk)
    qb = qb if Lq % qb == 0 else math.gcd(qb, Lq)
    kb = kb if Lk % kb == 0 else math.gcd(kb, Lk)
    assert Lq % qb == 0 and Lk % kb == 0, (Lq, qb, Lk, kb)
    nq, nk = Lq // qb, Lk // kb

    # flatten (B, H) and broadcast K/V over the GQA group
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Lq, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, Lk, D)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, Lk, D)
    # positions as (BH, Lq, 1) / (BH, 1, Lk): blocks (1, qb, 1) and
    # (1, 1, kb) keep legal last-two dims and land query positions on
    # sublanes, key positions on lanes — the (qb, kb) mask's own layout
    qpf = jnp.repeat(q_pos, H, axis=0)[:, :, None]
    kpf = jnp.repeat(k_pos, H, axis=0)[:, None, :]

    kernel = functools.partial(_flash_kernel, causal=causal, window=window,
                               softcap=softcap, nk=nk, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, qb, 1), lambda b, i, j: (b, i, 0)),  # q_pos
            pl.BlockSpec((1, 1, kb), lambda b, i, j: (b, 0, j)),  # k_pos
            pl.BlockSpec((1, qb, D), lambda b, i, j: (b, i, 0)),  # q
            pl.BlockSpec((1, kb, D), lambda b, i, j: (b, j, 0)),  # k
            pl.BlockSpec((1, kb, D), lambda b, i, j: (b, j, 0)),  # v
        ],
        out_specs=pl.BlockSpec((1, qb, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Lq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb, 1), jnp.float32),      # running max
            pltpu.VMEM((qb, 1), jnp.float32),      # running denom
            pltpu.VMEM((qb, D), jnp.float32),      # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(qpf, kpf, qf, kf, vf)
    return out.reshape(B, H, Lq, D).transpose(0, 2, 1, 3)
