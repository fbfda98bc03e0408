"""Pallas TPU kernel for the O(1) cache-hit decode step (paper Eq. 5).

One new query token attends over a *constant-size* KV buffer — the
compressed context (W_oh slots) or the generation window (W_og slots).
Because TConstFormer bounds both, the ENTIRE working set of a decode step
fits VMEM by construction: q (G x D), K/V (S x D) with S = W_oh <= 512.
This kernel is the TPU restatement of the paper's core claim — the decode
step never touches an O(N) buffer, so it cannot be HBM-bandwidth bound in
sequence length.

Grid: (B,) — fully parallel; no sequential dimension, no scratch.  One
program holds every KV head of its slot (a block whose last two dims are
the whole (KV, D), which Mosaic tiles for any head count) and fuses the
QK^T contraction, masked softmax and PV contraction per head; the slot's
``valid_len`` rides in SMEM as a scalar-prefetch operand.

Layout-native extensions (DecodeAPI v3, "KVView"):

* **int8 KV** — when ``k_scale``/``v_scale`` are given, ``k``/``v`` are
  int8 with per-vector float32 scales and the dequantisation is FUSED
  into the QK / PV loops: the kernel reads 1 byte per element from HBM
  and multiplies by the scale inside VMEM, so the quantized layout's 4x
  byte saving is realised on the hot path instead of being paid back by
  a dense dequantised materialisation.
* **sliding window** — positions ``<= valid_len - 1`` but within the last
  ``window`` slots are attended (the dense-LM per-layer local-attention
  pattern), matching ``layers.attention.decode_attend``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _decode_kernel(vl_ref, q_ref, k_ref, v_ref, *rest, softcap: float,
                   window: int, quant: bool, scale: Optional[float]):
    if quant:
        ks_ref, vs_ref, o_ref = rest
    else:
        (o_ref,) = rest
    vl = vl_ref[pl.program_id(0)]                      # scalar int32
    # one program holds every KV head of its slot: the block's last two
    # dims are the whole (KV, D), the only form Mosaic tiles for any KV
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h].astype(jnp.float32)            # (G, D)
        k = k_ref[0, :, h, :].astype(jnp.float32)      # (S, D)
        v = v_ref[0, :, h, :].astype(jnp.float32)      # (S, D)
        if quant:
            k = k * ks_ref[0, :, h:h + 1]              # (S, 1) scales
            v = v * vs_ref[0, :, h:h + 1]

        sc = q.shape[-1] ** -0.5 if scale is None else scale
        s = jax.lax.dot_general(q * sc, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, S)
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        slot = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = slot < vl
        if window > 0:
            mask = jnp.logical_and(mask, slot >= vl - window)
        s = jnp.where(mask, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        p = jnp.where(mask, p, 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        o_ref[0, h] = (o / (l + 1e-30)).astype(o_ref.dtype)


def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            valid_len: jax.Array, *, softcap: float = 0.0,
                            window: int = 0,
                            k_scale: Optional[jax.Array] = None,
                            v_scale: Optional[jax.Array] = None,
                            scale: Optional[float] = None,
                            interpret: bool = False) -> jax.Array:
    """q: (B, H, D) one token per sequence; k/v: (B, S, KV, D);
    valid_len: (B,) — slots [0, valid_len) attended (``window`` > 0
    additionally limits attention to the last ``window`` of them).
    ``scale`` multiplies the scores (None: ``D ** -0.5``).
    int8 KV: pass ``k_scale``/``v_scale`` (B, S, KV, 1) float32 and int8
    ``k``/``v`` — dequant is fused in-kernel.  Returns (B, H, D)."""
    B, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, D)
    vl = valid_len.reshape(B).astype(jnp.int32)
    quant = k_scale is not None

    kernel = functools.partial(_decode_kernel, softcap=softcap,
                               window=window, quant=quant, scale=scale)
    # index maps receive (b, valid_len_ref): valid_len rides in SMEM
    in_specs = [
        pl.BlockSpec((1, KV, G, D), lambda b, vl: (b, 0, 0, 0)),   # q
        pl.BlockSpec((1, S, KV, D), lambda b, vl: (b, 0, 0, 0)),   # k
        pl.BlockSpec((1, S, KV, D), lambda b, vl: (b, 0, 0, 0)),   # v
    ]
    args = [qg, k, v]
    if quant:
        # (B, S, KV, 1) -> (B, S, KV): the trailing 1 would pad to a
        # whole 128-lane tile per vector
        in_specs += [
            pl.BlockSpec((1, S, KV), lambda b, vl: (b, 0, 0)),     # kscale
            pl.BlockSpec((1, S, KV), lambda b, vl: (b, 0, 0)),     # vscale
        ]
        args += [k_scale.reshape(B, S, KV).astype(jnp.float32),
                 v_scale.reshape(B, S, KV).astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, G, D), lambda b, vl: (b, 0, 0, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), jnp.float32 if quant
                                       else q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="tconst_decode_attention",
    )(vl, *args)
    return out.reshape(B, H, D).astype(q.dtype)
