"""Layout-native paged decode attention: the kernel walks the page table.

vLLM-style paged KV ("Attention Once Is All You Need" line of work): the
physical cache is a shared pool of fixed-size pages plus a per-slot int32
page table, and the decode kernel consumes that representation DIRECTLY —
one page = one grid block, with the page table as a scalar-prefetch
operand so each block's DMA source address is computed from
``page_table[b, j]`` before the block body runs
(``pltpu.PrefetchScalarGridSpec``).  Nothing ever materialises the dense
``slots x max_len`` logical view; a decode step touches exactly the pages
the slot owns.

Two implementations, one contract (see ``repro.kernels.ops.paged_decode``):

* :func:`paged_decode_attention_pallas` — TPU kernel.  Grid
  ``(B, pages_per_slot)`` with the page dimension sequential
  ("arbitrary"): one page block carries every KV head, and per
  (slot, kv-head) the kernel runs an online-softmax accumulation over
  the slot's pages in VMEM scratch (running max / denominator /
  output).  int8 pools fuse the per-vector dequantisation
  into the QK and PV contractions (1 byte/element off HBM).
* :func:`paged_decode_attention_xla` — the CPU / interpret fallback: a
  ``lax.scan`` over pages, each iteration gathering ONE page per slot
  (``(B, page, KV, D)`` working set).  It uses a two-pass exact-max
  softmax so its output matches the dense oracle to float-associativity
  noise — the parity suite compares both against ``DecodeState.merged``.

Both accept logical ``valid_len`` (slots ``[0, valid_len)`` attended) and
an optional sliding ``window`` (the dense-LM local-attention layers), so
they are drop-in for every paged field: the dense-LM ``k/v``, the enc-dec
decoder KV and TLinFormer's per-block history KV.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


# ---------------------------------------------------------------------------
# Pallas TPU kernel: one page = one block, table walked via scalar prefetch
# ---------------------------------------------------------------------------


def _paged_kernel(pt_ref, vl_ref, win_ref, q_ref, k_ref, v_ref, *rest,
                  page: int, softcap: float, quant: bool,
                  scale: Optional[float]):
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    vl = vl_ref[b]
    win = win_ref[0]
    weff = jnp.where(win > 0, win, jnp.int32(2 ** 30))
    # one page block holds every KV head (its last two dims are the whole
    # (KV, D)); each head runs its own online softmax
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h].astype(jnp.float32)             # (G, D)
        k = k_ref[0, :, h, :].astype(jnp.float32)       # (page, D)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        if quant:
            k = k * ks_ref[0, :, h:h + 1]               # (page, 1) scales
            v = v * vs_ref[0, :, h:h + 1]

        sc = q.shape[-1] ** -0.5 if scale is None else scale
        s = jax.lax.dot_general(q * sc, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, page)
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        slot = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.logical_and(slot < vl, slot >= vl - weff)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[h]                                # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        m_ref[h] = m_new
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    (l_ref[...] + 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_pallas(
        q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
        page_table: jax.Array, valid_len: jax.Array, *,
        softcap: float = 0.0, window: "int | jax.Array" = 0,
        k_scale: Optional[jax.Array] = None,
        v_scale: Optional[jax.Array] = None,
        scale: Optional[float] = None,
        interpret: bool = False) -> jax.Array:
    """q: (B, H, D) one token per slot; pool_k/pool_v: (pool+1, page, KV, D)
    shared page pools (last page = trash, masked off by ``valid_len``);
    page_table: (B, pages_per_slot) int32; valid_len: (B,) — logical slots
    [0, valid_len) attended.  int8 pools: pass (pool+1, page, KV, 1) f32
    ``k_scale``/``v_scale`` (dequant fused in-kernel).  ``scale``
    multiplies the scores (None: ``D ** -0.5``).  Returns (B, H, D)."""
    B, H, D = q.shape
    n_pages, page, KV = pool_k.shape[0], pool_k.shape[1], pool_k.shape[2]
    pps = page_table.shape[1]
    G = H // KV
    qg = q.reshape(B, KV, G, D)
    vl = valid_len.astype(jnp.int32)
    win = jnp.asarray(window, jnp.int32).reshape(1)
    quant = k_scale is not None

    kernel = functools.partial(_paged_kernel, page=page, softcap=softcap,
                               quant=quant, scale=scale)
    # index maps receive (b, j, *scalar_prefetch_refs): the page-table
    # ref picks the physical page for grid step (b, j) — the "in-kernel
    # page-table walk".
    in_specs = [
        pl.BlockSpec((1, KV, G, D), lambda b, j, pt, vl, w: (b, 0, 0, 0)),
        pl.BlockSpec((1, page, KV, D),
                     lambda b, j, pt, vl, w: (pt[b, j], 0, 0, 0)),
        pl.BlockSpec((1, page, KV, D),
                     lambda b, j, pt, vl, w: (pt[b, j], 0, 0, 0)),
    ]
    args = [qg, pool_k, pool_v]
    if quant:
        # (pool+1, page, KV, 1) -> (pool+1, page, KV): the trailing 1
        # would pad to a whole 128-lane tile per vector
        in_specs += [
            pl.BlockSpec((1, page, KV),
                         lambda b, j, pt, vl, w: (pt[b, j], 0, 0)),
            pl.BlockSpec((1, page, KV),
                         lambda b, j, pt, vl, w: (pt[b, j], 0, 0)),
        ]
        args += [k_scale.reshape(n_pages, page, KV).astype(jnp.float32),
                 v_scale.reshape(n_pages, page, KV).astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, pps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, G, D),
                               lambda b, j, pt, vl, w: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),     # running max
            pltpu.VMEM((KV, G, 1), jnp.float32),     # running denominator
            pltpu.VMEM((KV, G, D), jnp.float32),     # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(page_table.astype(jnp.int32), vl, win, *args)
    return out.reshape(B, H, D)


# ---------------------------------------------------------------------------
# XLA fallback: scan over pages, (B, page, KV, D) working set, exact max
# ---------------------------------------------------------------------------


def paged_decode_attention_xla(
        q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
        page_table: jax.Array, valid_len: jax.Array, *,
        softcap: float = 0.0, window: "int | jax.Array" = 0,
        k_scale: Optional[jax.Array] = None,
        v_scale: Optional[jax.Array] = None,
        scale: Optional[float] = None) -> jax.Array:
    """Same contract as the Pallas kernel, in plain XLA: a page-at-a-time
    ``lax.scan`` whose largest intermediate is one (B, page, KV, D) gather
    — never the dense (B, max_len, KV, D) logical view.  Two passes with
    an exact global max (max is order-independent in fp) keep the output
    within float-associativity noise of the dense-softmax oracle."""
    B, H, D = q.shape
    page, KV = pool_k.shape[1], pool_k.shape[2]
    pps = page_table.shape[1]
    G = H // KV
    qg = q.reshape(B, KV, G, D).astype(jnp.float32) * (
        D ** -0.5 if scale is None else scale)
    vl = valid_len.astype(jnp.int32)
    win = jnp.asarray(window, jnp.int32)
    weff = jnp.where(win > 0, win, jnp.int32(2 ** 30))
    ptT = jnp.moveaxis(page_table.astype(jnp.int32), 1, 0)   # (pps, B)
    page_ids = jnp.arange(pps, dtype=jnp.int32)

    def logits(j, ptj):
        k = jnp.take(pool_k, ptj, axis=0)                # (B, page, KV, D)
        if k_scale is not None:
            k = k.astype(jnp.float32) * jnp.take(k_scale, ptj, axis=0)
        s = jnp.einsum("bkgd,bpkd->bkgp", qg, k.astype(jnp.float32))
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        slot = j * page + jnp.arange(page, dtype=jnp.int32)
        mask = jnp.logical_and(slot[None] < vl[:, None],
                               slot[None] >= (vl - weff)[:, None])  # (B, p)
        return jnp.where(mask[:, None, None, :], s, NEG_INF), mask

    def max_body(m, xs):
        s, _ = logits(*xs)
        return jnp.maximum(m, jnp.max(s, axis=-1)), None

    m, _ = jax.lax.scan(max_body, jnp.full((B, KV, G), NEG_INF, jnp.float32),
                        (page_ids, ptT))

    def acc_body(carry, xs):
        l, acc = carry
        j, ptj = xs
        s, mask = logits(j, ptj)
        e = jnp.exp(s - m[..., None]) * mask[:, None, None, :]
        v = jnp.take(pool_v, ptj, axis=0)
        if v_scale is not None:
            v = v.astype(jnp.float32) * jnp.take(v_scale, ptj, axis=0)
        acc = acc + jnp.einsum("bkgp,bpkd->bkgd", e, v.astype(jnp.float32))
        return (l + jnp.sum(e, axis=-1), acc), None

    (l, acc), _ = jax.lax.scan(
        acc_body,
        (jnp.zeros((B, KV, G), jnp.float32),
         jnp.zeros((B, KV, G, D), jnp.float32)),
        (page_ids, ptT))
    o = acc / (l[..., None] + 1e-30)
    return o.reshape(B, H, D).astype(q.dtype)
