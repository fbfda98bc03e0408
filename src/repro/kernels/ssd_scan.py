"""Pallas TPU kernel for the Mamba-2 SSD intra-chunk block.

The SSD decomposition [arXiv:2405.21060] splits the selective-scan into
(i) intra-chunk dense work — decay-masked (C B^T) score matmuls, ideal for
the MXU — and (ii) a cheap inter-chunk recurrence over per-chunk states.
This kernel computes (i): for one (batch, head, chunk) it fuses the
cumulative log-decay, the masked score matrix, the intra-chunk output and
the chunk-final state, entirely in VMEM (Q x max(P, N) working set).

The inter-chunk recurrence (a length-``nc`` ``jax.lax.scan`` over
(H, P, N) states) and the carried-state correction stay in XLA — they are
O(L/Q) and bandwidth-trivial.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_chunk_kernel(xdt_ref, da_ref, b_ref, c_ref, y_ref, st_ref):
    xdt = xdt_ref[0, 0, 0].astype(jnp.float32)        # (Q, P)
    da = da_ref[0, 0, 0].astype(jnp.float32)          # (Q,)
    b = b_ref[0, 0].astype(jnp.float32)               # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)               # (Q, N)
    Q = xdt.shape[0]

    cs = jnp.cumsum(da)                               # (Q,)
    diff = cs[:, None] - cs[None, :]                  # (Q, Q)
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tri = col <= row
    decay = jnp.where(tri, jnp.exp(diff), 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(scores * decay, xdt,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q, P)
    decay_end = jnp.exp(cs[-1] - cs)                  # (Q,)
    state = jax.lax.dot_general(xdt, b * decay_end[:, None],
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (P, N)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    st_ref[0, 0, 0] = state.astype(st_ref.dtype)


def ssd_intra_chunk_pallas(xdt: jax.Array, da: jax.Array, b: jax.Array,
                           c: jax.Array, *, interpret: bool = False
                           ) -> Tuple[jax.Array, jax.Array]:
    """xdt: (B, H, nc, Q, P) dt-scaled inputs; da: (B, H, nc, Q) log-decays;
    b, c: (B, nc, Q, N) (single group, shared over heads).
    Returns (y_intra (B, H, nc, Q, P), states (B, H, nc, P, N))."""
    B, H, nc, Q, P = xdt.shape
    N = b.shape[-1]
    y, st = pl.pallas_call(
        _ssd_chunk_kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda i, h, n: (i, h, n, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda i, h, n: (i, h, n, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda i, h, n: (i, n, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda i, h, n: (i, n, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda i, h, n: (i, h, n, 0, 0)),
            pl.BlockSpec((1, 1, 1, P, N), lambda i, h, n: (i, h, n, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, nc, P, N), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="ssd_intra_chunk",
    )(xdt, da, b, c)
    return y, st


def ssd_scan_pallas(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                    c: jax.Array, chunk: int,
                    init_state: Optional[jax.Array] = None,
                    interpret: bool = False
                    ) -> Tuple[jax.Array, jax.Array]:
    """Full SSD scan with the Pallas intra-chunk kernel; drop-in equivalent
    of :func:`repro.layers.ssm.ssd_chunked` (same signature/semantics)."""
    Bt, L, H, P = x.shape
    N = b.shape[-1]
    assert L % chunk == 0
    nc = L // chunk
    f32 = jnp.float32

    dtf = dt.astype(f32)
    da = (dtf * a.astype(f32)[None, None, :]).reshape(Bt, nc, chunk, H)
    da = jnp.moveaxis(da, -1, 1)                       # (Bt, H, nc, Q)
    xdt = (x.astype(f32) * dtf[..., None]).reshape(Bt, nc, chunk, H, P)
    xdt = jnp.moveaxis(xdt, 3, 1)                      # (Bt, H, nc, Q, P)
    bc = b.astype(f32).reshape(Bt, nc, chunk, N)
    cc = c.astype(f32).reshape(Bt, nc, chunk, N)

    y_intra, states = ssd_intra_chunk_pallas(xdt, da, bc, cc,
                                             interpret=interpret)

    # inter-chunk recurrence (XLA)
    chunk_decay = jnp.exp(jnp.sum(da, axis=-1))        # (Bt, H, nc)
    s0 = (init_state.astype(f32) if init_state is not None
          else jnp.zeros((Bt, H, P, N), f32))
    final, prev = jax.lax.scan(
        lambda cry, i: (cry * i[1][..., None, None] + i[0], cry),
        s0, (jnp.moveaxis(states, 2, 0), jnp.moveaxis(chunk_decay, 2, 0)))
    prev = jnp.moveaxis(prev, 0, 2)                    # (Bt, H, nc, P, N)
    decay_from_start = jnp.exp(jnp.cumsum(da, axis=-1))
    y_inter = jnp.einsum("bnlm,bhnl,bhnpm->bhnlp",
                         cc, decay_from_start, prev)
    y = (y_intra + y_inter)                            # (Bt, H, nc, Q, P)
    y = jnp.moveaxis(y, 1, 3).reshape(Bt, L, H, P)
    return y.astype(x.dtype), final
