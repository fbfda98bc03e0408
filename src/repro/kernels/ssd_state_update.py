"""Pallas TPU kernel: one Mamba-2 decode step, in place in the state stack.

The decode step's recurrent state is carried as one stacked buffer per
layer kind, ``(layers, B, H, P, N)`` float32, through the layer loop.
Slicing layer ``i`` out (``dynamic_index``), stepping it and writing it
back (``dynamic_update_index``) moves the layer's state three times:
the slice is materialised because the step, the per-row freeze and the
read-out all consume it, and the write-back is a second whole pass.
This kernel reads each (row, all heads) tile of ``stack[i]`` once and
writes it once, in place:

    new = s * dec + xdt (x) b          (per row and head: (P, N))
    y   = sum_n new * c                (P,)
    stack[i] <- live ? new : s         (a frozen row goes back bit-identical)

The layer index is a scalar-prefetch operand, so the block index maps
address ``stack[i]`` directly, as the paged decode kernel addresses its
pages through the page table; the stack is aliased input -> output.
Everything stays float32, the state's dtype; the read-out is a float32
lane reduction.

The caller (``repro.kernels.ops.ssd_state_update``) computes ``dec =
exp(dt * a)`` and ``xdt = x * dt`` as ``repro.layers.ssm.ssd_step`` does.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes of state a grid step moves each way: on a v5e, granite's step
# (2 MiB a slot, 96 slots) took 1.353 / 1.229 / 1.184 ms a layer at 2 /
# 4 / 8 MiB tiles
TILE_BYTES = 8 << 20
MAX_ROWS = 8


def block_rows(batch: int, row_bytes: int) -> int:
    """Rows of the state per grid step: the largest divisor of ``batch``
    whose tile stays within ``TILE_BYTES`` (at least one row)."""
    cap = max(1, min(MAX_ROWS, TILE_BYTES // row_bytes))
    return max(r for r in range(1, cap + 1) if batch % r == 0)


def _kernel(i_ref, live_ref, s_ref, dec_ref, xdt_ref, b_ref, c_ref,
            y_ref, o_ref, *, rows: int, heads: int):
    del i_ref                                   # used by the index maps
    g = pl.program_id(0)
    lane = jax.lax.broadcasted_iota(jnp.int32, y_ref.shape[1:], 1)  # (P, H)
    for r in range(rows):
        brow = b_ref[r]                         # (1, N)
        crow = c_ref[r]                         # (1, N)
        ys = jnp.zeros(y_ref.shape[1:], jnp.float32)
        for h in range(heads):
            s = s_ref[0, r, h]                  # (P, N)
            new = s * dec_ref[r, :, h:h + 1] + xdt_ref[r, :, h:h + 1] * brow
            o_ref[0, r, h] = new
            yh = jnp.sum(new * crow, axis=-1, keepdims=True)    # (P, 1)
            ys = jnp.where(lane == h, yh, ys)
        y_ref[r] = ys

        @pl.when(live_ref[g * rows + r] == 0)
        def _freeze():
            o_ref[0, r] = s_ref[0, r]


def ssd_state_update_pallas(
        stack: jax.Array, i: jax.Array, dec: jax.Array, xdt: jax.Array,
        b: jax.Array, c: jax.Array, live: Optional[jax.Array] = None, *,
        interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """stack: (L, B, H, P, N) float32; i: scalar layer index (traced);
    dec: (B, H) per-head decay; xdt: (B, H, P) dt-scaled input; b, c:
    (B, N); live: (B,) bool or None (every row steps).  Returns
    (y (B, H, P) float32, stack with layer i stepped for live rows)."""
    L, B, H, P, N = stack.shape
    f32 = jnp.float32
    row_bytes = H * P * N * stack.dtype.itemsize
    rows = block_rows(B, row_bytes)
    if live is None:
        live = jnp.ones((B,), bool)
    idx = jnp.asarray(i, jnp.int32).reshape(1)
    # per-head vectors with P on sublanes (the state's (P, N) tile) and
    # heads on lanes, so a head's column is a static lane slice
    dec_t = dec.astype(f32).reshape(B, 1, H)
    xdt_t = jnp.swapaxes(xdt.astype(f32), 1, 2)                 # (B, P, H)
    b3 = b.astype(f32).reshape(B, 1, N)
    c3 = c.astype(f32).reshape(B, 1, N)

    state_spec = pl.BlockSpec((1, rows, H, P, N),
                              lambda g, i, lv: (i[0], g, 0, 0, 0))
    row_spec = lambda d1, d2: pl.BlockSpec((rows, d1, d2),
                                           lambda g, i, lv: (g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B // rows,),
        in_specs=[state_spec, row_spec(1, H), row_spec(P, H),
                  row_spec(1, N), row_spec(1, N)],
        out_specs=[row_spec(P, H), state_spec],
    )
    y_t, stack = pl.pallas_call(
        functools.partial(_kernel, rows=rows, heads=H),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, P, H), f32),
                   jax.ShapeDtypeStruct(stack.shape, stack.dtype)],
        # operands count the two scalar-prefetch arguments first
        input_output_aliases={2: 1},
        # the state tile in and out, each double-buffered, and room for
        # the small blocks and temporaries
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=4 * rows * row_bytes + (4 << 20)),
        interpret=interpret,
        name="ssd_state_update",
    )(idx, live.astype(jnp.int32), stack, dec_t, xdt_t, b3, c3)
    return jnp.swapaxes(y_t, 1, 2), stack
