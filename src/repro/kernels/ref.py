"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the mathematically transparent O(L^2)-memory reference the
kernels are asserted against (``tests/test_kernels.py`` sweeps shapes and
dtypes).  They are deliberately naive — correctness over efficiency.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -2.3819763e38
INVALID_POS = jnp.iinfo(jnp.int32).max // 2


def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                  q_pos: jax.Array, k_pos: jax.Array,
                  window: int = 0, causal: bool = True,
                  softcap: float = 0.0,
                  scale: Optional[float] = None) -> jax.Array:
    """Naive GQA attention.  q (B, Lq, H, D); k/v (B, Lk, KV, D);
    q_pos (B, Lq); k_pos (B, Lk) with INVALID_POS marking dead slots;
    ``scale`` multiplies the scores (None: ``D ** -0.5``)."""
    B, Lq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = D ** -0.5 if scale is None else scale
    qg = q.reshape(B, Lq, KV, G, D).astype(jnp.float32)
    s = jnp.einsum("blkgd,bskd->bklgs", qg * scale, k.astype(jnp.float32))
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    mask = k_pos[:, None, :] != INVALID_POS
    if causal:
        mask = jnp.logical_and(mask, k_pos[:, None, :] <= q_pos[:, :, None])
    if window > 0:
        mask = jnp.logical_and(mask,
                               k_pos[:, None, :] > q_pos[:, :, None] - window)
    s = jnp.where(mask[:, None, :, None, :], s, NEG_INF)
    mx = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - mx) * mask[:, None, :, None, :]
    p = e / (jnp.sum(e, axis=-1, keepdims=True) + 1e-30)
    o = jnp.einsum("bklgs,bskd->blkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Lq, H, D).astype(q.dtype)


def decode_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid_len: jax.Array, softcap: float = 0.0,
                     scale: Optional[float] = None) -> jax.Array:
    """Single-token decode oracle.  q (B, H, D); k/v (B, S, KV, D);
    valid_len (B,): slots [0, valid_len) are attended; ``scale``
    multiplies the scores (None: ``D ** -0.5``)."""
    B, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = D ** -0.5 if scale is None else scale
    qg = q.reshape(B, KV, G, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg * scale, k.astype(jnp.float32))
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    valid = jnp.arange(S)[None] < valid_len[:, None]          # (B, S)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    mx = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - mx) * valid[:, None, None, :]
    p = e / (jnp.sum(e, axis=-1, keepdims=True) + 1e-30)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return o.reshape(B, H, D).astype(q.dtype)


def ssd_chunk_reference(x: jax.Array, da: jax.Array, b: jax.Array,
                        c: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Intra-chunk SSD oracle for ONE chunk.

    x: (Q, P) inputs already scaled by dt; da: (Q,) log-decays;
    b, c: (Q, N).  Returns (y_intra (Q, P), chunk_state (P, N)).
    """
    Q = x.shape[0]
    cs = jnp.cumsum(da)
    diff = cs[:, None] - cs[None, :]                          # (Q, Q)
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.where(tri, jnp.exp(diff), 0.0)
    scores = jnp.einsum("ln,sn->ls", c.astype(jnp.float32),
                        b.astype(jnp.float32))
    y = jnp.einsum("ls,ls,sp->lp", scores, decay, x.astype(jnp.float32))
    decay_to_end = jnp.exp(cs[-1] - cs)                       # (Q,)
    state = jnp.einsum("s,sn,sp->pn", decay_to_end,
                       b.astype(jnp.float32), x.astype(jnp.float32))
    return y.astype(x.dtype), state
