"""Plain float32 building blocks of the reference models.

Written from the published descriptions (RMSNorm, rotary embedding on
the two halves of each head, grouped-query softmax attention, SwiGLU);
nothing here imports the program.  Every matrix product goes through
``mm``, so the control (the same reference at a lower precision) is the
reference with ``quant`` set: operands rounded to float8 (e4m3) with one
scale per weight tensor and one per activation row, as an fp8 serving
path would hold them.  Attention scores and softmax stay in float32.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def fp8(x: jax.Array, axis: Optional[int]) -> jax.Array:
    """Round to float8 e4m3 with a scale over ``axis`` (None: the whole
    tensor), and back to float32."""
    amax = jnp.max(jnp.abs(x)) if axis is None else \
        jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def mm(x: jax.Array, w: jax.Array, quant: bool) -> jax.Array:
    """``x (..., d) @ w (d, ...)`` in float32; ``quant``: the fp8 control."""
    w = w.reshape(w.shape[0], -1)
    if quant:
        x, w = fp8(x, -1), fp8(w, None)
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    return y


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding of ``x (L, heads, hd)`` at integer ``pos (L,)``,
    rotating the first half of each head against the second."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              allowed: jax.Array) -> jax.Array:
    """Softmax attention, ``q (Lq, H, hd)``, ``k, v (Lk, KV, hd)``,
    ``allowed (Lq, Lk)``.  Query heads are grouped onto KV heads in
    order.  A query row with no allowed key gives a zero output."""
    Lq, H, hd = q.shape
    KV = k.shape[1]
    qg = q.reshape(Lq, KV, H // KV, hd)
    s = jnp.einsum("qkgd,skd->kgqs", qg, k) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(allowed[None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(allowed[None, None], jnp.exp(s - jnp.where(
        jnp.isfinite(m), m, 0.0)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("kgqs,skd->qkgd", p, v)
    return o.reshape(Lq, H, hd)


def swiglu(x: jax.Array, ffn: dict, quant: bool) -> jax.Array:
    g = mm(x, ffn["w_gate"], quant)
    u = mm(x, ffn["w_up"], quant)
    return mm(jax.nn.silu(g) * u, ffn["w_down"], quant)


def project(x: jax.Array, w: jax.Array, quant: bool) -> jax.Array:
    """``x (L, d) @ w (d, heads, hd) -> (L, heads, hd)``."""
    return mm(x, w, quant).reshape(x.shape[0], w.shape[1], w.shape[2])


def unproject(o: jax.Array, w: jax.Array, quant: bool) -> jax.Array:
    """``o (L, heads, hd) @ w (heads, hd, d) -> (L, d)``."""
    return mm(o.reshape(o.shape[0], -1), w.reshape(-1, w.shape[-1]), quant)


def normal_weights(key: jax.Array, shapes: dict) -> dict:
    """Draw a nested dict of float32 tensors.  ``shapes`` leaves are
    ``("proj", shape, fan_in)`` (normal / sqrt(fan_in)), ``("embed",
    shape)`` (normal x 0.02) or ``("norm", shape)`` (1 + 0.1 normal)."""
    leaves, tree = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and
        isinstance(x[0], str))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, leaf in zip(keys, leaves):
        kind, shape = leaf[0], leaf[1]
        z = jax.random.normal(k, shape, jnp.float32)
        if kind == "embed":
            out.append(z * 0.02)
        elif kind == "norm":
            out.append(1.0 + 0.1 * z)
        else:
            out.append(z / jnp.sqrt(jnp.float32(leaf[2])))
    return jax.tree_util.tree_unflatten(tree, out)
