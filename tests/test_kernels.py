"""Per-kernel correctness: Pallas (interpret=True) and XLA-blocked
implementations swept over shapes/dtypes against the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as REF
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_fwd_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.kernels.xla_flash import INVALID_POS, flash_attention
from repro.layers.ssm import ssd_chunked

KEY = jax.random.PRNGKey(7)


def _qkv(B, Lq, Lk, H, KV, D, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Lq, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Lk, KV, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Lk, KV, D), jnp.float32).astype(dtype)
    return q, k, v


SHAPES = [
    # B, Lq, Lk, H, KV, D, causal, window, softcap
    (1, 64, 64, 4, 4, 32, True, 0, 0.0),
    (2, 64, 128, 8, 2, 64, True, 32, 0.0),
    (2, 128, 64, 4, 1, 32, False, 0, 0.0),
    (1, 128, 128, 8, 8, 128, True, 0, 20.0),
    (3, 96, 160, 6, 2, 32, True, 16, 0.0),   # non-pow2 everything
]


@pytest.mark.parametrize("B,Lq,Lk,H,KV,D,causal,win,cap", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_flash_vs_oracle(B, Lq, Lk, H, KV, D, causal, win, cap,
                                dtype):
    if Lq % 32 or Lk % 32:
        pytest.skip("pallas path requires block-divisible shapes")
    q, k, v = _qkv(B, Lq, Lk, H, KV, D, dtype)
    qp = jnp.broadcast_to(jnp.arange(Lk - Lq, Lk), (B, Lq)).astype(jnp.int32)
    kp = jnp.broadcast_to(jnp.arange(Lk), (B, Lk)).astype(jnp.int32)
    o = flash_attention_fwd_pallas(q, k, v, qp, kp, causal=causal,
                                   window=win, softcap=cap, block_q=32,
                                   block_k=32, interpret=True)
    o_ref = REF.mha_reference(q, k, v, qp, kp, window=win, causal=causal,
                              softcap=cap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), atol=tol)


@pytest.mark.parametrize("B,Lq,Lk,H,KV,D,causal,win,cap", SHAPES)
def test_xla_flash_vs_oracle(B, Lq, Lk, H, KV, D, causal, win, cap):
    q, k, v = _qkv(B, Lq, Lk, H, KV, D, jnp.float32)
    qp = jnp.broadcast_to(jnp.arange(Lk - Lq, Lk), (B, Lq)).astype(jnp.int32)
    kp = jnp.broadcast_to(jnp.arange(Lk), (B, Lk)).astype(jnp.int32)
    kp = kp.at[:, -3:].set(INVALID_POS)      # dead cache slots
    o = flash_attention(q, k, v, qp, kp, win, causal, cap, 32, 64)
    o_ref = REF.mha_reference(q, k, v, qp, kp, window=win, causal=causal,
                              softcap=cap)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)


def test_xla_flash_shared_positions_match_batched():
    B, L, H, KV, D = 2, 80, 4, 2, 32
    q, k, v = _qkv(B, L, L, H, KV, D, jnp.float32)
    p1 = jnp.arange(L, dtype=jnp.int32)
    pB = jnp.broadcast_to(p1, (B, L))
    o1 = flash_attention(q, k, v, p1, p1, 16, True, 0.0, 32, 32)
    oB = flash_attention(q, k, v, pB, pB, 16, True, 0.0, 32, 32)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(oB), atol=1e-6)


def test_xla_flash_grads_vs_oracle():
    B, L, H, KV, D = 2, 48, 4, 2, 16
    q, k, v = _qkv(B, L, L, H, KV, D, jnp.float32)
    pos = jnp.arange(L, dtype=jnp.int32)

    def f_flash(q, k, v):
        return jnp.sum(jnp.sin(
            flash_attention(q, k, v, pos, pos, 8, True, 4.0, 16, 16)))

    def f_ref(q, k, v):
        pb = jnp.broadcast_to(pos, (B, L))
        return jnp.sum(jnp.sin(REF.mha_reference(
            q, k, v, pb, pb, window=8, causal=True, softcap=4.0)))

    g1 = jax.grad(f_flash, (0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


@pytest.mark.parametrize("B,S,H,KV,D", [
    (2, 64, 8, 2, 32), (4, 256, 12, 12, 64), (1, 128, 4, 1, 128),
    (3, 96, 6, 3, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_decode_vs_oracle(B, S, H, KV, D, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32).astype(dtype)
    vl = jnp.asarray(np.random.default_rng(0).integers(1, S, B), jnp.int32)
    o = decode_attention_pallas(q, k, v, vl, interpret=True)
    o_ref = REF.decode_reference(q, k, v, vl)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), atol=tol)


@pytest.mark.parametrize("Bt,L,H,P,N,chunk", [
    (2, 64, 4, 16, 32, 16), (1, 32, 2, 8, 16, 8), (2, 128, 8, 32, 64, 32),
])
def test_pallas_ssd_vs_chunked_oracle(Bt, L, H, P, N, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (Bt, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, L, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)))
    b = jax.random.normal(ks[3], (Bt, L, N))
    c = jax.random.normal(ks[4], (Bt, L, N))
    init = jax.random.normal(KEY, (Bt, H, P, N))
    y1, s1 = ssd_scan_pallas(x, dt, a, b, c, chunk, init_state=init,
                             interpret=True)
    y2, s2 = ssd_chunked(x, dt, a, b, c, chunk, init_state=init)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-4)


def test_ssd_chunked_matches_stepwise_recurrence():
    from repro.layers.ssm import ssd_step
    Bt, L, H, P, N = 2, 24, 3, 8, 16
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (Bt, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, L, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)))
    b = jax.random.normal(ks[3], (Bt, L, N))
    c = jax.random.normal(ks[4], (Bt, L, N))
    st = jnp.zeros((Bt, H, P, N))
    ys = []
    for t in range(L):
        y, st = ssd_step(st, x[:, t], dt[:, t], a, b[:, t], c[:, t])
        ys.append(y)
    y_seq = jnp.stack(ys, 1)
    y_chk, st_chk = ssd_chunked(x, dt, a, b, c, chunk=8)
    np.testing.assert_allclose(np.asarray(y_seq), np.asarray(y_chk),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_chk), atol=2e-4)


# (layers, slots, heads, head_dim, state, live rows): ragged masks, several
# rows a grid step (small states), and granite's H 64 / P 64 / N 128
SSD_STEP_CASES = [
    (3, 4, 4, 16, 16, (True, False, True, True)),
    (2, 6, 2, 8, 128, (False, True, True, False, True, False)),
    (2, 2, 64, 64, 128, (True, False)),
    (2, 3, 4, 16, 16, None),
]


@pytest.mark.parametrize("L,B,H,P,N,live", SSD_STEP_CASES)
def test_pallas_ssd_state_update_vs_step(L, B, H, P, N, live):
    """The in-place step against ``ssd_step`` on the sliced layer plus
    the per-row freeze: y and the stepped layer to f32 rounding, frozen
    rows and every other layer bit-identical, one program for every
    (traced) layer index."""
    from repro.kernels.ssd_state_update import ssd_state_update_pallas
    from repro.layers.common import where_rows
    from repro.layers.ssm import ssd_step
    ks = jax.random.split(KEY, 6)
    stack = jax.random.normal(ks[0], (L, B, H, P, N))
    x = jax.random.normal(ks[1], (B, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (B, H)))
    a = -jnp.exp(jax.random.normal(ks[3], (H,)))
    b = jax.random.normal(ks[4], (B, N))
    c = jax.random.normal(ks[5], (B, N))
    rows = None if live is None else jnp.asarray(live)
    dec = jnp.exp(dt * a[None])
    xdt = x * dt[..., None]
    step = jax.jit(lambda s, i: ssd_state_update_pallas(
        s, i, dec, xdt, b, c, rows, interpret=True))
    frozen = np.flatnonzero(~np.asarray(rows)) if live is not None else []
    for i in (L - 1, 0):
        y, out = step(stack, jnp.int32(i))
        y_ref, new = ssd_step(stack[i], x, dt, a, b, c)
        if rows is not None:
            new = where_rows(rows, new, stack[i], 0)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(new),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(out[i])[frozen],
                                      np.asarray(stack[i])[frozen])
        for j in set(range(L)) - {i}:
            np.testing.assert_array_equal(np.asarray(out[j]),
                                          np.asarray(stack[j]))
    assert step._cache_size() == 1     # the layer index is traced


# granite scales attention scores by 1/64 at head_dim 64, not 1/8: every
# attention kernel takes the scale as an argument (None: D ** -0.5)
SCALE = 0.015625


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_paged_decode_at_a_non_default_scale(impl):
    from repro.kernels.paged_decode_attention import (
        paged_decode_attention_pallas, paged_decode_attention_xla)
    B, S, H, KV, D, page = 3, 48, 8, 2, 64, 16
    ks = jax.random.split(KEY, 4)
    pps = S // page
    q = 4.0 * jax.random.normal(ks[0], (B, H, D), jnp.float32)
    pool_k = jax.random.normal(ks[1], (B * pps + 1, page, KV, D))
    pool_v = jax.random.normal(ks[2], (B * pps + 1, page, KV, D))
    pt = jax.random.permutation(ks[3], B * pps).reshape(B, pps).astype(
        jnp.int32)
    vl = jnp.asarray([5, 48, 30], jnp.int32)
    if impl == "pallas":
        o = paged_decode_attention_pallas(q, pool_k, pool_v, pt, vl,
                                          scale=SCALE, interpret=True)
    else:
        o = paged_decode_attention_xla(q, pool_k, pool_v, pt, vl,
                                       scale=SCALE)
    k = jnp.take(pool_k, pt, axis=0).reshape(B, S, KV, D)
    v = jnp.take(pool_v, pt, axis=0).reshape(B, S, KV, D)
    o_ref = REF.decode_reference(q, k, v, vl, scale=SCALE)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)
    o_default = REF.decode_reference(q, k, v, vl)
    assert float(jnp.max(jnp.abs(o_ref - o_default))) > 1e-2


def test_dense_decode_at_a_non_default_scale():
    B, S, H, KV, D = 2, 64, 8, 2, 64
    ks = jax.random.split(KEY, 3)
    q = 4.0 * jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
    vl = jnp.asarray([17, 64], jnp.int32)
    o = decode_attention_pallas(q, k, v, vl, scale=SCALE, interpret=True)
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(REF.decode_reference(q, k, v, vl,
                                                       scale=SCALE)),
        atol=2e-5)


@pytest.mark.parametrize("impl", ["pallas", "xla", "chunk"])
def test_prefill_attention_at_a_non_default_scale(impl):
    from repro.kernels import ops
    B, Lq, Lk, H, KV, D = 1, 64, 128, 8, 2, 64
    q, k, v = _qkv(B, Lq, Lk, H, KV, D, jnp.float32)
    q = 4.0 * q
    qp = jnp.broadcast_to(jnp.arange(Lk - Lq, Lk), (B, Lq)).astype(jnp.int32)
    kp = jnp.broadcast_to(jnp.arange(Lk), (B, Lk)).astype(jnp.int32)
    if impl == "pallas":
        o = flash_attention_fwd_pallas(q, k, v, qp, kp, block_q=32,
                                       block_k=32, scale=SCALE,
                                       interpret=True)
    elif impl == "xla":
        o = flash_attention(q, k, v, qp, kp, 0, True, 0.0, 32, 32, SCALE)
    else:
        o = ops.prefill_chunk_attention(q, k, v, qp[0], kp[0], 0, 0.0,
                                        SCALE)
    o_ref = REF.mha_reference(q, k, v, qp, kp, scale=SCALE)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)
