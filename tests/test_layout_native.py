"""Layout-native decode (KVView) parity and regression suite.

Three layers of checks:

1. **Kernel units** — the paged decode-attention implementations (Pallas
   interpret-mode page-table walk, page-at-a-time XLA fallback) and the
   fused int8 decode kernel against the dense pure-jnp oracle, swept
   over shapes / windows / quantisation.
2. **Dense-oracle parity** — for every family (tconst-tlin, dense LM,
   enc-dec) x layout (paged, int8, paged+int8): a staggered-phase decode
   chunk where every layout-native ``step`` is compared against the
   legacy dense-dict step run on ``DecodeState.merged()``.  Exact
   layouts (paged fp32) must match to float-associativity noise with
   identical argmax; int8 layouts are bounded by the symmetric-int8
   rounding of the one vector that is quantized-before-attend (the
   legacy path attended the f32 vector and quantized on repack).
3. **Regressions** — under ``--layout paged`` a decode ``step`` contains
   ZERO intermediates with the dense ``slots x max_len`` logical KV
   shape (the per-step densification this refactor retires), and the
   compacted resync lowers without a ``while`` loop (all pending rows
   sync in one batched dispatch).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from repro import runtime
from repro.config import get_config, reduced
from repro.core import tconst as TC
from repro.kernels.paged_decode_attention import (
    paged_decode_attention_pallas, paged_decode_attention_xla)
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels import ref as REF
from repro.models import encdec as ED
from repro.models import layouts as LT
from repro.models import lm as LM
from repro.models.api import build_decode, build_model
from repro.serving.engine import Engine
from repro.serving.scheduler import SlotScheduler
from repro.serving.session import Session

KEY = jax.random.PRNGKey(11)


# ---------------------------------------------------------------------------
# Kernel units: paged walk + fused int8 vs the dense oracle
# ---------------------------------------------------------------------------


def _paged_case(B, S, H, KV, D, page, pool_extra=2, quant=False, seed=0):
    """Random pool + per-slot table + the equivalent dense cache."""
    pps = -(-S // page)
    pool_pages = B * pps + pool_extra
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    pool_k = jax.random.normal(ks[1], (pool_pages + 1, page, KV, D))
    pool_v = jax.random.normal(ks[2], (pool_pages + 1, page, KV, D))
    perm = jax.random.permutation(ks[3], pool_pages)[:B * pps]
    pt = perm.reshape(B, pps).astype(jnp.int32)
    vl = jnp.asarray(np.random.default_rng(seed).integers(1, S + 1, B),
                     jnp.int32)
    kw = {}
    if quant:
        pool_k, ksc = LT.quantize_int8(pool_k)
        pool_v, vsc = LT.quantize_int8(pool_v)
        kw = dict(k_scale=ksc, v_scale=vsc)
    # dense logical view for the oracle
    dk = jnp.take(pool_k if not quant else
                  LT.dequantize_int8(pool_k, kw["k_scale"], jnp.float32),
                  pt, axis=0).reshape(B, pps * page, KV, D)[:, :S]
    dv = jnp.take(pool_v if not quant else
                  LT.dequantize_int8(pool_v, kw["v_scale"], jnp.float32),
                  pt, axis=0).reshape(B, pps * page, KV, D)[:, :S]
    return q, pool_k, pool_v, pt, vl, kw, dk, dv


@pytest.mark.parametrize("B,S,H,KV,D,page,win", [
    (2, 64, 4, 2, 32, 16, 0),
    (3, 96, 6, 3, 32, 32, 0),
    (2, 128, 8, 8, 64, 32, 24),      # sliding window
    (1, 48, 4, 1, 16, 16, 0),        # padded last page (48 = 3 pages)
])
@pytest.mark.parametrize("quant", [False, True])
def test_paged_xla_fallback_vs_dense_oracle(B, S, H, KV, D, page, win,
                                            quant):
    """The page-walk fallback against the dense oracle: the oracle sees
    the IDENTICAL logical values (paging is exact; the int8 case
    dequantises the same int8+scale data), so only float-associativity
    noise separates them."""
    q, pk, pv, pt, vl, kw, dk, dv = _paged_case(B, S, H, KV, D, page,
                                                quant=quant)
    o = paged_decode_attention_xla(q, pk, pv, pt, vl, window=win, **kw)
    slots = jnp.arange(dk.shape[1])[None]
    keep = slots < vl[:, None]
    if win:
        keep = jnp.logical_and(keep, slots >= vl[:, None] - win)
    o_ref = _masked_decode_reference(q, dk, dv, keep)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=3e-5)


def _masked_decode_reference(q, k, v, keep):
    """decode_reference with an arbitrary (B, S) validity mask."""
    B, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg * (D ** -0.5),
                   k.astype(jnp.float32))
    s = jnp.where(keep[:, None, None, :], s, -2.3819763e38)
    mx = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - mx) * keep[:, None, None, :]
    p = e / (jnp.sum(e, axis=-1, keepdims=True) + 1e-30)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return o.reshape(B, H, D).astype(q.dtype)


@pytest.mark.parametrize("B,S,H,KV,D,page,win", [
    (2, 64, 4, 2, 32, 16, 0),
    (2, 96, 4, 2, 32, 32, 16),
])
@pytest.mark.parametrize("quant", [False, True])
def test_paged_pallas_interpret_matches_xla_fallback(B, S, H, KV, D, page,
                                                     win, quant):
    """The Pallas page-walk kernel (interpret mode: same arithmetic as on
    TPU) must agree with the XLA fallback — one contract, two backends."""
    q, pk, pv, pt, vl, kw, _, _ = _paged_case(B, S, H, KV, D, page,
                                              quant=quant)
    o_xla = paged_decode_attention_xla(q, pk, pv, pt, vl, window=win, **kw)
    o_pls = paged_decode_attention_pallas(q, pk, pv, pt, vl, window=win,
                                          interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(o_pls), np.asarray(o_xla),
                               atol=1e-5)


def test_int8_fused_decode_kernel_vs_dequant_oracle():
    B, S, H, KV, D = 2, 64, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    kq, ksc = LT.quantize_int8(k)
    vq, vsc = LT.quantize_int8(v)
    vl = jnp.array([17, 64], jnp.int32)
    o = decode_attention_pallas(q, kq, vq, vl, k_scale=ksc, v_scale=vsc,
                                interpret=True)
    o_ref = REF.decode_reference(
        q, LT.dequantize_int8(kq, ksc, jnp.float32),
        LT.dequantize_int8(vq, vsc, jnp.float32), vl)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)


# ---------------------------------------------------------------------------
# Dense-oracle parity: staggered decode chunk, every family x layout
# ---------------------------------------------------------------------------


def _tconst_family():
    cfg = reduced(get_config("tconst_41m"), dtype="float32",
                  attention_mode="tlin")

    def oracle(params, cache, tok):
        rows = TC.pending_resync_rows(cache, cfg)
        cache = TC.resync_rows_compacted(params, cache, cfg, rows, "tlin")
        return TC.decode_step(params, cache, tok, cfg, mode="tlin")
    return cfg, oracle, {}


def _lm_family():
    cfg = reduced(get_config("llama3_405b"), dtype="float32")
    return cfg, (lambda p, c, t: LM.lm_decode_step(p, c, t, cfg)), {}


def _encdec_family():
    cfg = reduced(get_config("whisper_small"), dtype="float32")
    extras = lambda: {"audio_feats": jnp.zeros(  # noqa: E731
        (cfg.encoder_seq, cfg.frontend_dim), jnp.float32)}
    return cfg, (lambda p, c, t: ED.encdec_decode_step(p, c, t, cfg)), \
        {"extras": extras}


FAMILIES = {"tlin": _tconst_family, "lm": _lm_family, "encdec": _encdec_family}
LAYOUTS = {
    # (spec, logits atol vs the merged() oracle, argmax must match)
    # int8 bound: the step quantizes the NEW token's K/V before attending
    # (the legacy path attended it in f32 and quantized on repack), so
    # logits carry one vector's symmetric-int8 rounding (~0.4% of its
    # max magnitude) — the documented lossy-layout tolerance.
    "paged": (LT.LayoutSpec(kind="paged", page_size=16), 2e-5, True),
    "int8": (LT.LayoutSpec(kind="int8"), 2e-2, False),
    "paged_int8": (LT.LayoutSpec(kind="paged_int8", page_size=16), 2e-2,
                   False),
}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family(request):
    cfg, oracle, kw = FAMILIES[request.param]()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    return request.param, cfg, api, params, oracle, kw


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_layout_native_step_matches_merged_oracle(family, layout):
    """Every layout-native fused ``step`` of a STAGGERED two-slot decode
    (different prompt lengths => different phases, tconst rows crossing
    the W_og resync boundary at different steps) must match the legacy
    dense-dict step run on the same state's ``merged()`` oracle."""
    name, cfg, api, params, oracle, kw = family
    spec, tol, exact_argmax = LAYOUTS[layout]
    dec = build_decode(cfg, spec)
    state = dec.init_state(2, 96)
    extras = kw.get("extras", lambda: None)
    prompts = [(np.arange(1, 10) % cfg.vocab_size).astype(np.int32),
               ((np.arange(1, 14) * 7) % cfg.vocab_size).astype(np.int32)]
    tok = []
    for slot, p in enumerate(prompts):
        lg, state = dec.prefill_into_slot(params, state, jnp.int32(slot),
                                          jnp.asarray(p), extras=extras())
        tok.append(int(jnp.argmax(lg)))
    tok = jnp.asarray(tok, jnp.int32)

    step = jax.jit(dec.step)
    for t in range(10):
        lg_o, _ = oracle(params, state.merged(), tok)
        lg, state = step(params, state, tok)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_o),
                                   atol=tol, err_msg=f"{name}/{layout}@{t}")
        if exact_argmax:
            np.testing.assert_array_equal(
                np.asarray(jnp.argmax(lg, -1)),
                np.asarray(jnp.argmax(jnp.asarray(lg_o), -1)))
        tok = jnp.argmax(lg, -1).astype(jnp.int32)


def test_pallas_interpret_full_model_matches_xla_fallback(family,
                                                          monkeypatch):
    """Flipping the runtime flags routes the SAME step through the Pallas
    interpret kernels; logits must agree with the XLA fallback path."""
    name, cfg, api, params, oracle, kw = family
    dec = build_decode(cfg, LT.LayoutSpec(kind="paged_int8", page_size=16))
    state = dec.init_state(2, 96)
    extras = kw.get("extras", lambda: None)
    p = (np.arange(1, 10) % cfg.vocab_size).astype(np.int32)
    _, state = dec.prefill_into_slot(params, state, jnp.int32(0),
                                     jnp.asarray(p), extras=extras())
    tok = jnp.array([3, 5], jnp.int32)
    lg_xla, _ = dec.raw_step(params, state, tok)
    monkeypatch.setattr(runtime.flags, "use_pallas", True)
    monkeypatch.setattr(runtime.flags, "pallas_interpret", True)
    lg_pls, _ = dec.raw_step(params, state, tok)
    np.testing.assert_allclose(np.asarray(lg_pls), np.asarray(lg_xla),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# Regressions: densification retired, resync batched
# ---------------------------------------------------------------------------


def _collect_shapes(jaxpr, acc):
    for eqn in parity.walk_eqns(jaxpr):
        for v in eqn.outvars:
            if hasattr(v, "aval") and hasattr(v.aval, "shape"):
                acc.add(tuple(v.aval.shape))
    return acc


def _banned_dense_shapes(state, length_axes):
    dense = {tuple(s.shape) for k, s in state.dense_shapes().items()
             if k in length_axes}
    return dense | {s[1:] for s in dense}        # full + per-layer slice


def test_paged_lm_step_never_materializes_dense_kv():
    """Acceptance criterion: under ``--layout paged`` a decode ``step``
    performs ZERO dense ``slots x max_len`` KV materialisation — no
    intermediate in its jaxpr has the dense logical KV shape (full or
    per-layer).  The dense layout's own step DOES (control, so the
    check has teeth)."""
    cfg = reduced(get_config("llama3_405b"), dtype="float32")
    api = build_model(cfg)
    params_s = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    tok_s = jax.ShapeDtypeStruct((4,), jnp.int32)

    dec = build_decode(cfg, LT.LayoutSpec(kind="paged", page_size=16,
                                          pool_pages=10))
    state_s = jax.eval_shape(lambda: dec.init_state(4, 128))
    shapes = _collect_shapes(
        jax.make_jaxpr(dec.step)(params_s, state_s, tok_s).jaxpr, set())
    banned = _banned_dense_shapes(state_s, LM.LENGTH_AXES)
    assert not (banned & shapes), banned & shapes

    ctrl = build_decode(cfg, "dense")
    ctrl_state = jax.eval_shape(lambda: ctrl.init_state(4, 128))
    ctrl_shapes = _collect_shapes(
        jax.make_jaxpr(ctrl.step)(params_s, ctrl_state, tok_s).jaxpr, set())
    assert banned & ctrl_shapes      # the dense step does carry the shape


def test_paged_tlin_hit_step_never_materializes_dense_hist():
    """Same property for TLinFormer's O(N) history KV on the cache-HIT
    path (``raw_step``; the miss path is O(N) by definition)."""
    cfg = reduced(get_config("tconst_41m"), dtype="float32",
                  attention_mode="tlin")
    api = build_model(cfg)
    params_s = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    dec = build_decode(cfg, LT.LayoutSpec(kind="paged", page_size=16,
                                          pool_pages=10))
    state_s = jax.eval_shape(lambda: dec.init_state(4, 128))
    tok_s = jax.ShapeDtypeStruct((4,), jnp.int32)
    shapes = _collect_shapes(
        jax.make_jaxpr(dec.raw_step)(params_s, state_s, tok_s).jaxpr, set())
    banned = _banned_dense_shapes(state_s, TC.LENGTH_AXES)
    assert not (banned & shapes), banned & shapes


def _has_primitive(jaxpr, name):
    return any(eqn.primitive.name == name for eqn in parity.walk_eqns(jaxpr))


def test_compacted_resync_is_single_dispatch_not_while_loop():
    """Satellite: the compacted resync batches the gather/scatter over
    all pending rows — its jaxpr holds a ``cond``/``switch``, never the
    PR-2 per-row ``while`` loop."""
    cfg = reduced(get_config("tconst_41m"), dtype="float32")
    api = build_model(cfg)
    params_s = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    cache_s = jax.eval_shape(
        lambda: TC.init_tconst_cache(cfg, 4, 64, "tconst"))
    rows_s = jax.ShapeDtypeStruct((4,), jnp.bool_)
    closed = jax.make_jaxpr(
        lambda p, c, r: TC.resync_rows_compacted(p, c, cfg, r))(
        params_s, cache_s, rows_s)
    assert not _has_primitive(closed.jaxpr, "while")


def test_resync_buckets_cover_all_counts():
    for b in (1, 2, 3, 4, 5, 8, 13):
        buckets = TC.resync_buckets(b)
        assert buckets[0] == 0 and buckets[-1] == b
        for count in range(b + 1):
            k = buckets[int(np.searchsorted(np.asarray(buckets), count))]
            assert count <= k <= max(2 * count, buckets[1] if count else 0)


# ---------------------------------------------------------------------------
# Serving-level: paged_int8 end-to-end + byte accounting
# ---------------------------------------------------------------------------


def test_paged_int8_scheduler_sessions_complete_and_shrink_kv():
    cfg = reduced(get_config("tconst_41m"), dtype="float32",
                  attention_mode="tlin")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    pa = (np.arange(1, 10) % cfg.vocab_size).astype(np.int32)
    pb = ((np.arange(1, 14) * 7) % cfg.vocab_size).astype(np.int32)
    spec = LT.LayoutSpec(kind="paged_int8", page_size=16, pool_pages=10)
    sched = SlotScheduler(build_decode(cfg, spec), params, slots=2,
                          max_len=128, chunk_size=4)
    sa = sched.submit(Session(pa, max_new_tokens=12))
    sched.step()
    sb = sched.submit(Session(pb, max_new_tokens=9))
    sched.run()
    assert sa.done and len(sa.tokens) == 12
    assert sb.done and len(sb.tokens) == 9
    assert len(sched.free_pages) == 10           # pages recycled
    dense_bytes = SlotScheduler(api.decode, params, slots=2,
                                max_len=128).kv_bytes()
    # int8 pages + scales in an undersized pool: well under dense fp32
    assert sched.kv_bytes() < dense_bytes / 2


def test_step_view_bytes_accounting():
    """Per-step HBM bytes touched: the paged view counts only ASSIGNED
    pages (+ table), so it sits below the dense-logical bytes the
    retired ``merged()`` path would have materialised."""
    cfg = reduced(get_config("tconst_41m"), dtype="float32",
                  attention_mode="tlin")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    spec = LT.LayoutSpec(kind="paged", page_size=16, pool_pages=10)
    sched = SlotScheduler(build_decode(cfg, spec), params, slots=2,
                          max_len=128, chunk_size=4)
    pa = (np.arange(1, 10) % cfg.vocab_size).astype(np.int32)
    sched.submit(Session(pa, max_new_tokens=8))
    sched.step()
    state = sched.state
    assert state.step_view_bytes() < state.dense_logical_bytes()
    # dense layout: view bytes == logical bytes (identity layout)
    dstate = api.decode.init_state(2, 128)
    assert dstate.step_view_bytes() == dstate.dense_logical_bytes()


def test_engine_paged_int8_generates():
    cfg = reduced(get_config("tconst_41m"), dtype="float32",
                  attention_mode="tlin")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    out = Engine(api, params, max_len=96, layout="paged_int8").generate(
        {"tokens": jnp.ones((2, 9), jnp.int32)}, 12)
    assert out.shape == (2, 12) and (out >= 0).all()
