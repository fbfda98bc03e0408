"""Session/scheduler serving API: resync-boundary correctness of the
fused (on-device, compacted row-wise) synchronisation, continuous
batching with staggered admission, pluggable cache layouts
(dense / paged / int8), EOS early termination, and the zero-host-sync
decode chunk."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from repro.config import get_config, reduced
from repro.core import tconst as TC
from repro.models import layouts as LT
from repro.models.api import build_decode, build_model, decode_chunk
from repro.serving.engine import Engine
from repro.serving.scheduler import SlotScheduler
from repro.serving.session import Session


@pytest.fixture(scope="module", params=["tconst", "tlin"])
def setup(request):
    cfg = reduced(get_config("tconst_41m"), dtype="float32",
                  attention_mode=request.param)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    return cfg, api, params


def _solo(api, params, prompt, n, max_len=128):
    eng = Engine(api, params, max_len=max_len)
    return eng.generate({"tokens": jnp.asarray(prompt)[None]}, n)[0].tolist()


# ---------------------------------------------------------------------------
# Resync-boundary correctness
# ---------------------------------------------------------------------------


def test_chunk_across_boundary_matches_stepwise_reference(setup):
    """A chunked (single lax.scan, on-device compacted resync) generation
    crossing several W_og boundaries must equal the step-at-a-time
    reference path where the resync decision is made on host."""
    cfg, api, params = setup
    p = {"tokens": jnp.ones((2, 12), jnp.int32)}   # phase 12 % 8 = 4
    fast = Engine(api, params, max_len=128).generate(p, 30)
    ref_eng = Engine(api, params, max_len=128)
    ref = ref_eng.generate(p, 30, record_stats=True)
    np.testing.assert_array_equal(fast, ref)
    if cfg.attention_mode == "tconst":
        assert [s.kind for s in ref_eng.stats].count("miss") >= 3


def test_fused_step_resyncs_on_device(setup):
    """At gen_len == W_og the fused step folds the window into history
    inside the jitted step (no host decision) and matches
    sync_rows + raw_step."""
    cfg, api, params = setup
    dec = api.decode
    w_og = cfg.tconst.w_og
    _, state = dec.prefill(params, {"tokens": jnp.ones((1, w_og),
                                                       jnp.int32)}, 64)
    assert bool(dec.sync_mask(state).all())        # window exactly full
    tok = jnp.array([3], jnp.int32)
    lg_fused, st_fused = jax.jit(dec.step)(params, state, tok)
    synced = dec.sync_rows(params, state, dec.sync_mask(state))
    lg_ref, st_ref = dec.raw_step(params, synced, tok)
    np.testing.assert_allclose(np.asarray(lg_fused), np.asarray(lg_ref),
                               atol=1e-5)
    assert int(st_fused.bookkeeping["gen_len"][0]) == 1
    assert int(st_fused.bookkeeping["hist_len"][0]) == w_og


def test_row_selective_resync_leaves_other_rows_untouched(setup):
    """Only rows at the W_og boundary are resynced: a mid-phase row must
    come through resync_rows bit-identical."""
    cfg, api, params = setup
    dec = api.decode
    _, state = dec.prefill(params, {"tokens": jnp.ones((2, 12),
                                                       jnp.int32)}, 64)
    cache = state.merged()
    rows = jnp.array([True, False])
    out = TC.resync_rows(params, cache, cfg, rows, cfg.attention_mode)
    assert int(out["gen_len"][0]) == 0             # row 0 folded
    assert int(out["gen_len"][1]) == int(cache["gen_len"][1])
    for k in cache:
        ax = TC.CACHE_BATCH_AXES[k]
        old_row1 = np.take(np.asarray(cache[k]), 1, axis=ax)
        new_row1 = np.take(np.asarray(out[k]), 1, axis=ax)
        np.testing.assert_array_equal(old_row1, new_row1)


def test_compacted_sync_rows_matches_pr1_full_batch_resync(setup):
    """The compacted while-loop resync (gather masked rows, sync at batch
    size 1, scatter back — non-masked rows never computed) must produce
    the cache of the PR-1 compute-all-then-select path for any row mask:
    bit-identical bookkeeping and unmasked rows, float KV within fusion
    noise (the while-loop body fuses differently than the unrolled
    batch pass)."""
    cfg, api, params = setup
    dec = api.decode
    _, state = dec.prefill(params, {"tokens": jnp.ones((3, 12),
                                                       jnp.int32)}, 64)
    cache = state.merged()
    for rows in ([True, False, True], [False, False, False],
                 [True, True, True]):
        mask = jnp.array(rows)
        ref = TC.resync_rows(params, cache, cfg, mask, cfg.attention_mode)
        got = jax.jit(lambda c, m: TC.resync_rows_compacted(
            params, c, cfg, m, cfg.attention_mode))(cache, mask)
        for k in cache:
            a, b = np.asarray(got[k]), np.asarray(ref[k])
            if np.issubdtype(a.dtype, np.floating):
                np.testing.assert_allclose(a, b, atol=1e-5,
                                           err_msg=str((rows, k)))
            else:
                np.testing.assert_array_equal(a, b, err_msg=str((rows, k)))
        # unmasked rows: bit-identical (never touched by the loop)
        for i, r in enumerate(rows):
            if r:
                continue
            for k in cache:
                ax = TC.CACHE_BATCH_AXES[k]
                np.testing.assert_array_equal(
                    np.take(np.asarray(got[k]), i, axis=ax),
                    np.take(np.asarray(cache[k]), i, axis=ax))


def test_compacted_step_tokens_match_pr1_maybe_resync(setup):
    """Token-level PR-1 equivalence: greedy decode of a mixed-phase batch
    through the v2 fused step (compacted sync_rows) must emit exactly
    the tokens of the PR-1 path (monolithic maybe_resync: full-batch
    compute + row select) across several W_og boundaries."""
    cfg, api, params = setup
    dec = api.decode

    def pr1_step(p, st, tok):
        cache = TC.maybe_resync(p, st.merged(), cfg, cfg.attention_mode)
        lg, cache = TC.decode_step(p, cache, tok, cfg,
                                   mode=cfg.attention_mode)
        return lg, dec._rewrap(st, cache)

    _, state = dec.prefill(params, {"tokens": jnp.ones((2, 12),
                                                       jnp.int32)}, 96)
    s_new = s_old = state
    tok_new = tok_old = jnp.array([5, 9], jnp.int32)
    new_step = jax.jit(dec.step)
    old_step = jax.jit(pr1_step)
    for _ in range(20):
        lg_new, s_new = new_step(params, s_new, tok_new)
        lg_old, s_old = old_step(params, s_old, tok_old)
        tok_new = jnp.argmax(lg_new, -1).astype(jnp.int32)
        tok_old = jnp.argmax(lg_old, -1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(tok_new),
                                      np.asarray(tok_old))


# ---------------------------------------------------------------------------
# Continuous batching: staggered admission, variable prompt lengths
# ---------------------------------------------------------------------------


def test_staggered_sessions_match_solo_generation(setup):
    """Two sessions with different prompt lengths, admitted at different
    times (different W_og phases inside one batch), must each produce
    exactly the tokens of their single-session generation."""
    cfg, api, params = setup
    pa = (np.arange(1, 10) % cfg.vocab_size).astype(np.int32)     # len 9
    pb = ((np.arange(1, 14) * 7) % cfg.vocab_size).astype(np.int32)

    sched = SlotScheduler(api.decode, params, slots=2, max_len=128,
                          chunk_size=4)
    sa = sched.submit(Session(pa, max_new_tokens=25))
    sched.step()       # A runs a chunk alone -> staggered resync phases
    sb = sched.submit(Session(pb, max_new_tokens=21))
    sched.run()
    assert sa.done and sb.done
    assert sa.tokens == _solo(api, params, pa, 25)
    assert sb.tokens == _solo(api, params, pb, 21)


def test_sessions_stream_through_callback_and_reuse_slots(setup):
    cfg, api, params = setup
    streamed = []
    sched = SlotScheduler(api.decode, params, slots=1, max_len=128,
                          chunk_size=4)
    for i in range(3):                       # 3 sessions through 1 slot
        sched.submit(Session(np.full(5 + i, 2, np.int32),
                             max_new_tokens=6,
                             on_token=lambda s, t: streamed.append(
                                 (s.sid, t))))
    sched.run()
    assert len(streamed) == 18
    assert len({sid for sid, _ in streamed}) == 3


def test_eos_early_termination_frees_slot(setup):
    """A session whose EOS id is sampled mid-stream stops at the EOS
    (inclusive), its on-device done flag freezes the row inside the
    chunk, and the scheduler evicts it at the chunk boundary."""
    cfg, api, params = setup
    pa = (np.arange(1, 10) % cfg.vocab_size).astype(np.int32)
    ref = _solo(api, params, pa, 25)
    # an eos whose FIRST occurrence is mid-stream; degenerate all-same
    # streams (possible for other seeds/configs) can't test truncation
    eos = next((t for t in ref if ref.index(t) >= 2), None)
    if eos is None:
        pytest.skip("greedy reference stream has no mid-stream-first token")
    cut = ref.index(eos) + 1
    sched = SlotScheduler(api.decode, params, slots=2, max_len=128,
                          chunk_size=4)
    se = sched.submit(Session(pa, max_new_tokens=25, eos_id=eos))
    sched.run()
    assert se.done
    assert se.tokens == ref[:cut]
    assert sched.n_active == 0
    # the freed slot's state is cleared: no stale done/phase flags
    assert not bool(np.asarray(
        sched.state.bookkeeping["done"]).any())


# ---------------------------------------------------------------------------
# Cache layouts: paged / int8 parity and accounting
# ---------------------------------------------------------------------------


def test_paged_layout_staggered_sessions_token_identical(setup):
    """Paged layout with an UNDER-SIZED pool (the scheduler allocates and
    recycles pages at admission/eviction) must be token-identical to the
    dense path under staggered multi-slot admission."""
    cfg, api, params = setup
    pa = (np.arange(1, 10) % cfg.vocab_size).astype(np.int32)
    pb = ((np.arange(1, 14) * 7) % cfg.vocab_size).astype(np.int32)
    spec = LT.LayoutSpec(kind="paged", page_size=16, pool_pages=10)
    dec = build_decode(cfg, spec)
    sched = SlotScheduler(dec, params, slots=2, max_len=128, chunk_size=4)
    sa = sched.submit(Session(pa, max_new_tokens=25))
    sched.step()
    sb = sched.submit(Session(pb, max_new_tokens=21))
    sched.run()
    assert sa.tokens == _solo(api, params, pa, 25)
    assert sb.tokens == _solo(api, params, pb, 21)

    dense_bytes = SlotScheduler(api.decode, params, slots=2,
                                max_len=128).kv_bytes()
    if cfg.attention_mode == "tlin":
        # the O(N) history KV is paged: a 10/16 pool beats dense, and
        # pages were recycled back to the pool after eviction
        assert sched.kv_bytes() < dense_bytes
        assert len(sched.free_pages) == 10
    else:
        # pure tconst KV is already O(1): paged degenerates to dense and
        # the scheduler must not gate admission on the (unused) pool —
        # a session "needing" more pages than a tiny pool holds still
        # runs, because nothing is actually stored in pages
        assert sched.kv_bytes() == dense_bytes
        assert not sched._paged
        tiny_dec = build_decode(cfg, LT.LayoutSpec(
            kind="paged", page_size=16, pool_pages=2))
        tiny = SlotScheduler(tiny_dec, params, slots=1, max_len=128,
                             chunk_size=4)
        s = tiny.submit(Session(pa, max_new_tokens=25))   # needs 3 "pages"
        tiny.run()
        assert s.done and s.tokens == _solo(api, params, pa, 25)


def test_int8_layout_tolerance_and_bytes(setup):
    """int8 KV must (a) reproduce the dense KV within the symmetric-int8
    rounding bound (scale = vecmax/127 => error <= scale/2 per element),
    (b) shrink kv_bytes ~4x vs float32, (c) decode end-to-end."""
    cfg, api, params = setup
    dec8 = build_decode(cfg, "int8")
    batch = {"tokens": jnp.ones((2, 12), jnp.int32)}
    _, dense_state = api.decode.prefill(params, batch, 64)
    _, q_state = dec8.prefill(params, batch, 64)
    dense_kv = dense_state.merged()
    deq_kv = q_state.merged()
    for k in TC.QUANT_FIELDS:
        if k not in dense_kv:
            continue
        x = np.asarray(dense_kv[k], np.float32)
        y = np.asarray(deq_kv[k], np.float32)
        bound = np.max(np.abs(x), axis=-1, keepdims=True) / 127.0 * 0.5 \
            + 1e-7
        assert (np.abs(x - y) <= bound + 1e-6).all(), k

    ratio = dense_state.kv_bytes() / q_state.kv_bytes()
    hd = cfg.resolved_head_dim            # f32: 4 / (1 + 4/head_dim)
    assert abs(ratio - 4.0 / (1.0 + 4.0 / hd)) < 0.05

    out = Engine(api, params, max_len=128, layout="int8").generate(
        {"tokens": jnp.ones((1, 9), jnp.int32)}, 16)
    assert out.shape == (1, 16) and (out >= 0).all()


def test_engine_layouts_greedy_parity(setup):
    """Uniform-batch Engine: paged (full pool — no allocator needed) is
    token-identical to dense."""
    cfg, api, params = setup
    p = {"tokens": jnp.ones((2, 12), jnp.int32)}
    ref = Engine(api, params, max_len=128).generate(p, 24)
    got = Engine(api, params, max_len=128, layout="paged").generate(p, 24)
    np.testing.assert_array_equal(got, ref)


def test_undersized_pool_rejects_full_batch_prefill_iff_paged_fields(setup):
    """An under-sized pool has no allocator on the full-batch prefill
    path, so prefill must refuse it — but ONLY when the cache actually
    pages something (tlin's history KV); pure-tconst caches store
    nothing in pages and must prefill fine."""
    cfg, api, params = setup
    spec = LT.LayoutSpec(kind="paged", page_size=16, pool_pages=2)
    dec = build_decode(cfg, spec)
    batch = {"tokens": jnp.ones((2, 12), jnp.int32)}
    if cfg.attention_mode == "tlin":
        with pytest.raises(ValueError, match="under-sized paged pool"):
            dec.prefill(params, batch, 128)
    else:
        _, state = dec.prefill(params, batch, 128)
        assert state.slots == 2


# ---------------------------------------------------------------------------
# Zero per-token host syncs
# ---------------------------------------------------------------------------


def _jaxpr_has_host_comms(jaxpr) -> bool:
    bad = ("callback", "infeed", "outfeed", "host")
    return any(b in eqn.primitive.name
               for eqn in parity.walk_eqns(jaxpr) for b in bad)


def test_decode_chunk_is_single_dispatch_without_host_comms(setup):
    """A k-token decode chunk is one traced computation: its jaxpr holds
    no callback/transfer primitives, and a scheduler run records only
    'chunk' StepStats — never per-token 'hit'/'miss' entries."""
    cfg, api, params = setup
    dec = api.decode
    state = jax.eval_shape(lambda: dec.init_state(2, 64))
    tok = jax.ShapeDtypeStruct((2,), jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    temps = jax.ShapeDtypeStruct((2,), jnp.float32)
    act = jax.ShapeDtypeStruct((2,), jnp.bool_)
    eos = jax.ShapeDtypeStruct((2,), jnp.int32)
    closed = jax.make_jaxpr(
        lambda p, s, t, k, tp, a, e: decode_chunk(dec, p, s, t, k, tp, a,
                                                  n_steps=12, eos=e))(
        jax.eval_shape(api.init, jax.random.PRNGKey(0)),
        state, tok, key, temps, act, eos)
    assert not _jaxpr_has_host_comms(closed.jaxpr)

    sched = SlotScheduler(dec, params, slots=2, max_len=128, chunk_size=6)
    sched.submit(Session(np.full(12, 1, np.int32), max_new_tokens=13))
    sched.run()
    kinds = {s.kind for s in sched.stats}
    assert kinds == {"chunk"}
    # 1 prefill token + 12 chunked tokens in exactly 2 dispatches
    assert len(sched.stats) == 2
    assert all(s.tokens == 6 for s in sched.stats)


def test_stepstats_compiled_tagging(setup):
    """Entries whose wall-clock includes the one-time jit compile carry
    compiled=True (exactly the first dispatch of each kind/signature),
    so throughput aggregation can exclude them — a cold first chunk
    must never skew BENCH_inference tok/s again."""
    cfg, api, params = setup
    eng = Engine(api, params, max_len=64)
    p = {"tokens": jnp.ones((1, 8), jnp.int32)}
    eng.generate(p, 6, record_stats=True)
    by_kind = {}
    for s in eng.stats:
        by_kind.setdefault(s.kind, []).append(s.compiled)
    for kind, flags in by_kind.items():
        assert flags[0] and not any(flags[1:]), (kind, flags)
    eng.stats.clear()
    eng.generate(p, 6, record_stats=True)      # warm: nothing compiles
    assert not any(s.compiled for s in eng.stats)


# ---------------------------------------------------------------------------
# DecodeState partition (cache accounting)
# ---------------------------------------------------------------------------


def test_decode_state_partition_and_bytes(setup):
    cfg, api, params = setup
    state = api.init_cache(2, 256)
    assert set(state.bookkeeping) == {"tokens", "hist_len", "gen_len",
                                      "done", "ctx_valid"}
    assert all(k.endswith("_k") or k.endswith("_v") for k in state.kv)
    # partition-based accounting agrees with the core's name-based one
    assert state.kv_bytes() == TC.kv_cache_bytes(state.merged())
    if cfg.attention_mode == "tconst":
        # O(1): kv bytes independent of max_len; bookkeeping is the only
        # O(N) residue (int32 id buffer)
        big = api.init_cache(2, 1 << 14)
        assert big.kv_bytes() == state.kv_bytes()
