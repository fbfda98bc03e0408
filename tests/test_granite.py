"""granite-4.0-h-micro on the served path, against its plain reference at
a tiny size on the CPU: Mamba-2 and attention layers interleaved in one
cache, 10 layers in the published order (5 Mamba-2, attention, 4
Mamba-2).

The reference is the benchmark's own (``bench/configs/granite-4.0-h-
micro.py``): float32, the mixer as its position-by-position recurrence,
nothing of the program.  Program and reference run float32 on the same
seeded weights, so logits agree to rounding (the chunked SSD against the
sequential recurrence, the page walk against dense softmax): 2e-5 on
logits that spread about 0.01 across a row.  The whole forward reads
1.5e-8 from the reference in float32 and 4.3e-4 in bfloat16 activations;
the default 1/sqrt(hd) score scale in place of granite's 1/64 reads
1.1e-3, and no residual multiplier 2.9e-3.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import runtime
from repro.config import get_config
from repro.models import layouts as LT
from repro.models.api import build_decode, decode_chunk
from repro.serving.scheduler import SlotScheduler
from repro.serving.session import Session
from repro.serving.tier_store import TierStore

import parity as PT

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
import manifest as M  # noqa: E402

TOL = 2e-5
PAGE = 8
TINY = dict(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
            vocab_size=512, ssm_state=16, ssm_heads=4, ssm_head_dim=16,
            ssm_expand=1, ssm_chunk=8, dtype="float32")
REF = M.load_module(os.path.join(BENCH, "configs", "granite-4.0-h-micro.py"))


@pytest.fixture(scope="module")
def granite():
    """(cfg, weights, reference config dict) at the tiny size."""
    full = get_config("granite-4.0-h-micro")
    cfg = full.replace(n_layers=10, layer_pattern=list(full.layer_pattern[:10]),
                       **TINY)
    cfg.validate()
    c = {"model": {f: getattr(cfg, f) for f in (
        "vocab_size", "d_model", "n_heads", "n_kv_heads", "head_dim",
        "d_ff", "layer_pattern", "ssm_expand", "ssm_state", "ssm_heads",
        "ssm_head_dim", "ssm_conv", "norm_eps", "attention_multiplier",
        "embedding_multiplier", "residual_multiplier", "logits_scaling")},
        "serving": {"page_size": PAGE}}
    weights = jax.jit(lambda k: REF.make_weights(k, c))(
        jax.random.PRNGKey(11))
    return cfg, weights, c


def ref_logits(weights, c, seq):
    """Reference logits (len(seq), V) at every position of ``seq``."""
    pad = -(-len(seq) // 8) * 8
    with jax.default_matmul_precision("highest"):
        blocks = list(REF.reference_blocks(weights, np.asarray(seq), 0, c,
                                           pad))
    return np.concatenate([np.asarray(b) for _, b in blocks])[:len(seq)]


def test_layer_runs_and_cache_split(granite):
    from repro.models import lm as LM
    cfg, weights, _ = granite
    assert LM.layer_runs(cfg) == (("mamba", 0, 5), ("attention", 0, 1),
                                  ("mamba", 5, 4))
    assert cfg.has_recurrent_state and cfg.has_growing_kv
    shapes = jax.eval_shape(lambda: LM.init_kv_cache(cfg, 3, 32))
    assert shapes["k"].shape == (1, 3, 32, 2, 16)
    assert shapes["ssm"].shape == (9, 3, 4, 16, 16)
    assert shapes["conv"].shape == (9, 3, 3, 4 * 16 + 2 * 16)
    # the paged pool holds the attention layer's K/V only
    dec = build_decode(cfg, LT.LayoutSpec("paged", PAGE, 8))
    state = dec.init_state(3, 32)
    assert state.kv["k"].shape == (1, 9, PAGE, 2, 16)
    assert state.kv["ssm"].shape == shapes["ssm"].shape
    # fixed: the recurrent state whatever the streams' lengths; growing:
    # the pages the tables reference (none assigned yet)
    split = state.state_bytes(dec._LENGTH_AXES)
    assert split == {"fixed": state.kv["ssm"].nbytes +
                     state.kv["conv"].nbytes, "growing": 0}


def test_forward_matches_reference(granite):
    from repro.models.lm import lm_forward
    cfg, weights, c = granite
    seq = np.random.RandomState(0).randint(1, 512, size=37)
    logits, _ = lm_forward(weights, jnp.asarray(seq)[None], cfg, remat=False)
    np.testing.assert_allclose(np.asarray(logits[0]),
                               ref_logits(weights, c, seq), atol=TOL)


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_prefill_and_cached_decode_match_reference(granite, kind):
    """Chunked admission (13 and 21 prompt tokens over chunks of 8, so
    neither prompt is a chunk multiple; one-shot for a third), then
    teacher-forced decode through the cache: every position's logits
    against the reference's full forward."""
    cfg, weights, c = granite
    spec = None if kind == "dense" else LT.LayoutSpec("paged", PAGE, 24)
    dec = build_decode(cfg, spec, prefill_chunk=8)
    rng = np.random.RandomState(1)
    seqs = [rng.randint(1, 512, size=n) for n in (30, 36, 25)]
    plen = (13, 21, 9)
    state = dec.init_state(3, 64)
    if kind == "paged":
        pt = np.arange(3 * 8, dtype=np.int32).reshape(3, 8)
        state = state.with_bookkeeping(**{LT.PAGE_TABLE: jnp.asarray(pt)})
    first = []
    for s, (seq, n) in enumerate(zip(seqs, plen)):
        if s < 2:
            lg, state, info = dec.prefill_into_slot_chunked(
                weights, state, np.int32(s), seq[:n], chunk=8)
            assert info["forward_tokens"] == -(-n // 8) * 8
        else:
            lg, state = dec.prefill_into_slot(weights, state, np.int32(s),
                                              jnp.asarray(seq[:n]))
        first.append(np.asarray(lg))
    refs = [ref_logits(weights, c, seq) for seq in seqs]
    for s, n in enumerate(plen):
        np.testing.assert_allclose(first[s], refs[s][n - 1], atol=TOL)
    step = jax.jit(dec.step)
    for t in range(9):
        tok = jnp.asarray([seq[n + t] for seq, n in zip(seqs, plen)],
                          jnp.int32)
        lg, state = step(weights, state, tok)
        for s, n in enumerate(plen):
            np.testing.assert_allclose(np.asarray(lg[s]), refs[s][n + t],
                                       atol=TOL)


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_scheduler_streams_follow_reference(granite, kind):
    """Greedy streams served through the SlotScheduler: each served
    token is the reference's best at its position, to rounding."""
    cfg, weights, c = granite
    prompts = PT.make_prompts(cfg, (13, 21, 7), seed=4)
    streams, sched = PT.serve_streams(
        cfg, weights, prompts, PT.layout_spec(kind, PAGE, 24), gen=12,
        slots=2, max_len=48, chunk_size=4, prefill_chunk=8)
    assert sched.decode.layout.kind == kind
    for p, out in zip(prompts, streams):
        seq = np.concatenate([p, out])
        ref = ref_logits(weights, c, seq)
        rows = np.arange(len(p) - 1, len(seq) - 1)
        best = ref[rows].max(axis=-1)
        got = ref[rows, seq[rows + 1]]
        assert np.max(best - got) <= TOL


def _admitted_pair(cfg, weights):
    """A paged decode and a 2-slot state, both slots admitted."""
    dec = build_decode(cfg, LT.LayoutSpec("paged", PAGE, 24),
                       prefill_chunk=8)
    state = dec.init_state(2, 48)
    pt = np.arange(2 * 6, dtype=np.int32).reshape(2, 6)
    state = state.with_bookkeeping(**{LT.PAGE_TABLE: jnp.asarray(pt)})
    rng = np.random.RandomState(2)
    for s in range(2):
        _, state, _ = dec.prefill_into_slot_chunked(
            weights, state, np.int32(s), rng.randint(1, 512, size=11),
            chunk=8)
    return dec, state


def _chunk_slot_1_frozen(dec, weights, state):
    toks, after, _ = decode_chunk(
        dec, weights, state, jnp.asarray([3, 5], jnp.int32),
        jax.random.PRNGKey(0), jnp.zeros((2,)), jnp.asarray([True, False]),
        4)
    return toks, after


def test_frozen_row_bit_identical_through_a_chunk(granite):
    cfg, weights, _ = granite
    dec, state = _admitted_pair(cfg, weights)
    before = state.merged()
    toks, after = _chunk_slot_1_frozen(dec, weights, state)
    after = after.merged()
    for f in ("k", "v", "ssm", "conv", "len"):
        ax = state.axes[f]
        np.testing.assert_array_equal(
            np.take(np.asarray(after[f]), 1, axis=ax),
            np.take(np.asarray(before[f]), 1, axis=ax), err_msg=f)
        assert not np.array_equal(np.take(np.asarray(after[f]), 0, axis=ax),
                                  np.take(np.asarray(before[f]), 0, axis=ax))
    assert np.all(np.asarray(toks[1]) == 5)


def test_frozen_row_bit_identical_on_the_pallas_path(granite, monkeypatch):
    """The same chunk with the Mamba-2 state stepped in place by the
    kernel (interpret mode): the frozen slot's state comes through bit
    for bit, the live slot's follows the XLA path to rounding."""
    cfg, weights, _ = granite
    dec, state = _admitted_pair(cfg, weights)
    toks_xla, xla = _chunk_slot_1_frozen(dec, weights, state)
    monkeypatch.setattr(runtime.flags, "use_pallas", True)
    monkeypatch.setattr(runtime.flags, "pallas_interpret", True)
    toks, got = _chunk_slot_1_frozen(dec, weights, state)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(toks_xla))
    for f in ("ssm", "conv"):
        np.testing.assert_array_equal(np.asarray(got.kv[f])[:, 1],
                                      np.asarray(state.kv[f])[:, 1],
                                      err_msg=f)
        np.testing.assert_allclose(np.asarray(got.kv[f])[:, 0],
                                   np.asarray(xla.kv[f])[:, 0],
                                   rtol=1e-5, atol=1e-5, err_msg=f)


def _visits(jaxpr, want, times=1):
    """(equation, times it runs) for every equation ``want`` accepts,
    through nested jaxprs; a scan body runs ``length`` times."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for eqn in jaxpr.eqns:
        if want(eqn):
            yield eqn, times
        if eqn.primitive.name == "pallas_call":
            continue
        n = times * eqn.params.get("length", 1)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if isinstance(sub, ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, Jaxpr):
                    yield from _visits(sub, want, n)


@pytest.mark.parametrize("pallas", [False, True])
def test_mamba_state_steps_in_place_in_its_stack(granite, monkeypatch,
                                                 pallas):
    """On the Pallas path a decode step runs one ``ssd_state_update``
    kernel per Mamba-2 layer, the state stack aliased in and out, and
    neither slices the stack nor writes a layer back into it; the XLA
    path does both, once a layer."""
    cfg, weights, _ = granite
    dec, state = _admitted_pair(cfg, weights)
    if pallas:
        monkeypatch.setattr(runtime.flags, "use_pallas", True)
        monkeypatch.setattr(runtime.flags, "pallas_interpret", True)
    stack = state.kv["ssm"].shape
    jaxpr = jax.make_jaxpr(dec.raw_step)(
        weights, state, jnp.asarray([3, 5], jnp.int32),
        jnp.asarray([True, False])).jaxpr
    n_mamba = cfg.layer_kinds.count("mamba")
    calls = list(_visits(jaxpr, lambda e: e.primitive.name == "pallas_call"
                         and e.params["name"] == "ssd_state_update"))
    on_stack = list(_visits(jaxpr, lambda e: e.primitive.name in (
        "dynamic_slice", "dynamic_update_slice")
        and e.invars[0].aval.shape == stack))
    if pallas:
        assert sum(n for _, n in calls) == n_mamba
        for eqn, _ in calls:
            (src, dst), = eqn.params["input_output_aliases"]
            assert eqn.invars[src].aval.shape == stack
            assert eqn.outvars[dst].aval.shape == stack
        assert on_stack == []
    else:
        assert calls == []
        assert sorted(e.primitive.name for e, n in on_stack
                      for _ in range(n)) == \
            ["dynamic_slice"] * n_mamba + ["dynamic_update_slice"] * n_mamba


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_spill_resume_token_identical(granite, kind):
    cfg, weights, _ = granite
    prompts = PT.make_prompts(cfg, (13, 9, 17, 11), seed=6)

    def run(slots, store=None, preempt=None):
        sched = SlotScheduler(
            build_decode(cfg, PT.layout_spec(kind, PAGE, 40)), weights,
            slots=slots, max_len=48, chunk_size=4, prefill_chunk=8,
            tier_store=store, preempt_chunks=preempt)
        sessions = [sched.submit(Session(p, max_new_tokens=10))
                    for p in prompts]
        sched.run()
        return sched, [s.tokens for s in sessions]

    _, ref = run(slots=4)
    sched, got = run(slots=2, store=TierStore(capacity_bytes=1 << 30),
                     preempt=1)
    PT.assert_streams_equal(ref, got, f"granite spill/resume {kind}")
    assert sched.spill_stats["spills"] == sched.spill_stats["resumes"] > 0


def test_interleaved_hybrid_refuses_speculation_and_forwards_from_zero(
        granite):
    cfg, weights, _ = granite
    dec = build_decode(cfg, LT.LayoutSpec("paged", PAGE, 24),
                       prefill_chunk=8)
    assert not dec.supports_speculative()
    assert dec._chunk_resident_start(16) == 0
    with pytest.raises(ValueError, match="speculatively"):
        SlotScheduler(dec, weights, slots=2, max_len=48, speculate=2)
    # prefix sharing adopts the shared pages but the recurrence still
    # forwards the whole prompt
    prompts = PT.shared_prompts(cfg, 2, common_len=16, tail_len=5)
    streams, sched = PT.serve_streams(
        cfg, weights, prompts, PT.layout_spec("paged", PAGE, 24), gen=4,
        stagger=False, slots=2, max_len=48, prefill_chunk=8,
        prefix_sharing=True)
    fwd = [a.forward_tokens for a in sched.admit_stats
           if a.source == "cold"]
    assert fwd == [24, 24]
    ref, _ = PT.serve_streams(
        cfg, weights, prompts, PT.layout_spec("paged", PAGE, 24), gen=4,
        stagger=False, slots=2, max_len=48, prefill_chunk=8)
    PT.assert_streams_equal(ref, streams, "granite shared prefix")
