"""Scheduling-policy seam: fairness, determinism, cost-aware victims.

Four concerns:

1. **Overtake accounting regression** — the bounded head-skip budget
   must count EVERY admission of a session other than the arrival-order
   head, including RESUME-sourced re-admissions of spilled sessions
   (the pre-policy code counted queue positions, which breaks once
   resumes re-enter at the tail and a policy reorders the try list):
   a page-blocked head sees resumes overtake it at most
   ``max_head_skips`` times, then strict arrival order holds everything
   until the head admits.
2. **Stream identity** — with per-session sampling chains, a session's
   token stream at temperature > 0 is identical across scheduling
   policies and across runs, THROUGH spill/resume cycles (the bench's
   per-session identity gate, in miniature).
3. **Cost-aware victim selection** — ``spill_cost`` ranks a dense-LM
   slot by its live pages (and doubles it: cold re-admission re-pays
   the bytes) while a prompt-pure family (tconst ``admission_key``)
   re-admits for free; ``DeadlineCostPolicy`` spills the cheap slot
   and protects ITL-bound sessions.
4. **Telemetry integration** — a scheduler-attached
   ``ServingTelemetry`` records every submitted session to retirement
   with consistent counters.
"""
import jax
import numpy as np
import pytest

from repro.config import get_config, reduced
from repro.models import layouts as LT
from repro.models.api import build_decode, build_model
from repro.serving.metrics import ServingTelemetry
from repro.serving.policy import (DeadlineCostPolicy, FifoPolicy,
                                  get_policy, ttft_slack)
from repro.serving.scheduler import SlotScheduler
from repro.serving.session import Session
from repro.serving.tier_store import TierStore

PAGE = 8


@pytest.fixture(scope="module")
def tconst_setup():
    cfg = reduced(get_config("tconst_41m"), dtype="float32")
    api = build_model(cfg)
    return cfg, api, api.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def lm_setup():
    cfg = reduced(get_config("llama3_405b"), dtype="float32")
    api = build_model(cfg)
    return cfg, api, api.init(jax.random.PRNGKey(0))


def _prompt(rng, cfg, n):
    return rng.randint(1, cfg.vocab_size, size=n).astype(np.int32)


# ---------------------------------------------------------------------------
# 1. overtake accounting: resumes count against the head-skip budget
# ---------------------------------------------------------------------------


def test_resume_overtakes_count_toward_head_skip_budget(lm_setup):
    cfg, api, params = lm_setup
    spec = LT.LayoutSpec(kind="paged", page_size=PAGE, pool_pages=12)
    decode = build_decode(cfg, spec)
    sched = SlotScheduler(decode, params, slots=3, max_len=96,
                          chunk_size=2, tier_store=TierStore(),
                          max_head_skips=1)
    rng = np.random.RandomState(0)
    small_a = sched.submit(Session(_prompt(rng, cfg, 10),
                                   max_new_tokens=8))
    small_b = sched.submit(Session(_prompt(rng, cfg, 10),
                                   max_new_tokens=8))
    sched.step()                                 # both admitted, 6 free
    assert small_a.slot is not None and small_b.slot is not None
    big = sched.submit(Session(_prompt(rng, cfg, 60), max_new_tokens=16))
    # big needs 10 pages; spilling ONE small leaves 9 free -> head still
    # page-blocked, but the spilled session's RESUME (3 pages) fits
    sched.spill(small_a.slot)
    assert sched.pending[0] is big and sched.pending[1] is small_a
    sched.admit_pending()
    assert small_a.slot is not None              # resumed past the head
    assert sched.admit_stats[-1].source == "resume"
    assert sched._head_skips == 1                # the overtake was counted
    # budget (max_head_skips=1) is now spent: further resumes must NOT
    # overtake the still-blocked head — strict arrival order
    sched.spill(small_a.slot)
    sched.admit_pending()
    assert small_a.slot is None and big.slot is None
    # freeing the other small's pages lets the head in; budget resets
    sched.spill(small_b.slot)
    sched.admit_pending()
    assert big.slot is not None
    assert sched._head_skips == 0


def test_strict_mode_is_policy_proof(tconst_setup):
    # even a policy that always proposes the tail first cannot overtake
    # once the budget is spent: the scheduler only offers it the head
    class TailFirst(FifoPolicy):
        def order_pending(self, pending, sched):
            return list(reversed(pending))

    cfg, api, params = tconst_setup
    spec = LT.LayoutSpec(kind="paged", page_size=PAGE, pool_pages=8)
    decode = build_decode(cfg, spec)
    sched = SlotScheduler(decode, params, slots=1, max_len=64,
                          chunk_size=2, max_head_skips=0,
                          policy=TailFirst())
    rng = np.random.RandomState(1)
    first = sched.submit(Session(_prompt(rng, cfg, 6), max_new_tokens=4))
    second = sched.submit(Session(_prompt(rng, cfg, 6), max_new_tokens=4))
    sched.admit_pending()
    assert first.slot is not None and second.slot is None


# ---------------------------------------------------------------------------
# 2. per-session sampling chains: identity across policies and runs
# ---------------------------------------------------------------------------


def _drive(sched, sessions):
    for s in sessions:
        sched.submit(s)
    sched.run()
    return [tuple(s.tokens) for s in sessions]


def _make_sessions(cfg, n=5):
    rng = np.random.RandomState(7)
    return [Session(_prompt(rng, cfg, int(rng.randint(4, 12))),
                    max_new_tokens=int(rng.randint(4, 9)),
                    temperature=0.8, seed=100 + i) for i in range(n)]


@pytest.fixture(scope="module")
def spilled_streams(tconst_setup):
    """policy -> (token streams, spill count) of one oversubscribed run:
    5 sessions through 2 slots with aggressive preemption, which forces
    spill/resume cycles under every policy."""
    cfg, api, params = tconst_setup
    decode = build_decode(cfg, LT.LayoutSpec(kind="dense"))
    runs = {}
    for policy in ("fifo", "slo"):
        sched = SlotScheduler(decode, params, slots=2, max_len=64,
                              chunk_size=4, tier_store=TierStore(),
                              preempt_chunks=1, policy=policy)
        runs[policy] = (_drive(sched, _make_sessions(cfg)),
                        sched.spill_stats["spills"])
    return runs


@pytest.mark.parametrize("policy", ["fifo", "slo"])
def test_streams_identical_across_policies_through_spills(spilled_streams,
                                                          policy):
    streams, spills = spilled_streams[policy]
    assert spills > 0
    for other, (other_streams, _) in spilled_streams.items():
        assert streams == other_streams, \
            f"token streams changed between policies {policy} and {other}"


def test_streams_identical_across_runs_same_seed(tconst_setup):
    cfg, api, params = tconst_setup
    decode = build_decode(cfg, LT.LayoutSpec(kind="dense"))

    def once():
        sched = SlotScheduler(decode, params, slots=2, max_len=64,
                              chunk_size=4)
        return _drive(sched, _make_sessions(cfg, n=3))

    assert once() == once()


def test_sessions_without_seed_fall_back_to_sid_fold(tconst_setup):
    # no explicit seed: the chain derives from (scheduler seed, sid) —
    # still deterministic for a fixed sid, never slot-position-dependent
    cfg, api, params = tconst_setup
    decode = build_decode(cfg, LT.LayoutSpec(kind="dense"))
    rng = np.random.RandomState(3)
    prompt = _prompt(rng, cfg, 8)

    def run_at_slot(occupy_first):
        sched = SlotScheduler(decode, params, slots=2, max_len=64,
                              chunk_size=4, seed=9)
        if occupy_first:                   # push the probe to slot 1
            sched.submit(Session(_prompt(rng, cfg, 6), max_new_tokens=20,
                                 temperature=0.9, seed=1))
        probe = Session(prompt, max_new_tokens=6, temperature=0.9)
        probe.sid = 12345                  # pin identity across runs
        sched.submit(probe)
        sched.run()
        return tuple(probe.tokens)

    assert run_at_slot(False) == run_at_slot(True)


# ---------------------------------------------------------------------------
# 3. cost model + victim selection
# ---------------------------------------------------------------------------


def test_spill_cost_scales_with_live_pages_and_readmit(lm_setup):
    cfg, api, params = lm_setup
    spec = LT.LayoutSpec(kind="paged", page_size=PAGE, pool_pages=24)
    decode = build_decode(cfg, spec)
    sched = SlotScheduler(decode, params, slots=2, max_len=128,
                          chunk_size=2)
    rng = np.random.RandomState(2)
    short = sched.submit(Session(_prompt(rng, cfg, 6), max_new_tokens=4))
    long = sched.submit(Session(_prompt(rng, cfg, 60), max_new_tokens=4))
    sched.admit_pending()
    c_short = sched.spill_cost(short.slot)
    c_long = sched.spill_cost(long.slot)
    assert c_long["bytes"] > c_short["bytes"]
    # dense-LM admission is not prompt-pure: re-admission re-pays bytes
    assert c_short["readmit"] == c_short["bytes"] > 0
    assert c_long["total"] == 2 * c_long["bytes"]


def test_spill_cost_tconst_readmits_free(tconst_setup):
    cfg, api, params = tconst_setup
    decode = build_decode(cfg, LT.LayoutSpec(kind="dense"))
    sched = SlotScheduler(decode, params, slots=1, max_len=64,
                          chunk_size=2)
    rng = np.random.RandomState(2)
    s = sched.submit(Session(_prompt(rng, cfg, 8), max_new_tokens=4))
    sched.admit_pending()
    cost = sched.spill_cost(s.slot)
    assert cost["readmit"] == 0                  # admission_key: O(1) redo
    assert cost["total"] == cost["bytes"] > 0


def test_deadline_policy_spills_cheapest_and_protects_itl(lm_setup):
    cfg, api, params = lm_setup
    spec = LT.LayoutSpec(kind="paged", page_size=PAGE, pool_pages=24)
    decode = build_decode(cfg, spec)
    sched = SlotScheduler(decode, params, slots=3, max_len=128,
                          chunk_size=2, policy="slo")
    rng = np.random.RandomState(4)
    cheap = sched.submit(Session(_prompt(rng, cfg, 6), max_new_tokens=4))
    costly = sched.submit(Session(_prompt(rng, cfg, 60), max_new_tokens=4))
    bound = sched.submit(Session(_prompt(rng, cfg, 6), max_new_tokens=4,
                                 slo_itl_chunks=1))
    sched.admit_pending()
    ripe = [cheap.slot, costly.slot, bound.slot]
    picks = sched.policy.select_victims(sched, ripe, 3)
    assert picks[0] == cheap.slot                # cheapest bytes first
    assert picks[-1] == bound.slot               # ITL-bound spilled last


def test_deadline_policy_orders_by_slack_then_priority():
    class Clocked:
        clock = 10

    def sess(submit, slo, prio):
        s = Session(np.ones(4, np.int32), max_new_tokens=2, priority=prio,
                    slo_ttft_chunks=slo)
        s.submit_clock = submit
        return s

    tight = sess(9, 4, 0)              # slack 3
    loose = sess(0, 30, 0)             # slack 20
    free = sess(0, None, 0)            # slack inf
    vip = sess(9, 4, 2)                # slack 3, higher priority
    order = DeadlineCostPolicy().order_pending(
        [free, loose, tight, vip], Clocked())
    assert order == [vip, tight, loose, free]
    assert ttft_slack(free, 10) == float("inf")


def test_get_policy_registry():
    assert get_policy("fifo").name == "fifo"
    assert get_policy("slo").name == "slo"
    with pytest.raises(ValueError):
        get_policy("lifo")
    with pytest.raises(ValueError):
        DeadlineCostPolicy(defer_slack=-1)


# ---------------------------------------------------------------------------
# 4. telemetry through the scheduler
# ---------------------------------------------------------------------------


def test_telemetry_tracks_every_session_to_retirement(tconst_setup):
    cfg, api, params = tconst_setup
    decode = build_decode(cfg, LT.LayoutSpec(kind="dense"))
    tel = ServingTelemetry()
    sched = SlotScheduler(decode, params, slots=2, max_len=64,
                          chunk_size=4, tier_store=TierStore(),
                          preempt_chunks=1, telemetry=tel)
    sessions = _make_sessions(cfg, n=4)
    _drive(sched, sessions)
    assert len(tel.records) == 4
    for s in sessions:
        rec = tel.records[s.sid]
        assert rec.done and rec.tokens_out == len(s.tokens)
        assert rec.ttft_chunks is not None and rec.ttft_chunks >= 1
        assert rec.queue_wait_chunks is not None
        assert rec.spills == s.spills and rec.resumes == s.resumes
    summary = tel.summary()
    assert summary["finished"] == 4
    assert summary["spills"] == sched.spill_stats["spills"] > 0
    assert len(tel.occupancy) == sched.clock
