"""Workload generator + serving-telemetry units (no model, no device).

The serving bench's comparisons are only meaningful if (1) the traffic
trace is a pure function of ``(spec, seed)`` — both policies must replay
the SAME sessions — and (2) the telemetry aggregation is exact on known
inputs.  Everything here is host-side and fast; the scheduler-integrated
end is covered in ``test_serving_policy.py``.
"""
import numpy as np
import pytest

from repro.serving.metrics import (SessionRecord, ServingTelemetry,
                                   percentile)
from repro.serving.session import Session
from repro.serving.workload import (Arrival, WorkloadSpec,
                                    generate_workload)

VOCAB = 512


def _spec(**kw):
    base = dict(n_sessions=40, vocab=VOCAB)
    base.update(kw)
    return WorkloadSpec(**base)


# ---------------------------------------------------------------------------
# workload generation
# ---------------------------------------------------------------------------


def test_workload_deterministic_in_spec_and_seed():
    a = generate_workload(_spec(), seed=7)
    b = generate_workload(_spec(), seed=7)
    assert len(a) == len(b) == 40
    for x, y in zip(a, b):
        assert x.at_chunk == y.at_chunk
        np.testing.assert_array_equal(x.session.prompt, y.session.prompt)
        assert x.session.max_new_tokens == y.session.max_new_tokens
        assert x.session.seed == y.session.seed
        assert x.session.priority == y.session.priority
        assert x.session.slo_ttft_chunks == y.session.slo_ttft_chunks
    c = generate_workload(_spec(), seed=8)
    assert any(x.at_chunk != z.at_chunk or
               not np.array_equal(x.session.prompt, z.session.prompt)
               for x, z in zip(a, c))


def test_workload_sorted_and_shaped_by_mixes():
    arrivals = generate_workload(_spec(
        prompt_mix=((1.0, 5, 9),), output_mix=((1.0, 3, 4),)), seed=0)
    chunks = [a.at_chunk for a in arrivals]
    assert chunks == sorted(chunks) and chunks[0] >= 0
    for a in arrivals:
        assert 5 <= len(a.session.prompt) <= 9
        assert 3 <= a.session.max_new_tokens <= 4
        assert a.session.prompt.dtype == np.int32
        assert int(a.session.prompt.max()) < VOCAB


def test_bursty_arrivals_pile_up_on_shared_chunks():
    arrivals = generate_workload(_spec(
        arrival="bursty", burst_size=8, burst_every=50.0), seed=1)
    chunks = [a.at_chunk for a in arrivals]
    # bursts drop many sessions on one chunk: far fewer distinct chunks
    # than sessions (a poisson trace at matched load has no such pileup)
    assert len(set(chunks)) < len(chunks) // 2


def test_shared_prefix_population_reuses_the_common_heads():
    spec = _spec(shared_frac=1.0, n_prefixes=2, prefix_len=8,
                 prompt_mix=((1.0, 4, 6),))
    arrivals = generate_workload(spec, seed=2)
    heads = {a.session.prompt[:8].tobytes() for a in arrivals}
    assert len(heads) <= 2                     # every prompt uses one of 2
    assert all(len(a.session.prompt) > 8 for a in arrivals)


def test_repeat_population_reissues_verbatim_prompts():
    arrivals = generate_workload(_spec(repeat_frac=0.9), seed=3)
    seen = set()
    repeats = 0
    for a in arrivals:
        key = a.session.prompt.tobytes()
        repeats += key in seen
        seen.add(key)
    assert repeats >= len(arrivals) // 2


def test_slo_slice_carries_targets_and_priority():
    every = generate_workload(_spec(slo_frac=1.0, slo_ttft_chunks=5,
                                    slo_itl_chunks=2, slo_priority=3),
                              seed=4)
    for a in every:
        assert a.session.slo_ttft_chunks == 5
        assert a.session.slo_itl_chunks == 2
        assert a.session.priority == 3
    none = generate_workload(_spec(slo_frac=0.0), seed=4)
    assert all(a.session.slo_ttft_chunks is None for a in none)
    assert all(a.session.priority == 0 for a in none)


def test_max_prompt_len_clips():
    arrivals = generate_workload(_spec(prompt_mix=((1.0, 30, 60),)),
                                 seed=5, max_prompt_len=12)
    assert max(len(a.session.prompt) for a in arrivals) <= 12


@pytest.mark.parametrize("bad", [
    dict(n_sessions=0), dict(arrival="uniform"), dict(rate=0.0),
    dict(arrival="bursty", burst_size=0), dict(slo_frac=1.5),
    dict(prompt_mix=()), dict(prompt_mix=((1.0, 9, 4),)),
    dict(output_mix=((0.0, 1, 2),)),
])
def test_workload_spec_validation(bad):
    with pytest.raises(ValueError):
        _spec(**bad)


# ---------------------------------------------------------------------------
# telemetry aggregation
# ---------------------------------------------------------------------------


def test_percentile_nearest_rank():
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert percentile(xs, 50) == 3.0
    assert percentile(xs, 99) == 5.0           # a value a session saw
    assert percentile(xs, 0) == 1.0
    assert percentile([], 50) is None


def _session(**kw):
    base = dict(prompt=np.arange(1, 5, dtype=np.int32), max_new_tokens=4)
    base.update(kw)
    return Session(**base)


def test_telemetry_ttft_itl_and_slo_accounting():
    tel = ServingTelemetry()
    s = _session(slo_ttft_chunks=3, slo_itl_chunks=2)
    tel.on_submit(s, clock=2)
    tel.on_admit(s, clock=4, source="cold")
    tel.on_tokens(s, 1, clock=4, compiled=True)    # first token, compiling
    tel.on_tokens(s, 2, clock=6, compiled=False)   # gap 2, then same-tick 0
    tel.on_tokens(s, 1, clock=9, compiled=False)   # gap 3: ITL SLO miss
    tel.on_retire(s, clock=9)
    rec = tel.records[s.sid]
    assert rec.queue_wait_chunks == 2
    assert rec.ttft_chunks == 2 and rec.ttft_ok is True
    assert rec.ttft_compiled and rec.ttft_seconds is None   # excluded
    assert rec.itl_gaps_chunks == [2, 0, 3]
    assert rec.itl_ok is False and rec.slo_ok is False
    assert rec.tokens_out == 4 and rec.done


def test_telemetry_starved_slo_session_counts_as_miss():
    tel = ServingTelemetry()
    s = _session(slo_ttft_chunks=4)
    tel.on_submit(s, clock=0)
    assert tel.records[s.sid].ttft_ok is False       # no token ever
    t = _session()                                   # no SLO at all
    tel.on_submit(t, clock=0)
    assert tel.records[t.sid].slo_ok is None
    summary = tel.summary()
    assert summary["sessions"] == 2
    assert summary["slo"]["sessions_with_slo"] == 1
    assert summary["slo"]["attainment"] == 0.0


def test_telemetry_summary_shapes():
    tel = ServingTelemetry()
    for clock, s in enumerate([_session(), _session(slo_ttft_chunks=9)]):
        tel.on_submit(s, clock=clock)
        tel.on_admit(s, clock=clock + 1, source="cold")
        tel.on_tokens(s, 1, clock=clock + 1, compiled=False)
        tel.on_tokens(s, 1, clock=clock + 2, compiled=False)
        tel.on_retire(s, clock=clock + 2)
    tel.on_tick(1, n_active=2, n_pending=0, free_pages=4, total_pages=8)
    s = tel.summary()
    assert s["finished"] == 2 and s["tokens_out"] == 4
    assert s["ttft_chunks"]["p50"] == 1.0
    assert s["itl_chunks"]["p99"] == 1.0
    assert s["queue_wait_chunks"]["p50"] == 1.0
    assert s["ttft_seconds_warm"]["n"] == 2
    assert s["slo"]["ttft_attainment"] == 1.0
    assert s["pool_occupancy_mean"] == 0.5


def test_telemetry_queue_wait_seconds_reads_the_session_stamps():
    """Wall queue wait runs from the session's ``submit_wall`` to its first
    ``admit_wall`` (the scheduler's ``StepStats`` clock); a resume does
    not move it."""
    tel = ServingTelemetry()
    waits = [0.25, 0.5, 2.0]
    for i, w in enumerate(waits):
        s = _session()
        s.submit_wall = 100.0 + i
        tel.on_submit(s, clock=i)
        s.admit_wall = s.submit_wall + w
        tel.on_admit(s, clock=i + 1, source="cold")
        s.admit_wall += 7.0                          # spilled, resumed later
        tel.on_admit(s, clock=i + 9, source="resume")
        assert tel.records[s.sid].queue_wait_seconds == pytest.approx(w)
    q = tel.summary()["queue_wait_s"]
    assert q["p50"] == pytest.approx(0.5) and q["p99"] == pytest.approx(2.0)
    assert tel.summary()["queue_wait_chunks"]["p50"] == 1.0


def test_session_record_single_token_stream_meets_itl():
    rec = SessionRecord(sid=0, slo_itl_chunks=1)
    rec.tokens_out = 1
    assert rec.itl_ok is True                   # no gaps to violate
