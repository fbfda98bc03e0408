"""The metric readers on a run made by hand: what each end-to-end metric
takes over, and that the per-layer readers read nothing where there is
nothing to read."""
import numpy as np
import pytest

import tiny
import cell as C
import manifest as M
from observer import Record
from stats import percentile

CELL = M.load_cell("smollm360m.agent-prefix")
SHORT = M.cell_metrics(M.load_manifest(), "tconst41m.short-poisson")
READ = {m.name: m.reader.read for m in
        SHORT["end_to_end"] + SHORT["per_layer"] + CELL.end_to_end +
        CELL.per_layer}


def run_of(records, deliveries=(), admissions=(), traced=None):
    return C.RunData(cell=CELL, peaks=tiny.CPU_PEAKS, seed=1, setup_s=12.5,
                     t0=10.0, t1=20.0, records=records,
                     deliveries=list(deliveries), admissions=list(admissions),
                     chunk_s=[0.08, 0.09, 0.07], admit_s=[0.004, 0.006],
                     compiles=0, late_s=[], traced=traced)


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert percentile(xs, 95) == 95 and percentile(xs, 50) == 50
    assert percentile([3.0], 95) == 3.0 and percentile([], 50) is None


@pytest.mark.parametrize("name", ["ttft_p95_ms", "ttft_p95_ms.closed"])
def test_ttft_counts_requests_sent_in_the_window_from_their_send_time(name):
    recs = [Record(i, None, 10, sent=10.0 + i / 2,
                   first=10.0 + i / 2 + 0.01 * (i + 1))
            for i in range(20)]
    recs.append(Record(99, None, 10, sent=9.0, first=15.0))    # before
    assert READ[name](run_of(recs)) == pytest.approx(190.0)
    recs.append(Record(98, None, 10, sent=19.5, first=None))   # never came
    # 1 of 21 missing lies above the 95th percentile
    assert READ[name](run_of(recs)) == pytest.approx(200.0)
    recs.append(Record(97, None, 10, sent=19.6, first=None))
    # 2 of 22: the tail is infinite, and nothing is read
    assert READ[name](run_of(recs)) is None


def test_tpot_over_requests_completed_in_the_window():
    recs = [Record(1, None, 10, sent=11, first=12.0, last=12.9, n=10,
                   done=12.9),
            Record(2, None, 10, sent=11, first=12.0, last=22.0, n=11,
                   done=22.0),                                 # after
            Record(3, None, 10, sent=11, first=12.0, last=12.0, n=1,
                   done=12.0)]                                 # one token
    assert READ["tpot_p95_ms"](run_of(recs)) == pytest.approx(100.0)


def test_tokens_per_s_over_the_window():
    d = [(9.9, 1, 1, 16), (10.0, 1, 17, 16), (15.0, 2, 0, 1), (20.0, 2, 1, 16),
         (20.1, 2, 17, 16)]
    assert READ["tokens_per_s"](run_of([], d)) == pytest.approx(3.3)
    assert READ["setup_s"](run_of([])) == 12.5


def test_span_medians_and_silent_trace_readers():
    r = run_of([])
    assert READ["chunk_ms_p50.tput"](r) == pytest.approx(80.0)
    assert READ["admit_ms_p50"](r) == pytest.approx(4.0)
    assert READ["admit_ms_p50.closed"](r) == pytest.approx(4.0)
    assert READ["paged_attn_roofline"](r) is None
    assert READ["step_mfu"](r) is None


def test_roofline_and_mfu_from_live_lengths():
    # one slot of 1100 tokens delivers 16 tokens (positions 1100..1115)
    recs = [Record(7, None, 1100, sent=10.0, first=10.1)]
    d = [(12.0, 7, 1, 16)]
    red = {"window_s": 2.0, "busy_s": 1.0,
           "kernel_s": {"paged_decode_attention": 1e-3}}
    tr = C.Traced(t0=11.0, t1=13.0, reduction=red)
    run = run_of(recs, d, traced=tr)
    pos = 1100 + np.arange(1, 17) - 1
    nbytes, flops = CELL.model.kernel_work(CELL.config,
                                           "paged_decode_attention", pos)
    want = 100 * max(nbytes / 1e11, flops / 1e12) / 1e-3
    assert READ["paged_attn_roofline"](run) == pytest.approx(want)
    mfu = 100 * CELL.model.decode_flops(CELL.config, pos) / (2.0 * 1e12)
    assert READ["step_mfu"](run) == pytest.approx(mfu)


def test_resident_prefix_from_forwarded_count():
    # the traffic alone: prefix 1024 on the page (64) and chunk (128)
    # grid, whatever the program forwarded
    r = run_of([])
    assert r.resident(1100) == 1024
    assert r.resident(1024) == 896               # chunk grid of L - 1
    same = r.work(10.0, 20.0)[0]
    r2 = run_of([], admissions=[(12.0, 1, 1100)])
    want = CELL.model.admit_flops(CELL.config, 1100, 1024)
    assert r2.work(10.0, 20.0)[0] - same == pytest.approx(want)


def test_check_samples_finished_and_in_flight_requests():
    class S:
        def __init__(self, n):
            self.tokens = list(range(n))
    recs = [Record(1, None, 10, sent=11, first=12, n=5, done=13,
                   session=S(5)),
            Record(2, None, 900, sent=11, first=12, n=40, session=S(40)),
            Record(3, None, 10, sent=11, first=None, n=0, session=S(0)),
            Record(4, None, 20, sent=11, first=12, n=3, session=S(3))]
    got = C.sample_for_check(run_of(recs), 5)
    assert got[0].sid == 2                       # longest, still in flight
    assert sorted(r.sid for r in got) == [1, 2, 4]
