"""The check that decides ``correct``, driven through a whole run of tiny
cells on the CPU (the look for a chip skipped): sound runs pass, the
fp8 control put in the program's place fails, and a token altered where
the program produces it fails.

The limit here is this test's own, for these tiny float32 sizes, where
the program matches the reference to rounding: sound runs read 0, the
control 0.03-0.2 (measured on the CPU).  The cells' limits are set from
readings on the chip at their own sizes (PERF.md)."""
import time

import pytest

import tiny
import cell as C

LIMIT = 0.01


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(C.M, "load_peaks", lambda kind, root=None:
                        tiny.CPU_PEAKS)


def _run(name, seed, **kw):
    cell = tiny.tiny_cell(name)
    cell.traffic["check"]["logit_gap_limit"] = LIMIT
    return C.run(cell, seed, 2.0, False, time.perf_counter(),
                 log=lambda s: None, **kw)


@pytest.mark.parametrize("name,metrics", [
    ("tconst41m.stream-long", {"tokens_per_s", "setup_s"}),
    ("smollm360m.agent-prefix", {"tokens_per_s", "setup_s"})])
def test_sound_run_passes_and_control_fails(name, metrics):
    out = _run(name, 2 ** 31 + 3, control=True)
    ch = out["checks"]
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert ch["logit_gap"]["value"] <= LIMIT
    ctl = out["control"]
    assert not ctl["correct"]
    assert ctl["checks"]["logit_gap"]["value"] > LIMIT
    assert ctl["checks"]["unanswered"] == ch["unanswered"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == metrics


def test_token_altered_where_produced_fails(monkeypatch):
    import repro.serving.scheduler as S
    real = S.decode_chunk

    def altered(*args, **kw):
        toks, state, key = real(*args, **kw)
        return toks.at[:, -1].set((toks[:, -1] + 1) % 512), state, key

    monkeypatch.setattr(S, "decode_chunk", altered)
    out = _run("tconst41m.stream-long", 2 ** 31 + 3)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > LIMIT


def test_open_loop_run_reports_its_tails():
    out = _run("tconst41m.short-poisson", 17)
    assert out["correct"]
    assert set(out["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert out["attempted"] == round(tiny.TINY_TRAFFIC["short-poisson"]
                                     ["rate"] * 2.0)
