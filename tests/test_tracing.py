"""The serving path's tracing: ``StepStats`` admission records on the
``perf_counter`` clock, ``serve.*`` host spans in a live profile, and the
stable module names of the scheduler's jitted programs."""
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from repro.models.api import build_decode
from repro.serving.metrics import ServingTelemetry
from repro.serving.scheduler import SlotScheduler
from repro.serving.session import Session
from repro.serving.tier_store import TierStore

# (family, layout kind, prefill chunk): the tconst cells' dense one-shot
# admission and a paged LM with prefix sharing and chunked admission
SCHEDULERS = {
    "tconst": ("tconst", "dense", None),
    "lm_paged": ("lm", "paged", parity.PAGE),
}


def _scheduler(name, **kw):
    fam, kind, chunk = SCHEDULERS[name]
    cfg, _, params = parity.family(fam)
    spec = parity.layout_spec(kind, pool_pages=48)
    sched = SlotScheduler(build_decode(cfg, spec), params, slots=2,
                          max_len=96, chunk_size=4, prefill_chunk=chunk,
                          prefix_sharing=kind == "paged", **kw)
    return cfg, sched


@pytest.mark.parametrize("name", sorted(SCHEDULERS))
def test_admit_records_carry_their_spans(name):
    """Each admission's ``StepStats`` holds its start, session, queue wait
    and whole span; the prefill (``seconds``) lies inside the whole, the
    queue wait inside the run, and telemetry reads the same clock."""
    cfg, sched = _scheduler(name, telemetry=ServingTelemetry())
    prompts = parity.shared_prompts(cfg, 4, common_len=32, tail_len=8)
    t_run = time.perf_counter()
    sessions = [sched.submit(Session(p, max_new_tokens=6)) for p in prompts]
    sched.run()
    wall = time.perf_counter() - t_run
    by_sid = {s.sid: s for s in sessions}
    admits = sched.admit_stats
    assert sorted(st.sid for st in admits) == sorted(by_sid)
    for st in admits:
        s = by_sid[st.sid]
        assert st.start == s.admit_wall
        assert st.queued_s == pytest.approx(s.admit_wall - s.submit_wall)
        assert 0.0 <= st.queued_s <= wall
        assert st.total_s >= st.seconds > 0.0
        assert t_run <= st.start and st.start + st.total_s <= t_run + wall
        rec = sched.telemetry.records[st.sid]
        assert rec.queue_wait_seconds == pytest.approx(st.queued_s)
    # two slots, four sessions: the last two waited behind a chunk
    assert max(st.queued_s for st in admits) > min(st.queued_s
                                                  for st in admits)
    chunks = [st for st in sched.stats if st.kind == "chunk"]
    assert chunks and all(st.seconds > 0.0 for st in chunks)
    starts = [st.start for st in chunks]
    assert starts == sorted(starts)
    for a, b in zip(chunks, chunks[1:]):
        assert a.start + a.seconds <= b.start      # one chunk at a time
    assert all(st.sid is None and st.queued_s is None for st in chunks)


def _host_events(log_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("serve.", "bench.")):
                        out.append((e.name, e.start_ns, e.end_ns,
                                    {k: v for k, v in e.stats}))
    return out


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_live_profile_nests_serve_spans(tmp_path):
    """A profile of a process running the scheduler shows the ``serve.*``
    spans on the host plane, each inside the caller's span and each
    phase inside the span that causes it."""
    cfg, sched = _scheduler("lm_paged")
    prompts = parity.shared_prompts(cfg, 3, common_len=32, tail_len=8)
    warm = sched.submit(Session(prompts[0], max_new_tokens=5))
    sched.run()                                   # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        for p in prompts[1:]:
            sched.submit(Session(p, max_new_tokens=5))
        while sched.pending or sched.active.any():
            with jax.profiler.TraceAnnotation("bench.step"):
                sched.step()
    finally:
        jax.profiler.stop_trace()
    assert warm.done
    evs = _host_events(tmp_path)
    names = {e[0] for e in evs}
    assert {"serve.step", "serve.admit", "serve.admit.plan",
            "serve.admit.prefill", "serve.admit.first_token", "serve.cow",
            "serve.chunk.dispatch", "serve.chunk.sync", "serve.deliver",
            "serve.release"} <= names
    outer = [e for e in evs if e[0] == "bench.step"]
    steps = [e for e in evs if e[0] == "serve.step"]
    assert steps and all(any(_inside(s, b) for b in outer) for s in steps)
    for e in evs:
        if e[0].startswith("serve.") and e[0] != "serve.step":
            assert any(_inside(e, s) for s in steps), e[0]
    admits = [e for e in evs if e[0] == "serve.admit"]
    assert len(admits) == 2
    assert {int(e[3]["sid"]) for e in admits} == \
        {st.sid for st in sched.admit_stats[-2:]}
    for e in evs:
        if e[0] in ("serve.admit.prefill", "serve.admit.first_token"):
            assert any(_inside(e, a) for a in admits), e[0]
    delivers = [e for e in evs if e[0] == "serve.deliver"]
    for e in evs:
        if e[0] == "serve.release":
            assert any(_inside(e, d) for d in delivers)


def _module(lowered):
    return lowered.as_text().split("\n", 1)[0].split()[1]


def _has_scope(lowered, scope):
    """The scope names a location of the lowered program's ops."""
    text = lowered.as_text(debug_info=True)
    return re.search('["/]%s[/"]' % re.escape(scope), text) is not None


def test_programs_lower_to_stable_names():
    """Every program the scheduler dispatches is a module named after
    what it does; the decode chunk's ops carry their named scopes."""
    cfg, _, params = parity.family("lm")
    spec = parity.layout_spec("paged", pool_pages=48)
    sched = SlotScheduler(build_decode(cfg, spec), params, slots=2,
                          max_len=96, chunk_size=4, prefix_sharing=True,
                          tier_store=TierStore(), speculate=2)
    st, slot = sched.state, np.int32(0)
    idx = jnp.full((sched.layout.pages_per_slot,), sched.layout.trash,
                   jnp.int32)
    args = (sched.params, st, sched.last_token, sched.slot_keys,
            jnp.asarray(sched.temps), jnp.asarray(sched.active))
    eos = jnp.asarray(sched.eos)
    snap = jax.eval_shape(sched._snap, st, slot)
    pages = jax.eval_shape(sched._gather_pages, st, idx)
    draft = jnp.zeros((sched.slots, 2), jnp.int32)
    got = {
        "_chunk": sched._chunk.lower(*args, n_steps=4, eos=eos),
        "_clear": sched._clear.lower(st, slot, sched._empty_row),
        "_fork": sched._fork.lower(st, idx, idx),
        "_snap": sched._snap.lower(st, slot),
        "_restore": sched._restore.lower(st, slot, snap),
        "_gather_pages": sched._gather_pages.lower(st, idx),
        "_scatter_pages": sched._scatter_pages.lower(st, idx, pages),
        "_spec": sched._spec.lower(args[0], st, args[2], draft, *args[3:],
                                   eos=eos),
    }
    assert {k: _module(v) for k, v in got.items()} == {
        "_chunk": "@jit_decode_chunk", "_clear": "@jit_clear_slot",
        "_fork": "@jit_fork_pages", "_snap": "@jit_snapshot_slot",
        "_restore": "@jit_restore_slot",
        "_gather_pages": "@jit_gather_pages",
        "_scatter_pages": "@jit_scatter_pages",
        "_spec": "@jit_spec_chunk"}
    for scope in ("decode.step", "decode.sample", "decode.select"):
        assert _has_scope(got["_chunk"], scope), scope


def test_tconst_resync_scopes_name_their_bucket():
    """The resync's bucketed branches carry ``tconst.resync.k<bucket>``."""
    _, sched = _scheduler("tconst")
    low = sched._chunk.lower(sched.params, sched.state, sched.last_token,
                             sched.slot_keys, jnp.asarray(sched.temps),
                             jnp.asarray(sched.active), n_steps=4,
                             eos=jnp.asarray(sched.eos))
    assert _module(low) == "@jit_decode_chunk"
    assert _has_scope(low, "tconst.resync.k1")
    assert _has_scope(low, "tconst.resync.k2")
    assert not _has_scope(low, "tconst.resync.k3")     # not a bucket
