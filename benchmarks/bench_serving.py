"""SLO-aware serving bench: workload generator x scheduling policies.

Drives seeded traffic traces (``repro.serving.workload``) through the
slot scheduler under BOTH shipped policies (``fifo`` baseline,
``slo`` deadline/cost-aware) per {arch x layout}, collects per-session
telemetry (``repro.serving.metrics``), and writes ``BENCH_serving.json``
(cwd) so the serving trajectory is tracked per PR alongside
``BENCH_inference.json``.

Per {scenario x arch/layout x policy} the JSON records p50/p99 TTFT and
inter-token latency (scheduler-chunk units — deterministic across
hosts — plus compile-excluded wall seconds), queue wait, SLO
attainment, spill/resume counts and store hits.  Two gates ride along:

* **stream identity** — every session's token stream (temperature 0.7,
  per-session sampling chains) must be identical across policies; the
  bench raises otherwise.  A policy is a *scheduling* decision, never a
  *sampling* one.
* **SLO win** — in the oversubscribed bursty scenario the deadline/
  cost-aware policy must beat FIFO on TTFT SLO attainment (it trades
  best-effort p99 TTFT for deadline hits — both visible in the JSON).

Usage::

  PYTHONPATH=src python -m benchmarks.bench_serving            # full
  PYTHONPATH=src python -m benchmarks.bench_serving --smoke    # CI
  PYTHONPATH=src python -m benchmarks.bench_serving --check BENCH_serving.json
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro import runtime
from repro.config import get_config, reduced
from repro.models.api import build_decode, build_model
from repro.models.layouts import LayoutSpec
from repro.serving.metrics import ServingTelemetry
from repro.serving.scheduler import SlotScheduler
from repro.serving.session import Session
from repro.serving.tier_store import TierStore
from repro.serving.workload import WorkloadSpec, generate_workload

OUT_JSON = "BENCH_serving.json"
SEED = 42
POLICIES = ("fifo", "slo")
MAX_STEPS = 20_000                  # runaway guard per run

# arch x layout rows: the paper family (tconst: O(1) KV, spills are
# near-free, repeats re-admit O(1) from the store) vs a dense LM under
# a paged pool sized well below peak demand (page pressure + expensive
# spills — the regime cost-aware victim selection exists for)
ARCHS: Dict[str, Dict] = {
    "tconst/dense": {
        "config": "tconst_41m",
        "layout": None,
        "scheduler": dict(slots=3, max_len=104, chunk_size=4,
                          preempt_chunks=2, prefill_chunk=16),
    },
    "lm/paged": {
        "config": "smollm_360m",
        "layout": dict(kind="paged", page_size=8, pool_pages=30),
        "scheduler": dict(slots=3, max_len=104, chunk_size=4,
                          preempt_chunks=2, prefill_chunk=16,
                          prefix_sharing=True),
    },
}


def _scenarios(vocab: int, n_sessions: int) -> Dict[str, WorkloadSpec]:
    """The two committed traffic shapes.  ``steady_poisson`` is a
    moderately loaded open-loop trace with a shared-prefix population;
    ``bursty_oversubscribed`` drops whole bursts on a 3-slot scheduler
    with tight TTFT deadlines on a 40% slice — the scenario the SLO
    policy must win."""
    return {
        "steady_poisson": WorkloadSpec(
            n_sessions=n_sessions, vocab=vocab, arrival="poisson",
            rate=0.35, temperature=0.7,
            prompt_mix=((0.7, 8, 24), (0.3, 32, 56)),
            output_mix=((0.8, 8, 16), (0.2, 20, 32)),
            shared_frac=0.3, n_prefixes=2, prefix_len=16,
            repeat_frac=0.2, slo_frac=0.5, slo_ttft_chunks=8),
        "bursty_oversubscribed": WorkloadSpec(
            n_sessions=n_sessions, vocab=vocab, arrival="bursty",
            burst_size=14, burst_every=30.0, temperature=0.7,
            prompt_mix=((0.7, 8, 24), (0.3, 32, 56)),
            output_mix=((0.6, 12, 20), (0.4, 24, 40)),
            repeat_frac=0.25, slo_frac=0.4, slo_ttft_chunks=5),
    }


def _drive(sched: SlotScheduler, arrivals) -> None:
    """Clocked open-loop replay: submit each arrival once the scheduler
    clock reaches its chunk, step until drained."""
    i = 0
    while i < len(arrivals) or sched.pending or sched.active.any():
        while i < len(arrivals) and arrivals[i].at_chunk <= sched.clock:
            sched.submit(arrivals[i].session)
            i += 1
        sched.step()
        if sched.clock > MAX_STEPS:
            raise RuntimeError("bench run exceeded the step guard — "
                               "the scheduler is not draining")


def _run_once(arch: Dict, api, params, spec: WorkloadSpec,
              policy: str) -> Tuple[List[Tuple[int, ...]], Dict]:
    layout = arch["layout"] and LayoutSpec(**arch["layout"])
    decode = build_decode(api.cfg, layout)
    telemetry = ServingTelemetry()
    kw = dict(arch["scheduler"])
    sched = SlotScheduler(decode, params, tier_store=TierStore(),
                          policy=policy, telemetry=telemetry, **kw)
    arrivals = generate_workload(
        spec, SEED, max_prompt_len=kw["max_len"] - 48)
    _drive(sched, arrivals)
    streams = [tuple(a.session.tokens) for a in arrivals]
    summary = telemetry.summary()
    summary["store"] = {
        "spills": sched.spill_stats["spills"],
        "resumes": sched.spill_stats["resumes"],
        "admit_store_hits": sched.spill_stats["admit_store_hits"],
        "pages_readopted": sched.spill_stats["pages_readopted"],
    }
    return streams, summary


def _bench(smoke: bool, emit) -> Dict:
    n_sessions = 12 if smoke else 48
    archs = {k: v for k, v in ARCHS.items()
             if not smoke or k == "tconst/dense"}
    payload: Dict = {
        "meta": {"smoke": smoke, "seed": SEED, "policies": list(POLICIES),
                 "n_sessions_per_run": n_sessions},
        "scenarios": {},
        "derived": {},
    }
    wins: Dict[str, bool] = {}
    for arch_name, arch in archs.items():
        cfg = reduced(get_config(arch["config"]), dtype="float32")
        api = build_model(cfg)
        params = api.init(jax.random.PRNGKey(0))
        for scen_name, spec in _scenarios(cfg.vocab_size,
                                          n_sessions).items():
            scen = payload["scenarios"].setdefault(
                scen_name, {"spec": dataclasses.asdict(spec),
                            "runs": {}})
            run_row: Dict = {}
            streams: Dict[str, List] = {}
            for policy in POLICIES:
                streams[policy], run_row[policy] = _run_once(
                    arch, api, params, spec, policy)
                s = run_row[policy]
                emit(f"serving/{scen_name}/{arch_name}/{policy}"
                     f"/p99_ttft_chunks", s["ttft_chunks"]["p99"] or 0.0,
                     f"ttft_slo_attainment="
                     f"{s['slo']['ttft_attainment']}")
            identical = streams["fifo"] == streams["slo"]
            run_row["streams_identical_across_policies"] = identical
            if not identical:
                raise AssertionError(
                    f"{scen_name}/{arch_name}: token streams differ "
                    f"across scheduling policies — the policy seam "
                    f"leaked into sampling")
            att = {p: run_row[p]["slo"]["ttft_attainment"]
                   for p in POLICIES}
            if scen_name == "bursty_oversubscribed":
                wins[arch_name] = (att["slo"] or 0) > (att["fifo"] or 0)
            scen["runs"][arch_name] = run_row
    payload["derived"] = {
        "slo_beats_fifo_ttft_attainment_oversubscribed": wins,
        "any_oversubscribed_win": any(wins.values()),
        "all_streams_identical": True,       # raised above otherwise
    }
    if not smoke and not payload["derived"]["any_oversubscribed_win"]:
        raise AssertionError(
            "the deadline/cost-aware policy did not beat FIFO on TTFT "
            "SLO attainment in the oversubscribed scenario")
    return payload


# ---------------------------------------------------------------------------
# schema validation (CI gate for the committed artifact)
# ---------------------------------------------------------------------------

_PCTL_KEYS = {"p50", "p99"}
_RUN_KEYS = {"sessions", "finished", "tokens_out", "ttft_chunks",
             "ttft_seconds_warm", "ttft_compile_excluded", "itl_chunks",
             "queue_wait_chunks", "slo", "spills", "resumes",
             "pool_occupancy_mean", "store"}


def validate_payload(payload: Dict) -> List[str]:
    """Structural check of a ``BENCH_serving.json`` payload; returns a
    list of problems (empty = valid)."""
    errs: List[str] = []

    def need(cond: bool, msg: str) -> None:
        if not cond:
            errs.append(msg)

    need(isinstance(payload.get("meta"), dict), "missing meta")
    need(isinstance(payload.get("derived"), dict), "missing derived")
    scenarios = payload.get("scenarios")
    need(isinstance(scenarios, dict) and scenarios, "missing scenarios")
    for scen_name, scen in (scenarios or {}).items():
        need(isinstance(scen.get("spec"), dict),
             f"{scen_name}: missing spec")
        runs = scen.get("runs")
        need(isinstance(runs, dict) and runs, f"{scen_name}: no runs")
        for arch_name, row in (runs or {}).items():
            where = f"{scen_name}/{arch_name}"
            need(row.get("streams_identical_across_policies") is True,
                 f"{where}: streams not identical across policies")
            for policy in POLICIES:
                run = row.get(policy)
                if not isinstance(run, dict):
                    errs.append(f"{where}: missing {policy} run")
                    continue
                missing = _RUN_KEYS - set(run)
                need(not missing, f"{where}/{policy}: missing {missing}")
                for k in ("ttft_chunks", "itl_chunks",
                          "queue_wait_chunks"):
                    pct = run.get(k)
                    need(isinstance(pct, dict) and
                         _PCTL_KEYS <= set(pct),
                         f"{where}/{policy}: {k} lacks p50/p99")
                slo = run.get("slo") or {}
                need("ttft_attainment" in slo and "attainment" in slo,
                     f"{where}/{policy}: slo block incomplete")
                need(run.get("finished") == run.get("sessions"),
                     f"{where}/{policy}: not every session finished")
    der = payload.get("derived") or {}
    need("any_oversubscribed_win" in der,
         "derived lacks any_oversubscribed_win")
    return errs


def run(emit) -> None:
    """benchmarks.run entry point: full bench, committed artifact."""
    payload = _bench(smoke=False, emit=emit)
    with open(OUT_JSON, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    emit("bench_serving_json", 0.0, f"written to {OUT_JSON}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="small scale (CI): tconst arch only, "
                         "12 sessions per run")
    ap.add_argument("--out", default=OUT_JSON,
                    help=f"output path (default {OUT_JSON})")
    ap.add_argument("--check", metavar="JSON",
                    help="validate an existing payload and exit")
    args = ap.parse_args(argv)
    if args.check:
        with open(args.check) as f:
            errs = validate_payload(json.load(f))
        for e in errs:
            print(f"schema: {e}", file=sys.stderr)
        print(f"{args.check}: " + ("INVALID" if errs else "ok"))
        return 1 if errs else 0
    runtime.enable_compile_cache()

    def emit(name, value, derived=""):
        print(f"{name},{value},{derived}", flush=True)

    payload = _bench(smoke=args.smoke, emit=emit)
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    errs = validate_payload(payload)
    if errs:
        for e in errs:
            print(f"schema: {e}", file=sys.stderr)
        return 1
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
