"""Paper Fig. 8: inference latency, cache-hit/miss split, KV-cache memory,
and speedup ratios vs context length N, for Base / TLinFormer /
TConstFormer at matched (reduced) scale on CPU — plus the cache-layout
sweep (dense / paged / int8 / paged_int8) with the per-step HBM bytes
the LAYOUT-NATIVE kernels touch vs the dense-logical bytes the retired
per-step ``merged()`` densification used to pay.

Validates the paper's qualitative claims at reduced scale:
  (a-c) hit latency: baseline grows with N, TLin grows (gentler),
        TConst is FLAT;
  (g)   KV cache: baseline/TLin O(N), TConst O(1) — reported per
        layout, so paged pools and int8 scales show their true bytes;
  (h-i) hit-step speedup of TConst over Base / TLin grows with N.

Besides the CSV rows, the run writes ``BENCH_inference.json`` (cwd) with
tokens/s, cache bytes per layout, the compacted resync-miss cost, the
prefix-sharing byte accounting, the chunked-admission scenario
(forward tokens / est. prefill FLOPs + warm latency vs unshared-tail
length, shared vs cold vs one-shot, plus the prompt-length-bucketing
compile counts), and the session-tiering scenario (oversubscribed
spill/resume latency + host-tier bytes per layout, and the tconst
admission-cache hit vs cold admission), so the perf trajectory is
tracked across PRs.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.config import get_config, reduced
from repro.models.api import build_model
from repro.models.layouts import LayoutSpec
from repro.serving.engine import Engine

N_SWEEP = [256, 512, 1024, 2048]
GEN = 10
OUT_JSON = "BENCH_inference.json"
MESH_SHAPE = (2, 4)                 # (data, model) for the sharded section


def _time_steps(api, params, prompt_len: int, max_len: int) -> Dict:
    eng = Engine(api, params, max_len=max_len)
    batch = {"tokens": jnp.ones((1, prompt_len), jnp.int32)}
    eng.generate(batch, GEN, record_stats=True)       # includes compile
    eng.stats.clear()
    eng.generate(batch, GEN, record_stats=True)       # timed run
    # entries tagged compiled carry one-time jit cost: excluded from the
    # reported numbers (the warm-up run above makes this a no-op here,
    # but the tag keeps the JSON honest if the flow changes)
    hits = [s.seconds for s in eng.stats
            if s.kind == "hit" and not s.compiled]
    misses = [s.seconds for s in eng.stats
              if s.kind == "miss" and not s.compiled]
    prefill = [s.seconds for s in eng.stats if s.kind == "prefill"]
    # chunked decode: one lax.scan dispatch, resync fused on-device —
    # the serving path's zero-host-sync throughput (prefill excluded)
    chunk_s = eng.time_chunked_decode(batch, GEN)
    return {
        "hit_ms": 1e3 * float(np.median(hits)) if hits else float("nan"),
        "miss_ms": 1e3 * float(np.median(misses)) if misses else
                   1e3 * float(prefill[0]),           # baseline: full pass
        "cache_bytes": eng.cache_bytes(1),
        "chunk_tps": (GEN - 1) / chunk_s,
    }


def _layout_sweep(api, params, emit) -> Dict:
    """DecodeAPI v3 (layout-native kernels): cache bytes, chunked
    throughput, and PER-STEP HBM BYTES TOUCHED per layout — the view
    bytes the layout-native step actually reads (assigned pages + table
    for paged, int8+scales for quantized) vs the dense-logical bytes the
    retired per-step ``merged()`` densification used to materialise.
    Also the paged-pool saving for a short-session scenario (slots sized
    for max_len, sessions needing a quarter of it — Fig 8g)."""
    from repro.models.api import build_decode
    from repro.serving.scheduler import SlotScheduler
    from repro.serving.session import Session

    max_len, slots, short = 512, 4, 128
    out: Dict[str, Dict] = {}
    for kind in ("dense", "paged", "int8", "paged_int8"):
        eng = Engine(api, params, max_len=max_len, layout=kind)
        batch = {"tokens": jnp.ones((1, short), jnp.int32)}
        tps = (GEN - 1) / eng.time_chunked_decode(batch, GEN)
        row = {"cache_bytes": eng.cache_bytes(slots), "chunk_tps": tps}
        state = eng.decode.init_state(slots, max_len)
        row["step_view_bytes"] = state.step_view_bytes()
        row["step_dense_logical_bytes"] = state.dense_logical_bytes()
        if kind in ("paged", "paged_int8"):
            # pool + step bytes when sized for the short sessions actually
            # served: the scheduler assigns only the pages they need, and
            # the kernels walk only those
            page = 64
            pool = slots * (-(-short // page))
            spec = LayoutSpec(kind=kind, page_size=page, pool_pages=pool)
            short_eng = Engine(api, params, max_len=max_len, layout=spec)
            row["cache_bytes_short_pool"] = short_eng.cache_bytes(slots)
            sched = SlotScheduler(build_decode(api.cfg, spec), params,
                                  slots=slots, max_len=max_len,
                                  chunk_size=8)
            sched.submit(Session(np.ones(short - 16, np.int32),
                                 max_new_tokens=8))
            sched.step()
            row["step_view_bytes_short_pool"] = \
                sched.state.step_view_bytes()
        out[kind] = row
        emit(f"layout/{kind}/cache_bytes", row["cache_bytes"],
             f"{slots} slots @ max_len={max_len}")
        emit(f"layout/{kind}/chunk_tps", tps, "tok/s")
        emit(f"layout/{kind}/step_view_bytes", row["step_view_bytes"],
             f"per-step HBM bytes touched; dense-logical="
             f"{row['step_dense_logical_bytes']}")
    emit("layout/paged/cache_bytes_short_pool",
         out["paged"]["cache_bytes_short_pool"],
         f"pool sized for {short}-token sessions; dense pays "
         f"{out['dense']['cache_bytes']}")
    emit("layout/paged/step_view_bytes_short_pool",
         out["paged"]["step_view_bytes_short_pool"],
         "kernel walks only the assigned pages")
    emit("layout/int8_shrink",
         out["dense"]["cache_bytes"] / out["int8"]["cache_bytes"],
         "x smaller KV (~4x for f32)")
    return out


def _shared_prefix_scenario(api, params, kind, emit) -> Dict:
    """Prefix sharing (CoW): S sessions x one common system prompt.
    Reports the physical bytes the page tables reference (a shared page
    is stored — and counted — ONCE) and warm admission latency, with
    sharing on vs off, plus the S=1 baseline.  Acceptance: shared-prefix
    bytes < 1.5x the single-session bytes for S=4, streams identical to
    the no-sharing run."""
    from repro.models.api import build_decode
    from repro.serving.scheduler import SlotScheduler
    from repro.serving.session import Session

    S, page, gen, chunk = 4, 16, 4, 4
    # prompt 104 = 96-token shared system prefix + 8-token tail: stable
    # prefix (w_og=8 window part excluded) = 96 -> 6 shared pages, and
    # tail+gen+chunk fit one private page -> 7 pages/session
    rng = np.random.RandomState(7)
    common = rng.randint(1, api.cfg.vocab_size, size=96).astype(np.int32)
    prompts = [np.concatenate([common, rng.randint(
        1, api.cfg.vocab_size, size=8).astype(np.int32)]) for _ in range(S)]
    spec = LayoutSpec(kind=kind, page_size=page, pool_pages=28)

    def serve(n_sessions, sharing):
        sched = SlotScheduler(build_decode(api.cfg, spec), params,
                              slots=S, max_len=128, chunk_size=chunk,
                              prefix_sharing=sharing)
        sessions = [sched.submit(Session(p, max_new_tokens=gen))
                    for p in prompts[:n_sessions]]
        sched.admit_pending()
        bytes_admitted = sched.assigned_kv_bytes()
        sched.run()
        warm = [s.seconds for s in sched.admit_stats if not s.compiled]
        return {
            "assigned_kv_bytes": bytes_admitted,
            "admit_warm_ms": 1e3 * float(np.median(warm)) if warm
                             else float("nan"),
            "streams": [s.tokens for s in sessions],
        }

    shared = serve(S, True)
    solo = serve(1, True)
    noshare = serve(S, False)
    ratio = shared["assigned_kv_bytes"] / solo["assigned_kv_bytes"]
    identical = shared["streams"] == noshare["streams"]
    row = {
        "sessions": S,
        "shared_prefix_assigned_kv_bytes": shared["assigned_kv_bytes"],
        "no_sharing_assigned_kv_bytes": noshare["assigned_kv_bytes"],
        "single_session_assigned_kv_bytes": solo["assigned_kv_bytes"],
        "shared_over_single_ratio": ratio,
        "admit_warm_ms_sharing": shared["admit_warm_ms"],
        "admit_warm_ms_no_sharing": noshare["admit_warm_ms"],
        "streams_identical_to_no_sharing": identical,
    }
    emit(f"prefix_sharing/{kind}/assigned_kv_bytes",
         shared["assigned_kv_bytes"],
         f"S={S} shared prompt; no-sharing pays "
         f"{noshare['assigned_kv_bytes']}")
    emit(f"prefix_sharing/{kind}/shared_over_single_ratio", ratio,
         "acceptance: < 1.5 for S=4 (shared prefix stored once)")
    emit(f"prefix_sharing/{kind}/streams_identical", float(identical),
         "1.0 = token-identical to the no-sharing run")
    return row


def _chunked_prefill_scenario(emit) -> Dict:
    """Chunked KV-conditioned admission (PR 5): warm admission latency
    and forward compute (prefill FLOPs) vs the UNSHARED-TAIL length, for
    a prompt whose prefix is resident (prefix sharing) vs a cold prompt,
    on a small dense LM — the family where admission forward compute
    genuinely scales with the tail.  Also the one-shot admission
    baseline.  forward_tokens comes straight from the scheduler's
    admit_stats; FLOPs are estimated as 2 * params * forward_tokens."""
    from repro.models.api import build_decode
    from repro.serving.scheduler import SlotScheduler
    from repro.serving.session import Session

    cfg = reduced(get_config("smollm_360m"), dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    page = chunk = 16
    prefix_len = 64
    spec = LayoutSpec(kind="paged", page_size=page, pool_pages=64)
    rng = np.random.RandomState(11)
    common = rng.randint(1, cfg.vocab_size, size=prefix_len).astype(np.int32)

    def measure(tail: int, sharing: bool, prefill_chunk):
        """Median warm admission over 3 probes (max_new_tokens=1, so a
        probe's slot frees at admission) behind a resident holder."""
        sched = SlotScheduler(build_decode(cfg, spec), params, slots=2,
                              max_len=256, chunk_size=4,
                              prefix_sharing=sharing,
                              prefill_chunk=prefill_chunk)
        holder = np.concatenate([common, rng.randint(
            1, cfg.vocab_size, size=tail).astype(np.int32)])
        sched.submit(Session(holder, max_new_tokens=32))
        sched.admit_pending()          # prefix now resident + refcounted
        for _ in range(3):
            probe = np.concatenate([common, rng.randint(
                1, cfg.vocab_size, size=tail).astype(np.int32)])
            sched.submit(Session(probe, max_new_tokens=1))
            sched.admit_pending()
        stats = sched.admit_stats[1:]               # drop the holder
        warm = [s for s in stats if not s.compiled] or stats
        return {
            "admit_warm_ms": 1e3 * float(np.median(
                [s.seconds for s in warm])),
            "forward_tokens": warm[-1].forward_tokens,
            "prefill_flops_est": 2.0 * n_params * warm[-1].forward_tokens,
        }

    rows = []
    for tail in (16, 48, 96):
        shared = measure(tail, True, chunk)
        cold = measure(tail, False, chunk)
        oneshot = measure(tail, False, None)
        rows.append({"prefix_len": prefix_len, "tail": tail,
                     "shared": shared, "cold": cold, "oneshot": oneshot})
        emit(f"chunked_prefill/tail={tail}/shared_forward_tokens",
             shared["forward_tokens"],
             f"cold forwards {cold['forward_tokens']} "
             f"(prompt {prefix_len + tail})")
        emit(f"chunked_prefill/tail={tail}/shared_admit_ms",
             shared["admit_warm_ms"],
             f"cold {cold['admit_warm_ms']:.2f}ms, one-shot "
             f"{oneshot['admit_warm_ms']:.2f}ms")
    return {"arch": "smollm_360m(reduced)", "page": page, "chunk": chunk,
            "rows": rows}


def _spill_resume_scenario(api, params, emit) -> Dict:
    """Session tiering (PR 6): oversubscribed serving (4 sessions on 2
    slots, preemptive spill every chunk) per layout — warm RESUME
    latency (one jitted scatter from the host tier) vs the warm COLD
    admission it replaces, the host-tier bytes one spilled session
    costs in each PHYSICAL layout (paged: live pages only; int8: stays
    compressed), and the store occupancy after the run."""
    from repro.models.api import build_decode
    from repro.serving.scheduler import SlotScheduler
    from repro.serving.session import Session
    from repro.serving.tier_store import TierStore

    gen, L = 8, 24
    rng = np.random.RandomState(13)
    # equal lengths: the one-shot prefill compiles once, so 3 of the 4
    # cold admissions (and all but the first resume) report warm
    prompts = [rng.randint(1, api.cfg.vocab_size, size=L).astype(np.int32)
               for _ in range(4)]
    out: Dict[str, Dict] = {}
    for kind in ("dense", "paged", "int8", "paged_int8"):
        spec = None if kind == "dense" else LayoutSpec(
            kind=kind, page_size=16, pool_pages=32)
        store = TierStore()
        sched = SlotScheduler(build_decode(api.cfg, spec), params,
                              slots=2, max_len=128, chunk_size=4,
                              tier_store=store, preempt_chunks=1)
        for p in prompts:
            sched.submit(Session(p, max_new_tokens=gen))
        sched.run()
        cold = [s.seconds for s in sched.admit_stats
                if s.source == "cold" and not s.compiled]
        resume = [s.seconds for s in sched.admit_stats
                  if s.source == "resume" and not s.compiled]
        sp = sched.spill_stats
        row = {
            "cold_admit_warm_ms": 1e3 * float(np.median(cold)) if cold
                                  else float("nan"),
            "resume_warm_ms": 1e3 * float(np.median(resume)) if resume
                              else float("nan"),
            "spills": sp["spills"],
            "resumes": sp["resumes"],
            "host_bytes_per_spilled_session":
                sp["spilled_bytes"] / max(sp["spills"], 1),
            "store_occupancy_bytes": store.occupancy_bytes,
            "store_entries": len(store),
        }
        out[kind] = row
        emit(f"spill_resume/{kind}/resume_warm_ms", row["resume_warm_ms"],
             f"cold admission {row['cold_admit_warm_ms']:.2f}ms")
        emit(f"spill_resume/{kind}/host_bytes_per_spilled_session",
             row["host_bytes_per_spilled_session"],
             f"{sp['spills']} spills; store holds "
             f"{row['store_occupancy_bytes']} bytes")
    return {"sessions": 4, "slots": 2, "gen": gen, "prompt_len": L,
            "layouts": out}


def _admission_cache_scenario(api, params, emit) -> Dict:
    """The O(1) tconst re-admission: a prompt whose admission snapshot
    is resident in the tier store restores in one scatter (zero forward
    tokens) instead of re-running the O(N) prefill/resync — warm hit vs
    warm cold latency on the paper's own family."""
    from repro.models.api import build_decode
    from repro.serving.scheduler import SlotScheduler
    from repro.serving.session import Session
    from repro.serving.tier_store import TierStore

    L = 32
    rng = np.random.RandomState(17)
    store = TierStore()

    def admit(prompt):
        sched = SlotScheduler(build_decode(api.cfg), params, slots=1,
                              max_len=128, chunk_size=4, tier_store=store)
        sched.submit(Session(prompt.copy(), max_new_tokens=1))
        sched.admit_pending()
        return sched.admit_stats[-1]

    warmup = rng.randint(1, api.cfg.vocab_size, size=L).astype(np.int32)
    prompt = rng.randint(1, api.cfg.vocab_size, size=L).astype(np.int32)
    admit(warmup)                     # compile the cold prefill
    cold = admit(prompt)              # warm cold: writes the snapshot
    admit(prompt)                     # compile the restore
    hit = admit(prompt)               # warm store hit
    assert hit.source == "store" and hit.forward_tokens == 0
    row = {
        "prompt_len": L,
        "cold_admit_warm_ms": 1e3 * cold.seconds,
        "store_hit_warm_ms": 1e3 * hit.seconds,
        "cold_forward_tokens": cold.forward_tokens,
        "hit_forward_tokens": hit.forward_tokens,
    }
    emit("spill_resume/tconst_admission_cache/store_hit_warm_ms",
         row["store_hit_warm_ms"],
         f"cold {row['cold_admit_warm_ms']:.2f}ms forwarding "
         f"{cold.forward_tokens} tokens; hit forwards 0")
    return row


def _bucketed_admission_scenario(api, params, emit) -> Dict:
    """Prompt-length bucketing: K distinct prompt lengths should produce
    at most bucket-count compile-tagged admissions under the chunked
    (tconst: bucketed fixed-shape) prefill, vs one per length without."""
    from repro.models.api import build_decode
    from repro.serving.scheduler import SlotScheduler
    from repro.serving.session import Session

    lengths = [17, 26, 35, 44]

    def count(prefill_chunk):
        sched = SlotScheduler(build_decode(api.cfg), params, slots=1,
                              max_len=128, chunk_size=4,
                              prefill_chunk=prefill_chunk)
        rng = np.random.RandomState(5)
        for n in lengths:
            sched.submit(Session(rng.randint(
                1, api.cfg.vocab_size, size=n).astype(np.int32),
                max_new_tokens=1))
            sched.admit_pending()
        return sum(1 for s in sched.admit_stats if s.compiled)

    chunked, oneshot = count(16), count(None)
    emit("chunked_prefill/bucketed_compiled_admissions", chunked,
         f"{len(lengths)} distinct prompt lengths; one-shot tags "
         f"{oneshot}")
    return {"lengths": lengths, "chunked_compiled": chunked,
            "oneshot_compiled": oneshot}


def _sharded_decode_scenario(emit, mesh_shape=None) -> Dict:
    """Mesh-native decode (PR 9): the SAME decode path on a (data,
    model) device mesh — per-device vs global KV bytes (head-sharded
    fields split over the model axis), warm chunked-step latency, and
    stream identity against the 1-device run.  Runs on a CPU forced to
    d*m devices via XLA_FLAGS=--xla_force_host_platform_device_count;
    with fewer devices visible the section records WHY it was skipped
    instead of silently vanishing from the JSON."""
    from repro.launch.mesh import make_decode_mesh

    d, m = mesh_shape or MESH_SHAPE
    n = d * m
    if len(jax.devices()) < n:
        reason = (f"needs {n} devices for a {d}x{m} mesh, "
                  f"{len(jax.devices())} visible (set XLA_FLAGS="
                  f"--xla_force_host_platform_device_count={n})")
        emit("sharded_decode/skipped", 1.0, reason)
        return {"skipped": reason, "mesh": f"{d}x{m}"}
    mesh = make_decode_mesh(d, m)
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    B, L, max_len = 2, 32, 128
    rows: Dict[str, Dict] = {}
    scenarios = {
        "tconst/dense": (reduced(get_config("tconst_41m"),
                                 dtype="float32"), None),
        # tlin's O(N) history KV actually lives in pool pages — the row
        # that proves the paged pool + page tables run sharded
        "tlin/paged": (reduced(get_config("tconst_41m"), dtype="float32",
                               attention_mode="tlin"),
                       LayoutSpec(kind="paged", page_size=16,
                                  pool_pages=2 * B * (max_len // 16))),
    }
    for name, (cfg, spec) in scenarios.items():
        api = build_model(cfg)
        params = api.init(jax.random.PRNGKey(0))
        batch = {"tokens": jnp.ones((B, L), jnp.int32)}
        ref_eng = Engine(api, params, max_len=max_len, layout=spec)
        ref = ref_eng.generate(batch, GEN)
        eng = Engine(api, jax.device_put(params, repl), max_len=max_len,
                     layout=spec, mesh=mesh)
        out = eng.generate(batch, GEN)
        identical = bool(np.array_equal(ref, out))
        state = eng.decode.init_state(B, max_len)
        glob, per_dev = state.kv_bytes(), state.per_device_kv_bytes()
        row = {
            "stream_identical_to_1device": identical,
            "kv_bytes_global": glob,
            "kv_bytes_per_device": per_dev,
            "global_over_per_device": glob / max(per_dev, 1),
            "chunk_step_ms":
                1e3 * eng.time_chunked_decode(batch, GEN) / (GEN - 1),
            "chunk_step_ms_1device":
                1e3 * ref_eng.time_chunked_decode(batch, GEN) / (GEN - 1),
        }
        rows[name] = row
        emit(f"sharded_decode/{name}/stream_identical", float(identical),
             f"mesh {d}x{m} vs 1 device (greedy)")
        emit(f"sharded_decode/{name}/kv_bytes_per_device", per_dev,
             f"global {glob} ({row['global_over_per_device']:.2f}x; "
             f"model axis = {m})")
    return {"mesh": f"{d}x{m}", "devices": n, "batch": B,
            "prompt_len": L, "gen": GEN, "rows": rows}


def _spec_decode_scenario(emit, gen: int = 48) -> Dict:
    """Speculative decoding (PR 10): per-slot tokens/s of the n-gram
    self-drafter on a REPEAT-HEAVY prompt (a 16-token motif tiled to
    64), per family, against the sequential STREAMING baseline — one
    decode dispatch per token, the interactive serving regime
    speculation actually targets.  A verify round is also one dispatch
    (k+1 positions, fixed shape), so the speedup is committed-tokens-
    per-dispatch x dispatch-cost ratio; acceptance is verify-exact, so
    ``stream_identical`` is asserted (and schema-gated), never assumed.
    The zero-host-sync chunked scan is reported alongside for scale —
    it amortizes dispatch overhead across the whole chunk but cannot
    stream a token until the chunk retires."""
    from repro.config import TConstConfig
    k = 4
    rows: Dict[str, Dict] = {}
    # tconst: widen the generation window to 64 (the reduced default of 8
    # makes the verify budget cap every round at w_og - gen_len <= 8
    # tokens and a resync fires every 8 tokens — that measures the
    # window cap, not the drafter; budget capping has its own tests)
    base = get_config("tconst_41m")
    fams = (
        ("tconst", "tconst_41m(reduced,w_og=64)",
         reduced(base, dtype="float32",
                 tconst=TConstConfig(w_oh=8, w_og=64, h=base.tconst.h))),
        ("lm", "smollm_360m(reduced)",
         reduced(get_config("smollm_360m"), dtype="float32")),
    )
    for name, arch_label, cfg in fams:
        api = build_model(cfg)
        params = api.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(23)
        motif = rng.integers(1, cfg.vocab_size, (16,))
        prompt = np.tile(motif, 6)[:64].astype(np.int32)[None]
        batch = {"tokens": prompt}
        max_len = 64 + gen + 2 * k + 8

        # sequential streaming baseline: one dispatch per token (warm)
        eng = Engine(api, params, max_len=max_len)
        ref = eng.generate(dict(batch), gen, record_stats=True)
        eng.stats.clear()
        ref2 = eng.generate(dict(batch), gen, record_stats=True)
        assert np.array_equal(ref, ref2)
        seq = [s.seconds for s in eng.stats
               if s.kind in ("hit", "miss") and not s.compiled]
        seq_tps = (gen - 1) / sum(seq)
        chunk_tps = (gen - 1) / eng.time_chunked_decode(dict(batch), gen)

        # speculative: one verify dispatch per round, warm timing
        spec_eng = Engine(api, params, max_len=max_len)
        out = spec_eng.generate_speculative(dict(batch), gen, k=k)
        identical = bool(np.array_equal(ref, out))
        spec_eng.stats.clear()
        out2 = spec_eng.generate_speculative(dict(batch), gen, k=k)
        identical = identical and bool(np.array_equal(ref, out2))
        warm = [s for s in spec_eng.stats
                if s.kind == "spec_chunk" and not s.compiled]
        spec_tps = (sum(s.tokens for s in warm)
                    / sum(s.seconds for s in warm))
        rounds = spec_eng.spec_rounds
        row = {
            "arch": arch_label, "drafter": "ngram", "k": k,
            "gen": gen, "prompt_len": 64, "motif_len": 16,
            "stream_identical": identical,
            "sequential_tps": seq_tps,
            "spec_tps": spec_tps,
            "speedup_vs_sequential": spec_tps / seq_tps,
            "chunked_scan_tps": chunk_tps,
            "rounds": rounds,
            "tokens_per_round": (gen - 1) / rounds,
        }
        rows[name] = row
        emit(f"spec_decode/{name}/speedup_vs_sequential",
             row["speedup_vs_sequential"],
             f"spec {spec_tps:.0f} tok/s vs sequential {seq_tps:.0f} "
             f"({row['tokens_per_round']:.2f} tokens/round, k={k})")
        emit(f"spec_decode/{name}/stream_identical", float(identical),
             "1.0 = verify-exact: token-identical to plain generate")
    return {"drafter": "ngram", "k": k, "rows": rows}


def validate_payload(payload: Dict, smoke: bool = False) -> List[str]:
    """Structural check of a ``BENCH_inference.json`` payload (CI gate
    for the sharded section; full payloads also need the fig8 blocks).
    Returns a list of problems (empty = valid)."""
    errs: List[str] = []

    def need(cond: bool, msg: str) -> None:
        if not cond:
            errs.append(msg)

    sharded = payload.get("sharded_decode")
    need(isinstance(sharded, dict), "missing sharded_decode")
    if isinstance(sharded, dict):
        if "skipped" in sharded:
            need(isinstance(sharded["skipped"], str) and sharded["skipped"],
                 "sharded_decode.skipped must say why")
        else:
            rows = sharded.get("rows")
            need(isinstance(rows, dict) and rows, "sharded_decode: no rows")
            for name, row in (rows or {}).items():
                where = f"sharded_decode/{name}"
                need(row.get("stream_identical_to_1device") is True,
                     f"{where}: stream differs from the 1-device run")
                for k in ("kv_bytes_global", "kv_bytes_per_device",
                          "global_over_per_device", "chunk_step_ms"):
                    need(isinstance(row.get(k), (int, float)),
                         f"{where}: missing {k}")
                if "kv_bytes_per_device" in row:
                    need(row["kv_bytes_per_device"] <=
                         row.get("kv_bytes_global", 0),
                         f"{where}: per-device bytes exceed global")
    full = not smoke and not payload.get("meta", {}).get("smoke")
    spec = payload.get("spec_decode")
    need(isinstance(spec, dict), "missing spec_decode")
    if isinstance(spec, dict):
        rows = spec.get("rows")
        need(isinstance(rows, dict) and rows, "spec_decode: no rows")
        for name, row in (rows or {}).items():
            where = f"spec_decode/{name}"
            need(row.get("stream_identical") is True,
                 f"{where}: speculative stream differs from plain "
                 f"generate (verify-exactness broken)")
            for k in ("sequential_tps", "spec_tps",
                      "speedup_vs_sequential", "tokens_per_round"):
                need(isinstance(row.get(k), (int, float)),
                     f"{where}: missing {k}")
            if full and row.get("drafter") == "ngram":
                # perf floor only for full (artifact) runs — smoke/CI
                # runners gate exactness, not wall-clock
                need(row.get("speedup_vs_sequential", 0.0) >= 1.3,
                     f"{where}: ngram speedup "
                     f"{row.get('speedup_vs_sequential')} < 1.3x on the "
                     f"repeat-heavy workload")
    if full:
        for k in ("n_sweep", "variants", "layouts", "spill_resume",
                  "derived"):
            need(k in payload, f"missing {k}")
    return errs


def run(emit) -> None:
    variants = {
        "base": reduced(get_config("tconst_41m"), dtype="float32",
                        attention_mode="full"),
        "tlin": reduced(get_config("tconst_41m"), dtype="float32",
                        attention_mode="tlin"),
        "tconst": reduced(get_config("tconst_41m"), dtype="float32"),
    }
    results: Dict[str, List[Dict]] = {}
    layouts: Dict[str, Dict] = {}
    prefix_sharing: Dict[str, Dict] = {}
    bucketed: Dict[str, Dict] = {}
    spill_resume: Dict[str, Dict] = {}
    for name, cfg in variants.items():
        api = build_model(cfg)
        params = api.init(jax.random.PRNGKey(0))
        rows = []
        for n in N_SWEEP:
            r = _time_steps(api, params, n, n + GEN + 64)
            rows.append(r)
            emit(f"fig8_latency/{name}/N={n}/hit", r["hit_ms"] * 1e3,
                 f"miss_ms={r['miss_ms']:.1f}")
            emit(f"fig8_memory/{name}/N={n}", r["cache_bytes"],
                 "kv_cache_bytes")
            emit(f"chunked_decode_tps/{name}/N={n}", r["chunk_tps"],
                 "tok/s, single-dispatch chunked decode")
        results[name] = rows
        if name in ("tlin", "tconst"):
            layouts[name] = _layout_sweep(api, params,
                                          lambda k, v, d="": emit(
                                              f"{name}/{k}", v, d))
        if name == "tlin":
            # prefix sharing needs fields that actually live in pages:
            # tlin's O(N) history KV (pure-tconst KV is already O(1))
            prefix_sharing = {
                kind: _shared_prefix_scenario(api, params, kind, emit)
                for kind in ("paged", "paged_int8")}
            # session tiering on the family whose KV actually pages:
            # spill/resume latency + host-tier bytes per layout
            spill_resume = _spill_resume_scenario(api, params, emit)
        if name == "tconst":
            # bucketing headline for the paper's own family: admission
            # collapses to ONE fixed-shape dispatch (resync is already
            # max_len-shaped; the window pass pads to W_og)
            bucketed[name] = _bucketed_admission_scenario(api, params,
                                                          emit)
            spill_resume["tconst_admission_cache"] = \
                _admission_cache_scenario(api, params, emit)
    chunked_prefill = _chunked_prefill_scenario(emit)
    chunked_prefill["bucketed_admissions"] = bucketed

    # derived paper claims ---------------------------------------------------
    tc = results["tconst"]
    flat = tc[-1]["hit_ms"] / max(tc[0]["hit_ms"], 1e-9)
    emit("fig8c_tconst_hit_flatness", flat,
         "hit(Nmax)/hit(Nmin); ~1.0 = constant-time (paper: horizontal)")
    cache_ratio = tc[-1]["cache_bytes"] / tc[0]["cache_bytes"]
    emit("fig8g_tconst_cache_O1", cache_ratio, "must be 1.0")
    for other in ("base", "tlin"):
        o = results[other]
        grow = o[-1]["cache_bytes"] / o[0]["cache_bytes"]
        emit(f"fig8g_{other}_cache_growth", grow, "grows with N")
        sp_small = o[0]["hit_ms"] / tc[0]["hit_ms"]
        sp_big = o[-1]["hit_ms"] / tc[-1]["hit_ms"]
        emit(f"fig8hi_speedup_vs_{other}/N={N_SWEEP[0]}", sp_small, "x")
        emit(f"fig8hi_speedup_vs_{other}/N={N_SWEEP[-1]}", sp_big,
             "x (paper: grows with N)")

    payload = {
        "n_sweep": N_SWEEP,
        "gen": GEN,
        # per-variant rows: hit/miss latency (miss = compacted row-wise
        # resync cost for tconst/tlin), cache bytes, chunked tok/s
        "variants": results,
        "layouts": layouts,
        # S sessions x one system prompt: shared prefix pages stored
        # once (assigned_kv_bytes), streams identical, warm admission
        # latency with/without sharing (compile-tagged entries excluded)
        "prefix_sharing": prefix_sharing,
        # chunked KV-conditioned admission: forward tokens / est. FLOPs
        # and warm latency vs unshared-tail length (shared vs cold vs
        # one-shot), plus the prompt-length-bucketing compile counts
        "chunked_prefill": chunked_prefill,
        # session tiering: oversubscribed spill/resume latency + host-
        # tier bytes per layout, and the tconst admission-cache hit
        # (O(1) re-admission: zero forward tokens) vs cold admission
        "spill_resume": spill_resume,
        # mesh-native decode: per-device vs global KV bytes, step
        # latency, and stream identity vs the 1-device run on a forced
        # multi-device mesh (or a "skipped" reason on 1 device)
        "sharded_decode": _sharded_decode_scenario(emit),
        # speculative decoding: n-gram drafter tokens/s per slot vs the
        # sequential streaming baseline on the repeat-heavy workload,
        # with the verify-exact stream-identity bit (schema-gated)
        "spec_decode": _spec_decode_scenario(emit),
        "derived": {
            "tconst_hit_flatness": flat,
            "tconst_cache_O1_ratio": cache_ratio,
        },
    }
    with open(OUT_JSON, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    emit("bench_inference_json", 0.0, f"written to {OUT_JSON}")


def main(argv=None) -> int:
    """CLI mirror of ``benchmarks.run``'s entry point, plus the CI modes:
    ``--smoke --mesh 2x4`` runs JUST the sharded_decode section (the
    fig8 sweeps are minutes of CPU) and schema-checks it; ``--check``
    validates an existing payload file."""
    global MESH_SHAPE
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="sharded_decode section only (CI)")
    ap.add_argument("--mesh", default="x".join(map(str, MESH_SHAPE)),
                    help="DxM mesh for the sharded section "
                         f"(default {MESH_SHAPE[0]}x{MESH_SHAPE[1]})")
    ap.add_argument("--out", default=OUT_JSON,
                    help=f"output path (default {OUT_JSON})")
    ap.add_argument("--check", metavar="JSON",
                    help="validate an existing payload and exit")
    ap.add_argument("--section", choices=["spec_decode"],
                    help="run ONE section and merge it into --out "
                         "(existing payload kept if the file parses); "
                         "the CI spec-decode lane uses this")
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

    try:
        d, m = (int(s) for s in args.mesh.lower().split("x"))
    except ValueError:
        ap.error(f"--mesh {args.mesh!r} must be DxM, e.g. 2x4")
    if args.section:
        try:
            with open(args.out) as f:
                payload = json.load(f)
        except (OSError, ValueError):
            payload = {}
        # a fresh or partial file is a smoke artifact; merging into an
        # existing full payload must NOT demote it to smoke (the --check
        # perf floor would silently stop applying) — and a full payload
        # gets the full-length scenario
        smoke_flag = bool(payload.get("meta", {}).get("smoke", not payload))
        payload.setdefault("meta", {})["smoke"] = smoke_flag
        payload["spec_decode"] = _spec_decode_scenario(
            emit, gen=24 if smoke_flag else 48)
        if "sharded_decode" not in payload:
            payload["sharded_decode"] = {
                "skipped": "spec_decode section run only", "mesh": "-"}
    elif args.smoke:
        payload = {"meta": {"smoke": True, "mesh": args.mesh},
                   "sharded_decode":
                       _sharded_decode_scenario(emit, (d, m)),
                   "spec_decode": _spec_decode_scenario(emit, gen=24)}
    else:
        MESH_SHAPE = (d, m)
        payload = None
        run(emit)
        with open(OUT_JSON) as f:
            payload = json.load(f)
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    errs = validate_payload(payload, smoke=args.smoke)
    if errs:
        for e in errs:
            print(f"schema: {e}", file=sys.stderr)
        return 1
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
