"""Serving launcher: uniform-batch generation (Engine) or the session-
based streaming path (SlotScheduler) with continuous batching.

Both paths take ``--layout dense|paged|int8`` — the physical cache
representation behind the DecodeState (see ``repro.models.layouts``).
``paged`` splits length-axis KV into fixed-size pages (``--page-size``)
in a shared pool; ``--pool-pages`` sizes the pool below
``slots * pages_per_slot`` so short sessions stop paying ``max_len``
bytes (sessions mode only — the scheduler is the page allocator).
``int8`` stores KV quantized with per-vector scales (~4x smaller,
tokens may differ within the documented tolerance).

``--prefix-sharing`` (sessions mode, paged layouts) turns on the
refcounted content-addressed page map: every session gets the SAME
system prompt plus a distinct tail, and sessions admitted while the
prefix is resident map its pages instead of re-writing them — the
shared prefix is stored once, writes copy-on-write (a page is writable
iff its refcount is 1).

``--prefill-chunk N`` (sessions mode) switches admission to the chunked
KV-conditioned prefill: prompts are processed in fixed-size N-token
chunks attending the KV already resident in the slot (adopted
prefix-shared pages included), so prefill compiles are bounded by the
chunk shape instead of one per prompt length, and with
``--prefix-sharing`` a shared-prefix admission forwards only its
unshared tail.  See docs/serving.md for the full admission lifecycle.

Uniform batch (benchmark-style, same-length prompts)::

  PYTHONPATH=src python -m repro.launch.serve --arch tconst-41m --reduced \\
      --prompt-len 64 --gen 64 --batch 4

Streaming sessions (per-request prompt lengths, staggered admission,
chunked zero-host-sync decode; prints each session's stream and checks
it against single-session generation)::

  PYTHONPATH=src python -m repro.launch.serve --arch tconst-41m --reduced \\
      --sessions 3 --gen 24 --slots 2 --layout paged --page-size 16 \\
      --pool-pages 12

Shared-system-prompt demo (prefix sharing / CoW)::

  PYTHONPATH=src python -m repro.launch.serve --arch tconst-41m --reduced \\
      --sessions 4 --slots 4 --gen 16 --prompt-len 64 \\
      --layout paged --page-size 16 --prefix-sharing

Chunked tail-only admission on top (bucketed prefill compiles)::

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \\
      --sessions 4 --slots 4 --gen 16 --prompt-len 64 \\
      --layout paged --page-size 16 --prefix-sharing --prefill-chunk 16

Session tiering (oversubscribed: sessions >> slots, idle sessions spill
to a host-RAM tier store and resume token-identically; prints spill /
resume cycles and assigned-vs-spilled bytes)::

  PYTHONPATH=src python -m repro.launch.serve --arch tconst-41m --reduced \\
      --sessions 6 --slots 2 --gen 16 --layout paged --page-size 16 \\
      --spill-capacity-mb 64

Speculative decoding (sessions mode): each scheduler tick drafts k
tokens per slot (``--drafter ngram`` self-drafts from the session's own
window; ``tconst`` runs a reduced small-W model), verifies them in ONE
fixed-shape ``verify_chunk`` dispatch, and commits the verify-exact
accepted prefix — streams stay token-identical to the non-speculative
run (checked against solo generation below) while repeat-heavy text
commits up to k+1 tokens per dispatch::

  PYTHONPATH=src python -m repro.launch.serve --arch tconst-41m --reduced \\
      --sessions 3 --slots 2 --gen 24 --speculate 4 --drafter ngram

SLO-aware scheduling demo (``--workload`` replays a seeded traffic
trace — poisson or bursty arrivals, length mixes, SLO slice — through
the scheduler under a named policy and prints the telemetry summary;
compare ``--policy fifo`` vs ``--policy slo`` on the same trace)::

  PYTHONPATH=src python -m repro.launch.serve --arch tconst-41m --reduced \\
      --sessions 8 --slots 2 --chunk 4 --max-len 104 \\
      --workload bursty --policy slo --slo-ttft-chunks 6
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.config import get_config, reduced
from repro.launch.mesh import make_decode_mesh
from repro.models.api import build_decode, build_model
from repro.models.layouts import LayoutSpec
from repro.serving.engine import Engine
from repro.serving.metrics import ServingTelemetry
from repro.serving.scheduler import SlotScheduler
from repro.serving.session import Session
from repro.serving.tier_store import TierStore
from repro.serving.workload import WorkloadSpec, generate_workload


def _layout_spec(args) -> LayoutSpec:
    return LayoutSpec(kind=args.layout, page_size=args.page_size,
                      pool_pages=args.pool_pages or None)


def _session_prompt_lens(args) -> list:
    """Prompt lengths the sessions demo will submit.  Prefix sharing
    uses one common system prompt + equal-length distinct tails (equal
    lengths keep greedy parity with the solo runs bitwise-exact);
    otherwise lengths vary per session to exercise staggered phases."""
    if args.prefix_sharing:
        return [args.prompt_len + 8] * args.sessions
    return [args.prompt_len + 5 * i for i in range(args.sessions)]


def validate_layout_args(ap, cfg, args, max_len: int) -> None:
    """Startup validation of the paged-layout knobs against the model
    config and launch geometry, so a mis-sized pool fails with a clear
    message instead of a shape crash (or a scheduler rejection) at
    first admission."""
    if args.prefix_sharing:
        if not args.sessions:
            ap.error("--prefix-sharing needs --sessions N — the session "
                     "scheduler owns the prefix map and the page "
                     "refcounts (uniform batch has no admission path)")
        if args.layout not in ("paged", "paged_int8"):
            ap.error(f"--prefix-sharing shares pool PAGES; --layout "
                     f"{args.layout} has none (use paged or paged_int8)")
    if args.prefill_chunk < 0:
        ap.error(f"--prefill-chunk {args.prefill_chunk} must be positive "
                 f"(0 disables chunked admission)")
    if args.prefill_chunk:
        if not args.sessions:
            ap.error("--prefill-chunk shapes ADMISSION dispatches; the "
                     "uniform batch has no admission path (its prefill "
                     "is one fixed-shape dispatch already) — add "
                     "--sessions N")
        if args.layout in ("paged", "paged_int8") and \
                args.prefill_chunk % args.page_size != 0:
            ap.error(f"--prefill-chunk {args.prefill_chunk} must be a "
                     f"multiple of --page-size {args.page_size} — "
                     f"chunk-granular page writes cover whole pages")
    if args.speculate < 0:
        ap.error(f"--speculate {args.speculate} must be >= 0 (tokens "
                 f"drafted per slot per tick; 0 disables speculation)")
    if args.speculate and not args.sessions:
        ap.error("--speculate rides the session scheduler's verify "
                 "dispatch (the uniform batch path is greedy-Engine "
                 "only — see Engine.generate_speculative) — add "
                 "--sessions N")
    if args.workload and not args.sessions:
        ap.error("--workload replays a traffic trace through the session "
                 "scheduler (arrivals, SLOs, policies are admission-side "
                 "concepts; the uniform batch has none) — add --sessions N")
    if args.slo_ttft_chunks < 1:
        ap.error(f"--slo-ttft-chunks {args.slo_ttft_chunks} must be >= 1 "
                 f"(the deadline is counted in scheduler chunks from "
                 f"submission)")
    if args.spill_capacity_mb < 0:
        ap.error(f"--spill-capacity-mb {args.spill_capacity_mb} must be "
                 f"positive (0 disables session tiering)")
    if (args.spill_capacity_mb or args.spill_dir) and not args.sessions:
        ap.error("--spill-capacity-mb/--spill-dir tier per-SESSION slot "
                 "state; the uniform batch has no sessions to spill — "
                 "add --sessions N")
    if args.spill_dir and not args.spill_capacity_mb:
        ap.error("--spill-dir is the tier BELOW a bounded host-RAM store: "
                 "demotions to disk only happen when --spill-capacity-mb "
                 "caps the RAM tier, so without it the directory would "
                 "stay empty forever.  Size the cap in the layout's "
                 "PHYSICAL bytes — paged layouts spill only each "
                 "session's live pages and int8 snapshots stay "
                 "compressed, so one spilled session costs far less than "
                 "a dense max_len slot")
    if args.layout not in ("paged", "paged_int8"):
        return
    if cfg.attention_mode == "tconst" and cfg.arch_type not in \
            ("ssm", "audio"):
        # model-config check: pure-tconst KV is already O(1) — nothing
        # has a length axis, so the pool stores nothing and the knobs
        # are inert (tlin / dense-LM / enc-dec configs do page)
        print("[serve] note: pure tconst KV is O(1); the paged layout "
              "stores nothing in pages for this config (--page-size/"
              "--pool-pages are inert)")
    pages_per_slot = -(-max_len // args.page_size)
    slots = args.slots if args.sessions else args.batch
    full_pool = slots * pages_per_slot
    if not args.pool_pages:
        return                       # full pool: always valid, no allocator
    if args.pool_pages > full_pool:
        ap.error(
            f"--pool-pages {args.pool_pages} exceeds the full pool: "
            f"{slots} slots x {pages_per_slot} pages/slot "
            f"(max_len {max_len} / page {args.page_size}) = {full_pool} "
            f"pages — lower it or drop it for the full pool")
    if not args.sessions and args.pool_pages < full_pool:
        ap.error(
            f"--pool-pages {args.pool_pages} < full pool {full_pool} needs "
            f"the sessions-mode page allocator (uniform-batch prefill "
            f"cannot place rows in an under-sized pool); add --sessions N "
            f"or drop --pool-pages")
    # largest session this launcher will submit must be admissible
    worst_prompt = max(_session_prompt_lens(args)) if args.sessions \
        else args.prompt_len
    headroom = max(args.chunk, args.speculate + 1)
    worst_need = -(-(worst_prompt + args.gen + headroom)
                   // args.page_size)
    if worst_need > args.pool_pages:
        ap.error(
            f"--pool-pages {args.pool_pages} cannot admit the largest "
            f"session: prompt {worst_prompt} + gen {args.gen} + headroom "
            f"{headroom} needs {worst_need} pages of {args.page_size} "
            f"tokens — raise --pool-pages to >= {worst_need} or shrink "
            f"the sessions")


def build_mesh(ap, cfg, args):
    """Parse and validate ``--mesh DxM`` against the visible devices and
    the model config, so a bad geometry fails with a clear argparse
    error instead of a shape crash at first dispatch.  Returns the
    (data, model) Mesh, or None when --mesh is unset."""
    if not args.mesh:
        return None
    try:
        d_str, m_str = args.mesh.lower().split("x")
        d, m = int(d_str), int(m_str)
        if d < 1 or m < 1:
            raise ValueError
    except ValueError:
        ap.error(f"--mesh {args.mesh!r} must be DxM with positive "
                 f"integers, e.g. --mesh 2x4")
    n_dev = len(jax.devices())
    if d * m != n_dev:
        ap.error(
            f"--mesh {args.mesh}: axis product {d}x{m} = {d * m} must "
            f"equal the device count ({n_dev} visible); on CPU force "
            f"devices with XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={d * m}")
    if cfg.n_kv_heads > 1 and cfg.n_kv_heads % m != 0:
        # MQA (1 KV head) replicates its KV over model instead — exempt
        ap.error(
            f"--mesh {args.mesh}: model axis ({m}) must divide the KV "
            f"heads ({cfg.n_kv_heads}) — decode shards the KV head dim "
            f"over 'model' (try a model axis in "
            f"{[k for k in (1, 2, 4, 8) if cfg.n_kv_heads % k == 0]})")
    return make_decode_mesh(d, m)


def run_workload(cfg, api, params, args, max_len: int, mesh=None) -> int:
    """SLO-aware scheduling demo: replay a seeded traffic trace through
    the scheduler under a named policy and print the telemetry summary.

    The trace is a pure function of ``(spec, --seed)`` — rerunning with a
    different ``--policy`` replays the SAME sessions (same prompts,
    arrival chunks, SLO targets, per-session sampling seeds), so the
    printed TTFT / ITL / SLO-attainment numbers are directly comparable
    across policies.  Arrivals are denominated in scheduler chunks: the
    loop submits each session once the scheduler clock reaches its
    ``at_chunk``, then steps until every session drains."""
    spec = WorkloadSpec(
        n_sessions=args.sessions, vocab=cfg.vocab_size,
        arrival=args.workload, temperature=args.temperature,
        shared_frac=0.25 if args.prefix_sharing else 0.0,
        prefix_len=args.page_size if args.prefix_sharing else 16,
        repeat_frac=0.2, slo_frac=0.5,
        slo_ttft_chunks=args.slo_ttft_chunks)
    store = None
    if args.spill_capacity_mb:
        store = TierStore(
            capacity_bytes=int(args.spill_capacity_mb * (1 << 20)),
            spill_dir=args.spill_dir or None)
    decode = build_decode(cfg, _layout_spec(args),
                          prefill_chunk=args.prefill_chunk or None,
                          mesh=mesh)
    telemetry = ServingTelemetry()
    sched = SlotScheduler(decode, params, slots=args.slots,
                          max_len=max_len, chunk_size=args.chunk,
                          seed=args.seed,
                          prefix_sharing=args.prefix_sharing,
                          tier_store=store,
                          preempt_chunks=1 if store is not None else None,
                          policy=args.policy, telemetry=telemetry,
                          speculate=args.speculate, drafter=args.drafter)
    # leave headroom for the longest output draw (32) + one chunk of
    # over-generation so every generated session is admissible
    arrivals = generate_workload(
        spec, args.seed, max_prompt_len=max(8, max_len - 40 - args.chunk))

    t0 = time.time()
    i = 0
    while i < len(arrivals) or sched.pending or sched.active.any():
        while i < len(arrivals) and arrivals[i].at_chunk <= sched.clock:
            sched.submit(arrivals[i].session)
            i += 1
        sched.step()
        if sched.clock > 20_000:
            raise RuntimeError("workload did not drain within 20k chunks "
                               "— the scheduler is stuck")
    dt = time.time() - t0

    summary = telemetry.summary()
    total = summary["tokens_out"]
    print(f"[serve] arch={cfg.name} mode={cfg.attention_mode} "
          f"layout={sched.layout.name} workload={args.workload} "
          f"policy={args.policy} served {summary['sessions']} sessions "
          f"({total} tokens) on {args.slots} slots in {dt:.2f}s "
          f"({total / dt:.1f} tok/s)")
    print(json.dumps(summary, indent=2, sort_keys=True))
    ok = summary["finished"] == summary["sessions"]
    print(f"[serve] workload drained: {'ok' if ok else 'FAIL'} "
          f"(clock={sched.clock} chunks)")
    return 0 if ok else 1


def run_sessions(cfg, api, params, args, mesh=None) -> int:
    """Continuous-batching demo: N sessions with different prompt lengths
    admitted at staggered times into a fixed-slot batch; each streams its
    tokens and must match its own single-session generation."""
    rng = np.random.RandomState(args.seed)
    lens = _session_prompt_lens(args)
    if args.prefix_sharing:
        # shared system prompt + distinct tails: the prefix map stores
        # the common pages once, refcounted across sessions
        common = rng.randint(1, cfg.vocab_size,
                             size=args.prompt_len).astype(np.int32)
        prompts = [np.concatenate([common, rng.randint(
            1, cfg.vocab_size, size=n - args.prompt_len).astype(np.int32)])
            for n in lens]
    else:
        prompts = [rng.randint(1, cfg.vocab_size, size=n).astype(np.int32)
                   for n in lens]

    store = None
    if args.spill_capacity_mb:
        store = TierStore(
            capacity_bytes=int(args.spill_capacity_mb * (1 << 20)),
            spill_dir=args.spill_dir or None)
    decode = build_decode(cfg, _layout_spec(args),
                          prefill_chunk=args.prefill_chunk or None,
                          mesh=mesh)
    telemetry = ServingTelemetry() if args.speculate else None
    sched = SlotScheduler(decode, params, slots=args.slots,
                          max_len=args.max_len or
                          (max(len(p) for p in prompts) + args.gen + 64),
                          chunk_size=args.chunk, seed=args.seed,
                          prefix_sharing=args.prefix_sharing,
                          tier_store=store,
                          preempt_chunks=1 if store is not None else None,
                          speculate=args.speculate, drafter=args.drafter,
                          telemetry=telemetry)

    def stream(sess, tok):
        print(f"[serve]   session {sess.sid}: token[{len(sess.tokens) - 1}]"
              f" = {tok}")

    t0 = time.time()
    sessions = []
    for i, p in enumerate(prompts):
        sessions.append(sched.submit(Session(
            p, max_new_tokens=args.gen,
            temperature=args.temperature,
            eos_id=args.eos if args.eos >= 0 else None,
            on_token=stream if args.verbose else None)))
        # staggered admission: run one chunk between submissions so slots
        # sit at different W_og resync phases.  Prefix sharing admits
        # everything up front instead — sessions in flight together keep
        # the shared prefix resident and refcounted.  Tiering also
        # submits up front: staggering drains the queue one session per
        # chunk, so the oversubscription the spill path exists for
        # would never build up.
        if not args.prefix_sharing and store is None:
            sched.step()
    if args.prefix_sharing:
        sched.admit_pending()
        if sched.prefix_sharing:
            refs = sched.page_refcounts()
            print(f"[serve] prefix sharing: {int((refs > 1).sum())} shared "
                  f"pages (refcount > 1), {int((refs > 0).sum())} assigned "
                  f"of {sched.layout.pool_pages} pool pages; assigned KV "
                  f"bytes (shared prefix counted once): "
                  f"{sched.assigned_kv_bytes()}")
        else:
            print("[serve] note: this config stores nothing in pages — "
                  "prefix sharing is inert (see the paged-layout note)")
    sched.run()
    dt = time.time() - t0

    total = sum(len(s.tokens) for s in sessions)
    print(f"[serve] arch={cfg.name} mode={cfg.attention_mode} "
          f"layout={sched.layout.name} "
          f"served {len(sessions)} sessions ({total} tokens) on "
          f"{args.slots} slots in {dt:.2f}s ({total / dt:.1f} tok/s)")
    chunks = [s for s in sched.stats if s.kind == "chunk"]
    if chunks:
        # compiled entries carry the one-time jit cost; report without them
        warm = [s.seconds for s in chunks if not s.compiled] or \
            [s.seconds for s in chunks]
        print(f"[serve] decode chunks: n={len(chunks)} "
              f"({args.chunk} tokens/dispatch, zero per-token host syncs) "
              f"median={np.median(warm) * 1e3:.2f}ms")
    if args.speculate and telemetry is not None:
        spec = telemetry.summary()["spec_decode"]
        if spec:
            rounds = [s for s in sched.stats if s.kind == "spec_chunk"]
            print(f"[serve] speculative ({args.drafter} drafter, "
                  f"k={args.speculate}): {spec['rounds']} verify rounds, "
                  f"acceptance {spec['acceptance_rate']:.2f} "
                  f"({spec['accepted']}/{spec['drafted']} draft tokens), "
                  f"{spec['tokens_per_round']:.2f} committed tokens per "
                  f"{args.speculate + 1}-token verify dispatch "
                  f"(n={len(rounds)} dispatches)")
    admits = [s.seconds for s in sched.admit_stats if not s.compiled]
    if admits:
        print(f"[serve] admissions: n={len(sched.admit_stats)} "
              f"warm median={np.median(admits) * 1e3:.2f}ms")
    if sched.prefill_chunk:
        tagged = sum(1 for s in sched.admit_stats if s.compiled)
        fwd = [s.forward_tokens for s in sched.admit_stats]
        print(f"[serve] chunked prefill (chunk={sched.prefill_chunk}): "
              f"forward tokens per admission {fwd} "
              f"(prompt lengths {[len(p) for p in prompts]}); "
              f"{tagged} compile-tagged admission(s) across "
              f"{len(set(len(p) for p in prompts))} distinct lengths")
    print(f"[serve] KV-cache bytes ({args.slots} slots, "
          f"{sched.layout.name} layout): {sched.kv_bytes()}")
    if mesh is not None:
        # global vs largest per-device shard — head-sharded fields split
        # over the model axis; greedy solo-run checks below run UNMESHED,
        # so a match is the meshed-vs-1-device stream identity.
        print(f"[serve] mesh {'x'.join(str(s) for s in mesh.devices.shape)}"
              f" ({mesh.devices.size} devices): per-device KV bytes "
              f"{sched.per_device_kv_bytes()} of {sched.kv_bytes()} global")

    ok = True
    if store is not None:
        sp = sched.spill_stats
        print(f"[serve] tiering: {sp['spills']} spills / {sp['resumes']} "
              f"resumes ({sp['spilled_bytes']} snapshot bytes through the "
              f"host tier); admission cache {sp['admit_store_hits']} hits "
              f"/ {sp['admit_store_puts']} puts; {sp['pages_retired']} "
              f"prefix pages retired / {sp['pages_readopted']} re-adopted")
        for s in sessions:
            print(f"[serve]   session {s.sid}: {s.spills} spills, "
                  f"{s.resumes} resumes")
        print(f"[serve] tiering: assigned device KV bytes "
              f"{sched.assigned_kv_bytes()} vs host tier: "
              f"{store.occupancy_bytes} RAM + {store.disk_bytes} disk "
              f"({len(store)} blobs; {store.stats})")
        if args.sessions > args.slots:
            need = args.sessions - args.slots
            cycles = sum(1 for s in sessions if s.resumes >= 1)
            cyc_ok = cycles >= need
            ok = ok and cyc_ok
            print(f"[serve] tiering: {cycles} session(s) completed >= 1 "
                  f"spill->resume cycle (oversubscribed by {need}): "
                  f"{'ok' if cyc_ok else 'FAIL'}")
    if args.temperature <= 0.0 and args.eos < 0:
        if args.layout in ("int8", "paged_int8"):
            print("[serve]   (int8 layouts: tokens may differ from the "
                  "dense solo run within the quantization tolerance — "
                  "skipping the exact-match check)")
        else:                         # greedy: must match solo runs
            eng = Engine(api, params, max_len=sched.max_len)
            for s, p in zip(sessions, prompts):
                ref = eng.generate({"tokens": jnp.asarray(p)[None]},
                                   args.gen)[0].tolist()
                match = s.tokens == ref
                ok = ok and match
                print(f"[serve]   session {s.sid} (prompt {len(p)}): "
                      f"{len(s.tokens)} tokens, matches solo run: {match}")
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tconst-41m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--dtype", default="", choices=["", "bfloat16",
                                                    "float32"],
                    help="activation dtype (default: the config's); "
                         "float32 also runs matmuls at float32 precision. "
                         "The greedy solo-run check is bit-exact only in "
                         "float32: otherwise a batch of another shape may "
                         "round differently and flip a near-tie")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=64)
    ap.add_argument("--max-len", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--layout", default="dense",
                    choices=["dense", "paged", "int8", "paged_int8"],
                    help="physical cache layout behind the DecodeState "
                         "(paged_int8 = int8 pages in the shared pool, "
                         "scales in the page metadata)")
    ap.add_argument("--page-size", type=int, default=64,
                    help="tokens per page (paged layout)")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="total pages in the shared pool; 0 = full "
                         "slots*pages_per_slot (sessions mode can go "
                         "smaller — the scheduler allocates pages)")
    ap.add_argument("--eos", type=int, default=-1,
                    help="end-of-sequence token id for sessions mode "
                         "(< 0 disables early termination)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="refcounted content-addressed page sharing "
                         "(sessions mode, paged layouts): sessions get a "
                         "common system prompt whose pages are stored "
                         "once and mapped copy-on-write")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked KV-conditioned admission (sessions "
                         "mode): prefill prompts in fixed-size chunks of "
                         "N tokens (paged layouts: a page-size multiple) "
                         "so compiles are bounded by the chunk shape, "
                         "not the prompt length, and a prefix-shared "
                         "admission forwards only its unshared tail; "
                         "0 = one-shot full-prompt prefill")
    ap.add_argument("--workload", default="",
                    choices=["", "poisson", "bursty"],
                    help="replay a seeded traffic trace (sessions mode): "
                         "poisson or bursty arrivals, prompt/output "
                         "length mixes, a 50%% TTFT-SLO slice; prints "
                         "the telemetry summary (TTFT/ITL percentiles, "
                         "SLO attainment) instead of per-session streams")
    ap.add_argument("--policy", default="fifo", choices=["fifo", "slo"],
                    help="admission/victim scheduling policy (workload "
                         "mode): fifo = arrival order; slo = deadline/"
                         "cost-aware (TTFT-slack admission ordering, "
                         "cheapest-victim spills)")
    ap.add_argument("--slo-ttft-chunks", type=int, default=8,
                    help="TTFT deadline (in scheduler chunks from "
                         "submission) carried by the workload's SLO "
                         "slice")
    ap.add_argument("--speculate", type=int, default=0,
                    help="speculative decoding (sessions mode): draft N "
                         "tokens per slot per tick and verify them in "
                         "one fixed-shape dispatch; streams stay token-"
                         "identical to the non-speculative run "
                         "(verify-exact acceptance); 0 disables")
    ap.add_argument("--drafter", default="ngram",
                    choices=["ngram", "tconst"],
                    help="draft proposer for --speculate: ngram = "
                         "self-drafting from the session's own token "
                         "window (zero model cost); tconst = a reduced "
                         "small-W tconst model with its own O(1) decode "
                         "state")
    ap.add_argument("--sessions", type=int, default=0,
                    help="serve N streaming sessions (staggered admission, "
                         "variable prompt lengths) instead of one batch")
    ap.add_argument("--slots", type=int, default=2,
                    help="scheduler decode slots (sessions mode)")
    ap.add_argument("--chunk", type=int, default=8,
                    help="decode tokens per dispatch (sessions mode)")
    ap.add_argument("--verbose", action="store_true",
                    help="print every streamed token (sessions mode)")
    ap.add_argument("--spill-capacity-mb", type=float, default=0.0,
                    help="session tiering (sessions mode): host-RAM tier "
                         "store capacity in MiB for spilled slot "
                         "snapshots, retired prefix pages and admission "
                         "snapshots; oversubscribed sessions preempt-"
                         "spill at chunk boundaries and resume token-"
                         "identically; 0 disables tiering")
    ap.add_argument("--mesh", default="",
                    help="decode on a (data, model) device mesh, e.g. "
                         "--mesh 2x4: KV head dim shards over the model "
                         "axis, slot/batch dims over data; the SAME "
                         "decode path, token-identical to the 1-device "
                         "run (see docs/sharding.md; on CPU force "
                         "devices with XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N)")
    ap.add_argument("--spill-dir", default="",
                    help="disk tier below the RAM store: entries evicted "
                         "from --spill-capacity-mb demote to this "
                         "directory (mmap'd .npy, durable across runs) "
                         "instead of being dropped")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.dtype:
        cfg = cfg.replace(dtype=args.dtype)

    if args.sessions:
        eff_max_len = args.max_len or \
            (max(_session_prompt_lens(args)) + args.gen + 64)
    else:
        eff_max_len = args.max_len or (args.prompt_len + args.gen + 64)
    validate_layout_args(ap, cfg, args, eff_max_len)
    mesh = build_mesh(ap, cfg, args)
    runtime.enable_compile_cache()
    dev = jax.devices()
    print(f"[serve] platform={dev[0].platform} "
          f"device_kind={dev[0].device_kind} devices={len(dev)}")
    # float32 also runs its matmuls in float32: a TPU otherwise runs them
    # as one bfloat16 pass, rounded as the shape's dot emitter does
    with jax.default_matmul_precision(
            "highest" if args.dtype == "float32" else None):
        return _launch(cfg, args, mesh, eff_max_len)


def _launch(cfg, args, mesh, eff_max_len: int) -> int:
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(args.seed))
    if mesh is not None:
        # params replicate over the mesh (the decode step shards the KV
        # state, not the weights) — explicit placement keeps GSPMD from
        # re-deciding per dispatch
        params = jax.device_put(params, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()))

    if args.sessions:
        if args.workload:
            return run_workload(cfg, api, params, args, eff_max_len,
                                mesh=mesh)
        return run_sessions(cfg, api, params, args, mesh=mesh)

    max_len = args.max_len or (args.prompt_len + args.gen + 64)
    eng = Engine(api, params, max_len=max_len,
                 sample_temperature=args.temperature, seed=args.seed,
                 layout=_layout_spec(args), mesh=mesh)

    key = jax.random.PRNGKey(args.seed + 1)
    batch = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size)}
    if cfg.arch_type == "vlm":
        Tv = cfg.frontend_tokens
        batch["vision_embeds"] = jnp.zeros(
            (args.batch, Tv, cfg.frontend_dim), jnp.dtype(cfg.dtype))
        batch["vision_mask"] = jnp.zeros(
            (args.batch, args.prompt_len), bool).at[:, :Tv].set(True)
    if cfg.is_encdec:
        batch["audio_feats"] = jnp.zeros(
            (args.batch, cfg.encoder_seq, cfg.frontend_dim),
            jnp.dtype(cfg.dtype))

    t0 = time.time()
    out = eng.generate(batch, args.gen, record_stats=True)
    dt = time.time() - t0
    hits = [s.seconds for s in eng.stats if s.kind == "hit"]
    misses = [s.seconds for s in eng.stats if s.kind == "miss"]
    print(f"[serve] arch={cfg.name} mode={cfg.attention_mode} "
          f"layout={args.layout} generated {out.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    if hits:
        print(f"[serve] cache-hit steps: n={len(hits)} "
              f"mean={np.mean(hits)*1e3:.2f}ms")
    if misses:
        print(f"[serve] cache-miss resyncs (compacted row-wise): "
              f"n={len(misses)} mean={np.mean(misses)*1e3:.2f}ms")
    print(f"[serve] KV-cache bytes @max_len ({args.layout} layout): "
          f"{eng.cache_bytes(args.batch)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
