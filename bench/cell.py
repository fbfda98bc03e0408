"""One run of one cell: set-up, the measured window, the check.

The program is driven the way ``repro.launch.serve`` serves sessions:
``build_decode(cfg, LayoutSpec, prefill_chunk=...)`` and a
``SlotScheduler``, fed through ``submit`` and ``step`` only.  The
benchmark makes the weights, the traffic and the reference; from the
program it takes the scheduler's served tokens, its ``StepStats`` spans
and the trace of the device.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

import manifest as M
import trace_reduce as TR
from observer import Observer, Record
from traffic_common import rng_for

SRC = os.path.join(M.ROOT, "src")
TRACE_S = 3.0          # a traced run traces the first seconds of its window
DRAIN_S = 60.0         # after the window, wait this long at most for answers
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileCounter:
    """Counts lowerings and compiles (persistent-cache reads included)."""

    def __init__(self):
        self.n = 0
        self.seconds = 0.0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.n += 1
            self.seconds += secs


_COUNTER: Optional[CompileCounter] = None


def compile_counter() -> CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        import jax
        _COUNTER = CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(_COUNTER)
    return _COUNTER


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# building the system under test
# ---------------------------------------------------------------------------

def weight_key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              (seed // 2 ** 31) % (2 ** 31))


def use_program() -> None:
    """Make the program (``src/repro`` beside the benchmark) importable."""
    if SRC not in sys.path:
        sys.path.insert(0, SRC)


def model_config(cell: M.Cell):
    """The program's ModelConfig with every size of the configuration
    file applied."""
    use_program()
    from repro.config import TConstConfig, get_config
    sizes = dict(cell.config["model"])
    if "tconst" in sizes:
        sizes["tconst"] = TConstConfig(**sizes["tconst"])
    cfg = get_config(cell.config["arch"]).replace(**sizes)
    cfg.validate()
    return cfg


def make_weights(cell: M.Cell, seed: int):
    """The weights, on the device, from the seed, in one jitted call."""
    import jax
    fn = jax.jit(lambda k: cell.model.make_weights(k, cell.config))
    return jax.block_until_ready(fn(weight_key(seed)))


def check_tree(cfg, weights) -> None:
    """The weights must have exactly the program's parameter tree."""
    import jax
    from repro.models.api import build_model
    want = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: weights)
    if jax.tree_util.tree_structure(want) != \
            jax.tree_util.tree_structure(got):
        raise ValueError("benchmark weights do not have the program's "
                         "parameter tree")
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"weight {b.shape} {b.dtype} where the "
                             f"program has {a.shape} {a.dtype}")


def build_scheduler(cell: M.Cell, cfg, weights, obs: Observer, seed: int):
    from repro.models.api import build_decode
    from repro.models.layouts import LayoutSpec
    from repro.serving.scheduler import SlotScheduler
    sv = cell.config["serving"]
    pool = cell.traffic.get("pool_pages")
    layout = LayoutSpec(kind=sv["layout"], page_size=int(sv["page_size"]),
                        pool_pages=int(pool) if pool else None)
    decode = build_decode(cfg, layout, prefill_chunk=sv["prefill_chunk"])
    sched = SlotScheduler(decode, weights, slots=int(cell.traffic["slots"]),
                          max_len=int(cell.traffic["max_len"]),
                          chunk_size=int(sv["chunk"]), seed=seed % (2 ** 31),
                          prefix_sharing=bool(sv["prefix_sharing"]),
                          telemetry=obs)
    return sched


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Traced:
    """The traced part of the window: its bounds (perf_counter seconds)
    and the reduction of its trace."""
    t0: float
    t1: float
    reduction: Dict[str, Any]


@dataclasses.dataclass
class RunData:
    """Everything a metric reader may read."""
    cell: M.Cell
    peaks: Dict[str, Any]
    seed: int
    setup_s: float
    t0: float                        # window, perf_counter seconds
    t1: float
    records: List[Record]
    deliveries: list
    admissions: list
    chunk_s: List[float]             # StepStats chunk walls in the window
    admit_s: List[float]             # StepStats admit walls in the window
    compiles: int                    # lowerings + compiles in the window
    late_s: List[float]              # how late each open-loop submit was
    traced: Optional[Traced] = None

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def work(self, t0: float, t1: float, kernel: Optional[str] = None):
        """Required FLOPs (and a kernel's (bytes, flops), if named) of the
        deliveries and admissions stamped in ``[t0, t1]``."""
        mod, c = self.cell.model, self.cell.config
        by_sid = {r.sid: r for r in self.records}
        pos = []
        for t, sid, k0, n in self.deliveries:
            if t0 <= t <= t1 and k0 > 0:
                L = by_sid[sid].prompt_len
                pos.append(L + np.arange(k0, k0 + n) - 1)
        pos = np.concatenate(pos) if pos else np.zeros((0,), np.int64)
        flops = mod.decode_flops(c, pos)
        for t, sid, L in self.admissions:
            if t0 <= t <= t1:
                flops += mod.admit_flops(c, L, self.resident(L))
        kw = mod.kernel_work(c, kernel, pos) if kernel else None
        return flops, kw

    def resident(self, L: int) -> int:
        """Prompt positions that a prompt of ``L`` tokens attends from
        adopted pages rather than forwards: with prefix sharing, the
        traffic's shared prefix floored to the page and to the prefill
        chunk grid.  Taken from the traffic alone, so that the work
        required does not follow what the program chose to forward."""
        sv = self.cell.config["serving"]
        if not sv.get("prefix_sharing"):
            return 0
        page, chunk = int(sv["page_size"]), int(sv["prefill_chunk"])
        r = int(self.cell.traffic.get("prefix_len", 0)) // page * page
        return min(r, (L - 1) // chunk * chunk)


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run(cell: M.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, log=print, control: bool = False,
        on_data=None) -> Dict[str, Any]:
    """Run ``cell`` once; returns the result line's dict.  ``control``
    (calibration only, never in a benchmark run) also reads the fp8
    control on the same sample and judges it by the same rule, under
    ``control``: its ``correct`` and its ``checks``, the control's gap in
    the place of the program's; ``on_data`` is handed the run's
    ``RunData`` (the rate sweep)."""
    import jax
    use_program()
    from repro import runtime
    from repro.serving.session import Session

    counter = compile_counter()
    runtime.enable_compile_cache()
    devices = jax.devices()[:cell.chips]
    peaks = M.load_peaks(devices[0].device_kind)
    cfg = model_config(cell)
    weights = make_weights(cell, seed)
    check_tree(cfg, weights)
    obs = Observer()
    sched = build_scheduler(cell, cfg, weights, obs, seed)
    sv = cell.config["serving"]
    traffic = cell.generator.build(cell.traffic, seed, cfg.vocab_size,
                                   int(sv["chunk"]), seconds)

    def submit(req, sent):
        s = Session(req.prompt, max_new_tokens=req.max_new)
        obs.track(s, req, sent)
        sched.submit(s)

    # warm-up: every shape the window uses, through admission, a chunk
    # and retirement; then forget it
    for req in traffic.warmup():
        submit(req, time.perf_counter())
    while sched.pending or sched.active.any():
        sched.step()
    obs.records.clear()
    obs.deliveries.clear()
    obs.admissions.clear()
    obs.drain_retired()
    # closed loop: every client sends; the window opens once all are in
    now = time.perf_counter()
    for req in traffic.initial():
        submit(req, now)
    while sched.pending:
        sched.step()
    jax.block_until_ready(sched.state)

    late: List[float] = []
    n_chunk0, n_admit0 = len(sched.stats), len(sched.admit_stats)
    kernels = [m.reader.KERNEL for m in cell.per_layer
               if hasattr(m.reader, "KERNEL")]
    tracer = _Tracer(cell.chips, kernels) if trace else None
    if tracer:
        tracer.start()
    c0 = counter.n
    t0 = time.perf_counter()
    setup_s = t0 - t_process
    t_end = t0 + seconds
    trace_end = t0 + min(seconds, TRACE_S)
    while True:
        now = time.perf_counter()
        if tracer and tracer.on and trace_end < t_end and now >= trace_end:
            tracer.stop()
        if now >= t_end:
            break
        with span("bench.submit"):
            for req in traffic.due(now - t0):
                late.append(now - (t0 + req.due))
                submit(req, t0 + req.due)
        if sched.pending or sched.active.any():
            with span("bench.step"):
                sched.step()
        else:
            nd = traffic.next_due()
            wake = min(t0 + nd if nd is not None else t_end, t_end,
                       trace_end if tracer and tracer.on else t_end)
            with span("bench.wait"):
                time.sleep(max(0.0, wake - time.perf_counter()))
        with span("bench.bookkeeping"):
            for rec in obs.drain_retired():
                if traffic.closed:
                    submit(traffic.next_request(rec.request.client),
                           rec.last)
    t1 = time.perf_counter()
    if tracer and tracer.on:
        tracer.stop()
    compiles = counter.n - c0
    chunk_s = [s.seconds for s in sched.stats[n_chunk0:]
               if s.kind == "chunk" and not s.compiled]
    admit_s = [s.seconds for s in sched.admit_stats[n_admit0:]
               if not s.compiled]
    # answers due in the window that have not come yet: wait for them
    waiting = [r for r in obs.records.values()
               if t0 <= r.sent < t1 and r.first is None]
    t_drain = time.perf_counter() + DRAIN_S
    while waiting and time.perf_counter() < t_drain:
        sched.step()
        waiting = [r for r in waiting if r.first is None]
    memory_peak = _memory_peak(devices)

    data = RunData(cell=cell, peaks=peaks, seed=seed, setup_s=setup_s,
                   t0=t0, t1=t1, records=list(obs.records.values()),
                   deliveries=list(obs.deliveries),
                   admissions=list(obs.admissions), chunk_s=chunk_s,
                   admit_s=admit_s, compiles=compiles, late_s=late)
    if tracer:
        data.traced = tracer.result()
    if on_data is not None:
        on_data(data)
    # free the program's state before the reference runs
    del sched
    gc.collect()

    # attempted: requests sent in the window or in flight in it (a closed
    # loop's streams may all have been sent during set-up); failed: those
    # sent in the window that were never answered
    attempted = [r for r in data.records if r.sent < t1 and
                 (r.done is None or r.done >= t0)]
    unanswered = sum(1 for r in attempted
                     if r.sent >= t0 and r.first is None)
    limit = cell.traffic["check"]["logit_gap_limit"]
    g = check_outputs(cell, weights, data, seed, control)
    checks = {"logit_gap": {"value": g["gap"], "limit": limit},
              "unanswered": {"value": unanswered, "limit": 0}}
    n_tok = g["tokens"]
    correct = judge(checks, n_tok)
    ctl = None
    if control:
        ctl_checks = dict(checks, logit_gap={"value": g["control_gap"],
                                             "limit": limit})
        ctl = {"correct": judge(ctl_checks, n_tok), "checks": ctl_checks}

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.reader.read(data)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    out = {"correct": bool(correct), "attempted": len(attempted),
           "failed": unanswered, "metrics": metrics, "device": device}
    if data.traced is not None:
        red = data.traced.reduction
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    info = {"compiles_in_window": compiles, "tokens_compared": n_tok,
            "window_s": data.window_s,
            "generator_late_ms_max": 1e3 * max(late) if late else 0.0}
    log(f"[bench] {cell.name} seed {seed}: " + " ".join(
        f"{k}={v}" for k, v in info.items()))
    if ctl is not None:
        out["control"] = ctl
    out["checks"] = checks
    return out


def judge(checks: Dict[str, Dict[str, float]], n_tok: int) -> bool:
    """``correct``: some tokens compared, and every number within its
    limit."""
    return n_tok > 0 and all(v["value"] <= v["limit"]
                             for v in checks.values())


class _Tracer:
    """Profiler trace of the first seconds of the window (host Python
    calls not traced), reduced and deleted once stopped.  ``kernels``:
    the names whose device time the per-layer readers take."""

    def __init__(self, n_devices: int, kernels: List[str]):
        self.n_devices = n_devices
        self.kernels = kernels
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.on = False

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on = True
        self.span = span("bench.window")
        self.span.__enter__()
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        import jax
        self.t1 = time.perf_counter()
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False

    def result(self) -> Traced:
        try:
            tr = TR.read_xspace(TR.find_xspace(self.dir), self.n_devices)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        lo, hi = TR.window_of(tr, "bench.window")
        return Traced(self.t0, self.t1, TR.reduce(tr, lo, hi, self.kernels))


# ---------------------------------------------------------------------------
# the check: served tokens against the plain reference
# ---------------------------------------------------------------------------

def sample_for_check(data: RunData, seed: int) -> List[Record]:
    """Requests drawn from the seed among those served at least one
    token, finished or still in flight (their served prefix), the
    longest among them."""
    served = [r for r in data.records if r.n >= 1]
    if not served:
        return []
    n = int(data.cell.traffic["check"]["requests"])
    longest = max(served, key=lambda r: (r.prompt_len + r.n, r.sid))
    rest = [r for r in served if r is not longest]
    rng = rng_for(seed, 7)
    pick = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def gaps(cell: M.Cell, weights, records: List[Record], quant_control: bool
         ) -> Dict[str, Any]:
    """Widest gap by which a served token's reference logit lies below
    the reference's best, over ``records``; with ``quant_control`` also
    the gap of the token the fp8 control puts first at each position."""
    import jax
    import jax.numpy as jnp
    pad = int(cell.traffic["max_len"])
    worst, worst_c, n_tok = 0.0, 0.0, 0
    with jax.default_matmul_precision("highest"):
        for r in records:
            served = np.asarray(r.session.tokens[:r.n], np.int32)
            seq = np.concatenate([r.session.prompt, served])
            first = r.prompt_len - 1
            last = len(seq) - 2              # predicts the last served token
            ref = cell.model.reference_blocks(weights, seq, first,
                                              cell.config, pad)
            ctl = cell.model.reference_blocks(
                weights, seq, first, cell.config, pad, quant=True) \
                if quant_control else None
            for p0, logits in ref:
                lo, hi = max(first, p0), min(last, p0 + logits.shape[0] - 1)
                cl = next(ctl)[1] if ctl is not None else None
                if hi < lo:
                    continue
                rows = np.arange(lo - p0, hi - p0 + 1)
                tgt = jnp.asarray(seq[rows + p0 + 1])
                rows_j = jnp.asarray(rows)
                best = jnp.max(logits[rows_j], axis=-1)
                got = logits[rows_j, tgt]
                worst = max(worst, float(jnp.max(best - got)))
                n_tok += len(rows)
                if cl is not None:
                    pick = jnp.argmax(cl[rows_j], axis=-1)
                    worst_c = max(worst_c, float(jnp.max(
                        best - logits[rows_j, pick])))
    return {"gap": worst, "control_gap": worst_c, "tokens": n_tok}


def check_outputs(cell: M.Cell, weights, data: RunData, seed: int,
                  control: bool = False) -> Dict[str, Any]:
    recs = sample_for_check(data, seed)
    if not recs:
        return {"gap": math.inf, "control_gap": math.inf, "tokens": 0}
    return gaps(cell, weights, recs, quant_control=control)
