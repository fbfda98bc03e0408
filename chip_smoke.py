#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU, at published widths.

  python3 chip_smoke.py            # one chip: phases 1-4 below
  python3 chip_smoke.py --chips 4  # four chips: one chip vs a 2x2 mesh

One chip, weights random from a fixed seed, each model run through
``repro.launch.serve``, the entry point a user runs:

1. tconst-41m, dense layout, sessions mode: prompts and generations cross
   W_og = 256, so every session resyncs on device; the run fails unless
   every stream matches its solo run.
2. tconst-41m, ``--layout int8``: the fused int8 decode kernel, compiled.
3. smollm-360m, paged layout with prefix sharing and chunked prefill:
   the paged page-walk kernel, compiled.
4. Each Pallas kernel, compiled, against its XLA or reference twin at the
   widths of both models.

Four chips: phase 1's sessions on one chip and on a ``--mesh 2x2`` (12
heads over model = 2, 2 slots over data = 2), for the dense and int8
layouts; the token streams must be identical.

Each phase prints its wall time and, apart from it, the time JAX spent
lowering and compiling (set-up).  The last line of standard output is one JSON object
naming the device.  Where JAX finds no TPU, where any phase fails, or
where the ``repro`` package is not next to this file, the script exits
non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import io
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# phase 1: four sessions, prompts of 300-315 tokens and 300 generated
# tokens each, both past W_og = 256
SESSIONS = ["--arch", "tconst-41m", "--sessions", "4", "--slots", "2",
            "--prompt-len", "300", "--gen", "300", "--chunk", "16",
            "--max-len", "704"]
PAGED = ["--arch", "smollm-360m", "--layout", "paged", "--page-size", "64",
         "--prefix-sharing", "--prefill-chunk", "64", "--sessions", "4",
         "--slots", "4", "--prompt-len", "128", "--gen", "32",
         "--chunk", "16", "--max-len", "256"]
# runs whose streams are compared bit for bit: in bfloat16 (the configs'
# dtype, which phase 2 keeps) a batch of another shape rounds differently
# and flips near-ties of the greedy argmax over a 50k vocabulary
EXACT = ["--dtype", "float32"]

# lowering to StableHLO and the XLA compile (or persistent-cache read):
# JAX's tracing events nest, so they would count a jit inside another twice
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


def _on_duration(event: str, seconds: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _compile_s[0] += seconds


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def _phase(name: str, fn):
    c0, t0 = _compile_s[0], time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    print(f"[chip_smoke] phase {name}: ok, wall {wall:.1f}s, of which "
          f"lowering + compile {_compile_s[0] - c0:.1f}s (set-up)",
          flush=True)
    return out


def _serve(argv) -> list:
    """Run ``repro.launch.serve`` in this process; returns the sessions'
    streamed tokens (from ``--verbose``) in submission order and echoes
    the other lines."""
    from repro.launch import serve
    buf = io.StringIO()
    streams: dict = {}
    try:
        with contextlib.redirect_stdout(buf):
            rc = serve.main(list(argv) + ["--verbose"])
    finally:
        for line in buf.getvalue().splitlines():
            m = re.match(r"\[serve\]\s+session (\d+): token\[\d+\] = "
                         r"(\d+)$", line)
            if m:
                streams.setdefault(int(m[1]), []).append(int(m[2]))
            else:
                print(line)
    _require(rc == 0, f"serve {' '.join(argv)} exited {rc}")
    _require(bool(streams), f"serve {' '.join(argv)} streamed no tokens")
    return [streams[sid] for sid in sorted(streams)]


def _value(argv, flag: str) -> str:
    return argv[argv.index(flag) + 1]


def _decode_custom_calls(argv) -> int:
    """Mosaic kernels in the compiled decode program the scheduler runs
    for ``argv`` (the same jitted ``decode_chunk`` at the same shapes)."""
    import jax
    import jax.numpy as jnp
    from repro.config import get_config
    from repro.models.api import build_decode, build_model, decode_chunk
    from repro.models.layouts import LayoutSpec
    cfg = get_config(_value(argv, "--arch"))
    dtype = _value(argv, "--dtype") if "--dtype" in argv else cfg.dtype
    cfg = cfg.replace(dtype=dtype)
    layout = LayoutSpec(kind=_value(argv, "--layout"),
                        page_size=int(_value(argv, "--page-size"))
                        if "--page-size" in argv else 64)
    prefill_chunk = int(_value(argv, "--prefill-chunk")) \
        if "--prefill-chunk" in argv else None
    slots, chunk = int(_value(argv, "--slots")), int(_value(argv, "--chunk"))
    max_len = int(_value(argv, "--max-len"))
    decode = build_decode(cfg, layout, prefill_chunk=prefill_chunk)
    params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda: decode.init_state(slots, max_len))
    s = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype)
    step = jax.jit(functools.partial(decode_chunk, decode),
                   static_argnames=("n_steps",))
    with jax.default_matmul_precision(
            "highest" if dtype == "float32" else None):    # as serve does
        hlo = step.lower(params, state, s((slots,), jnp.int32),
                         s((slots, 2), jnp.uint32), s((slots,), jnp.float32),
                         s((slots,), jnp.bool_), n_steps=chunk,
                         eos=s((slots,), jnp.int32)).compile().as_text()
    return hlo.count("tpu_custom_call")


def _kernel_served(argv, kernel: str) -> None:
    _serve(argv)
    n = _decode_custom_calls(argv)
    print(f"[chip_smoke]   {n} tpu_custom_call(s) in the compiled decode "
          f"program ({kernel})")
    _require(n > 0, f"{kernel}: the decode program ran no Mosaic kernel")


def _kernel_parity() -> None:
    """Each Pallas kernel, compiled, against its twin at served widths.

    Tolerance: 5e-2 absolute on outputs of unit scale.  The inputs are
    unit normals in float32 and the references run at HIGHEST matmul
    precision; the MXU may round float32 operands to bfloat16 (8-bit
    mantissa, relative error 2^-9), which moves scores of magnitude ~5 by
    up to ~1e-2 and the softmax-weighted output by a few 1e-3 - 1e-2.  A
    wrong head, page or mask is off by O(1)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.config import get_config
    from repro.kernels import ref as REF
    from repro.kernels.decode_attention import decode_attention_pallas
    from repro.kernels.flash_attention import flash_attention_fwd_pallas
    from repro.kernels.paged_decode_attention import (
        paged_decode_attention_pallas, paged_decode_attention_xla)
    from repro.models import layouts as LT
    tol = 5e-2
    page = 64
    hi = jax.default_matmul_precision("highest")
    for arch, B, S in (("tconst_41m", 2, None), ("smollm_360m", 4, 512)):
        cfg = get_config(arch)
        H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
        S = S or cfg.tconst.w_oh
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        # a shuffled pool of pages (+ the trash page) and, read through
        # the page table, the same cache as dense (B, S, KV, D) rows
        n = B * (S // page)
        table = jax.random.permutation(ks[0], n).reshape(B, -1)
        table = table.astype(jnp.int32)
        rows = lambda p: jnp.take(p, table, axis=0).reshape(
            (B, S) + p.shape[2:])
        pk = jax.random.normal(ks[1], (n + 1, page, KV, D), jnp.float32)
        pv = jax.random.normal(ks[2], (n + 1, page, KV, D), jnp.float32)
        pkq, pks = LT.quantize_int8(pk)
        pvq, pvs = LT.quantize_int8(pv)
        q = jax.random.normal(ks[3], (B, H, D), jnp.float32)
        vl = jax.random.randint(ks[4], (B,), 1, S + 1, jnp.int32)
        k, v = rows(pk), rows(pv)
        kd = LT.dequantize_int8(rows(pkq), rows(pks), jnp.float32)
        vd = LT.dequantize_int8(rows(pvq), rows(pvs), jnp.float32)
        qs = jax.random.normal(ks[5], (1, S, H, D), jnp.float32)
        pos = jnp.arange(S, dtype=jnp.int32)[None]
        with hi:
            want = {
                "decode": REF.decode_reference(q, k, v, vl),
                "decode_int8": REF.decode_reference(q, kd, vd, vl),
                "paged": paged_decode_attention_xla(q, pk, pv, table, vl),
                "paged_int8": paged_decode_attention_xla(
                    q, pkq, pvq, table, vl, k_scale=pks, v_scale=pvs),
                "flash": REF.mha_reference(qs, k[:1], v[:1], pos, pos),
            }
        got = {
            "decode": jax.jit(decode_attention_pallas)(q, k, v, vl),
            "decode_int8": jax.jit(decode_attention_pallas)(
                q, rows(pkq), rows(pvq), vl, k_scale=rows(pks),
                v_scale=rows(pvs)),
            "paged": jax.jit(paged_decode_attention_pallas)(
                q, pk, pv, table, vl),
            "paged_int8": jax.jit(paged_decode_attention_pallas)(
                q, pkq, pvq, table, vl, k_scale=pks, v_scale=pvs),
            "flash": jax.jit(flash_attention_fwd_pallas)(
                qs, k[:1], v[:1], pos, pos),
        }
        for name in want:
            err = float(np.max(np.abs(np.asarray(got[name], np.float32) -
                                      np.asarray(want[name], np.float32))))
            print(f"[chip_smoke]   {arch} {name}: max |pallas - reference| "
                  f"= {err:.3e} (tolerance {tol})")
            _require(err <= tol, f"{arch} {name}: kernel off its reference "
                                 f"by {err}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax
    from repro import runtime

    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{dev[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(dev) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(dev)} visible",
              file=sys.stderr)
        return 1
    print(f"[chip_smoke] compile cache: {runtime.enable_compile_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    if args.chips == 1:
        _phase("1 tconst-41m dense sessions",
               lambda: _serve(SESSIONS + EXACT))
        _phase("2 tconst-41m int8 sessions", lambda: _kernel_served(
            SESSIONS + ["--layout", "int8"], "fused int8 decode"))
        _phase("3 smollm-360m paged prefix-sharing sessions",
               lambda: _kernel_served(PAGED + EXACT, "paged page walk"))
        _phase("4 kernel parity", _kernel_parity)
    else:
        for layout in ("dense", "int8"):
            argv = SESSIONS + EXACT + ["--layout", layout]
            one = _phase(f"{layout} sessions on one chip",
                         lambda: _serve(argv))
            mesh = _phase(f"{layout} sessions on a 2x2 mesh",
                          lambda: _serve(argv + ["--mesh", "2x2"]))
            _require(one == mesh, f"{layout}: token streams differ between "
                                  f"one chip and the 2x2 mesh")
            print(f"[chip_smoke]   {layout}: {len(one)} token streams "
                  f"identical on one chip and on the 2x2 mesh")

    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
