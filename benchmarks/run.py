"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig8,eq,fig6,table1,serving]

Prints ``name,us_per_call,derived`` CSV rows (plus derived claim checks).
Roofline terms come from the dry-run artifacts via ``benchmarks.roofline``
(separate entry point — it needs the 512-device XLA_FLAGS env).
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: fig8,eq,fig6,table1,ablation,serving")
    args = ap.parse_args(argv)
    from repro import runtime
    runtime.enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    def emit(name: str, value: float, derived: str = "") -> None:
        print(f"{name},{value:.6g},{derived}", flush=True)

    print("name,us_per_call,derived")
    suites = [
        ("eq", "benchmarks.bench_complexity"),
        ("fig6", "benchmarks.bench_training"),
        ("fig8", "benchmarks.bench_inference"),
        ("table1", "benchmarks.bench_ppl"),
        ("ablation", "benchmarks.bench_ablation"),
        ("serving", "benchmarks.bench_serving"),
    ]
    for key, modname in suites:
        if only is not None and key not in only:
            continue
        t0 = time.time()
        print(f"# --- {modname} ---", flush=True)
        mod = __import__(modname, fromlist=["run"])
        mod.run(emit)
        print(f"# {modname} done in {time.time() - t0:.1f}s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
