#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
from ``BENCHMARK.json`` (see ``bench/manifest.py``).  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared beside its
limit, which also end standard error.  Where JAX finds no TPU, or fewer
chips than the cell asks for, or the program is not beside the
benchmark, the run exits non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
# the TPU runtime logs to /tmp/tpu_logs unless told otherwise; a run
# writes only inside its checkout and $TMPDIR
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, BENCH)
    import manifest
    cell = manifest.load_cell(args.workload)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench: the program (src/repro) is not beside the benchmark",
              file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r});"
              f" nothing was run", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1
    import cell as C
    result = C.run(cell, args.seed, args.seconds, bool(args.trace),
                   T_PROCESS, log=lambda s: print(s, file=sys.stderr,
                                                  flush=True))
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"[bench] check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
