#!/usr/bin/env python3
"""Readings that the limit of a cell's check is set from, in one process
on the chip.

  python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 8

For each seed, one whole run of the cell (set-up, a window of
``--seconds`` at the cell's own load, the check), with the fp8 control
also read on the same sample and judged by the same rule as the
program: one JSON line per seed with the program's widest logit gap
(``gap``) and ``correct``, and the control's (``control_gap``,
``control_correct``, which has to come out false).  The limit lies
between the largest ``gap`` over a dozen seeds or more and the smallest
``control_gap``.  Benchmark runs never run the control.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    import manifest
    cell = manifest.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: JAX found no TPU", file=sys.stderr)
        return 1
    import cell as C
    t = T_PROCESS
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = C.run(cell, seed, args.seconds, False, t, control=True,
                    log=lambda s: print(s, file=sys.stderr, flush=True))
        ch, ctl = out["checks"], out["control"]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "gap": ch["logit_gap"]["value"],
            "control_gap": ctl["checks"]["logit_gap"]["value"],
            "limit": ch["logit_gap"]["limit"],
            "correct": out["correct"],
            "control_correct": ctl["correct"],
            "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"]}),
            flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
