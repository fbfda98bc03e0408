#!/usr/bin/env python3
"""Offered-rate sweep of an open-loop cell, in one process on the chip,
to find its knee once: the highest rate whose completions keep up over
the window.  The cell's traffic file then fixes its rate as a number.

  python3 bench/sweep.py --workload <name> --rates 100,200,300 --seconds 30

One JSON line per rate: requests due in the window, those completed in
it, those still unanswered or unfinished at its close, and the tails.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    import manifest
    cell = manifest.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 1
    import cell as C
    from stats import percentile
    seen = {}

    def keep(data):
        seen["data"] = data

    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic = dict(cell.traffic, rate=rate)
        out = C.run(cell, args.seed, args.seconds, False, time.perf_counter(),
                    log=lambda s: print(s, file=sys.stderr, flush=True),
                    on_data=keep)
        d = seen["data"]
        due = [r for r in d.records if d.t0 <= r.sent < d.t1]
        done = [r for r in due if r.done is not None and r.done <= d.t1]
        late = [r for r in due if r.sent < d.t1 - 1.0 and
                (r.done is None or r.done > d.t1)]
        ttft = [1e3 * (r.first - r.sent) for r in due if r.first is not None]
        print(json.dumps({
            "rate": rate, "due": len(due), "completed": len(done),
            "completed_per_s": len(done) / d.window_s,
            "unfinished_due_1s_before_close": len(late),
            "ttft_p50_ms": percentile(ttft, 50),
            "ttft_p95_ms": percentile(ttft, 95),
            "admit_ms_p50": 1e3 * (percentile(d.admit_s, 50) or 0),
            "chunk_ms_p50": 1e3 * (percentile(d.chunk_s, 50) or 0),
            "metrics": out["metrics"], "correct": out["correct"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
