"""ttft_p95_ms: 95th percentile, over every request due (open loop) or
sent (closed loop) in the window, of its first token's arrival on the
host minus that time.  Answers that come after the window count at
their real time; a request never answered makes the tail infinite,
and then nothing is read (the run is not correct either)."""
import math

from stats import percentile


def read(run):
    xs = [(r.first - r.sent) * 1e3 if r.first is not None else math.inf
          for r in run.records if run.t0 <= r.sent < run.t1]
    v = percentile(xs, 95)
    return v if v is not None and math.isfinite(v) else None
