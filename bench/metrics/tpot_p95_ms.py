"""tpot_p95_ms: 95th percentile, over requests completed in the window
with two tokens or more, of (last token - first token) / (tokens - 1)."""
from stats import percentile


def read(run):
    xs = [(r.last - r.first) * 1e3 / (r.n - 1) for r in run.records
          if r.done is not None and run.t0 <= r.done <= run.t1 and r.n >= 2]
    return percentile(xs, 95)
