"""ttft_p95_ms.closed: 95th percentile, over every request a closed
loop's client sent in the window, of its first token's arrival on the
host minus the send time (the moment its previous request completed).
Answers that come after the window count at their real time; a request
never answered makes the tail infinite, and then nothing is read.  Read
per layer: in a closed loop the tail follows how many agents finish in
one chunk, which the order that the seed draws sets, so it swings from
seed to seed far more than a bound could hold."""
import math

from stats import percentile


def read(run):
    xs = [(r.first - r.sent) * 1e3 if r.first is not None else math.inf
          for r in run.records if run.t0 <= r.sent < run.t1]
    v = percentile(xs, 95)
    return v if v is not None and math.isfinite(v) else None
