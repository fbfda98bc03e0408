"""admit_ms_p50.closed: median wall time of one admission, as the
scheduler times it (``SlotScheduler.admit_stats``, prefill through its
``block_until_ready``), over the window's admissions that compiled
nothing.  Read in the closed-loop cells, where a slot waits for its
admission and the wait costs throughput."""
from stats import percentile


def read(run):
    v = percentile(run.admit_s, 50)
    return None if v is None else v * 1e3
