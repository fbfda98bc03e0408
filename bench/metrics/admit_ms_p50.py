"""admit_ms_p50: median wall time of one admission, as the scheduler
times it (``SlotScheduler.admit_stats``, prefill through its
``block_until_ready``), over the window's admissions that compiled
nothing."""
from stats import percentile


def read(run):
    v = percentile(run.admit_s, 50)
    return None if v is None else v * 1e3
