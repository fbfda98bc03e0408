"""chunk_ms_p50.tput: median wall time of one decode chunk, as the
scheduler times it (``SlotScheduler.stats``, dispatch through the host
sync on the sampled ids), over the window's chunks that compiled
nothing.  Read in the closed-loop throughput cells."""
from stats import percentile


def read(run):
    v = percentile(run.chunk_s, 50)
    return None if v is None else v * 1e3
