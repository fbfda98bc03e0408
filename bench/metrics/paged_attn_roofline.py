"""paged_attn_roofline: the least time the chip needs for the paged
decode attention of the traced window, over the device time of the
``paged_decode_attention`` kernel's events in the trace.

Needed time is the larger of bytes / peak HBM bandwidth and FLOPs /
peak bf16 FLOP/s.  Bytes and FLOPs come from the configuration's
``kernel_work`` over every decode step whose tokens reached the host in
the traced window, at each slot's live context length (not the pages
the kernel walks).  At about 3 FLOPs per byte the bytes bound it."""

KERNEL = "paged_decode_attention"


def read(run):
    tr = run.traced
    if tr is None:
        return None
    busy = tr.reduction["kernel_s"].get(KERNEL, 0.0)
    _, kw = run.work(tr.t0, tr.t1, KERNEL)
    if kw is None or busy <= 0:
        return None
    nbytes, flops = kw
    need = max(nbytes / run.peaks["hbm_bytes_per_s"],
               flops / run.peaks["bf16_flops_per_s"])
    return 100.0 * need / busy
