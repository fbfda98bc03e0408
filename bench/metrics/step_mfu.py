"""step_mfu: FLOPs the traced window's work required, over the window's
length times the chip's bf16 peak.  Decode steps count at their live
lengths, admissions only the prompt beyond the shared prefix that the
traffic leaves resident on pages (``RunData.resident``), and each
TConst synchronisation at its true history length (the configuration's
``decode_flops`` and ``admit_flops``)."""


def read(run):
    tr = run.traced
    if tr is None:
        return None
    flops, _ = run.work(tr.t0, tr.t1)
    return 100.0 * flops / (tr.reduction["window_s"] *
                            run.peaks["bf16_flops_per_s"])
