"""tokens_per_s: output tokens delivered to the host in the window (first
tokens included), over the window's length."""


def read(run):
    n = sum(k for t, _, _, k in run.deliveries if run.t0 <= t <= run.t1)
    return n / run.window_s
