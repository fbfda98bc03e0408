"""setup_s: process start to the first moment of the measured window
(imports, weights, executables compiled or read from the cache,
warm-up, and filling the slots of a closed loop)."""


def read(run):
    return run.setup_s
