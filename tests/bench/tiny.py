"""Tiny cells for running the benchmark's harness on the CPU: the shipped
configurations and mixes with every size cut small.  Widths here are
test sizes only; the cells themselves run at published widths."""
from __future__ import annotations

import copy
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "bench")
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import manifest as M  # noqa: E402

TINY_MODEL = {
    "tconst-41m": dict(vocab_size=512, d_model=64, n_heads=4, n_kv_heads=4,
                       head_dim=16, d_ff=128, n_layers=8,
                       tconst={"w_oh": 8, "w_og": 8, "h": 2}),
    "smollm-360m": dict(vocab_size=512, d_model=64, n_heads=4, n_kv_heads=2,
                        head_dim=16, d_ff=128, n_layers=2),
}
TINY_TRAFFIC = {
    "stream-long": dict(slots=4, max_len=96, clients=4, backlog=16,
                        prompt_mix=[[1.0, 10, 20]],
                        output_mix=[[1.0, 20, 40]],
                        check={"requests": 2, "logit_gap_limit": 0.05}),
    "short-poisson": dict(slots=4, max_len=32, rate=20.0,
                          prompt_mix=[[0.7, 4, 8], [0.3, 9, 12]],
                          output_mix=[[1.0, 4, 12]],
                          check={"requests": 4, "logit_gap_limit": 0.05}),
    "agent-prefix": dict(slots=4, max_len=96, pool_pages=40, clients=4,
                         backlog=16,
                         n_prefixes=2, prefix_len=32,
                         prompt_mix=[[1.0, 8, 24]],
                         output_mix=[[1.0, 8, 24]],
                         check={"requests": 3, "logit_gap_limit": 0.05}),
}
TINY_SERVING = {
    "tconst-41m": dict(chunk=4, prefill_chunk=8),
    "smollm-360m": dict(chunk=4, prefill_chunk=16, page_size=8),
}
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
             "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}


def tiny_cell(name: str, dtype: str = "float32") -> M.Cell:
    """The shipped cell ``name`` with test sizes (float32 activations by
    default, so the program and the reference agree to rounding)."""
    cell = M.load_cell(name)
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(TINY_MODEL[cell.config_name])
    cell.config["model"]["dtype"] = dtype
    cell.config["serving"].update(TINY_SERVING[cell.config_name])
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.traffic.update(TINY_TRAFFIC[cell.traffic_name])
    return cell
