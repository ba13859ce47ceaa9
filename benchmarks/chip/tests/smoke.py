"""Smoke-size cells for the CPU tests: the real cells' files with every size
cut, so that a run fits a test.  Nothing here is a chip result."""
from __future__ import annotations

import copy
import time

from benchmarks.chip import harness

SMOKE_SIZES = {
    "encoder_layers": 2, "encoder_d_model": 128, "encoder_heads": 4,
    "encoder_head_dim": 32, "encoder_d_ff": 256, "encoder_vocab": 256,
    "dit_layers": 2, "dit_d_model": 128, "dit_heads": 4, "dit_d_ff": 256,
    "dit_latent_dim": 16, "decoder_latent_channels": 4,
    "decoder_base_channels": 32, "cond_len": 8,
}
CPU_PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}


def smoke_cell(name: str, rate: float = 4.0) -> harness.Cell:
    """``name``'s cell at smoke sizes: 32 and 64 px classes in place of
    the cell's own, at ``rate`` requests per second."""
    cell = copy.deepcopy(harness.load_cell(name))
    cell.config.update(SMOKE_SIZES)
    cell.config["num_steps"] = min(cell.config["num_steps"], 3)
    classes = cell.traffic["classes"]
    sizes = (32, 64) if len(classes) > 1 else (64,)
    for c, res in zip(classes, sizes * len(classes)):
        c["resolution"] = res
        c["deadline_s"] = 0.5
    cell.traffic.update(rate_per_s=rate, trace_margin_s=0.5, trace_seconds=1.0,
                        drain_s=10, check_sample=2)
    return cell


def run(cell, seed=12345678901, seconds=2.0, traced=False, control=False):
    return harness.run_cell(cell, seed, seconds, traced, time.perf_counter(),
                            require_chip=False, control=control,
                            peaks=CPU_PEAKS)
