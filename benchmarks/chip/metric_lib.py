"""Arithmetic shared by the metric readers in ``metrics/``."""
from __future__ import annotations

import numpy as np


def end_of_follow(run) -> float:
    t = run.cell.traffic
    return run.seconds + (t["drain_s"] if t["at_window_end"] == "follow_to_completion" else 0.0)


def latencies(run) -> list:
    """Due -> image on the host, for every request sent.  A request that
    never delivered counts from its due time to the end of following."""
    end = end_of_follow(run)
    return [(r["done"] if r["done"] is not None else end) - r["due"]
            for r in run.requests]


def percentile(values, q: float):
    return float(np.percentile(values, q)) if len(values) else None


def programs(run):
    """(stage, resolution, device seconds, runs) of each stage program in
    the traced window."""
    if run.trace is None:
        return []
    out = []
    for name, v in run.trace.programs.items():
        _, stage, size = name.split("_")[:3]
        out.append((stage, int(size), v["seconds"], v["runs"]))
    return out


def flops(run, stage, res, runs):
    return run.fam.flops(stage, res) * runs
