"""The one traffic generator: open-loop arrivals from a traffic file.

A traffic file (``traffic/<name>.json``) gives the offered rate, the request
classes with their weights and deadlines, what to do at the window's end,
and ``schedule_seed``, from which the schedule is drawn:

* classes are drawn in blocks that hold each class ``weight`` times, so any
  run of whole blocks has the mix's exact proportions;
* the gaps are the quantiles (i + 1/2) / n of an exponential distribution,
  a Poisson process's gaps, stratified, in an order drawn from
  ``schedule_seed`` and scaled to fill the window: the count of requests
  (whole blocks, as near ``rate * seconds`` as they come) and the total gap
  are fixed.  The first request is due as the window opens.

The schedule does not change with a run's ``--seed``, which draws the
prompts, the noise and the weights.  The order of arrivals alone moves a
tail of some 185 requests at 0.8 of the knee by a third: six seeds' orders
gave ``latency_p95_s`` from 1.32 to 2.52 s on one v5e, while one order run
twice agreed within 2%.  A fixed schedule per cell keeps what a run
measures to the system's own variation.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass
class Arrival:
    index: int
    due: float            # seconds after the window opens
    res: int              # resolution, px
    deadline_s: float     # time allowed from due to image on host


def load(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def schedule(traffic: dict, seconds: float) -> list:
    """The arrivals of one run of ``seconds``, in order of due time."""
    rng = np.random.default_rng(traffic["schedule_seed"])
    rate = traffic["rate_per_s"]
    block = [c for c in traffic["classes"] for _ in range(c["weight"])]
    n_blocks = max(1, round(rate * seconds / len(block)))
    n = n_blocks * len(block)
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps * seconds / gaps.sum())
    classes = [c for _ in range(n_blocks) for c in rng.permutation(block)]
    due = np.cumsum(gaps) - gaps[0]
    return [Arrival(i, float(t), int(c["resolution"]), float(c["deadline_s"]))
            for i, (t, c) in enumerate(zip(due, classes))]


def resolutions(traffic: dict) -> list:
    return sorted({int(c["resolution"]) for c in traffic["classes"]})
