"""Fig. 10 — end-to-end SLO attainment / mean / P95 across 4 pipelines x
workloads x {TridentServe, B1..B6}.

Also hosts:

* ``--smoke``: a CI-sized scenario set that times the event-driven clock
  against the legacy tick clock on identical traces and records the
  speedup in ``BENCH_event_sim.json`` (acceptance: >= 5x);
* ``--mixed``: the 512-chip mixed SD3+Flux+CogVideoX deployment — three
  stage-level sub-clusters under one arrival budget.  At this horizon the
  O(horizon/tick) loop does ~10^5 scheduler iterations per pipeline; the
  event clock makes the scenario routine.
* ``--mixed --shared``: the same 512 chips as ONE shared cluster
  (core/fleet.py) under a heterogeneous trace with a mid-trace traffic-mix
  flip.  Compares the fleet scheduler trio — static sub-clusters (the
  ``--mixed`` paradigm), proportional-share, adaptive — and records the
  adaptive-vs-static goodput and P95 deltas in ``BENCH_shared_cluster.json``
  (acceptance: >= 1.2x P95 improvement).
"""
from __future__ import annotations

import json
import time
from typing import Dict, List, Optional, Tuple

from benchmarks.common import Row, duration
from repro.core.baselines import BASELINES
from repro.core.simulator import SimConfig, run_sim
from repro.core.trident import TridentScheduler

PIPES_QUICK = ("flux", "hunyuanvideo")
PIPES_FULL = ("sd3", "flux", "cogvideox", "hunyuanvideo")
WORKLOADS_QUICK = ("medium", "dynamic")
WORKLOADS_FULL = ("light", "medium", "heavy", "dynamic", "proprietary")

SCHEDS = {"trident": TridentScheduler, **BASELINES}

BENCH_REPEATS = 3   # best-of-N sim-core timing (damps machine noise)

# CI smoke set: small enough to run in seconds under the event clock, with
# enough sparse-video coverage that the tick clock's O(horizon/tick) cost
# shows.  (pipeline, scheduler, workload, duration_s, rate_override)
SMOKE_SCENARIOS: Tuple[Tuple[str, str, str, float, Optional[float]], ...] = (
    ("sd3", "trident", "light", 60.0, None),
    ("sd3", "B4", "light", 60.0, None),
    ("flux", "trident", "medium", 120.0, None),
    ("hunyuanvideo", "trident", "heavy", 300.0, None),
    ("hunyuanvideo", "B6", "heavy", 300.0, None),
    ("cogvideox", "trident", "medium", 300.0, None),
    # the event clock's home turf: long sparse video traces, where the tick
    # loop burns 1/tick iterations per simulated second doing nothing —
    # overnight-valley traffic at a twentieth of the Table-5 rates
    ("hunyuanvideo", "trident", "dynamic", 3600.0, None),
    ("hunyuanvideo", "trident", "proprietary", 3600.0, 0.05),
    ("hunyuanvideo", "trident", "light", 3600.0, 0.05),
    ("cogvideox", "trident", "light", 3600.0, 0.05),
    ("cogvideox", "trident", "medium", 3600.0, 0.1),
    ("flux", "trident", "light", 3600.0, 0.1),
)

# 512-chip mixed deployment: static sub-clusters per pipeline, each run by
# its own TridentServe instance over its share of the arrival budget.
MIXED_PARTITION: Dict[str, int] = {"sd3": 128, "flux": 192, "cogvideox": 192}

# Shared-cluster variant: one 512-chip pool, heterogeneous trace with a
# mid-trace mix flip (image-dominated first half, heavy-pipeline second
# half).  Rates/flip live next to the trace generator so there is exactly
# one tuned scenario definition (workloads.FLEET_RATES / MIX_FLIP).
from repro.core.workloads import FLEET_RATES as SHARED_RATES
from repro.core.workloads import MIX_FLIP as SHARED_FLIP

SHARED_PIPELINES = ("sd3", "flux", "cogvideox")
SHARED_MODES = ("static", "proportional", "adaptive")


def run(quick: bool = True) -> List[Row]:
    rows: List[Row] = []
    pipes = PIPES_QUICK if quick else PIPES_FULL
    workloads = WORKLOADS_QUICK if quick else WORKLOADS_FULL
    dur = duration(quick)
    for pid in pipes:
        for wl in workloads:
            for name, cls in SCHEDS.items():
                res = run_sim(pid, cls, wl, dur)
                rows.append((
                    f"e2e/{pid}/{wl}/{name}/slo_pct",
                    round(res.slo_attainment * 100, 2),
                    {"mean_s": (round(res.mean_latency, 3)
                                if not res.oom else "OOM"),
                     "p95_s": (round(res.p95_latency, 3)
                               if not res.oom else "OOM"),
                     "oom": res.oom,
                     "finished": res.n_finished,
                     "requests": res.n_requests}))
    return rows


# ---------------------------------------------------------------- smoke bench

def run_smoke_mode(mode: str) -> Tuple[List[Row], float, int]:
    """Run the smoke set under one clock mode; returns (rows, wall_s, wakeups).

    Only ``Simulator.run`` is timed: profiler tables and traces are built
    outside the timer (they are identical across modes — same seeds, same
    cost model), so the wall-clock ratio measures the simulation core the
    clock mode actually changes.
    """
    import repro.configs as configs
    from repro.core import workloads
    from repro.core.profiler import Profiler
    from repro.core.simulator import Simulator

    rows: List[Row] = []
    wakeups = 0
    wall = 0.0
    profs: Dict[Tuple[str, Optional[int]], Profiler] = {}
    for pid, sched, wl, dur, rate in SMOKE_SCENARIOS:
        cls = SCHEDS[sched]
        k_min = getattr(cls, "FORCE_KMIN", None)
        prof = profs.get((pid, k_min))
        if prof is None:
            prof = profs[(pid, k_min)] = Profiler(configs.get(pid),
                                                  force_k_min=k_min)
        trace = workloads.make_trace(pid, wl, dur, prof, seed=0, rate=rate)
        sim_cfg = SimConfig(mode=mode)
        sim = Simulator(pid, cls(prof, sim_cfg, trace), trace, sim_cfg)
        t0 = time.perf_counter()
        res = sim.run()
        wall += time.perf_counter() - t0
        wakeups += res.sched_wakeups
        # duration/rate are part of the name: the set may contain the same
        # (pipeline, workload, scheduler) at several scales
        tag = f"{wl}{int(dur)}s" + (f"r{rate:g}" if rate is not None else "")
        rows.append((f"e2e_smoke/{pid}/{tag}/{sched}/{mode}/slo_pct",
                     round(res.slo_attainment * 100, 2),
                     {"mean_s": round(res.mean_latency, 3),
                      "p95_s": round(res.p95_latency, 3),
                      "wakeups": res.sched_wakeups,
                      "finished": res.n_finished}))
    return rows, wall, wakeups


_SEED_DRIVER = r"""
import json, sys, time
import repro.configs as configs
from repro.core import workloads
from repro.core.baselines import BASELINES
from repro.core.profiler import Profiler
from repro.core.simulator import SimConfig, Simulator
from repro.core.trident import TridentScheduler
SCHEDS = {"trident": TridentScheduler, **BASELINES}
payload = json.load(sys.stdin)
scenarios, repeats = payload[0], payload[1]
mode = payload[2] if len(payload) > 2 else None
best = None
for _ in range(repeats):
    wall = 0.0
    for pid, sched, wl, dur, rate in scenarios:
        cls = SCHEDS[sched]
        prof = Profiler(configs.get(pid),
                        force_k_min=getattr(cls, "FORCE_KMIN", None))
        trace = workloads.make_trace(pid, wl, dur, prof, seed=0, rate=rate)
        # no mode given: the seed SimConfig (fixed-tick loop only)
        cfg = SimConfig() if mode is None else SimConfig(mode=mode)
        sim = Simulator(pid, cls(prof, cfg, trace), trace, cfg)
        t0 = time.perf_counter()
        sim.run()
        wall += time.perf_counter() - t0
    best = wall if best is None else min(best, wall)
print(json.dumps({"wall_s": best}))
"""


def _time_ref_tree(ref_root: str, mode: Optional[str],
                   label: str) -> Optional[float]:
    """Run the smoke scenarios against a checked-out reference tree and
    return its best-of sim-core wall-clock (``mode=None`` for the seed
    tree, whose SimConfig predates clock modes)."""
    import os
    import subprocess
    import sys as _sys
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ref_root, "src")
    env["JAX_PLATFORMS"] = "cpu"  # host-only child: never contend for a chip
    payload = [[list(s) for s in SMOKE_SCENARIOS], BENCH_REPEATS]
    if mode is not None:
        payload.append(mode)
    try:
        out = subprocess.run([_sys.executable, "-c", _SEED_DRIVER],
                             input=json.dumps(payload),
                             capture_output=True, text=True, env=env,
                             timeout=1800, check=True)
        return float(json.loads(out.stdout.strip().splitlines()[-1])["wall_s"])
    except Exception as e:  # missing worktree etc. — report, don't fail smoke
        print(f"# {label} timing unavailable: {e}", flush=True)
        return None


def time_seed_tree(seed_ref: str) -> Optional[float]:
    """Seed-tree timing (the original fixed-tick loop, pre hot-path
    optimizations); ``seed_ref`` is the seed repo root (e.g. a worktree)."""
    return _time_ref_tree(seed_ref, None, "seed-ref")


def kernel_overhead_pct(pre_ref: str, mode: str,
                        rounds: int = 3) -> Optional[Tuple[float, float,
                                                           float]]:
    """Unified-kernel overhead vs a pre-unification tree, one clock mode.

    Machine load drifts on the minutes scale, so timing one tree and then
    the other lets noise masquerade as overhead; this interleaves the two
    trees in alternating subprocesses and takes best-of-rounds for each,
    which is what the <= 5% acceptance ceiling is judged against.
    Returns (overhead_pct, wall_now_s, wall_pre_s), or None when the
    reference tree is unusable."""
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    now_best = pre_best = None
    for _ in range(rounds):
        now = _time_ref_tree(here, mode, f"self({mode})")
        pre = _time_ref_tree(pre_ref, mode, f"pre-ref({mode})")
        if now is None or pre is None:
            return None
        now_best = now if now_best is None else min(now_best, now)
        pre_best = pre if pre_best is None else min(pre_best, pre)
    pct = 100.0 * (now_best - pre_best) / max(pre_best, 1e-9)
    return pct, now_best, pre_best


def _best_of(mode: str) -> Tuple[List[Row], float, int]:
    best: Optional[Tuple[List[Row], float, int]] = None
    for _ in range(BENCH_REPEATS):
        rows, wall, wk = run_smoke_mode(mode)
        if best is None or wall < best[1]:
            best = (rows, wall, wk)
    return best


def run_smoke(bench_path: Optional[str] = "BENCH_event_sim.json",
              seed_ref: Optional[str] = None,
              unified_bench_path: Optional[str] = None,
              pre_ref: Optional[str] = None) -> List[Row]:
    """Event vs tick clock on identical traces; records the speedup.

    With ``unified_bench_path`` also writes the unified-kernel BENCH: the
    same smoke measurements re-badged as the kernel's acceptance record,
    plus — when ``pre_ref`` points at a checked-out pre-unification tree
    (the last commit with the two hand-rolled loops) — the kernel's
    overhead vs those old loops, per clock mode (acceptance: <= 5%).
    """
    rows, wall_event, wk_event = _best_of("event")
    tick_rows, wall_tick, wk_tick = _best_of("tick")
    speedup = wall_tick / max(wall_event, 1e-9)
    rows.append(("e2e_smoke/wallclock_speedup_event_vs_tick", round(speedup, 2),
                 {"wall_event_s": round(wall_event, 3),
                  "wall_tick_s": round(wall_tick, 3),
                  "wakeups_event": wk_event, "wakeups_tick": wk_tick}))
    # machine-checkable parity row: benchmarks.run --smoke exits nonzero
    # when the event clock stops reproducing the tick clock's metrics
    rows.append(("e2e_smoke/metrics_match_event_vs_tick",
                 float(_smoke_metrics_match(rows, tick_rows)), {}))
    bench = {
        "bench": "event_driven_simulator_smoke",
        "scenarios": [list(s) for s in SMOKE_SCENARIOS],
        "wall_event_s": round(wall_event, 4),
        "wall_tick_s": round(wall_tick, 4),
        "speedup_event_vs_tick": round(speedup, 2),
        "sched_wakeups_event": wk_event,
        "sched_wakeups_tick": wk_tick,
        "metrics_match": _smoke_metrics_match(rows, tick_rows),
    }
    if seed_ref:
        wall_seed = time_seed_tree(seed_ref)
        if wall_seed is not None:
            bench["wall_seed_tick_s"] = round(wall_seed, 4)
            bench["speedup_vs_seed_tick"] = round(
                wall_seed / max(wall_event, 1e-9), 2)
            rows.append(("e2e_smoke/wallclock_speedup_vs_seed_tick",
                         bench["speedup_vs_seed_tick"],
                         {"wall_seed_tick_s": bench["wall_seed_tick_s"]}))
    if bench_path:
        with open(bench_path, "w") as f:
            json.dump(bench, f, indent=2)
            f.write("\n")
    if unified_bench_path:
        unified = {
            "bench": "unified_clock_kernel",
            "scenarios": [list(s) for s in SMOKE_SCENARIOS],
            "wall_event_s": round(wall_event, 4),
            "wall_tick_s": round(wall_tick, 4),
            "speedup_event_vs_tick": round(speedup, 2),
            "sched_wakeups_event": wk_event,
            "sched_wakeups_tick": wk_tick,
            "metrics_match": bench["metrics_match"],
        }
        if pre_ref:
            for label in ("event", "tick"):
                measured = kernel_overhead_pct(pre_ref, label)
                if measured is None:
                    continue
                pct, now, pre = measured
                unified[f"wall_pre_{label}_s"] = round(pre, 4)
                unified[f"kernel_overhead_pct_{label}"] = round(pct, 2)
                rows.append((f"e2e_smoke/unified_kernel_overhead_pct_{label}",
                             unified[f"kernel_overhead_pct_{label}"],
                             {"wall_pre_s": round(pre, 4),
                              "wall_now_s": round(now, 4)}))
        with open(unified_bench_path, "w") as f:
            json.dump(unified, f, indent=2)
            f.write("\n")
    return rows


def _smoke_metrics_match(event_rows: List[Row], tick_rows: List[Row]) -> bool:
    ev = {n.rsplit("/", 2)[0]: (v, d.get("mean_s"), d.get("p95_s"))
          for n, v, d in event_rows if "/slo_pct" in n}
    tk = {n.rsplit("/", 2)[0]: (v, d.get("mean_s"), d.get("p95_s"))
          for n, v, d in tick_rows if "/slo_pct" in n}
    return ev == tk


# ---------------------------------------------------------------- mixed-512

def run_mixed(quick: bool = True) -> List[Row]:
    """512-chip mixed SD3+Flux+CogVideoX deployment (event clock).

    Each pipeline gets a static sub-cluster (chips per MIXED_PARTITION) and
    its Table-5 arrival rate; the trace horizon is 1h in full mode.  Under
    the tick loop this is ~4 * 3600 / 0.25 = 57k scheduler iterations per
    pipeline even when idle — the event clock visits only arrivals,
    completions, and window boundaries.
    """
    dur = 600.0 if quick else 3600.0
    rows: List[Row] = []
    tot_reqs = tot_fin = 0
    slo_weighted = 0.0
    lat_weighted = 0.0
    p95_max = 0.0
    t0 = time.perf_counter()
    wakeups = 0
    for pid, chips in MIXED_PARTITION.items():  # detlint: ignore[DET001] module-literal dict: iteration order is source order
        cfg = SimConfig(num_chips=chips, mode="event")
        res = run_sim(pid, TridentScheduler, "dynamic", dur, sim_cfg=cfg)
        wakeups += res.sched_wakeups
        rows.append((f"e2e_mixed512/{pid}/slo_pct",
                     round(res.slo_attainment * 100, 2),
                     {"chips": chips, "mean_s": round(res.mean_latency, 3),
                      "p95_s": round(res.p95_latency, 3),
                      "finished": res.n_finished, "requests": res.n_requests,
                      "wakeups": res.sched_wakeups}))
        tot_reqs += res.n_requests
        tot_fin += res.n_finished
        slo_weighted += res.slo_attainment * res.n_requests
        lat_weighted += res.mean_latency * res.n_requests
        p95_max = max(p95_max, res.p95_latency)
    rows.append(("e2e_mixed512/aggregate/slo_pct",
                 round(100.0 * slo_weighted / max(1, tot_reqs), 2),
                 {"chips": sum(MIXED_PARTITION.values()),  # detlint: ignore[DET001] int chip counts: exact
                  "duration_s": dur,
                  "mean_s": round(lat_weighted / max(1, tot_reqs), 3),
                  "p95_max_s": round(p95_max, 3),
                  "finished": tot_fin, "requests": tot_reqs,
                  "wakeups": wakeups,
                  "wall_s": round(time.perf_counter() - t0, 2)}))
    return rows


# ---------------------------------------------------------------- shared-512

def run_mixed_shared(quick: bool = True,
                     bench_path: Optional[str] = "BENCH_shared_cluster.json",
                     duration: Optional[float] = None,
                     modes: Tuple[str, ...] = SHARED_MODES,
                     fleet_cfg_kw: Optional[Dict] = None) -> List[Row]:
    """512-chip shared cluster, SD3+Flux+CogVideoX, mid-trace mix flip.

    One heterogeneous trace per mode (same seed -> identical arrivals);
    modes are the fleet scheduler trio.  The static baseline partitions the
    pool from the first-window traffic (today's ``--mixed`` paradigm) and
    never moves; when the mix flips, its Flux/CogVideoX slices drown while
    SD3 chips idle — the adaptive fleet re-partitions and the gap between
    the two is the headline number.
    """
    from repro.core import workloads
    from repro.core.fleet import FleetConfig, PipelineRegistry, run_fleet

    dur = duration if duration is not None else (600.0 if quick else 3600.0)
    registry = PipelineRegistry(SHARED_PIPELINES)
    profs = {pid: registry.profiler(pid) for pid in SHARED_PIPELINES}
    rows: List[Row] = []
    results = {}
    for mode in modes:
        cfg = FleetConfig(num_chips=512, **(fleet_cfg_kw or {}))
        # a fresh trace per mode (requests are mutated by the sim; the seed
        # makes arrivals identical), built outside the wall timer so the
        # per-mode wall_s measures the fleet simulator alone
        trace = workloads.fleet_trace(SHARED_PIPELINES, dur, profs, seed=0,
                                      rates=SHARED_RATES, phases=SHARED_FLIP)
        t0 = time.perf_counter()
        res = run_fleet(SHARED_PIPELINES, mode=mode, duration=dur, cfg=cfg,
                        registry=registry, trace=trace)
        wall = time.perf_counter() - t0
        results[mode] = res
        rows.append((f"e2e_shared512/{mode}/p95_s", round(res.p95_latency, 3),
                     {"slo_pct": round(res.slo_attainment * 100, 2),
                      "goodput_rps": round(res.goodput, 3),
                      "mean_s": round(res.mean_latency, 3),
                      "finished": res.n_finished, "requests": res.n_requests,
                      "repartitions": len(res.repartitions) - 1,
                      "swap_cost_s": round(res.swap_cost_s, 2),
                      "wakeups": res.sched_wakeups,
                      "wall_s": round(wall, 2)}))
        for pid, m in res.per_pipeline.items():
            rows.append((f"e2e_shared512/{mode}/{pid}/p95_s",
                         round(m["p95_s"], 3),
                         {"slo_pct": round(m["slo"] * 100, 2),
                          "mean_s": round(m["mean_s"], 3),
                          "finished": int(m["finished"]),
                          "requests": int(m["requests"]),
                          "chips_final": int(m["chips"])}))
    return _shared_summary_rows(rows, results, bench_path, dur)


# ---------------------------------------------------------------- lending-256

LENDING_PIPELINES = ("sd3", "cogvideox")


def run_lending(quick: bool = True,
                bench_path: Optional[str] = "BENCH_unit_lending.json",
                duration: Optional[float] = None) -> List[Row]:
    """Cross-pipeline unit lending on the bursty-E/C trace.

    256 chips, sd3 + cogvideox, calm sizing window then three sub-window
    decode bursts (``workloads.BURSTY_EC``): too short for the adaptive
    re-partitioner's hysteresis + cooldown to chase, so without lending the
    burst pipeline drowns while sd3 units idle.  Compares ``adaptive``
    against ``adaptive`` + lending on identical arrivals; the headline is
    the worst-pipeline P95 ratio, with the diffuse path untouched by
    construction (borrowed units host E/C only — the run asserts it).

    The scenario is tuned at its 600 s scale (burst lengths are the point),
    so ``--full`` widens across seeds instead of lengthening the trace:
    the worst-pipeline ratio must hold on every seed, while aggregate
    metrics legitimately vary with the adaptive re-partition trajectory.
    """
    from repro.core import workloads
    from repro.core.fleet import FleetConfig, PipelineRegistry, run_fleet

    dur = duration if duration is not None else 600.0
    seeds = (0,) if quick else (0, 1, 2)
    registry = PipelineRegistry(LENDING_PIPELINES)
    profs = {pid: registry.profiler(pid) for pid in LENDING_PIPELINES}
    rows: List[Row] = []
    results = {}
    worst_by_seed = {}
    phases = workloads.bursty_ec_phases(dur)
    for seed in seeds:
        per_mode = {}
        for mode, lending in (("adaptive", False),
                              ("adaptive+lending", True)):
            cfg = FleetConfig(num_chips=256, lending=lending)
            trace = workloads.fleet_trace(LENDING_PIPELINES, dur, profs,
                                          seed=seed,
                                          rates=workloads.LENDING_RATES,
                                          phases=phases)
            t0 = time.perf_counter()
            res = run_fleet(LENDING_PIPELINES, mode="adaptive", duration=dur,
                            cfg=cfg, registry=registry, trace=trace)
            wall = time.perf_counter() - t0
            per_mode[mode] = res
            tag = f"e2e_lending256/{mode}" + (f"/s{seed}" if seed else "")
            rows.append((f"{tag}/p95_s", round(res.p95_latency, 3),
                         {"slo_pct": round(res.slo_attainment * 100, 2),
                          "goodput_rps": round(res.goodput, 3),
                          "mean_s": round(res.mean_latency, 3),
                          "loans": res.loans,
                          "borrowed_unit_s":
                              round(res.borrowed_unit_seconds, 1),
                          "lend_swap_cost_s":
                              round(res.lend_swap_cost_s, 2),
                          "repartitions": len(res.repartitions) - 1,
                          "wall_s": round(wall, 2)}))
            for pid, m in res.per_pipeline.items():
                rows.append((f"{tag}/{pid}/p95_s", round(m["p95_s"], 3),
                             {"slo_pct": round(m["slo"] * 100, 2),
                              "mean_s": round(m["mean_s"], 3)}))
        ad, lend = per_mode["adaptive"], per_mode["adaptive+lending"]
        worst_by_seed[seed] = (
            max(m["p95_s"] for m in ad.per_pipeline.values())  # detlint: ignore[DET004] numeric extremum over values: order-free
            / max(1e-9, max(m["p95_s"]  # detlint: ignore[DET004] numeric extremum over values: order-free
                            for m in lend.per_pipeline.values())))
        if seed == seeds[0]:
            results = per_mode
    ad, lend = results["adaptive"], results["adaptive+lending"]
    worst_x = min(worst_by_seed.values())  # detlint: ignore[DET004] numeric extremum over values: order-free
    p95_x = ad.p95_latency / max(lend.p95_latency, 1e-9)
    rows.append(("e2e_lending256/worst_pipeline_p95_improvement",
                 round(worst_x, 3),
                 {"p95_x": round(p95_x, 3),
                  "per_seed": {s: round(v, 3)
                               for s, v in worst_by_seed.items()},
                  "slo_pts": round((lend.slo_attainment
                                    - ad.slo_attainment) * 100, 2)}))
    if bench_path:
        bench = {
            "bench": "unit_lending_bursty_ec",
            "num_chips": 256,
            "pipelines": list(LENDING_PIPELINES),
            "duration_s": dur,
            "rates_rps": workloads.LENDING_RATES,
            "phases": [[f, dict(m)] for f, m in phases],
            "worst_pipeline_p95_improvement_lending_vs_adaptive":
                round(worst_x, 3),
            "worst_pipeline_p95_improvement_per_seed":
                {s: round(v, 3) for s, v in worst_by_seed.items()},
            "p95_improvement_lending_vs_adaptive": round(p95_x, 3),
            "slo_improvement_pts": round((lend.slo_attainment
                                          - ad.slo_attainment) * 100, 2),
            "loans": lend.loans,
            "borrowed_unit_seconds": round(lend.borrowed_unit_seconds, 1),
            "lend_swap_cost_s": round(lend.lend_swap_cost_s, 2),
            "borrowed_stage_runs": lend.borrowed_stage_runs,
            "diffuse_runs_on_borrowed_units":
                lend.borrowed_stage_runs.get("D", 0),
            "modes": {
                mode: {
                    "p95_s": round(r.p95_latency, 3),
                    "mean_s": round(r.mean_latency, 3),
                    "slo_pct": round(r.slo_attainment * 100, 2),
                    "goodput_rps": round(r.goodput, 3),
                    "repartitions": len(r.repartitions) - 1,
                    "per_pipeline": {
                        pid: {k: (round(v, 3) if isinstance(v, float)
                                  else v) for k, v in m.items()}
                        for pid, m in r.per_pipeline.items()},
                } for mode, r in results.items()},
        }
        with open(bench_path, "w") as f:
            json.dump(bench, f, indent=2)
            f.write("\n")
    return rows


# ---------------------------------------------------------------- predictive

PREDICTIVE_PIPELINES = ("sd3", "cogvideox")

# diurnal mix-flip scenario: 5 anti-phase square-wave periods, windows and
# forecast knobs scaled to the period so the forecaster sees >= 2 full
# periods before the trace's second half.  Rates live next to the trace
# generator (workloads.PREDICTIVE_RATES / diurnal_phases) so there is
# exactly one tuned scenario definition; these hold the fleet knobs.
from repro.core.workloads import PREDICTIVE_RATES

PREDICTIVE_PERIODS = 5
PREDICTIVE_DURATION = 1500.0
PREDICTIVE_CFG: Dict = dict(
    num_chips=256, t_win=120.0, cooldown=100.0,
    forecast_bin=10.0, forecast_history=600.0, forecast_horizon=250.0,
    prewarm_lead=50.0, prewarm_cooldown=80.0, prewarm_ttl=240.0,
    forecast_grace=60.0)

# CI-sized variant: same shape, 4 periods of 240 s on 128 chips (the
# forecaster needs 2 full periods of history, so 3 of the 7 flips land in
# the forecastable second half)
PREDICTIVE_SMOKE: Dict = dict(
    duration=960.0, periods=4,
    rates={"sd3": 14.0, "cogvideox": 0.42},
    cfg=dict(num_chips=128, t_win=90.0, cooldown=70.0,
             forecast_bin=5.0, forecast_history=480.0,
             forecast_horizon=200.0, prewarm_lead=40.0,
             prewarm_cooldown=60.0, prewarm_ttl=200.0,
             forecast_grace=50.0))


def run_predictive(quick: bool = True,
                   bench_path: Optional[str] = "BENCH_predictive.json",
                   duration: Optional[float] = None,
                   periods: int = PREDICTIVE_PERIODS,
                   rates: Optional[Dict[str, float]] = None,
                   fleet_cfg_kw: Optional[Dict] = None,
                   seeds: Optional[Tuple[int, ...]] = None) -> List[Row]:
    """Predictive re-partitioning on the diurnal mix-flip trace.

    Anti-phase square-wave demand between sd3 and cogvideox
    (``workloads.diurnal_phases``): every half period the mix flips hard,
    and the adaptive scheduler detects each flip a demand-window late,
    re-partitions with trailing-window sizing, and pays the weight reloads
    mid-queue.  The ``predictive`` scheduler (core/forecast.py) fits the
    period from rate history, pre-warms the target partition's weights on
    the units that will flip before the shift lands, and fires the swap as
    soon as the freshest observed rates confirm the predicted mix — the
    headline is the worst-pipeline P95 ratio on identical arrivals
    (acceptance: >= 1.15x at the committed scale, >= 1.0x on every
    ``--full`` seed).
    """
    from repro.core import workloads
    from repro.core.fleet import FleetConfig, PipelineRegistry, run_fleet

    dur = duration if duration is not None else PREDICTIVE_DURATION
    seeds = seeds if seeds is not None else ((0,) if quick else (0, 1, 2))
    rates = rates or PREDICTIVE_RATES
    cfg_kw = dict(PREDICTIVE_CFG)
    cfg_kw.update(fleet_cfg_kw or {})
    phases = workloads.diurnal_phases(n_periods=periods)
    registry = PipelineRegistry(PREDICTIVE_PIPELINES)
    profs = {pid: registry.profiler(pid) for pid in PREDICTIVE_PIPELINES}
    rows: List[Row] = []
    results = {}
    worst_by_seed = {}
    for seed in seeds:
        per_mode = {}
        for mode in ("adaptive", "predictive"):
            cfg = FleetConfig(**cfg_kw)
            trace = workloads.fleet_trace(PREDICTIVE_PIPELINES, dur, profs,
                                          seed=seed, rates=rates,
                                          phases=phases)
            t0 = time.perf_counter()
            res = run_fleet(PREDICTIVE_PIPELINES, mode=mode, duration=dur,
                            cfg=cfg, registry=registry, trace=trace)
            wall = time.perf_counter() - t0
            per_mode[mode] = res
            tag = f"e2e_predictive/{mode}" + (f"/s{seed}" if seed else "")
            rows.append((f"{tag}/p95_s", round(res.p95_latency, 3),
                         {"slo_pct": round(res.slo_attainment * 100, 2),
                          "goodput_rps": round(res.goodput, 3),
                          "mean_s": round(res.mean_latency, 3),
                          "repartitions": len(res.repartitions) - 1,
                          "predictive_repartitions":
                              res.predictive_repartitions,
                          "prewarm_units": res.prewarm_units,
                          "prewarm_hits": res.prewarm_hits,
                          "prewarm_cost_s": round(res.prewarm_cost_s, 2),
                          "swap_cost_s": round(res.swap_cost_s, 2),
                          "wall_s": round(wall, 2)}))
            for pid, m in res.per_pipeline.items():
                rows.append((f"{tag}/{pid}/p95_s", round(m["p95_s"], 3),
                             {"slo_pct": round(m["slo"] * 100, 2),
                              "mean_s": round(m["mean_s"], 3)}))
        ad, pr = per_mode["adaptive"], per_mode["predictive"]
        worst_by_seed[seed] = (
            max(m["p95_s"] for m in ad.per_pipeline.values())  # detlint: ignore[DET004] numeric extremum over values: order-free
            / max(1e-9, max(m["p95_s"]  # detlint: ignore[DET004] numeric extremum over values: order-free
                            for m in pr.per_pipeline.values())))
        if seed == seeds[0]:
            results = per_mode
    ad, pr = results["adaptive"], results["predictive"]
    worst_x = min(worst_by_seed.values())  # detlint: ignore[DET004] numeric extremum over values: order-free
    p95_x = ad.p95_latency / max(pr.p95_latency, 1e-9)
    rows.append(("e2e_predictive/worst_pipeline_p95_improvement",
                 round(worst_x, 3),
                 {"p95_x": round(p95_x, 3),
                  "per_seed": {s: round(v, 3)
                               for s, v in worst_by_seed.items()},
                  "slo_pts": round((pr.slo_attainment
                                    - ad.slo_attainment) * 100, 2)}))
    if bench_path:
        bench = {
            "bench": "predictive_prewarm_diurnal",
            "num_chips": cfg_kw["num_chips"],
            "pipelines": list(PREDICTIVE_PIPELINES),
            "duration_s": dur,
            "periods": periods,
            "rates_rps": dict(rates),
            "worst_pipeline_p95_improvement_predictive_vs_adaptive":
                round(worst_x, 3),
            "worst_pipeline_p95_improvement_per_seed":
                {s: round(v, 3) for s, v in worst_by_seed.items()},
            "p95_improvement_predictive_vs_adaptive": round(p95_x, 3),
            "slo_improvement_pts": round((pr.slo_attainment
                                          - ad.slo_attainment) * 100, 2),
            "predictive_repartitions": pr.predictive_repartitions,
            "prewarm_units": pr.prewarm_units,
            "prewarm_hits": pr.prewarm_hits,
            "prewarm_cost_s": round(pr.prewarm_cost_s, 3),
            "prewarm_loan_returns": pr.prewarm_loan_returns,
            "modes": {
                mode: {
                    "p95_s": round(r.p95_latency, 3),
                    "mean_s": round(r.mean_latency, 3),
                    "slo_pct": round(r.slo_attainment * 100, 2),
                    "goodput_rps": round(r.goodput, 3),
                    "repartitions": len(r.repartitions) - 1,
                    "predictive_repartitions": r.predictive_repartitions,
                    "prewarm_units": r.prewarm_units,
                    "swap_cost_s": round(r.swap_cost_s, 3),
                    "per_pipeline": {
                        pid: {k: (round(v, 3) if isinstance(v, float)
                                  else v) for k, v in m.items()}
                        for pid, m in r.per_pipeline.items()},
                } for mode, r in results.items()},
        }
        with open(bench_path, "w") as f:
            json.dump(bench, f, indent=2)
            f.write("\n")
    return rows


def run_predictive_smoke(bench_path: Optional[str] = None) -> List[Row]:
    """CI-sized ``--predictive`` variant: 4 diurnal periods on 128 chips,
    seed 0 only — exercises the whole forecast → pre-warm → predictive-fire
    path on every smoke run without touching BENCH_predictive.json.  The
    scale-aware acceptance floor is 1.0x (never worse than adaptive);
    the committed full-scale baseline pins 1.15x."""
    sm = PREDICTIVE_SMOKE
    return run_predictive(bench_path=bench_path, duration=sm["duration"],
                          periods=sm["periods"], rates=sm["rates"],
                          fleet_cfg_kw=sm["cfg"], seeds=(0,))


# -------------------------------------------------------------- cross-batch

# Fleet-level cross-lane dynamic batching on the long-prompt burst-storm
# trace (workloads.cross_batch_trace): identical arrivals, predictive
# scheduler both arms, ``cross_lane_batching`` off vs on.  The scenario
# and its rates live next to the trace generator
# (workloads.CROSS_BATCH_*); these hold the fleet knobs.
CROSS_BATCH_PIPELINES = ("flux", "hunyuanvideo")
CROSS_BATCH_DURATION = 900.0
CROSS_BATCH_CFG: Dict = dict(num_chips=96, t_win=120.0, cooldown=100.0)
CROSS_BATCH_MAX_BATCH = 8

# CI-sized variant: same burst shape at 2/3 scale (64 chips, 600 s with a
# shortened head so two full burst cycles still land).  The scale-aware
# acceptance floor is 1.0x (never worse than batching-off); the committed
# full-scale baseline pins 1.15x.
CROSS_BATCH_SMOKE: Dict = dict(
    duration=600.0, head=160.0,
    base_rates={"flux": 1.45, "hunyuanvideo": 0.35},
    wave_rates={"flux": 4.6, "hunyuanvideo": 0.2},
    cfg=dict(num_chips=64, t_win=120.0, cooldown=100.0))


def run_cross_batch(quick: bool = True,
                    bench_path: Optional[str] = "BENCH_cross_batch.json",
                    duration: Optional[float] = None,
                    base_rates: Optional[Dict[str, float]] = None,
                    wave_rates: Optional[Dict[str, float]] = None,
                    head: float = 240.0,
                    fleet_cfg_kw: Optional[Dict] = None,
                    seeds: Optional[Tuple[int, ...]] = None,
                    narrative_arms: bool = True) -> List[Row]:
    """Cross-lane dynamic batching on the long-prompt burst-storm trace.

    Correlated waves of cond-4096 prompt-expansion requests overload each
    lane's single auxiliary encode unit (the steady cheap-prompt base
    stream froze the plans with exactly one).  With ``cross_lane_batching``
    on, the fleet dispatcher fuses flux and hunyuanvideo encodes that
    share a placement shape into one batched launch on the freer aux unit
    (~1.55x batch amortization at this prompt length); the headline is the
    aggregate P95 ratio off/on on identical arrivals (acceptance:
    >= 1.15x at the committed scale, worst over ``--full`` seeds).

    ``narrative_arms`` adds two seed-0 reference runs showing the
    alternatives are structurally out on this trace: adaptive
    re-partitioning (every plan shape carries exactly one aux E unit, and
    each burst is sub-window) and unit lending (flux's 0.37 s encode sits
    below the ``lend_min_stage_s`` gate and the waves are correlated, so
    lending only adds force-return thrash).
    """
    from repro.core import workloads
    from repro.core.fleet import FleetConfig, PipelineRegistry, run_fleet

    dur = duration if duration is not None else CROSS_BATCH_DURATION
    seeds = seeds if seeds is not None else ((0,) if quick else (0, 1, 2))
    cfg_kw = dict(CROSS_BATCH_CFG)
    cfg_kw.update(fleet_cfg_kw or {})
    registry = PipelineRegistry(CROSS_BATCH_PIPELINES)
    profs = {pid: registry.profiler(pid) for pid in CROSS_BATCH_PIPELINES}

    def mk_trace(seed):
        return workloads.cross_batch_trace(dur, profs, seed=seed,
                                           base_rates=base_rates,
                                           wave_rates=wave_rates, head=head)

    def one(mode, seed, **extra_cfg):
        cfg = FleetConfig(**{**cfg_kw, **extra_cfg})
        t0 = time.perf_counter()
        res = run_fleet(CROSS_BATCH_PIPELINES, mode=mode, duration=dur,
                        cfg=cfg, registry=registry, trace=mk_trace(seed))
        return res, time.perf_counter() - t0

    rows: List[Row] = []
    results = {}
    ratio_by_seed = {}
    for seed in seeds:
        per_arm = {}
        for arm, extra in (("off", {}),
                           ("batching", dict(
                               cross_lane_batching=True,
                               cross_lane_max_batch=CROSS_BATCH_MAX_BATCH))):
            res, wall = one("predictive", seed, **extra)
            per_arm[arm] = res
            tag = f"e2e_cross_batch/{arm}" + (f"/s{seed}" if seed else "")
            rows.append((f"{tag}/p95_s", round(res.p95_latency, 3),
                         {"slo_pct": round(res.slo_attainment * 100, 2),
                          "goodput_rps": round(res.goodput, 3),
                          "mean_s": round(res.mean_latency, 3),
                          "cross_lane_merges": res.cross_lane_merges,
                          "repartitions": len(res.repartitions) - 1,
                          "wall_s": round(wall, 2)}))
            for pid, m in res.per_pipeline.items():
                rows.append((f"{tag}/{pid}/p95_s", round(m["p95_s"], 3),
                             {"slo_pct": round(m["slo"] * 100, 2),
                              "mean_s": round(m["mean_s"], 3)}))
        off, on = per_arm["off"], per_arm["batching"]
        ratio_by_seed[seed] = off.p95_latency / max(on.p95_latency, 1e-9)
        if seed == seeds[0]:
            results = per_arm
    off, on = results["off"], results["batching"]
    worst_x = min(ratio_by_seed.values())  # detlint: ignore[DET004] numeric extremum over values: order-free
    rows.append(("e2e_cross_batch/p95_improvement_batching_vs_off",
                 round(worst_x, 3),
                 {"per_seed": {s: round(v, 3)
                               for s, v in ratio_by_seed.items()},
                  "cross_lane_merges": on.cross_lane_merges,
                  "slo_pts": round((on.slo_attainment
                                    - off.slo_attainment) * 100, 2)}))
    narrative = {}
    if narrative_arms:
        ad, _ = one("adaptive", seeds[0])
        ln, _ = one("predictive", seeds[0], lending=True)
        narrative = {
            "adaptive_p95_s": round(ad.p95_latency, 3),
            "adaptive_repartitions": len(ad.repartitions) - 1,
            "lending_p95_s": round(ln.p95_latency, 3),
            "lending_loans": ln.loans,
        }
        rows.append(("e2e_cross_batch/narrative/adaptive_p95_s",
                     round(ad.p95_latency, 3),
                     {"repartitions": len(ad.repartitions) - 1}))
        rows.append(("e2e_cross_batch/narrative/lending_p95_s",
                     round(ln.p95_latency, 3), {"loans": ln.loans}))
    if bench_path:
        bench = {
            "bench": "cross_lane_batching_burst_storm",
            "num_chips": cfg_kw["num_chips"],
            "pipelines": list(CROSS_BATCH_PIPELINES),
            "duration_s": dur,
            "base_rates_rps": dict(base_rates
                                   or workloads.CROSS_BATCH_BASE_RATES),
            "wave_rates_rps": dict(wave_rates
                                   or workloads.CROSS_BATCH_WAVE_RATES),
            "cond_len": dict(workloads.CROSS_BATCH_COND),
            "cross_lane_max_batch": CROSS_BATCH_MAX_BATCH,
            "p95_improvement_batching_vs_off": round(worst_x, 3),
            "p95_improvement_per_seed":
                {s: round(v, 3) for s, v in ratio_by_seed.items()},
            "slo_improvement_pts": round((on.slo_attainment
                                          - off.slo_attainment) * 100, 2),
            "cross_lane_merges": on.cross_lane_merges,
            "narrative": narrative,
            "modes": {
                arm: {
                    "p95_s": round(r.p95_latency, 3),
                    "mean_s": round(r.mean_latency, 3),
                    "slo_pct": round(r.slo_attainment * 100, 2),
                    "goodput_rps": round(r.goodput, 3),
                    "cross_lane_merges": r.cross_lane_merges,
                    "repartitions": len(r.repartitions) - 1,
                    "per_pipeline": {
                        pid: {k: (round(v, 3) if isinstance(v, float)
                                  else v) for k, v in m.items()}
                        for pid, m in r.per_pipeline.items()},
                } for arm, r in results.items()},
        }
        with open(bench_path, "w") as f:
            json.dump(bench, f, indent=2)
            f.write("\n")
    return rows


def run_cross_batch_smoke(bench_path: Optional[str] = None) -> List[Row]:
    """CI-sized ``--cross-batch`` variant: the same burst storm at 2/3
    scale, seed 0 only, no narrative arms — exercises the whole cross-lane
    fuse path (candidate marking, E-hold, grouped ILP column, merged
    completion events) on every smoke run without touching
    BENCH_cross_batch.json."""
    sm = CROSS_BATCH_SMOKE
    return run_cross_batch(bench_path=bench_path, duration=sm["duration"],
                           head=sm["head"], base_rates=sm["base_rates"],
                           wave_rates=sm["wave_rates"],
                           fleet_cfg_kw=sm["cfg"], seeds=(0,),
                           narrative_arms=False)


# ------------------------------------------------------------------ elastic

# Elastic, failure-prone fleet on the preemption-storm capacity script
# (workloads.preemption_storm_schedule): identical arrivals and identical
# capacity events on both arms, drain-aware (act on the preemption notice
# — decommission doomed units, force-return their loans, pre-warm the
# announced join) vs drain-unaware (ignore the notice, eat the full
# in-flight requeue at the loss).  The scenario rates and schedule
# generators live next to the trace generators (workloads.ELASTIC_*);
# these hold the fleet knobs.
from repro.core.workloads import ELASTIC_LEVEL, ELASTIC_RATES

ELASTIC_PIPELINES = ("sd3", "hunyuanvideo")
ELASTIC_DURATION = 900.0
ELASTIC_CFG: Dict = dict(num_chips=256, t_win=120.0, cooldown=100.0)
# recovery window: the headline is P95 latency over requests arriving
# between a preemption *notice* and this long after its *landing* — the
# tail the drain window exists to protect.
ELASTIC_RECOVERY_TAIL = 120.0

# CI-sized variant: one storm on 128 chips at ~half rate.  Too small to
# show the drain win (the two-node storm's requeues don't back a 128-chip
# pool up), so smoke is a *mechanism canary*: the unaware arm must pay
# requeues, the aware arm must drain, and recovery P95 must hold parity
# (>= 0.9x).  The committed full-scale baseline pins the 1.15x win.
ELASTIC_SMOKE: Dict = dict(
    duration=480.0, n_storms=1,
    rates={"sd3": 4.0, "hunyuanvideo": 0.8},
    cfg=dict(num_chips=128, t_win=90.0, cooldown=70.0))


def _recovery_windows(schedule, tail: float) -> List[Tuple[float, float]]:
    """[notice, land + tail] span of every preemption in the schedule."""
    return [(ev.t - ev.lead, ev.t + tail)
            for ev in schedule if ev.kind == "preempt"]


def _recovery_p95(trace, windows, horizon_lat: float) -> Tuple[float, int]:
    """P95 latency (censored at the horizon, like FleetResult) over the
    requests that arrive inside any recovery window."""
    lat: List[float] = []
    for r in trace:
        if not any(lo <= r.arrival <= hi for lo, hi in windows):
            continue
        f = r.stage_done.get("C")
        lat.append((f - r.arrival) if f is not None
                   else (horizon_lat - r.arrival))
    lat.sort()
    n = len(lat)
    return (lat[int(0.95 * (n - 1))] if n else 0.0), n


def run_elastic(quick: bool = True,
                bench_path: Optional[str] = "BENCH_elastic.json",
                duration: Optional[float] = None,
                rates: Optional[Dict[str, float]] = None,
                n_storms: int = 2,
                fleet_cfg_kw: Optional[Dict] = None,
                seeds: Optional[Tuple[int, ...]] = None) -> List[Row]:
    """Elastic capacity + fault injection on the preemption-storm script.

    Both arms play the *same* capacity schedule through the FaultInjector
    wake source on identical arrivals: degraded node (detected and
    quarantined), announced preemption storms, autoscale joins.  The
    drain-aware arm acts on each notice — doomed units drain (only work
    that lands before the loss keeps flowing through them), their loans
    force-return, the join's incoming chips pre-warm — while the
    drain-unaware arm ignores notices and pays the full in-flight
    requeue when the nodes vanish.

    The storm script is *fixed* (``preemption_storm_schedule(seed=0)``,
    the canonical committed scenario) and bench seeds vary only the
    arrival trace — a controlled experiment: re-rolling the script with
    the seed would conflate storm-severity variance with the arm
    difference.  The headline is the recovery-window P95 ratio
    unaware/aware on the canonical trace (``seeds[0]``; acceptance:
    >= 1.15x at the committed scale, >= 0.9x in smoke); the remaining
    seeds are a robustness sweep with a never-worse floor (>= 0.95x —
    window P95 sits on the long video pipeline's runtime tail, so
    off-canonical traces read as noisy parity whenever the loss
    transient, which both arms share, dominates their windows).
    """
    from repro.core import workloads
    from repro.core.fleet import FleetConfig, PipelineRegistry, run_fleet

    dur = duration if duration is not None else ELASTIC_DURATION
    seeds = seeds if seeds is not None else ((0,) if quick else (0, 1, 2))
    rates = rates or ELASTIC_RATES
    cfg_kw = dict(ELASTIC_CFG)
    cfg_kw.update(fleet_cfg_kw or {})
    chips = cfg_kw["num_chips"]
    registry = PipelineRegistry(ELASTIC_PIPELINES)
    profs = {pid: registry.profiler(pid) for pid in ELASTIC_PIPELINES}
    rows: List[Row] = []
    results = {}
    rec = {}
    ratio_by_seed = {}
    # one canonical storm script for every bench seed (see docstring)
    schedule = workloads.preemption_storm_schedule(
        dur, chips, seed=0, n_storms=n_storms)
    windows = _recovery_windows(schedule, ELASTIC_RECOVERY_TAIL)
    for seed in seeds:
        per_arm = {}
        rec_arm = {}
        for arm, act in (("drain_aware", True), ("drain_unaware", False)):
            cfg = FleetConfig(**cfg_kw, elastic=True,
                              elastic_schedule=schedule,
                              elastic_drain=act, elastic_prewarm=act)
            trace = workloads.fleet_trace(ELASTIC_PIPELINES, dur, profs,
                                          seed=seed, rates=rates,
                                          level=ELASTIC_LEVEL)
            t0 = time.perf_counter()
            res = run_fleet(ELASTIC_PIPELINES, mode="adaptive", duration=dur,
                            cfg=cfg, registry=registry, trace=trace)
            wall = time.perf_counter() - t0
            trace_end = trace[-1].arrival if trace else 0.0
            rp95, n_rec = _recovery_p95(trace, windows,
                                        trace_end + cfg.horizon_slack)
            per_arm[arm] = res
            rec_arm[arm] = (rp95, n_rec)
            tag = f"e2e_elastic/{arm}" + (f"/s{seed}" if seed else "")
            rows.append((f"{tag}/recovery_p95_s", round(rp95, 3),
                         {"recovery_requests": n_rec,
                          "p95_s": round(res.p95_latency, 3),
                          "slo_pct": round(res.slo_attainment * 100, 2),
                          "requeued": res.requeued_requests,
                          "drained_units": res.drained_units,
                          "nodes_lost": res.nodes_lost,
                          "nodes_joined": res.nodes_joined,
                          "prewarm_chips": res.elastic_prewarm_chips,
                          "quarantined": res.quarantined_units,
                          "final_chips": res.final_chips,
                          "wall_s": round(wall, 2)}))
        aware, unaware = rec_arm["drain_aware"], rec_arm["drain_unaware"]
        ratio_by_seed[seed] = unaware[0] / max(aware[0], 1e-9)
        if seed == seeds[0]:
            results = per_arm
            rec = rec_arm
    aware, unaware = results["drain_aware"], results["drain_unaware"]
    headline_x = ratio_by_seed[seeds[0]]
    sweep_floor = min(ratio_by_seed.values())  # detlint: ignore[DET004] numeric extremum over values: order-free
    rows.append(("e2e_elastic/recovery_p95_improvement_drain_vs_unaware",
                 round(headline_x, 3),
                 {"per_seed": {s: round(v, 3)
                               for s, v in ratio_by_seed.items()},
                  "sweep_floor": round(sweep_floor, 3),
                  "requeued_unaware": unaware.requeued_requests,
                  "requeued_aware": aware.requeued_requests,
                  "slo_pts": round((aware.slo_attainment
                                    - unaware.slo_attainment) * 100, 2)}))
    if bench_path:
        bench = {
            "bench": "elastic_preemption_storm",
            "num_chips": chips,
            "pipelines": list(ELASTIC_PIPELINES),
            "duration_s": dur,
            "rates_rps": dict(rates),
            "n_storms": n_storms,
            "recovery_tail_s": ELASTIC_RECOVERY_TAIL,
            "recovery_p95_improvement_drain_vs_unaware": round(headline_x, 3),
            "recovery_p95_improvement_per_seed":
                {s: round(v, 3) for s, v in ratio_by_seed.items()},
            "recovery_p95_sweep_floor": round(sweep_floor, 3),
            "slo_improvement_pts": round((aware.slo_attainment
                                          - unaware.slo_attainment) * 100, 2),
            "modes": {
                arm: {
                    "recovery_p95_s": round(rec[arm][0], 3),
                    "recovery_requests": rec[arm][1],
                    "p95_s": round(r.p95_latency, 3),
                    "mean_s": round(r.mean_latency, 3),
                    "slo_pct": round(r.slo_attainment * 100, 2),
                    "goodput_rps": round(r.goodput, 3),
                    "capacity_events": r.capacity_events,
                    "nodes_joined": r.nodes_joined,
                    "nodes_lost": r.nodes_lost,
                    "requeued_requests": r.requeued_requests,
                    "drained_units": r.drained_units,
                    "quarantined_units": r.quarantined_units,
                    "elastic_prewarm_chips": r.elastic_prewarm_chips,
                    "final_chips": r.final_chips,
                    "repartitions": len(r.repartitions) - 1,
                    "per_pipeline": {
                        pid: {k: (round(v, 3) if isinstance(v, float)
                                  else v) for k, v in m.items()}
                        for pid, m in r.per_pipeline.items()},
                } for arm, r in results.items()},
        }
        with open(bench_path, "w") as f:
            json.dump(bench, f, indent=2)
            f.write("\n")
    return rows


def run_elastic_smoke(bench_path: Optional[str] = None) -> List[Row]:
    """CI-sized ``--elastic`` variant: one preemption storm on 128 chips
    at half rate, seed 0 only — exercises the whole fault path (notice →
    drain → loss → requeue → compacted re-partition, join pre-warm,
    degrade quarantine) on every smoke run without touching
    BENCH_elastic.json."""
    sm = ELASTIC_SMOKE
    return run_elastic(bench_path=bench_path, duration=sm["duration"],
                       rates=sm["rates"], n_storms=sm["n_storms"],
                       fleet_cfg_kw=sm["cfg"], seeds=(0,))


# ---------------------------------------------------------------- scale tier

# 8-pipeline fleet at datacenter scale: the 4 base configs plus a -v2 alias
# of each (same profile, separately-tracked traffic), rates tuned so 4096
# chips sit hot-but-not-saturated (~528 req/s aggregate).  The canonical
# definition lives in workloads.SCALE_* — the values here only name the two
# committed tiers.
SCALE_SMOKE_CHIPS = 512
SCALE_SMOKE_REQUESTS = 100_000
SCALE_FULL_CHIPS = 4096
SCALE_FULL_REQUESTS = 1_000_000
SCALE_LEVEL = "medium"
# the three flag-gated hot paths this tier exists to measure (FleetConfig
# fields; the committed BENCH baselines all run with these at their off
# defaults, pinned bit-exact by tests/test_scale_parity.py)
SCALE_FAST_KW: Dict = dict(array_state=True, incremental_ilp=True,
                           step_changed_lanes_only=True)

# Self-contained so it also runs against a pre-scale-out reference tree:
# the trace is built from the (rates, aliases, level) payload via the
# pre-existing fleet_trace API instead of workloads.scale_trace (which the
# reference tree does not have), and unknown FleetConfig fields are
# filtered out.  Only ``FleetSimulator.run`` is timed.
_SCALE_DRIVER = r"""
import dataclasses, gc, json, sys, time
from repro.core import workloads
from repro.core.fleet import (FleetConfig, FleetOrchestrator, FleetSimulator,
                              PipelineRegistry, FLEET_SCHEDULERS)
p = json.load(sys.stdin)
aliases = p["aliases"]
scale = p["num_chips"] / p["base_chips"]
rates = {pid: r * scale for pid, r in p["rates"].items()}
duration = p["n_requests"] / sum(rates.values())
pipelines = list(p["rates"])
mix = {a: workloads.MIXES[b][p["level"]] for a, b in aliases.items()}
# older trees resolve RATES[pid] eagerly inside fleet_trace's rate lookup;
# aliases only need the key to exist (their real rate comes from ``rates``)
for a in aliases:
    workloads.RATES.setdefault(a, 0.0)
fields = {f.name for f in dataclasses.fields(FleetConfig)}
cfg_kw = {k: v for k, v in p["cfg_kw"].items() if k in fields}
best = None
for _ in range(p["repeats"]):
    reg = PipelineRegistry()
    for pid in pipelines:
        if pid not in aliases:
            reg.register(pid)
    for a, b in aliases.items():
        reg.register(a, profiler=reg.profiler(b))
    profs = {pid: reg.profiler(pid) for pid in pipelines}
    trace = workloads.fleet_trace(pipelines, duration, profs, seed=0,
                                  rates=rates, level=p["level"],
                                  mix_override=mix)
    cfg = FleetConfig(num_chips=p["num_chips"], **cfg_kw)
    orch = FleetOrchestrator(reg, num_chips=p["num_chips"])
    sched = FLEET_SCHEDULERS["adaptive"](orch, cfg)
    sim = FleetSimulator(reg, sched, trace, cfg)
    # cyclic-GC pauses scale with the live heap (every trace request stays
    # reachable), so leaving the collector on taxes the longer tier
    # superlinearly for work that is not the sim core's.  Both trees are
    # timed under the same policy, so speedup ratios stay apples-to-apples.
    gc.collect()
    gc.disable()
    t0 = time.perf_counter()
    res = sim.run()
    wall = time.perf_counter() - t0
    gc.enable()
    if best is None or wall < best["wall_s"]:
        best = {"wall_s": wall, "duration_s": duration,
                "n_requests": len(trace), "n_finished": res.n_finished,
                "slo": res.slo_attainment, "wakeups": res.sched_wakeups,
                "repartitions": len(res.repartitions) - 1}
print(json.dumps(best))
"""


def _time_scale_tree(root: str, num_chips: int, n_requests: int,
                     fast: bool, repeats: int, label: str) -> Optional[Dict]:
    """Run the scale scenario against a checked-out tree; returns the
    best-of-``repeats`` sim-core measurement dict, or None."""
    import os
    import subprocess
    import sys as _sys
    from repro.core import workloads as wl
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    env["JAX_PLATFORMS"] = "cpu"  # host-only child: never contend for a chip
    payload = {"num_chips": num_chips, "n_requests": n_requests,
               "base_chips": wl.SCALE_BASE_CHIPS, "rates": wl.SCALE_RATES,
               "aliases": wl.SCALE_ALIASES, "level": SCALE_LEVEL,
               "cfg_kw": SCALE_FAST_KW if fast else {}, "repeats": repeats}
    try:
        out = subprocess.run([_sys.executable, "-c", _SCALE_DRIVER],
                             input=json.dumps(payload),
                             capture_output=True, text=True, env=env,
                             timeout=3600, check=True)
        return json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:  # missing worktree etc. — report, don't fail
        print(f"# {label} timing unavailable: {e}", flush=True)
        return None


def run_scale(full: bool = False,
              bench_path: Optional[str] = "BENCH_scale.json",
              scale_ref: Optional[str] = None) -> List[Row]:
    """The 4096-chip / 1M-request sim-core throughput tier (``--scale``).

    Headline: requests per second of *wall clock* the simulator core
    sustains on the 8-pipeline scale trace with the three flag-gated hot
    paths on (``SCALE_FAST_KW``) — the same role BENCH_unified_clock.json
    plays for kernel overhead, at fleet scale.  Smoke mode runs the
    512-chip / 100k-request slice; ``--full`` runs the committed
    4096-chip / 1M-request tier.

    With ``scale_ref`` (a checked-out pre-scale-out tree), a 100k-request
    probe slice at the same chip count is timed against both trees in
    alternating subprocesses (best-of interleaved rounds, the
    BENCH_unified_clock method, so minutes-scale machine drift cannot
    masquerade as speedup).  ``speedup_same_tier`` is the probe ratio;
    ``speedup_extrapolated`` divides the full run's throughput by the
    reference tree's probe throughput — flat extrapolation across request
    count, which is *generous* to the reference (its per-wake-up costs
    cannot shrink on a 10x longer trace).
    """
    import os
    from repro.core import workloads as wl
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    chips = SCALE_FULL_CHIPS if full else SCALE_SMOKE_CHIPS
    n_req = SCALE_FULL_REQUESTS if full else SCALE_SMOKE_REQUESTS
    probe_req = min(n_req, SCALE_SMOKE_REQUESTS)
    rows: List[Row] = []

    now_probe = pre_probe = None
    for _ in range(BENCH_REPEATS):
        now = _time_scale_tree(here, chips, probe_req, True, 1,
                               "self(scale)")
        if now is None:
            return rows
        if now_probe is None or now["wall_s"] < now_probe["wall_s"]:
            now_probe = now
        if scale_ref:
            pre = _time_scale_tree(scale_ref, chips, probe_req, False, 1,
                                   "scale-ref")
            if pre is not None and (pre_probe is None
                                    or pre["wall_s"] < pre_probe["wall_s"]):
                pre_probe = pre

    if full:
        head = _time_scale_tree(here, chips, n_req, True, 1, "self(scale)")
        if head is None:
            return rows
    else:
        head = now_probe
    rps = head["n_requests"] / max(head["wall_s"], 1e-9)
    rows.append((f"e2e_scale/{chips}chips/{head['n_requests']}req"
                 "/throughput_rps", round(rps, 1),
                 {"wall_s": round(head["wall_s"], 2),
                  "slo_pct": round(head["slo"] * 100, 2),
                  "finished": head["n_finished"],
                  "wakeups": head["wakeups"],
                  "repartitions": head["repartitions"]}))
    bench = {
        "bench": "scale_sim_core",
        "num_chips": chips,
        "pipelines": list(wl.SCALE_PIPELINES),
        "level": SCALE_LEVEL,
        "fast_path": dict(SCALE_FAST_KW),
        "n_requests": head["n_requests"],
        "duration_s": round(head["duration_s"], 1),
        "wall_s": round(head["wall_s"], 2),
        "throughput_rps": round(rps, 1),
        "n_finished": head["n_finished"],
        "slo_pct": round(head["slo"] * 100, 2),
        "sched_wakeups": head["wakeups"],
    }
    if pre_probe is not None:
        rps_now_probe = now_probe["n_requests"] / max(now_probe["wall_s"],
                                                      1e-9)
        rps_pre_probe = pre_probe["n_requests"] / max(pre_probe["wall_s"],
                                                      1e-9)
        bench["probe"] = {
            "num_chips": chips, "n_requests": now_probe["n_requests"],
            "wall_now_s": round(now_probe["wall_s"], 2),
            "wall_pre_s": round(pre_probe["wall_s"], 2),
            "throughput_now_rps": round(rps_now_probe, 1),
            "throughput_pre_rps": round(rps_pre_probe, 1),
        }
        bench["speedup_same_tier"] = round(rps_now_probe
                                           / max(rps_pre_probe, 1e-9), 2)
        bench["speedup_extrapolated"] = round(rps
                                              / max(rps_pre_probe, 1e-9), 2)
        rows.append((f"e2e_scale/{chips}chips/speedup_same_tier",
                     bench["speedup_same_tier"],
                     {"pre_rps": round(rps_pre_probe, 1),
                      "now_rps": round(rps_now_probe, 1)}))
        rows.append((f"e2e_scale/{chips}chips/speedup_extrapolated",
                     bench["speedup_extrapolated"],
                     {"full_rps": round(rps, 1),
                      "pre_probe_rps": round(rps_pre_probe, 1)}))
    if bench_path:
        with open(bench_path, "w") as f:
            json.dump(bench, f, indent=2)
            f.write("\n")
    return rows


# ------------------------------------------------------------- wall profile

# per-subsystem wall-share buckets: (bucket, module path, class, methods)
_PROFILE_TARGETS = (
    ("dispatch_ilp", "repro.core.dispatcher", "Dispatcher", ("dispatch",)),
    ("cross_lane_batching", "repro.core.dispatcher", "CrossLaneBatcher",
     ("select", "step")),
    ("monitor", "repro.core.monitor", "Monitor",
     ("record_stage", "record_backlog", "next_window_boundary",
      "pattern_change")),
    ("monitor", "repro.core.monitor", "FleetMonitor",
     ("record_arrival", "record_finish", "record_util",
      "record_class_demand", "demand", "demand_shares", "slo_attainment",
      "backlog_pressure", "idle_supply", "next_window_boundary",
      "mix_shift")),
    ("orchestrator", "repro.core.fleet", "FleetOrchestrator",
     ("generate", "budgets")),
    ("lending", "repro.core.lending", "UnitLendingBroker",
     ("step", "sample")),
    ("engine_execute", "repro.core.runtime", "RuntimeEngine", ("execute",)),
)


def run_profile(full: bool = False) -> List[Row]:
    """``--profile``: per-subsystem wall shares of one scale-tier run.

    Wraps the subsystem entry points (dispatch/ILP, monitor, orchestrator,
    lending, cross-lane batching, engine execute) with wall accumulators
    and runs the scale slice in-process; whatever wall is left over is the
    clock kernel + lane bookkeeping.  A single global re-entrancy guard
    attributes nested calls (e.g. the orchestrator consulting the monitor)
    to the *outermost* bucket, so the shares are additive.
    """
    import importlib
    from repro.core import workloads
    from repro.core.fleet import (FleetConfig, FleetOrchestrator,
                                  FleetSimulator, PipelineRegistry,
                                  FLEET_SCHEDULERS)

    chips = SCALE_FULL_CHIPS if full else SCALE_SMOKE_CHIPS
    n_req = (SCALE_FULL_REQUESTS if full else SCALE_SMOKE_REQUESTS) // 10
    acc: Dict[str, float] = {}
    depth = [0]
    patched = []
    for bucket, modname, clsname, methods in _PROFILE_TARGETS:
        try:
            cls = getattr(importlib.import_module(modname), clsname)
        except (ImportError, AttributeError):
            continue
        for meth in methods:
            orig = cls.__dict__.get(meth)
            if orig is None:
                continue

            def timed(*a, __orig=orig, __b=bucket, **kw):
                if depth[0]:
                    return __orig(*a, **kw)
                depth[0] = 1
                t0 = time.perf_counter()
                try:
                    return __orig(*a, **kw)
                finally:
                    depth[0] = 0
                    acc[__b] = (acc.get(__b, 0.0)
                                + time.perf_counter() - t0)
            setattr(cls, meth, timed)
            patched.append((cls, meth, orig))
    try:
        reg = PipelineRegistry()
        for pid in workloads.SCALE_PIPELINES:
            if pid not in workloads.SCALE_ALIASES:
                reg.register(pid)
        for a, b in workloads.SCALE_ALIASES.items():
            reg.register(a, profiler=reg.profiler(b))
        profs = {pid: reg.profiler(pid) for pid in workloads.SCALE_PIPELINES}
        dur = workloads.scale_duration(n_req, chips)
        trace = workloads.scale_trace(dur, profs, seed=0, num_chips=chips,
                                      level=SCALE_LEVEL)
        cfg = FleetConfig(num_chips=chips, **SCALE_FAST_KW)
        orch = FleetOrchestrator(reg, num_chips=chips)
        sched = FLEET_SCHEDULERS["adaptive"](orch, cfg)
        sim = FleetSimulator(reg, sched, trace, cfg)
        t0 = time.perf_counter()
        sim.run()
        wall = time.perf_counter() - t0
    finally:
        for cls, meth, orig in patched:
            setattr(cls, meth, orig)
    rows: List[Row] = []
    accounted = sum(acc[k] for k in sorted(acc))
    acc["clock_kernel_and_lanes"] = max(0.0, wall - accounted)
    for bucket in sorted(acc):
        rows.append((f"e2e_scale_profile/{chips}chips/{bucket}/wall_s",
                     round(acc[bucket], 3),
                     {"share_pct": round(100.0 * acc[bucket]
                                         / max(wall, 1e-9), 1)}))
    rows.append((f"e2e_scale_profile/{chips}chips/total/wall_s",
                 round(wall, 3),
                 {"requests": len(trace),
                  "throughput_rps": round(len(trace) / max(wall, 1e-9), 1)}))
    return rows


def run_shared_smoke(bench_path: Optional[str] = None) -> List[Row]:
    """CI-sized ``--mixed --shared`` variant: short flip trace, static vs
    adaptive only, fleet windows shrunk to match — exercises the whole fleet
    path (partition, mix-shift detection, re-partition with reload costs)
    on every smoke run without touching BENCH_shared_cluster.json.
    ``bench_path`` (used by ``benchmarks.run --smoke``) writes the smoke
    run's own JSON for the check_regression gate."""
    return run_mixed_shared(bench_path=bench_path, duration=240.0,
                            modes=("static", "adaptive"),
                            fleet_cfg_kw={"t_win": 90.0, "cooldown": 60.0})


def _shared_summary_rows(rows: List[Row], results: Dict,
                         bench_path: Optional[str], dur: float) -> List[Row]:
    if "static" in results and "adaptive" in results:
        st, ad = results["static"], results["adaptive"]
        p95_x = st.p95_latency / max(ad.p95_latency, 1e-9)
        goodput_x = ad.goodput / max(st.goodput, 1e-9)
        worst_x = (max(m["p95_s"] for m in st.per_pipeline.values())  # detlint: ignore[DET004] numeric extremum over values: order-free
                   / max(1e-9, max(m["p95_s"]  # detlint: ignore[DET004] numeric extremum over values: order-free
                                   for m in ad.per_pipeline.values())))
        rows.append(("e2e_shared512/p95_improvement_adaptive_vs_static",
                     round(p95_x, 2),
                     {"goodput_x": round(goodput_x, 3),
                      "worst_pipeline_p95_x": round(worst_x, 2)}))
        if bench_path:
            bench = {
                "bench": "shared_cluster_mix_flip",
                "num_chips": 512,
                "pipelines": list(SHARED_PIPELINES),
                "duration_s": dur,
                "rates_rps": SHARED_RATES,
                "phases": [[f, dict(m)] for f, m in SHARED_FLIP],
                "p95_improvement_adaptive_vs_static": round(p95_x, 2),
                "goodput_improvement_adaptive_vs_static": round(goodput_x, 3),
                "worst_pipeline_p95_improvement": round(worst_x, 2),
                "modes": {
                    mode: {
                        "p95_s": round(r.p95_latency, 3),
                        "mean_s": round(r.mean_latency, 3),
                        "slo_pct": round(r.slo_attainment * 100, 2),
                        "goodput_rps": round(r.goodput, 3),
                        "finished": r.n_finished,
                        "requests": r.n_requests,
                        "repartitions": len(r.repartitions) - 1,
                        "swap_cost_s": round(r.swap_cost_s, 2),
                        "units_reloaded": r.units_reloaded,
                        "per_pipeline": {
                            pid: {k: (round(v, 3) if isinstance(v, float)
                                      else v) for k, v in m.items()}
                            for pid, m in r.per_pipeline.items()},
                    } for mode, r in results.items()},
            }
            with open(bench_path, "w") as f:
                json.dump(bench, f, indent=2)
                f.write("\n")
    return rows


if __name__ == "__main__":
    import argparse

    from benchmarks.common import emit

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke set + event-vs-tick speedup "
                         "(writes BENCH_event_sim.json)")
    ap.add_argument("--mixed", action="store_true",
                    help="512-chip mixed SD3+Flux+CogVideoX scenario")
    ap.add_argument("--shared", action="store_true",
                    help="one shared 512-chip cluster under a mix-flip "
                         "trace; fleet scheduler trio (writes "
                         "BENCH_shared_cluster.json); implies --mixed")
    ap.add_argument("--lending", action="store_true",
                    help="cross-pipeline unit lending on the bursty-E/C "
                         "trace: adaptive vs adaptive+lending (writes "
                         "BENCH_unit_lending.json); implies --mixed "
                         "--shared")
    ap.add_argument("--predictive", action="store_true",
                    help="predictive re-partitioning on the diurnal "
                         "mix-flip trace: adaptive vs predictive (writes "
                         "BENCH_predictive.json)")
    ap.add_argument("--cross-batch", action="store_true",
                    help="cross-lane dynamic batching on the long-prompt "
                         "burst-storm trace: predictive with batching off "
                         "vs on (writes BENCH_cross_batch.json)")
    ap.add_argument("--elastic", action="store_true",
                    help="elastic, failure-prone fleet on the "
                         "preemption-storm capacity script: drain-aware "
                         "vs drain-unaware recovery (writes "
                         "BENCH_elastic.json)")
    ap.add_argument("--scale", action="store_true",
                    help="sim-core throughput tier: the 8-pipeline scale "
                         "trace with the flag-gated hot paths on — "
                         "512 chips / 100k requests by default, "
                         "4096 chips / 1M requests with --full (writes "
                         "BENCH_scale.json)")
    ap.add_argument("--profile", action="store_true",
                    help="per-subsystem wall shares (clock kernel, "
                         "dispatch/ILP, monitor, orchestrator, lending, "
                         "cross-lane batching) of one scale-tier run")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--bench-json", default="BENCH_event_sim.json")
    ap.add_argument("--seed-ref", default=None,
                    help="path to a checked-out seed tree; also times the "
                         "original tick loop for the BENCH record")
    ap.add_argument("--unified-json", default=None,
                    help="with --smoke: also write the unified-kernel "
                         "BENCH (e.g. BENCH_unified_clock.json)")
    ap.add_argument("--shared-json", default="BENCH_shared_cluster.json",
                    help="output path for the --shared BENCH (point it "
                         "away from the committed baseline when the run "
                         "feeds the regression gate, e.g. in nightly CI)")
    ap.add_argument("--lending-json", default="BENCH_unit_lending.json",
                    help="output path for the --lending BENCH (same "
                         "caveat as --shared-json)")
    ap.add_argument("--predictive-json", default="BENCH_predictive.json",
                    help="output path for the --predictive BENCH (same "
                         "caveat as --shared-json)")
    ap.add_argument("--cross-batch-json", default="BENCH_cross_batch.json",
                    help="output path for the --cross-batch BENCH (same "
                         "caveat as --shared-json)")
    ap.add_argument("--elastic-json", default="BENCH_elastic.json",
                    help="output path for the --elastic BENCH (same "
                         "caveat as --shared-json)")
    ap.add_argument("--pre-ref", default=None,
                    help="path to a checked-out pre-unification tree (the "
                         "last commit with the two hand-rolled loops); "
                         "records the kernel's overhead vs them in the "
                         "unified-kernel BENCH")
    ap.add_argument("--scale-ref", default=None,
                    help="path to a checked-out pre-scale-out tree; times "
                         "a same-chip-count probe slice against it in "
                         "interleaved subprocesses and records the "
                         "speedup in the scale BENCH")
    ap.add_argument("--scale-json", default="BENCH_scale.json",
                    help="output path for the --scale BENCH (same caveat "
                         "as --shared-json)")
    args = ap.parse_args()
    if args.scale:
        emit(run_scale(full=args.full, bench_path=args.scale_json,
                       scale_ref=args.scale_ref))
    if args.profile:
        emit(run_profile(full=args.full))
    if args.smoke:
        emit(run_smoke(bench_path=args.bench_json, seed_ref=args.seed_ref,
                       unified_bench_path=args.unified_json,
                       pre_ref=args.pre_ref))
    if args.predictive:
        emit(run_predictive(quick=not args.full,
                            bench_path=args.predictive_json))
    if args.cross_batch:
        emit(run_cross_batch(quick=not args.full,
                             bench_path=args.cross_batch_json))
    if args.elastic:
        emit(run_elastic(quick=not args.full,
                         bench_path=args.elastic_json))
    if args.lending:
        emit(run_lending(quick=not args.full, bench_path=args.lending_json))
    elif args.shared:
        emit(run_mixed_shared(quick=not args.full,
                              bench_path=args.shared_json))
    elif args.mixed:
        emit(run_mixed(quick=not args.full))
    if not (args.smoke or args.mixed or args.shared or args.lending
            or args.predictive or args.cross_batch or args.elastic
            or args.scale or args.profile):
        emit(run(quick=not args.full))
