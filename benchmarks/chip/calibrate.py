"""Measure what a traffic file is set from: solo latencies and the knee.

    python3 benchmarks/chip/calibrate.py --workload sd3.preview --seed 11 \
        --solo 5 --rates 3 3.5 4 4.5 --seconds 30 [--trace-out DIR]

On the chip, in one process (one set-up):

* ``--solo K``: each of the cell's classes alone, ``K`` times one
  request in an otherwise empty window: the median due -> image-on-host
  latency is the class's solo latency (deadlines are 2.5 times it; a
  surge's busy rate is its inverse);
* ``--rates``: the cell's mix at each offered rate for ``--seconds``,
  every request followed to completion: latency tails, attainment, how
  long the backlog took to drain after the window, and the mean queue wait
  in the window's last third against its first.  The knee is the highest
  rate whose backlog does not grow;
* ``--trace-out DIR``: a short traced window of the cell's smallest class,
  written to ``DIR/trace_small.xplane.pb.gz`` for the trace reduction's
  test, with the planes and lines it holds printed.

Each measurement prints one JSON line.  Not a benchmark run: it makes no
check against the reference.
"""
import time

T_ORIGIN = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _window(h, cell, setup, traffic, seed, seconds, traced=False):
    c = copy.copy(cell)
    c.traffic = traffic
    from benchmarks.chip import traffic as tr
    n = len(tr.schedule(traffic, seconds))
    inputs = h.request_inputs(setup.fam, seed, n, cell.config["encoder_vocab"],
                              setup.devices[0])
    spans = h.Spans()
    sent, _, _, trace_dir = h.serve_window(
        c, setup.fam, setup.pcfg, setup.stages, setup.params, inputs, seconds,
        traced, spans, time.perf_counter())
    return sent, spans, trace_dir


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--solo", type=int, default=0)
    ap.add_argument("--rates", type=float, nargs="*", default=())
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace-out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmarks.chip import harness as h

    cell = h.load_cell(args.workload)
    try:
        devices = h.check_chip(cell.chips)
    except h.NoChip as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 2
    setup = h.prepare(cell, args.seed, devices, T_ORIGIN)
    for line in setup.log:
        print(line, flush=True)
    print(f"set-up total {time.perf_counter() - T_ORIGIN:.3f} s", flush=True)

    def emit(obj):
        print(json.dumps(obj), flush=True)

    for c in cell.traffic["classes"] if args.solo else ():
        one = dict(cell.traffic, classes=[dict(c, weight=1)], rate_per_s=0.1,
                   at_window_end="follow_to_completion")
        lat, disp = [], []
        for rep in range(args.solo):
            sent, spans, _ = _window(h, cell, setup, one, args.seed + rep, 1.0)
            lat += [r["done"] - r["due"] for r in sent]
            disp += spans.durations("dispatch")
        emit({"solo": c["resolution"], "n": len(lat),
              "median_s": float(np.median(lat)), "all_s": lat,
              "dispatch_ms": 1e3 * float(np.mean(disp))})

    for rate in args.rates:
        t = dict(cell.traffic, rate_per_s=rate,
                 at_window_end="follow_to_completion", drain_s=120.0)
        sent, spans, _ = _window(h, cell, setup, t, args.seed, args.seconds)
        lat = [r["done"] - r["due"] for r in sent]
        wait = [(r["due"], r["launch"] - r["due"]) for r in sent]
        third = args.seconds / 3
        first = [w for d, w in wait if d < third]
        last = [w for d, w in wait if d >= 2 * third]
        emit({"rate": rate, "n": len(sent),
              "p50_s": float(np.percentile(lat, 50)),
              "p95_s": float(np.percentile(lat, 95)),
              "attainment": sum(r["done"] <= r["deadline"] for r in sent) / len(sent),
              "drain_s": max(r["done"] for r in sent) - args.seconds,
              "wait_first_third_s": float(np.mean(first)),
              "wait_last_third_s": float(np.mean(last)),
              "dispatch_ms": 1e3 * float(np.mean(spans.durations("dispatch"))),
              "by_class": {str(res): float(np.median(
                  [r["done"] - r["due"] for r in sent if r["res"] == res]))
                  for res in sorted({r["res"] for r in sent})}})

    if args.trace_out:
        small = min(cell.traffic["classes"], key=lambda c: c["resolution"])
        t = dict(cell.traffic, classes=[dict(small, weight=1)], rate_per_s=4.0,
                 at_window_end="follow_to_completion", trace_margin_s=0.7,
                 trace_seconds=0.5)
        _, _, trace_dir = _window(h, cell, setup, t, args.seed, 1.5, traced=True)
        f = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1]
        out = pathlib.Path(args.trace_out)
        out.mkdir(parents=True, exist_ok=True)
        with open(f, "rb") as src, gzip.open(out / "trace_small.xplane.pb.gz", "wb") as dst:
            shutil.copyfileobj(src, dst)
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(str(f))
        for plane in pd.planes:
            lines = []
            for ln in plane.lines:
                evs = list(ln.events)
                lines.append([ln.name, len(evs), [e.name for e in evs[:4]]])
            emit({"plane": plane.name, "lines": lines})
        from benchmarks.chip import trace as trace_lib
        s = trace_lib.reduce(f)
        emit({"reduced": {"window_s": s.window_s, "busy_s": s.busy_s,
                          "devices": s.devices, "programs": s.programs,
                          "gaps": s.gap_totals(),
                          "ops": sorted(s.ops.items(), key=lambda kv: (-kv[1], kv[0]))[:10]}})
        shutil.rmtree(trace_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
