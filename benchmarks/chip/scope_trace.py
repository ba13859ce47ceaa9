"""Record the scope reduction's test data on the chip.

    python3 benchmarks/chip/scope_trace.py --workload sd3.preview --seed 11 \
        --out benchmarks/chip/tests/data

One set-up of the cell (every class's shape compiled), then a short traced
window of its smallest class, as ``calibrate.py --trace-out`` makes it.
Writes ``trace_scoped.xplane.pb.gz`` and ``trace_scoped.hlo.json.gz``
({program: compiled HLO text} of the stage programs that ran), and prints
one JSON line: the instructions in which the text ``scope_lib.stage_texts``
rebuilds differs from each executable's own (none, where the rebuild is
the program that ran), and each program's time by scope.
"""
import time

T_ORIGIN = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmarks.chip import calibrate, harness as h, scope_lib, trace

    cell = h.load_cell(args.workload)
    try:
        devices = h.check_chip(cell.chips)
    except h.NoChip as e:
        print(f"scope_trace.py: {e}", file=sys.stderr)
        return 2
    setup = h.prepare(cell, args.seed, devices, T_ORIGIN)
    small = min(cell.traffic["classes"], key=lambda c: c["resolution"])
    t = dict(cell.traffic, classes=[dict(small, weight=1)], rate_per_s=4.0,
             at_window_end="follow_to_completion", trace_margin_s=0.7,
             trace_seconds=0.5)
    _, spans, trace_dir = calibrate._window(h, cell, setup, t, args.seed, 1.5,
                                            traced=True)
    f = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1]
    summary = trace.reduce(f)
    texts = {name: exe.as_text() for name, exe in setup.stages.exe.items()
             if name in summary.programs}
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    with open(f, "rb") as src, gzip.open(out / "trace_scoped.xplane.pb.gz", "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(out / "trace_scoped.hlo.json.gz", "wt") as dst:
        json.dump(texts, dst)
    shutil.rmtree(trace_dir, ignore_errors=True)

    # every program of the set-up rebuilt, to hold each against its own
    run = h.Record(cell=cell, fam=setup.fam, seconds=1.5, setup_s=0.0,
                   requests=[], spans=spans, device={}, peaks={}, memory={},
                   param_shapes=setup.shapes,
                   trace=dataclasses.replace(
                       summary, programs=dict.fromkeys(setup.stages.exe)))
    rebuilt = scope_lib.stage_texts(run)
    differ = {}
    for k, exe in setup.stages.exe.items():
        a, b = _instructions(exe.as_text()), _instructions(rebuilt.get(k, ""))
        differ[k] = [x for x, y in zip(a, b) if x != y][:3] + (
            [] if len(a) == len(b) else [f"{len(a)} against {len(b)} lines"])
    print(json.dumps({"instructions_differ": differ,
                      "scopes": scope_lib.scope_seconds(run, texts)}), flush=True)
    return 0


def _instructions(text):
    """The instruction lines, metadata left out."""
    return [re.sub(r", metadata=\{[^}]*\}", "", line) for line in text.splitlines()
            if " = " in line]


if __name__ == "__main__":
    sys.exit(main())
