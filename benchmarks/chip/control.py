"""The readings the check's limits are set from, many seeds in one process.

    python3 benchmarks/chip/control.py --workload sd3.preview \
        --seeds 11 12 13 --seconds 15

For each seed, on the chip: weights from the seed, a window of the cell's
own traffic at its own load, and the check of a sample of the requests it
finished, at the timed sizes.  Each compared number is read twice on the
same sample, by the run's own check: once for the program (the lower
reading), and once for the control, the reference computed in float8 and
put in the program's place to serve the same requests (the upper reading),
with the control's own ``correct``.  One JSON line per seed.  The
benchmark's own runs never run the control.
"""
import time

T_ORIGIN = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmarks.chip import harness as h

    cell = h.load_cell(args.workload)
    try:
        devices = h.check_chip(cell.chips)
    except h.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 2
    peaks = h.peaks_for(devices[0].device_kind)
    for seed in args.seeds:
        t = time.perf_counter()
        setup = h.prepare(cell, seed, devices, t)
        result, log, control = h.measure(cell, setup, seed, args.seconds,
                                         False, t, control=True, peaks=peaks)
        print(json.dumps({
            "seed": seed, "correct": result["correct"],
            "program": {k: v["value"] for k, v in result["checks"].items()},
            "control_correct": control["correct"],
            "control": control["checks"],
            "sampled": [line for line in log if line.startswith("check: sampled")],
            "seconds": time.perf_counter() - t}), flush=True)
        del setup
    return 0


if __name__ == "__main__":
    sys.exit(main())
