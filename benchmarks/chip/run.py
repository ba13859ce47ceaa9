"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload sd3.preview --seed 7 \
        --seconds 51 --trace 0

Run from the root of a checkout.  ``--trace 0`` prints the cell's
end-to-end metrics; ``--trace 1`` records a profiler trace of a steady
sub-window and prints the per-layer metrics.  Every run checks what the
timed path produced against the plain reference, prints each compared
number beside its limit as the last lines of standard error, and prints
one JSON object as the last line of standard output.  Without a TPU, or
with fewer chips than the cell asks for, it prints no result and exits 2.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    try:
        result, log, _ = harness.run_cell(cell, args.seed, args.seconds,
                                          bool(args.trace), T_ORIGIN)
    except harness.NoChip as e:
        print(f"run.py: {e}; nothing was measured", file=sys.stderr)
        return 2
    for line in log:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
