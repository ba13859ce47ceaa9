"""The pipeline's share of the chips' peak over the traced window, %: the
operations of the E, D and C runs inside it over window x chips x peak."""
from benchmarks.chip.metric_lib import flops, programs


def read(run):
    p = programs(run)
    if not p or run.trace.window_s <= 0:
        return None
    work = sum(flops(run, st, res, n) for st, res, _, n in p)
    chips = max(1, run.trace.devices)
    return 100.0 * work / (run.trace.window_s * chips * run.peaks["flops_bf16"])
