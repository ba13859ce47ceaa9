"""The whole pipeline's share of the chip's peak while it runs, %: the
operations of E, D and C in the trace over their device time times peak."""
from benchmarks.chip.metric_lib import flops, programs


def read(run):
    p = programs(run)
    secs = sum(s for _, _, s, _ in p)
    if secs <= 0:
        return None
    work = sum(flops(run, st, res, n) for st, res, _, n in p)
    return 100.0 * work / (secs * run.peaks["flops_bf16"])
