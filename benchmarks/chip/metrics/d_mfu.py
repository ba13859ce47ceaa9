"""Stage D's share of the chip's peak, %: the operations of its runs in the
trace (all steps, from shapes) over its device time there times peak FLOP/s."""
from benchmarks.chip.metric_lib import flops, programs


def read(run):
    d = [(res, s, n) for st, res, s, n in programs(run) if st == "D"]
    secs = sum(s for _, s, _ in d)
    if secs <= 0:
        return None
    work = sum(flops(run, "D", res, n) for res, _, n in d)
    return 100.0 * work / (secs * run.peaks["flops_bf16"])
