"""Stage E's share of its roofline, %: the least time the chip could take
(the larger of its operations over peak FLOP/s and its bytes over HBM
bandwidth, from shapes) over E's mean device time per run in the trace."""
from benchmarks.chip.metric_lib import programs


def read(run):
    e = [(res, s, n) for st, res, s, n in programs(run) if st == "E"]
    secs = sum(s for _, s, _ in e)
    runs = sum(n for _, _, n in e)
    if not e or secs <= 0:
        return None
    res = e[0][0]
    least = max(run.fam.flops("E", res) / run.peaks["flops_bf16"],
                run.fam.bytes("E", res, run.param_shapes) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * runs / secs
