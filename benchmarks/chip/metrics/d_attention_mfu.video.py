"""Stage D's attention core's share of the chip's peak in a video cell, %:
its operations (``4 l^2 d`` per layer, step and guided sample, the
family's ``attention_flops``) in the D runs of the trace over the device
seconds of the ``dit/attention`` scope in D's programs, with the fusions
the compiler made of it and another scope's products, times peak FLOP/s.
Prints each stage program's time by scope."""
import sys

from benchmarks.chip import scope_lib
from benchmarks.chip.metric_lib import programs


def read(run):
    count = getattr(run.fam, "attention_flops", None)
    by_prog = scope_lib.scope_seconds(run) if count else {}
    for line in scope_lib.report(by_prog):
        print(line, file=sys.stderr, flush=True)
    scope = scope_lib.program_scopes.DIT_ATTENTION if by_prog else None
    secs = sum(sorted(t for prog, v in by_prog.items() if prog.startswith("stage_D_")
                      for k, t in v.items() if scope in k.split("+")))
    if secs <= 0:
        return None
    work = sum(count(res) * n for st, res, _, n in programs(run) if st == "D")
    return 100.0 * work / (secs * run.peaks["flops_bf16"])
