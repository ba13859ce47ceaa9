"""Stage D's attention core's share of the chip's peak, %: its operations
(``4 l^2 d`` per layer and step, from shapes) in the D runs of the trace
over the device seconds of the ``dit/attention`` scope in D's programs,
with the fusions the compiler made of it and another scope's products,
times peak FLOP/s.  Prints each stage program's time by scope."""
import sys

from benchmarks.chip.scope_lib import attention_mfu, report, scope_seconds


def read(run):
    by_prog = scope_seconds(run)
    for line in report(by_prog):
        print(line, file=sys.stderr, flush=True)
    return attention_mfu(run, by_prog)
