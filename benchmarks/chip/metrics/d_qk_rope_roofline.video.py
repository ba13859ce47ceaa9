"""Stage D's per-head q/k norms and rotary positions against their
roofline, %: the least time they can take (q and k read and written once,
and the rotation tables read, per layer, step and guided sample, the
family's ``qk_rope_bytes``, over HBM bandwidth) in the D runs of the trace
over the device seconds of the ``dit/qk_rope`` scope in D's programs.  A
program without that scope reads nothing."""
from benchmarks.chip import scope_lib
from benchmarks.chip.metric_lib import programs


def read(run):
    count = getattr(run.fam, "qk_rope_bytes", None)
    scope = getattr(scope_lib.program_scopes, "DIT_QK_ROPE", None)
    by_prog = scope_lib.scope_seconds(run) if count and scope else {}
    secs = sum(sorted(t for prog, v in by_prog.items() if prog.startswith("stage_D_")
                      for k, t in v.items() if scope in k.split("+")))
    if secs <= 0:
        return None
    least = sum(count(res) * n for st, res, _, n in programs(run) if st == "D")
    return 100.0 * least / (run.peaks["hbm_bytes_per_s"] * secs)
