"""The trace reduction on a trace recorded on a TPU v5e: one chip, one sd3
request at 128 px (E, 20 steps of D, C) and its copy to the host, recorded
by ``calibrate.py --trace-out``."""
import pathlib

from benchmarks.chip import trace

TRACE = pathlib.Path(__file__).parent / "data" / "trace_small.xplane.pb.gz"


def test_reduction_of_a_chip_trace():
    s = trace.reduce(TRACE)
    assert s.devices == 1
    assert 0.05 < s.window_s < 1.0
    assert 0 < s.busy_s < s.window_s
    for stage in "EDC":
        assert s.program_seconds(stage) > 0, stage
    # programs run one at a time on one chip: their time fits in busy time
    assert sum(sorted(v["seconds"] for v in s.programs.values())) <= s.busy_s * 1.001
    # the gaps and the busy time make up the window
    assert abs(sum(g for _, g in s.gaps) + s.busy_s - s.window_s) < 1e-6
    names = {n for n, _ in s.gap_totals()}
    assert names <= set(trace.HOST_SPANS) | {"other"}
    top = max(s.ops, key=s.ops.get)
    assert top.startswith("stage_D_128 ")
    assert not any(" while" in k for k in s.ops)


def test_union_merges_overlaps():
    assert trace._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
