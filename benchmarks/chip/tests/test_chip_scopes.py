"""Device time by named scope (``scope_lib``) and the readers of
``d_attention_mfu.*`` and ``host_hold_max_s``.  The chip trace was recorded
on a TPU v5e by ``scope_trace.py --workload sd3.preview`` (one chip, sd3
requests at 128 px, the scoped program), with its stage programs' HLO
text."""
import dataclasses
import gzip
import json
import pathlib
import time

import pytest

from benchmarks.chip import harness, scope_lib, trace
from benchmarks.chip.tests import smoke
from repro.models import scopes

DATA = pathlib.Path(__file__).parent / "data"
TRACE = DATA / "trace_scoped.xplane.pb.gz"
HLO = DATA / "trace_scoped.hlo.json.gz"


def _record(cell, fam=None, summary=None, spans=None, shapes=None):
    return harness.Record(cell=cell, fam=fam or harness.family(cell.config),
                          seconds=1.0, setup_s=0.0, requests=[],
                          spans=spans or harness.Spans(), device={},
                          peaks=smoke.CPU_PEAKS, memory={}, param_shapes=shapes,
                          trace=summary)


@pytest.fixture(scope="module")
def chip_trace():
    cell = harness.load_cell("sd3.preview")
    with gzip.open(HLO, "rt") as f:
        texts = json.load(f)
    summary = trace.reduce(TRACE)
    return _record(cell, summary=summary), texts


def test_scope_seconds_add_up_to_the_op_seconds(chip_trace):
    run, texts = chip_trace
    before = dataclasses.asdict(run.trace)
    by_prog = scope_lib.scope_seconds(run, texts)
    # the reduction the existing metrics read is left as it was
    assert dataclasses.asdict(run.trace) == before
    assert set(by_prog) == set(texts) == set(run.trace.programs)
    for prog, v in by_prog.items():
        ops = sum(sorted(s for k, s in run.trace.ops.items() if k.startswith(prog + " ")))
        assert sum(sorted(v.values())) == pytest.approx(ops, rel=1e-12), prog
    d = next(v for k, v in by_prog.items() if k.startswith("stage_D_"))
    assert d[scopes.DIT_MLP] > 0
    assert any(t > 0 for k, t in d.items() if scopes.DIT_ATTENTION in k.split("+"))
    assert d[scope_lib.UNSCOPED] <= 0.05 * sum(sorted(d.values()))
    parts = {part for k in d for part in k.split("+")}
    assert parts <= set(scopes.DIFFUSE) | {scope_lib.UNSCOPED}
    lines = scope_lib.report(by_prog)
    assert len(lines) == len(by_prog)
    assert all(line.startswith("scopes: stage_") and "; unscoped " in line
               for line in lines)


def test_attention_share_of_the_chip_trace(chip_trace):
    run, texts = chip_trace
    run = dataclasses.replace(run, peaks=harness.peaks_for("TPU v5 lite"))
    share = scope_lib.attention_mfu(run, scope_lib.scope_seconds(run, texts))
    assert 0 < share < 100


@pytest.mark.parametrize("cell", ["sd3.preview", "flux.surge"])
def test_rebuilt_programs_are_the_ones_that_ran(cell):
    """The text ``stage_texts`` rebuilds for each stage program equals the
    text of the executable the harness compiled and ran."""
    c = smoke.smoke_cell(cell)
    devices = harness.check_chip(c.chips, require_chip=False)
    setup = harness.prepare(c, 12345678901, devices, time.perf_counter())
    summary = trace.Summary(window_s=1.0, busy_s=1.0, devices=1,
                            programs=dict.fromkeys(setup.stages.exe), gaps=[], ops={})
    run = _record(c, fam=setup.fam, summary=summary, shapes=setup.shapes)
    rebuilt = scope_lib.stage_texts(run)
    assert set(rebuilt) == set(setup.stages.exe)
    for name, exe in setup.stages.exe.items():
        assert rebuilt[name] == exe.as_text(), name


def test_attention_reader_on_a_hand_made_record():
    cell = harness.load_cell("flux.surge")
    fam = harness.family(cell.config)
    summary = trace.Summary(window_s=5.0, busy_s=4.9, devices=1,
                            programs={"stage_D_1024": {"seconds": 4.0, "runs": 8.5},
                                      "stage_E_77": {"seconds": 0.2, "runs": 8.0}},
                            gaps=[], ops={})
    run = _record(cell, fam=fam, summary=summary)
    by_prog = {"stage_D_1024": {scopes.DIT_ATTENTION: 1.5, scopes.DIT_MLP: 1.5,
                                f"{scopes.DIT_ATTENTION}+{scopes.DIT_QKV}": 0.5,
                                scope_lib.UNSCOPED: 0.1},
               "stage_E_77": {scopes.ENCODER_MLP: 0.2, scope_lib.UNSCOPED: 0.0}}
    l = 4096 + 77
    want = 4 * l * l * 3072 * 6 * 4 * 8.5 / (2.0 * smoke.CPU_PEAKS["flops_bf16"])
    assert scope_lib.attention_mfu(run, by_prog) == pytest.approx(100 * want)
    by_prog["stage_D_1024"][scopes.DIT_ATTENTION] = 0.0
    by_prog["stage_D_1024"].pop(f"{scopes.DIT_ATTENTION}+{scopes.DIT_QKV}")
    assert scope_lib.attention_mfu(run, by_prog) is None
    assert scope_lib.attention_mfu(run, {}) is None


@pytest.mark.parametrize("metric", ["d_attention_mfu.latency",
                                    "d_attention_mfu.throughput"])
def test_attention_readers_read_nothing_without_a_device_trace(metric, monkeypatch):
    cell = harness.load_cell("sd3.preview")
    assert harness.reader(metric)(_record(cell)) is None
    # a program built without named scopes (as before them) reads nothing
    monkeypatch.setattr(scope_lib, "program_scopes", None)
    summary = trace.Summary(window_s=1.0, busy_s=1.0, devices=1,
                            programs={"stage_D_128": {"seconds": 1.0, "runs": 1.0}},
                            gaps=[], ops={"stage_D_128 fusion.1 tuple": 1.0})
    assert harness.reader(metric)(_record(cell, summary=summary)) is None


def test_host_hold_reads_the_longest_dispatch_or_launch():
    spans = harness.Spans()
    spans.rec += [("dispatch", 0.0, 0.002), ("launch", 0.01, 0.013),
                  ("copy", 0.02, 3.0), ("wait_arrival", 3.0, 9.0),
                  ("launch", 9.0, 9.12), ("dispatch", 9.2, 9.201)]
    read = harness.reader("host_hold_max_s")
    cell = harness.load_cell("sd3.preview")
    assert read(_record(cell, spans=spans)) == pytest.approx(0.12)
    assert read(_record(cell)) is None
