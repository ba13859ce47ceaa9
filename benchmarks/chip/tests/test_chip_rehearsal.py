"""Both cells' control flow end to end on the CPU at smoke size: traffic,
planning, dispatch, the stage programs, the metrics' arithmetic, the check
and the result line.  CPU rehearsals: no number here is a chip result."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from benchmarks.chip.tests import smoke

ROOT = pathlib.Path(__file__).resolve().parents[3]


@pytest.mark.parametrize("cell", ["sd3.preview", "flux.surge"])
@pytest.mark.parametrize("traced", [False, True])
def test_rehearsal_result_line(cell, traced):
    c = smoke.smoke_cell(cell)
    result, log, _ = smoke.run(c, traced=traced)
    line = json.loads(json.dumps(result))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    want = c.per_layer if traced else c.end_to_end
    if traced:
        # the CPU has no device plane: only host-side metrics read a number
        assert set(line["metrics"]) <= {m["name"] for m in want}
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert "breakdown" in line
    else:
        assert set(line["metrics"]) == {m["name"] for m in want}
    for m in want:
        if m["name"] in line["metrics"]:
            assert line["metrics"][m["name"]]["unit"] == m["unit"]
    assert line["device"]["platform"] == "cpu"
    assert "window: compilations inside the window 0" in log
    assert log[-len(line["checks"]):] == [
        f"check {k}: {v['value']!r} limit {v['limit']!r}"
        for k, v in line["checks"].items()]


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload",
                        "sd3.preview", "--seed", "3", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmarks" / "chip", tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload",
                        "sd3.preview", "--seed", "3", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
