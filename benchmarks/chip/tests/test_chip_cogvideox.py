"""The cells ``cogvideox.t2v`` and ``sd3.surge`` on the CPU at smoke size:
their result lines, the check that decides ``correct`` for the video
family (the float8 control and a planted fault come out not correct), the
family's work counts against the compiled programs, and the video cell's
two readers.  CPU rehearsals: no number here is a chip result."""
import json

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import harness, scope_lib, trace
from benchmarks.chip.tests import smoke
from repro.models import diffusion, scopes
from repro.roofline import hlo

VIDEO = "cogvideox.t2v"
READERS = ("d_attention_mfu.video", "d_qk_rope_roofline.video")


@pytest.mark.parametrize("cell", [VIDEO, "sd3.surge"])
@pytest.mark.parametrize("traced", [False, True])
def test_rehearsal_result_line(cell, traced):
    c = smoke.smoke_cell(cell)
    result, log, _ = smoke.run(c, traced=traced)
    line = json.loads(json.dumps(result))
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    want = c.per_layer if traced else c.end_to_end
    if traced:
        assert set(line["metrics"]) <= {m["name"] for m in want}
    else:
        assert set(line["metrics"]) == {m["name"] for m in want} == {"images_per_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"
    assert "window: compilations inside the window 0" in log
    assert log[-len(line["checks"]):] == [
        f"check {k}: {v['value']!r} limit {v['limit']!r}"
        for k, v in line["checks"].items()]


def test_video_control_is_not_correct():
    result, _, control = smoke.run(smoke.smoke_cell(VIDEO), control=True)
    assert result["correct"] is True
    assert control["correct"] is False


def test_one_video_token_left_unrotated_is_not_correct(monkeypatch):
    """The rotation of one video token (the last) is dropped from the
    tables the timed D program reads.  The clip is one frame of 2 x 3
    latent tokens: the error is a norm over every token, so one token's
    fault weighs one sixth here (and one in 17,550 at the cell's size,
    where the comparison cannot see it)."""
    orig = diffusion.rope_3d

    def rope_3d(grid, head_dim, text_len):
        cos, sin = orig(grid, head_dim, text_len)
        row = text_len + grid[0] * grid[1] * grid[2] - 1
        return cos.at[row].set(1.0), sin.at[row].set(0.0)

    cell = smoke.smoke_cell(VIDEO)
    cell.config["frames"] = 1
    cell.traffic["classes"][0]["resolution"] = 32
    result, _, _ = smoke.run(cell)
    assert result["correct"] is True
    monkeypatch.setattr(diffusion, "rope_3d", rope_3d)
    result, _, _ = smoke.run(cell)
    assert result["correct"] is False
    assert result["checks"]["d_rel_err"]["value"] > result["checks"]["d_rel_err"]["limit"]


@pytest.mark.parametrize("stage", ["E", "D", "C"])
def test_video_counts_are_at_most_the_compiled_counts(stage):
    c = smoke.smoke_cell(VIDEO)
    fam = harness.family(c.config)
    pcfg = fam.program_config()
    shapes = fam.param_shapes(pcfg)
    cfg = c.config
    res = c.traffic["classes"][0]["resolution"]
    args = {
        "E": (shapes["encode"], jax.ShapeDtypeStruct((1, fam.cond_len), jnp.int32)),
        "D": (shapes["diffuse"],
              jax.ShapeDtypeStruct((2, fam.cond_len, cfg["encoder_d_model"]),
                                   jnp.dtype(cfg["dtype"])),
              jax.ShapeDtypeStruct((2,), jnp.uint32)),
        "C": (shapes["decode"],
              jax.ShapeDtypeStruct((1, fam.latent_tokens(res), cfg["dit_latent_dim"]),
                                   jnp.float32)),
    }[stage]
    name, fn = fam.stage_fns(pcfg, res)[stage]
    fn.__name__ = name
    text = jax.jit(fn).lower(*args).compile().as_text()
    costs = hlo.module_costs(text, 1)
    flops = fam.flops(stage, res)
    assert 0 < flops <= costs.flops * (1 + 1e-9)
    assert flops >= 0.5 * costs.flops
    assert 0 < fam.bytes(stage, res, shapes) <= costs.hbm_bytes
    if stage == "D":
        attn = hlo.scope_flops(text, scopes.DIFFUSE)[scopes.DIT_ATTENTION]
        assert 0.5 * attn <= fam.attention_flops(res) <= attn * (1 + 1e-9)


def _record(cell, summary=None):
    return harness.Record(cell=cell, fam=harness.family(cell.config), seconds=1.0,
                          setup_s=0.0, requests=[], spans=harness.Spans(), device={},
                          peaks=smoke.CPU_PEAKS, memory={}, param_shapes=None,
                          trace=summary)


@pytest.mark.parametrize("metric", READERS)
def test_video_readers_read_nothing_without_a_device_trace(metric, monkeypatch):
    cell = harness.load_cell(VIDEO)
    assert harness.reader(metric)(_record(cell)) is None
    # a program built without named scopes reads nothing either
    monkeypatch.setattr(scope_lib, "program_scopes", None)
    summary = trace.Summary(window_s=1.0, busy_s=1.0, devices=1,
                            programs={"stage_D_480": {"seconds": 1.0, "runs": 1.0}},
                            gaps=[], ops={"stage_D_480 fusion.1 tuple": 1.0})
    assert harness.reader(metric)(_record(cell, summary)) is None


@pytest.mark.parametrize("metric", READERS)
def test_video_readers_on_a_hand_made_record(metric, monkeypatch):
    cell = harness.load_cell(VIDEO)
    fam = harness.family(cell.config)
    summary = trace.Summary(window_s=8.0, busy_s=8.0, devices=1,
                            programs={"stage_D_480": {"seconds": 7.5, "runs": 0.75},
                                      "stage_E_226": {"seconds": 0.1, "runs": 1.0}},
                            gaps=[], ops={})
    by_prog = {"stage_D_480": {scopes.DIT_ATTENTION: 4.0, scopes.DIT_QK_ROPE: 0.5,
                               f"{scopes.DIT_ATTENTION}+{scopes.DIT_QKV}": 1.0,
                               scopes.DIT_MLP: 2.0, scope_lib.UNSCOPED: 0.0}}
    monkeypatch.setattr(scope_lib, "scope_seconds", lambda run: by_prog)
    got = harness.reader(metric)(_record(cell, summary))
    if metric.startswith("d_attention"):
        l = 13 * 30 * 45 + 226
        want = 4 * l * l * 3072 * 6 * 10 * 2 * 0.75 / (5.0 * smoke.CPU_PEAKS["flops_bf16"])
    else:
        want = fam.qk_rope_bytes(480) * 0.75 / (0.5 * smoke.CPU_PEAKS["hbm_bytes_per_s"])
    assert got == pytest.approx(100 * want)
