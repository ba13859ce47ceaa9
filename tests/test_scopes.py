"""Named scopes in the stage programs, and ``repro.roofline.hlo`` reading
them back from the compiled text."""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

import repro.configs as C
from repro.models import diffusion
from repro.models import pipeline as pl
from repro.models import scopes
from repro.roofline import hlo

RES = 64


def _stage_texts(cfg):
    """{stage: compiled HLO text} of E, D and C at smoke size."""
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: pl.init(cfg, k), key)
    lat = (1, cfg.latent_tokens(RES), cfg.dit.latent_dim)
    tokens = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    cond = jax.eval_shape(lambda p, t: pl.encode(cfg, p, t), params, tokens)
    fns = {
        "E": (lambda p, t: pl.encode(cfg, p, t), (params, tokens)),
        "D": (lambda p, c, k: pl.diffuse(cfg, p, c, lat, k), (params, cond, key)),
        "C": (lambda p, z: pl.decode(cfg, p, z, cfg.latent_grid(RES)),
              (params, jax.ShapeDtypeStruct(lat, jnp.float32))),
    }
    return {st: jax.jit(fn).lower(*args).compile().as_text()
            for st, (fn, args) in fns.items()}


@pytest.fixture(scope="module")
def sd3_texts():
    cfg = C.get_smoke("sd3")
    return cfg, _stage_texts(cfg)


@pytest.mark.parametrize("stage", ["E", "D", "C"])
def test_every_dot_and_convolution_has_a_scope(sd3_texts, stage):
    cfg, texts = sd3_texts
    names = scopes.names(cfg.decoder.num_upsamples)
    own = {"E": scopes.ENCODE, "D": scopes.DIFFUSE,
           "C": scopes.decode(cfg.decoder.num_upsamples)}[stage]
    where = hlo.op_scopes(texts[stage], names)
    work = [op.name for ops in hlo.parse_computations(texts[stage]).values()
            for op in ops if op.opcode in ("dot", "convolution")]
    assert work
    assert {where.get(n) for n in work} <= set(own)
    flops = hlo.scope_flops(texts[stage], names)
    assert None not in flops
    if stage == "D":
        assert flops[scopes.DIT_ATTENTION] > 0 and flops[scopes.DIT_MLP] > 0


def _strip(text):
    """The instructions alone: no metadata, no source tables."""
    return [re.sub(r", metadata=\{[^}]*\}", "", line) for line in text.splitlines()
            if " = " in line and not line.startswith("HloModule")]


def test_scopes_change_only_metadata(sd3_texts, monkeypatch):
    cfg, texts = sd3_texts
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _stage_texts(cfg)
    for stage in "EDC":
        assert "dit/" not in bare[stage] and "decoder/" not in bare[stage]
        assert _strip(bare[stage]) == _strip(texts[stage]), stage


def test_cpu_diffuse_stage_keeps_xla_attention_at_kernel_lengths(monkeypatch):
    """Past the flash kernel's joint length the D stage lowered for the CPU
    holds no kernel and is the program XLA's attention alone gives."""
    cfg = C.get_smoke("sd3")
    res = 512
    assert cfg.latent_tokens(res) >= diffusion.FLASH_MIN_LEN
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: pl.init(cfg, k), key)
    cond = jax.ShapeDtypeStruct((1, 8, cfg.dit.cond_dim), jnp.bfloat16)
    lat = (1, cfg.latent_tokens(res), cfg.dit.latent_dim)
    compile_d = lambda: jax.jit(lambda p, c, k: pl.diffuse(cfg, p, c, lat, k)).lower(
        params, cond, key).compile().as_text()
    text = compile_d()
    assert "custom-call" not in text
    monkeypatch.setattr(diffusion, "FLASH_MIN_LEN", 10 ** 9)
    assert _strip(compile_d()) == _strip(text)


@pytest.mark.parametrize("cell", ["sd3.preview", "flux.surge"])
@pytest.mark.parametrize("res", [32, 64])
def test_attention_count_is_a_floor_of_the_compiled_scope(cell, res):
    """The chip benchmark's count of D's attention core (the numerator of
    ``d_attention_mfu.*``) is at most what the ``dit/attention`` scope of
    the compiled program computes."""
    from benchmarks.chip import harness, scope_lib
    from benchmarks.chip.tests import smoke

    c = smoke.smoke_cell(cell)
    fam = harness.family(c.config)
    pcfg = fam.program_config()
    shapes = fam.param_shapes(pcfg)
    name, fn = fam.stage_fns(pcfg, res)["D"]
    fn.__name__ = name
    cond = jax.ShapeDtypeStruct((1, fam.cond_len, c.config["encoder_d_model"]),
                                jnp.dtype(c.config["dtype"]))
    text = jax.jit(fn).lower(shapes["diffuse"], cond,
                             jax.ShapeDtypeStruct((2,), jnp.uint32)).compile().as_text()
    flops = hlo.scope_flops(text, scopes.DIFFUSE)
    count = scope_lib.attention_flops(fam, res)
    assert 0 < count <= flops[scopes.DIT_ATTENTION] * (1 + 1e-9)
    # a floor far below the compiled count would read the share low
    assert count >= 0.5 * flops[scopes.DIT_ATTENTION]


# a module as the compiler prints it: a fusion whose own metadata names no
# scope, a dot a pass made without metadata, and a fusion (with the TPU's
# tiled layouts) that computes the products of two scopes
_HLO = """HloModule m, is_scheduled=true

%fused_computation (param_0: f32[4,4]) -> f32[4,4] {
  %param_0 = f32[4,4]{1,0} parameter(0)
  %exp.1 = f32[4,4]{1,0} exponential(%param_0), metadata={op_name="jit(f)/dit/attention/exp"}
  %neg.1 = f32[4,4]{1,0} negate(%exp.1), metadata={op_name="jit(f)/dit/attention/neg"}
  ROOT %add.1 = f32[4,4]{1,0} add(%neg.1, %param_0), metadata={op_name="jit(f)/add"}
}

%fused_inner (p0: f32[4,4], p1: f32[4,4]) -> f32[4,4] {
  %p0 = f32[4,4]{1,0} parameter(0)
  %p1 = f32[4,4]{1,0} parameter(1)
  ROOT %dot.3 = f32[4,4]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/dit/qkv/dot_general"}
}

%fused_outer (q0: f32[4,4], q1: f32[4,4]) -> (f32[4,4], f32[4]) {
  %q0 = f32[4,4]{1,0} parameter(0)
  %q1 = f32[4,4]{1,0} parameter(1)
  %fusion.3 = f32[4,4]{1,0} fusion(%q0, %q1), kind=kOutput, calls=%fused_inner, metadata={op_name="jit(f)/dit/qkv/dot_general"}
  %dot.4 = f32[4,4]{1,0} dot(%fusion.3, %q1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/dit/attention/dot_general"}
  %r = f32[4]{0} reduce(%dot.4, %q0), dimensions={1}, to_apply=%fused_computation
  ROOT %t = (f32[4,4]{1,0}, f32[4]{0}) tuple(%dot.4, %r)
}

ENTRY %main (x: f32[4,4], w: f32[4,4]) -> f32[4,4] {
  %x = f32[4,4]{1,0} parameter(0), metadata={op_name="x"}
  %w = f32[4,4]{1,0} parameter(1), metadata={op_name="w"}
  %dot.1 = f32[4,4]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/ddim/dit/qkv/dot_general"}
  %dot.2 = f32[4,4]{1,0} dot(%dot.1, %dot.1), lhs_contracting_dims={1}, rhs_contracting_dims={1}
  %fusion.1 = f32[4,4]{1,0} fusion(%dot.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/add"}
  %fusion.2 = (f32[4,4]{1,0:T(8,128)}, f32[4]{0:T(256)}) fusion(%fusion.1, %w), kind=kOutput, calls=%fused_outer, metadata={op_name="jit(f)/dit/attention/dot_general"}
  %gte.1 = f32[4,4]{1,0:T(8,128)} get-tuple-element(%fusion.2), index=0
  ROOT %add.2 = f32[4,4]{1,0} add(%gte.1, %x), metadata={op_name="jit(f)/while/body/add"}
}
"""


def test_op_scopes_follows_fusions_and_bare_instructions():
    where = hlo.op_scopes(_HLO, scopes.DIFFUSE)
    # the innermost scope named in the metadata
    assert where["dot.1"] == scopes.DIT_QKV
    # its own metadata names no scope: most of its fused instructions do
    assert where["fusion.1"] == scopes.DIT_ATTENTION
    # no metadata at all: the scope of its user
    assert where["dot.2"] == scopes.DIT_ATTENTION
    # metadata without a scope stays outside every scope
    assert "add.2" not in where and "add.1" not in where
    assert where["fusion.2"] == scopes.DIT_ATTENTION
    assert hlo.scope_flops(_HLO, scopes.DIFFUSE) == {
        scopes.DIT_QKV: 256.0, scopes.DIT_ATTENTION: 256.0}


def test_fused_work_spans_the_scopes_of_nested_products():
    both = frozenset({scopes.DIT_QKV, scopes.DIT_ATTENTION})
    assert hlo.fused_work_scopes(_HLO, scopes.DIFFUSE) == {
        "fusion.2": both, "fusion.3": frozenset({scopes.DIT_QKV})}
    # the tiled tuple result parses, operands and all
    ops = {op.name: op for ops in hlo.parse_computations(_HLO).values() for op in ops}
    assert ops["fusion.2"].opcode == "fusion"
    assert hlo._operands(ops["fusion.2"].line) == ["fusion.1", "w"]


def test_scope_names_match_whole_parts():
    assert hlo.op_scopes(_HLO.replace("dit/attention/", "dit/attentionx/"),
                         scopes.DIFFUSE).get("fusion.1") is None
    assert len(set(scopes.names(3))) == len(scopes.names(3))
    assert scopes.decode(2) == ("decoder/conv_in", "decoder/up0", "decoder/up1",
                                "decoder/conv_out")
