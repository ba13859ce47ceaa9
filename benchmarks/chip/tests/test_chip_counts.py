"""The work counts are floors of what the compiled program computes, so a
share of peak or of a roofline cannot read high because of them."""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests import smoke
from repro.roofline import hlo


@pytest.mark.parametrize("cell", ["sd3.preview", "flux.surge"])
@pytest.mark.parametrize("res", [32, 64])
@pytest.mark.parametrize("stage", ["E", "D", "C"])
def test_counts_are_at_most_the_compiled_counts(cell, res, stage):
    c = smoke.smoke_cell(cell)
    fam = harness.family(c.config)
    pcfg = fam.program_config()
    shapes = fam.param_shapes(pcfg)
    cfg = c.config
    args = {
        "E": (shapes["encode"],
              jax.ShapeDtypeStruct((1, fam.cond_len), jnp.int32)),
        "D": (shapes["diffuse"],
              jax.ShapeDtypeStruct((1, fam.cond_len, cfg["encoder_d_model"]),
                                   jnp.dtype(cfg["dtype"])),
              jax.ShapeDtypeStruct((2,), jnp.uint32)),
        "C": (shapes["decode"],
              jax.ShapeDtypeStruct((1, fam.latent_tokens(res),
                                    cfg["dit_latent_dim"]), jnp.float32)),
    }[stage]
    name, fn = fam.stage_fns(pcfg, res)[stage]
    fn.__name__ = name
    costs = hlo.module_costs(jax.jit(fn).lower(*args).compile().as_text(), 1)
    flops = fam.flops(stage, res)
    nbytes = fam.bytes(stage, res, shapes)
    assert 0 < flops <= costs.flops * (1 + 1e-9)
    assert 0 < nbytes <= costs.hbm_bytes
    # a floor that is far below the program's count would hide work
    assert flops >= 0.5 * costs.flops
