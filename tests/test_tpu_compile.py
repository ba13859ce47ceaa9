"""Compiles for a described TPU v5e chip, at sd3 widths, with no chip attached.

The TPU compiler refuses what interpret mode cannot see: tiles that do not
fit, fast memory over budget, a program larger than the device.  The
topology is described inside a module fixture (never at import), so every
xdist worker collects the same tests and only the worker given this file
loads the TPU library.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as C
from repro.kernels import ops
from repro.models import diffusion, scopes
from repro.models import pipeline as pl
from repro.roofline import hlo

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs outside the tree
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
        mp.undo()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_attention_noncausal_compiles_for_v5e(one_chip):
    """The DiT's attention layout: 4096 latent tokens, 24 heads of 64."""
    x = _sds((1, 4096, 24, 64), jnp.bfloat16, one_chip)
    fn = jax.jit(functools.partial(ops.flash_attention, causal=False,
                                   use_kernel=True))
    assert "tpu_custom_call" in fn.lower(x, x, x).compile().as_text()


def test_adaln_rmsnorm_compiles_for_v5e(one_chip):
    """The sd3 DiT's joint stream at 1024 px: 4096 + 77 rows of 1536."""
    x = _sds((1, 4173, 1536), jnp.bfloat16, one_chip)
    mod = _sds((1, 1536), jnp.bfloat16, one_chip)
    fn = jax.jit(functools.partial(ops.adaln_rmsnorm, use_kernel=True))
    assert "tpu_custom_call" in fn.lower(x, mod, mod).compile().as_text()


def _diffuse_compiled(sharding, arch: str, res: int, layers=None):
    """The D stage of ``arch`` at ``res`` px (``layers`` DiT layers if
    given), compiled for the described chip."""
    cfg = C.get(arch)
    if layers is not None:
        cfg = dataclasses.replace(cfg, dit=dataclasses.replace(cfg.dit, num_layers=layers))
    shapes = jax.eval_shape(functools.partial(diffusion.init, cfg.dit),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    params = {"diffuse": jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, sharding), shapes)}
    cond = _sds((1, 77, cfg.dit.cond_dim), jnp.bfloat16, sharding)
    key = _sds((2,), jnp.uint32, sharding)
    latent_shape = (1, cfg.latent_tokens(res), cfg.dit.latent_dim)
    fn = jax.jit(functools.partial(pl.diffuse, cfg), static_argnums=(2,))
    return fn.lower(params, cond, latent_shape, key).compile()


def _hbm_bytes(compiled):
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def test_sd3_diffuse_stage_fits_v5e(one_chip):
    """The sd3 D stage, 20 steps at 512 px, fits one chip's HBM."""
    compiled = _diffuse_compiled(one_chip, "sd3", 512)
    assert compiled.memory_analysis().argument_size_in_bytes > 2 ** 30  # 24 x 1536 DiT
    assert _hbm_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize("arch,res,layers", [("sd3", 512, None), ("flux", 1024, 6)])
def test_long_diffuse_stage_runs_flash_kernel_on_v5e(one_chip, arch, res, layers):
    """From the kernel's joint length on, D's attention is the Pallas call,
    named by the ``dit/attention`` scope, and the stage fits one chip."""
    compiled = _diffuse_compiled(one_chip, arch, res, layers)
    text = compiled.as_text()
    calls = [op.name for ops in hlo.parse_computations(text).values() for op in ops
             if "tpu_custom_call" in op.line]
    assert calls
    where = hlo.op_scopes(text, scopes.DIFFUSE)
    assert {where.get(c) for c in calls} == {scopes.DIT_ATTENTION}
    assert _hbm_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize("res", [128, 256])
def test_short_diffuse_stage_keeps_xla_attention_on_v5e(one_chip, res):
    """Below the kernel's joint length the sd3 D stage holds no kernel."""
    assert "tpu_custom_call" not in _diffuse_compiled(one_chip, "sd3", res).as_text()
