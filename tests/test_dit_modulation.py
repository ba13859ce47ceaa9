"""D's AdaLN modulations, taken once per launch: ``ddim_denoise`` computes
every step's modulations before its step loop (``diffusion.adaln``), one
product per weight over the stacked layers, and gives ``forward`` step i's
(CPU, smoke sizes).  The denoised latents match a loop of standalone
``forward`` calls, each computing its own modulations from its timestep;
and no modulation weight is read inside the compiled step loop."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import diffusion
from repro.models import pipeline as pl
from repro.roofline import hlo

GRID = (2, 2, 3)                   # the cogvideox case's latent grid: 12 tokens


def _pipeline(arch: str, dtype=jnp.float32) -> pl.PipelineConfig:
    """The smoke pipeline of sd3 or flux; for ``cogvideox``, sd3's smoke
    widths in the cogvideox block with guidance 6.  ``d_ff`` is 3d and the
    single block's time embedding d/2, so that only the modulation weights
    have a dimension of 2d or 6d (the smoke's d_ff and time embedding are
    2d)."""
    cfg = C.get_smoke("sd3" if arch == "cogvideox" else arch)
    d = cfg.dit.d_model
    dit = dataclasses.replace(cfg.dit, d_ff=3 * d, time_embed_dim=d // 2, dtype=dtype)
    if arch == "cogvideox":
        dit = dataclasses.replace(dit, block="cogvideox", guidance_scale=6.0,
                                  time_embed_dim=d // 4, norm_eps=1e-5)
    return dataclasses.replace(cfg, dit=dit)


def _inputs(cfg: pl.PipelineConfig, seed: int = 3):
    """Weights with every leaf non-zero (the zero-initialised modulations
    included), noise, a condition of ``cond_batch`` rows, the grid."""
    dit = cfg.dit
    params = diffusion.init(dit, jax.random.PRNGKey(seed))
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves) + 2)
    params = jax.tree_util.tree_unflatten(tree, [
        w + (0.05 * jax.random.normal(k, w.shape)).astype(w.dtype)
        for w, k in zip(leaves, keys)])
    video = dit.block == "cogvideox"
    lx = int(np.prod(GRID)) if video else cfg.latent_tokens(64)
    noise = jax.random.normal(keys[-2], (1, lx, dit.latent_dim), jnp.float32)
    cond = jax.random.normal(keys[-1], (dit.cond_batch, 8, dit.cond_dim), dit.dtype)
    return params, noise, cond, (GRID if video else None)


def _exact(f, *args):
    """``f`` compiled with XLA's excess precision off, so that every bf16
    value the program states is rounded where it is stated.  With it on,
    the CPU keeps a modulation computed inside ``forward`` in f32 between
    its product and its bias, where one taken before the loop is stored in
    bf16 as ``cfg.dtype`` says: the guided bf16 cogvideox case then reads
    0.77% of what the network added, every other case below 1e-7."""
    return jax.jit(f).lower(*args).compile(
        compiler_options={"xla_allow_excess_precision": False})


def _forward_per_step(dit, params, noise, cond, steps, grid):
    """The DDIM loop of ``ddim_denoise`` in Python, one standalone
    ``forward`` a step (each computes its modulations from its t)."""
    tb = jnp.zeros((noise.shape[0] * dit.cond_batch,), jnp.float32)
    fwd = _exact(lambda p, x, t, c: diffusion.forward(dit, p, x, t, c, grid=grid),
                 params, jnp.concatenate([noise] * dit.cond_batch), tb, cond)
    alpha_bar = jnp.cumprod(1.0 - jnp.linspace(1e-4, 0.02, 1000, dtype=jnp.float32))
    ts = np.linspace(999, 0, steps).astype(np.int32)
    x = noise
    for i, t in enumerate(ts):
        ab_t = alpha_bar[t]
        ab_n = alpha_bar[ts[i + 1]] if i + 1 < steps else 1.0
        out = fwd(params, jnp.concatenate([x] * dit.cond_batch), tb + float(t), cond)
        if dit.guidance_scale == 1.0:
            eps = out
        else:
            e_c, e_u = jnp.split(out, 2)
            eps = e_u + dit.guidance_scale * (e_c - e_u)
        x0 = (x - jnp.sqrt(1 - ab_t) * eps) / jnp.sqrt(ab_t)
        x = jnp.sqrt(ab_n) * x0 + jnp.sqrt(1 - ab_n) * eps
    return x


def _rel(have, want, base):
    have, want, base = (np.asarray(a, np.float64) for a in (have, want, base))
    return np.linalg.norm(have - want) / np.linalg.norm(want - base)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("arch", ["sd3", "flux", "cogvideox"])
def test_hoisted_modulation_matches_a_forward_per_step(arch, dtype):
    """The same products in another order: a [steps, d] x [d, 6d] product
    per weight against a [1, d] x [d, 6d] one per step, the same roundings
    to ``dtype``.  Relative error of what the network added to the noise
    at most 1e-6 (it reads below 1e-7 on every case)."""
    cfg = _pipeline(arch, dtype)
    params, noise, cond, grid = _inputs(cfg)
    ddim = lambda p, n, c: diffusion.ddim_denoise(cfg.dit, p, n, c, cfg.num_steps, grid=grid)
    have = _exact(ddim, params, noise, cond)(params, noise, cond)
    want = _forward_per_step(cfg.dit, params, noise, cond, cfg.num_steps, grid)
    assert _rel(have, want, noise) < 1e-6
    # the modulations are in play: without them the result moves
    zeroed = jax.tree_util.tree_map_with_path(
        lambda path, w: jnp.zeros_like(w) if "mod" in jax.tree_util.keystr(path) else w,
        params)
    assert _rel(jax.jit(ddim)(zeroed, noise, cond), want, noise) > 0.05


def _mod_dots(arch: str):
    """Of the compiled smoke D program: the operand shapes of each dot
    inside the step loop (a loop multiplier above 1), and of each outside
    it; the modulation outputs' widths (6d per layer, 2d final); the layers."""
    cfg = _pipeline(arch)
    dit = cfg.dit
    params, noise, cond, grid = _inputs(cfg)
    text = jax.jit(lambda p, c, k: pl.diffuse(cfg, p, c, noise.shape, k, grid=grid)).lower(
        {"diffuse": params}, cond, jax.random.PRNGKey(0)).compile().as_text()
    comps = hlo.parse_computations(text)
    mult = hlo.loop_multipliers(text, comps)
    table = hlo._symbol_table(comps)
    inside, outside = [], []
    for name, ops in comps.items():
        for op in ops:
            if op.opcode == "dot":
                shapes = [dims for o in hlo._operands(op.line) for _, dims in table[o]]
                (inside if mult[name] > 1 else outside).append(shapes)
    return inside, outside, 6 * dit.d_model, 2 * dit.d_model, dit.num_layers


@pytest.mark.parametrize("arch,per_layer", [("sd3", 1), ("flux", 1), ("cogvideox", 2)])
def test_no_modulation_weight_is_read_in_the_step_loop(arch, per_layer):
    """Only the modulation weights have a dimension of 2d or 6d (or L x 6d,
    the stacked layers folded).  Inside the step loop no dot takes such an
    operand; outside it one dot per layer weight (``mod``, or ``mod1`` and
    ``mod2``) and one for ``final_mod`` do."""
    inside, outside, w6, w2, layers = _mod_dots(arch)
    wide = lambda dots, widths: [s for s in dots if any(set(widths) & set(d) for d in s)]
    assert len(inside) >= 6           # the loop is found: q, k, v, o and the MLP's two
    assert wide(inside, (w6, layers * w6, w2)) == []
    assert len(wide(outside, (w6, layers * w6))) == per_layer
    assert len(wide(outside, (w2,))) == 1
