"""The cogvideox block (CogVideoX-5B's expert-AdaLN DiT block) and
classifier-free guidance against the plain reference of the chip benchmark
(``benchmarks/chip/configs/cogvideox_ref.py``), on the CPU at smoke size;
its 3-D rotary positions; the video grid; the profiler's guided batch; and
sd3's and flux's D programs, which the block must leave as they were."""
import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.core.profiler import Profiler
from repro.core.request import Request
from repro.models import diffusion
from repro.models import pipeline as pl
from repro.roofline import hlo

SMOKE = {"encoder_layers": 2, "encoder_d_model": 64, "encoder_heads": 4,
         "encoder_head_dim": 16, "encoder_d_ff": 128, "encoder_vocab": 256,
         "dit_d_model": 128, "dit_heads": 2, "dit_d_ff": 256,
         "dit_time_embed_dim": 32, "dit_latent_dim": 16, "decoder_latent_channels": 4,
         "decoder_base_channels": 32, "cond_len": 8, "frames": 9,
         "dtype": "float32"}
RES = 64                                  # a 3 x 4 x 6 latent grid


def _family(layers=2, steps=2, **over):
    from benchmarks.chip import harness
    cell = harness.load_cell("cogvideox.t2v")
    return harness.family(dict(cell.config, **SMOKE, dit_layers=layers,
                               num_steps=steps, **over))


def _weights(fam, seed=7):
    """Every leaf drawn from the family's scales, none zero: the
    modulations, biases and norm offsets are all in play."""
    from benchmarks.chip import harness
    params, _ = harness.make_weights(fam, fam.param_shapes(fam.program_config()), seed)
    return params


def _rel(have, want):
    have, want = np.asarray(have, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(have - want) / np.linalg.norm(want)


@pytest.mark.parametrize("layers", [1, 2])
def test_block_and_forward_match_the_reference(layers):
    """One block, and two, between the embeddings and the final AdaLN, in
    float32 on both sides.  Tolerance 1e-5: the same float32 arithmetic in
    another order (the joint stream with per-token modulation against
    separate text and video streams, a permutation product against slices,
    whole-sequence attention against blocks of queries) differs by a few
    float32 roundings through a few layers."""
    from benchmarks.chip.configs import cogvideox_ref as ref
    fam = _family(layers)
    pcfg = fam.program_config()
    p = _weights(fam)["diffuse"]
    grid = pcfg.latent_grid(RES)
    lx = int(np.prod(grid))
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, (1, lx, 16))
    cond = jax.random.normal(k2, (1, 8, 64))
    t = jnp.array([640.0])
    with jax.default_matmul_precision("highest"):
        have = diffusion.forward(pcfg.dit, p, x, t, cond, grid=grid)
    want = ref._net(fam.reference_sizes(RES)[1], ref.F32, p, x, t, cond)
    assert have.shape == (1, lx, 16)
    assert float(jnp.std(want)) > 0.1
    assert _rel(have, want) < 1e-5


def test_guided_ddim_matches_the_reference():
    """Two guided DDIM steps (s = 6) from the same noise and the same two
    conditions.  Tolerance 1e-4 of what the network added to the scaled
    noise: guidance multiplies the difference of the two evaluations by 6,
    and its float32 roundings with it."""
    from benchmarks.chip.configs import cogvideox_ref as ref
    fam = _family()
    pcfg = fam.program_config()
    assert pcfg.dit.guidance_scale == 6.0 and pcfg.dit.cond_batch == 2
    p = _weights(fam)["diffuse"]
    grid = pcfg.latent_grid(RES)
    shape = (1, int(np.prod(grid)), 16)
    cond = jax.random.normal(jax.random.PRNGKey(5), (2, 8, 64))
    key = jax.random.PRNGKey(9)
    with jax.default_matmul_precision("highest"):
        have = pl.diffuse(pcfg, {"diffuse": p}, cond, shape, key, grid=grid)
    want, noise = ref.diffuse(fam.reference_sizes(RES)[1], ref.F32, 2, p, cond, shape, key)
    added = np.asarray(want) - np.asarray(noise)
    assert np.linalg.norm(np.asarray(have) - np.asarray(want)) < 1e-4 * np.linalg.norm(added)
    # guidance is in play: the empty prompt's row changes the result
    with jax.default_matmul_precision("highest"):
        same = pl.diffuse(pcfg, {"diffuse": p}, jnp.concatenate([cond[:1], cond[:1]]),
                          shape, key, grid=grid)
    assert _rel(same, have) > 1e-3


def test_encode_adds_the_empty_prompt_with_guidance():
    fam = _family()
    pcfg = fam.program_config()
    p = _weights(fam)
    tokens = jnp.arange(8, dtype=jnp.int32)[None] + 3
    both = pl.encode(pcfg, p, tokens)
    assert both.shape == (2, 8, 64)
    plain = dataclasses.replace(pcfg, dit=dataclasses.replace(pcfg.dit, guidance_scale=1.0))
    np.testing.assert_allclose(both[:1], pl.encode(plain, p, tokens), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(both[1:], pl.encode(plain, p, jnp.zeros_like(tokens)),
                               rtol=1e-6, atol=1e-6)


def test_rotary_positions_split_16_24_24_and_leave_text_alone():
    grid, dh, lc = (13, 30, 45), 64, 226
    cos, sin = diffusion.rope_3d(grid, dh, lc)
    assert cos.shape == sin.shape == (lc + 13 * 30 * 45, dh)
    assert bool(jnp.all(cos[:lc] == 1.0)) and bool(jnp.all(sin[:lc] == 0.0))
    freqs = lambda n: 10000.0 ** (-np.arange(0, n, 2) / n)
    f, r, c = 7, 11, 23
    row = lc + (f * 30 + r) * 45 + c
    ang = np.concatenate([f * freqs(16), r * freqs(24), c * freqs(24)]).repeat(2)
    np.testing.assert_allclose(cos[row], np.cos(ang), atol=1e-5)
    np.testing.assert_allclose(sin[row], np.sin(ang), atol=1e-5)
    from benchmarks.chip.configs import cogvideox_ref as ref
    rc, rs = ref.rotary_tables(grid, dh)
    np.testing.assert_allclose(cos[lc:], rc, atol=1e-5)
    np.testing.assert_allclose(sin[lc:], rs, atol=1e-5)
    # the text rows come out of the rotation as the norm left them
    x = jax.random.normal(jax.random.PRNGKey(0), (1, lc + 13 * 30 * 45, 2, dh))
    w = jnp.full((dh,), 0.1)
    out = diffusion._qk_norm_rope(x, w, -w, cos, sin)
    normed = diffusion._layer_norm(x, w, -w, diffusion.QK_NORM_EPS)
    np.testing.assert_array_equal(out[:, :lc], normed[:, :lc])
    assert float(jnp.max(jnp.abs(out[:, lc + 1:] - normed[:, lc + 1:]))) > 0.1
    # a rotation: each pair keeps its length
    pairs = lambda a: jnp.linalg.norm(a.reshape(*a.shape[:-1], -1, 2), axis=-1)
    np.testing.assert_allclose(pairs(out), pairs(normed), rtol=1e-5)


def test_latent_grid_of_the_480px_class():
    assert _family().program_config().latent_grid(RES) == (3, 4, 6)
    from benchmarks.chip import harness
    real = harness.family(harness.load_cell("cogvideox.t2v").config)
    assert real.grid(480) == (13, 30, 45)
    assert real.latent_tokens(480) + real.cond_len == 17776
    # images and the simulator's videos keep their square grids
    assert C.get("sd3").latent_grid(1024) == (1, 64, 64)
    assert C.get("cogvideox").latent_grid(512, 2.0) == (8, 32, 32)


def test_profiler_counts_the_guided_batch():
    cfg = C.get("cogvideox")
    guided = dataclasses.replace(cfg, dit=dataclasses.replace(cfg.dit, guidance_scale=6.0))
    req = Request("cogvideox", 480, seconds=2.0, cond_len=226)
    plain, cfg_prof = Profiler(cfg), Profiler(guided)
    for stage in "ED":
        assert cfg_prof.stage_flops(req, stage) == pytest.approx(
            2 * plain.stage_flops(req, stage))
        assert cfg_prof.stage_act_bytes(req, stage) == pytest.approx(
            2 * plain.stage_act_bytes(req, stage))
    assert cfg_prof.d_live_bytes(req) == pytest.approx(2 * plain.d_live_bytes(req))
    assert cfg_prof.stage_flops(req, "C") == plain.stage_flops(req, "C")


def _d_names(arch: str, res: int = 64) -> dict:
    """{computation: [instruction names]} of ``arch``'s smoke D program as
    the CPU compiles it."""
    cfg = C.get_smoke(arch)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: pl.init(cfg, k), key)
    cond = jax.ShapeDtypeStruct((1, 8, cfg.dit.cond_dim), cfg.dit.dtype)
    lat = (1, cfg.latent_tokens(res), cfg.dit.latent_dim)
    text = jax.jit(lambda p, c, k: pl.diffuse(cfg, p, c, lat, k)).lower(
        params, cond, key).compile().as_text()
    return {comp: [op.name for op in ops]
            for comp, ops in hlo.parse_computations(text).items()}


# (instruction count, sha256 of the names as sorted JSON) of each smoke D
# program, taken before the cogvideox block and guidance were added, and
# re-taken when the AdaLN modulation moved out of the step loop
IMAGE_D_NAMES = {
    "sd3": (979, "77316c142a5818018cca7a0b041923508f1a782a42df44ea16cb41df2d32b6c3"),
    "flux": (939, "aebeb85f6ed79a395cc686bb48fbba7052fc14e7dda6e6637def99fab59cd001"),
}


@pytest.mark.parametrize("arch", sorted(IMAGE_D_NAMES))
def test_image_d_programs_keep_their_instructions(arch):
    names = _d_names(arch)
    digest = hashlib.sha256(json.dumps(names, sort_keys=True).encode()).hexdigest()
    assert (sum(map(len, names.values())), digest) == IMAGE_D_NAMES[arch]
