"""DiT diffusion transformer with AdaLN-Zero conditioning (Diffuse stage).

Architecture follows Peebles & Xie DiT / SD3-style joint conditioning
simplified to a single stream: latent patches and text-condition tokens are
concatenated into one sequence; per-block modulation (shift/scale/gate x2)
comes from the timestep + pooled-condition embedding.  Layers are
homogeneous, executed with one ``lax.scan``.  Each block runs under a
named scope of ``repro.models.scopes`` (metadata only).

``DiTConfig.block`` selects the block.  ``"single"`` is the block above.
``"cogvideox"`` is CogVideoX's expert-AdaLN block (arXiv:2408.06072): text
and video tokens share the joint stream and every weight, but take their
own shift, scale and gate; LayerNorms with affine weights, per-head q/k
LayerNorm, 3-D rotary positions on the video tokens, biased projections.
With ``guidance_scale`` other than 1, ``ddim_denoise`` runs the network on
the prompt's and the empty prompt's condition in one batch
(classifier-free guidance).

The Diffuse stage runs ``num_steps`` denoising iterations of this network —
the compute-dominant, SP-scalable stage the paper's dispatcher reasons
about.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models import common, scopes
from repro.models.common import Array, dense_init


# joint length from which the flash kernel beats XLA's attention on a TPU
# v5e: XLA is faster at 141 and 333, the kernel at 1101 and 4173 (PERF.md,
# benchmarks/attention_bench.py)
FLASH_MIN_LEN = 1024


@dataclasses.dataclass(frozen=True)
class DiTConfig:
    name: str
    num_layers: int
    d_model: int
    num_heads: int
    d_ff: int
    latent_dim: int               # channels per latent token (after patchify)
    cond_dim: int                 # encoder hidden size
    time_embed_dim: int = 256
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6
    use_fused_adaln: bool = False  # route modulated norms through the Pallas kernel
    block: str = "single"          # "single" | "cogvideox"
    guidance_scale: float = 1.0    # classifier-free guidance; 1 is none
    source: str = ""

    @property
    def cond_batch(self) -> int:
        """Condition rows per request: the prompt, and with guidance the
        empty prompt after it."""
        return 1 if self.guidance_scale == 1.0 else 2


def init(cfg: DiTConfig, key: Array) -> dict:
    if cfg.block == "cogvideox":
        return _init_cogvideox(cfg, key)
    d = cfg.d_model
    ks = common.split_keys(key, 8)
    scale_o = 1.0 / max(1, cfg.num_layers) ** 0.5

    def layer_init(k):
        kk = common.split_keys(k, 7)
        return {
            "wq": dense_init(kk[0], (d, d), cfg.dtype),
            "wk": dense_init(kk[1], (d, d), cfg.dtype),
            "wv": dense_init(kk[2], (d, d), cfg.dtype),
            "wo": dense_init(kk[3], (d, d), cfg.dtype, scale=scale_o),
            "w_up": dense_init(kk[4], (d, cfg.d_ff), cfg.dtype),
            "w_down": dense_init(kk[5], (cfg.d_ff, d), cfg.dtype, scale=scale_o),
            # AdaLN-Zero: 6 modulation vectors, zero-init so blocks start as identity
            "mod": jnp.zeros((d, 6 * d), cfg.dtype),
        }

    lkeys = jnp.stack(common.split_keys(ks[0], cfg.num_layers))
    return {
        "x_in": dense_init(ks[1], (cfg.latent_dim, d), cfg.dtype),
        "cond_in": dense_init(ks[2], (cfg.cond_dim, d), cfg.dtype),
        "t_mlp1": dense_init(ks[3], (cfg.time_embed_dim, d), cfg.dtype),
        "t_mlp2": dense_init(ks[4], (d, d), cfg.dtype),
        "layers": jax.vmap(layer_init)(lkeys),
        "final_mod": jnp.zeros((d, 2 * d), cfg.dtype),
        "x_out": dense_init(ks[5], (d, cfg.latent_dim), cfg.dtype, scale=0.02),
        "pos_freq": dense_init(ks[6], (2, d // 2), jnp.float32, scale=1.0),
    }


def timestep_embedding(t: Array, dim: int) -> Array:
    """Sinusoidal embedding; t: (B,) float in [0, 1000]."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def _modulated_norm(cfg: DiTConfig, x, scale, shift):
    if cfg.use_fused_adaln:
        return kops.adaln_rmsnorm(x, scale, shift, eps=cfg.norm_eps, use_kernel=True)
    return kops.adaln_rmsnorm(x, scale, shift, eps=cfg.norm_eps, use_kernel=False)


def joint_attention(q: Array, k: Array, v: Array) -> Array:
    """Bidirectional attention over the joint sequence, (B, L, H, Dh).

    On a TPU, from ``FLASH_MIN_LEN`` positions on, the Pallas flash kernel,
    which never writes the (H, L, L) scores to HBM; below it XLA's
    materialised scores, which the compiler fuses with the projections.
    Every other platform lowers ``common.attention`` alone.
    """
    xla = functools.partial(common.attention, mask=None)
    if q.shape[1] < FLASH_MIN_LEN:
        return xla(q, k, v)
    return jax.lax.platform_dependent(
        q, k, v, default=xla,
        tpu=functools.partial(kops.flash_attention, causal=False, use_kernel=True))


def forward(cfg: DiTConfig, params: dict, latents: Array, t: Array,
            cond: Array, cond_pooled: Optional[Array] = None,
            grid: Optional[Tuple[int, int, int]] = None) -> Array:
    """One denoising network evaluation.

    latents: (B, Lx, latent_dim); t: (B,); cond: (B, Lc, cond_dim).
    Returns predicted noise (B, Lx, latent_dim).  The cogvideox block also
    takes the latent ``grid`` (frames, h, w) its rotary positions follow.
    """
    if cfg.block == "cogvideox":
        return _forward_cogvideox(cfg, params, latents, t, cond, grid)
    b, lx, _ = latents.shape
    lc = cond.shape[1]
    h = cfg.num_heads
    dh = cfg.d_model // h

    with jax.named_scope(scopes.DIT_EMBED):
        x = jnp.einsum("blc,cd->bld", latents.astype(cfg.dtype), params["x_in"])
        c = jnp.einsum("blc,cd->bld", cond.astype(cfg.dtype), params["cond_in"])
        x = jnp.concatenate([c, x], axis=1)                   # joint stream
        l = lx + lc

        # absolute 2-channel sin/cos positions (latent grid is 1D-flattened here)
        pos = jnp.arange(l, dtype=jnp.float32)
        pf = params["pos_freq"].astype(jnp.float32)
        pe = jnp.concatenate([jnp.sin(pos[:, None] * pf[0][None]),
                              jnp.cos(pos[:, None] * pf[1][None])], axis=-1)
        x = x + pe[None].astype(cfg.dtype)

        temb = timestep_embedding(t, cfg.time_embed_dim)
        tc = jnp.einsum("be,ed->bd", temb.astype(cfg.dtype), params["t_mlp1"])
        if cond_pooled is not None:
            tc = tc + cond_pooled.astype(cfg.dtype)
        tc = jnp.einsum("bd,de->be",
                        jax.nn.silu(tc.astype(jnp.float32)).astype(cfg.dtype),
                        params["t_mlp2"])

    def block(x, p):
        with jax.named_scope(scopes.DIT_ADALN):
            mod = jnp.einsum("bd,de->be", tc, p["mod"]).reshape(b, 6, cfg.d_model)
            s1, sh1, g1, s2, sh2, g2 = [mod[:, i] for i in range(6)]
            hn = _modulated_norm(cfg, x, s1, sh1)
        with jax.named_scope(scopes.DIT_QKV):
            q = jnp.einsum("bld,de->ble", hn, p["wq"]).reshape(b, l, h, dh)
            k = jnp.einsum("bld,de->ble", hn, p["wk"]).reshape(b, l, h, dh)
            v = jnp.einsum("bld,de->ble", hn, p["wv"]).reshape(b, l, h, dh)
        with jax.named_scope(scopes.DIT_ATTENTION):
            a = joint_attention(q, k, v)
        with jax.named_scope(scopes.DIT_ATTN_OUT):
            a = jnp.einsum("ble,ed->bld", a.reshape(b, l, cfg.d_model), p["wo"])
            x = x + g1[:, None, :] * a
        with jax.named_scope(scopes.DIT_ADALN):
            hn = _modulated_norm(cfg, x, s2, sh2)
        with jax.named_scope(scopes.DIT_MLP):
            f = common.gelu_mlp(hn, p["w_up"], p["w_down"])
            x = x + g2[:, None, :] * f
        return x, 0

    with jax.named_scope(scopes.DIT_LAYERS):
        x, _ = jax.lax.scan(block, x, params["layers"])
    with jax.named_scope(scopes.DIT_FINAL):
        fmod = jnp.einsum("bd,de->be", tc, params["final_mod"]).reshape(b, 2, cfg.d_model)
        x = _modulated_norm(cfg, x, fmod[:, 0], fmod[:, 1])
        eps = jnp.einsum("bld,dc->blc", x[:, lc:, :], params["x_out"])
        return eps.astype(jnp.float32)


# ---------------------------------------------------------------------------
# CogVideoX's block (arXiv:2408.06072; the CogVideoX-5B transformer)
# ---------------------------------------------------------------------------

QK_NORM_EPS = 1e-6
ROPE_THETA = 10000.0


def _init_cogvideox(cfg: DiTConfig, key: Array) -> dict:
    """Weights of the cogvideox block.  ``time_embed_dim`` is the width of
    the time embedding e (the sinusoid has ``d_model`` channels).  Each
    bias is ``<weight>_b``; each norm's gain is ``<norm>_w``, stored as an
    offset from 1 as the repo's other norms are.  Biases, gain offsets and
    modulations start at zero, so blocks start as the identity."""
    d, e, f = cfg.d_model, cfg.time_embed_dim, cfg.d_ff
    dh = d // cfg.num_heads
    ks = common.split_keys(key, 6)
    scale_o = 1.0 / max(1, cfg.num_layers) ** 0.5
    zeros = lambda *shape: jnp.zeros(shape, cfg.dtype)

    def layer_init(k):
        kk = common.split_keys(k, 6)
        return {
            "mod1": zeros(e, 6 * d), "mod1_b": zeros(6 * d),
            "mod2": zeros(e, 6 * d), "mod2_b": zeros(6 * d),
            "ln1_w": zeros(d), "ln1_b": zeros(d),
            "ln2_w": zeros(d), "ln2_b": zeros(d),
            "wq": dense_init(kk[0], (d, d), cfg.dtype), "wq_b": zeros(d),
            "wk": dense_init(kk[1], (d, d), cfg.dtype), "wk_b": zeros(d),
            "wv": dense_init(kk[2], (d, d), cfg.dtype), "wv_b": zeros(d),
            "q_norm_w": zeros(dh), "q_norm_b": zeros(dh),
            "k_norm_w": zeros(dh), "k_norm_b": zeros(dh),
            "wo": dense_init(kk[3], (d, d), cfg.dtype, scale=scale_o), "wo_b": zeros(d),
            "w_up": dense_init(kk[4], (d, f), cfg.dtype), "w_up_b": zeros(f),
            "w_down": dense_init(kk[5], (f, d), cfg.dtype, scale=scale_o),
            "w_down_b": zeros(d),
        }

    lkeys = jnp.stack(common.split_keys(ks[0], cfg.num_layers))
    return {
        "text_in": dense_init(ks[1], (cfg.cond_dim, d), cfg.dtype), "text_in_b": zeros(d),
        "x_in": dense_init(ks[2], (cfg.latent_dim, d), cfg.dtype), "x_in_b": zeros(d),
        "t_mlp1": dense_init(ks[3], (d, e), cfg.dtype), "t_mlp1_b": zeros(e),
        "t_mlp2": dense_init(ks[4], (e, e), cfg.dtype), "t_mlp2_b": zeros(e),
        "layers": jax.vmap(layer_init)(lkeys),
        "final_norm_w": zeros(d), "final_norm_b": zeros(d),
        "final_mod": zeros(e, 2 * d), "final_mod_b": zeros(2 * d),
        "out_norm_w": zeros(d), "out_norm_b": zeros(d),
        "x_out": dense_init(ks[5], (d, cfg.latent_dim), cfg.dtype, scale=0.02),
        "x_out_b": zeros(cfg.latent_dim),
    }


def rope_3d(grid: Tuple[int, int, int], head_dim: int, text_len: int):
    """(cos, sin), each (text_len + f*h*w, head_dim) float32: CogVideoX's
    3-D rotary positions.  A head's dims split dh/4, 3dh/8, 3dh/8 over
    (frame, row, column) at the latent grid's integer positions, frame-major
    as the tokens are; each part has its own frequencies theta^(-2i/part),
    each for one interleaved pair of dims.  The text rows, first, rotate by
    nothing (cos 1, sin 0)."""
    parts = (head_dim // 4, head_dim // 8 * 3, head_dim // 8 * 3)
    pos = jnp.meshgrid(*(jnp.arange(n, dtype=jnp.float32) for n in grid),
                       indexing="ij")
    ang = jnp.concatenate([p.reshape(-1, 1) * common.rope_freqs(n, ROPE_THETA)
                           for p, n in zip(pos, parts)], axis=-1)
    ang = jnp.concatenate([jnp.zeros((text_len, head_dim), jnp.float32),
                           jnp.repeat(ang, 2, axis=-1)])
    return jnp.cos(ang), jnp.sin(ang)


def _layer_norm(x: Array, w: Array, b: Array, eps: float) -> Array:
    """Affine LayerNorm in float32, the gain stored as an offset from 1."""
    return common.layer_norm(x.astype(jnp.float32), 1.0 + w.astype(jnp.float32), b, eps)


def _qk_norm_rope(x: Array, w: Array, b: Array, cos: Array, sin: Array) -> Array:
    """Per-head LayerNorm of q or k (B, L, H, Dh), then each interleaved
    pair (x0, x1) rotated to (x0 cos - x1 sin, x1 cos + x0 sin).  The pairs
    turn to (-x1, x0) by a signed permutation product, exact at the highest
    precision: on a TPU it moves a third of the bytes that slicing the
    pairs apart does (v5e compile of the 480-px cogvideox layer)."""
    x = _layer_norm(x, w, b, QK_NORM_EPS)
    dh = x.shape[-1]
    even, odd = jnp.arange(0, dh, 2), jnp.arange(1, dh, 2)
    turn = jnp.zeros((dh, dh), jnp.float32).at[odd, even].set(-1.0).at[even, odd].set(1.0)
    turned = jnp.einsum("blhd,de->blhe", x, turn, precision=jax.lax.Precision.HIGHEST)
    return x * cos[None, :, None] + turned * sin[None, :, None]


def _forward_cogvideox(cfg: DiTConfig, params: dict, latents: Array, t: Array,
                       cond: Array, grid: Tuple[int, int, int]) -> Array:
    """One evaluation of the cogvideox network; the text first in the joint
    stream.  Per block, from the time embedding e: (shift, scale, gate) for
    the video tokens and the same for the text tokens, twice (before the
    attention and before the MLP); both kinds share one affine LayerNorm
    and every projection."""
    b, lx, _ = latents.shape
    lc = cond.shape[1]
    l = lx + lc
    d, nh = cfg.d_model, cfg.num_heads
    dh = d // nh
    dt = cfg.dtype

    def dense(x, w, bias):
        return jnp.einsum("...i,io->...o", x.astype(dt), w) + bias

    def silu(x):
        return jax.nn.silu(x.astype(jnp.float32)).astype(dt)

    with jax.named_scope(scopes.DIT_EMBED):
        x = jnp.concatenate([dense(cond, params["text_in"], params["text_in_b"]),
                             dense(latents, params["x_in"], params["x_in_b"])], axis=1)
        e = dense(timestep_embedding(t, d), params["t_mlp1"], params["t_mlp1_b"])
        e = dense(silu(e), params["t_mlp2"], params["t_mlp2_b"])
        se = silu(e)                 # what every modulation projects
        cos, sin = rope_3d(grid, dh, lc)
        is_text = (jnp.arange(l) < lc)[None, :, None]

    def modulated(x, w, bias, mod, mod_b):
        """LN(x)(1 + scale) + shift, and the gate, per token's kind."""
        m = dense(se, mod, mod_b).astype(jnp.float32).reshape(b, 2, 3, 1, d)
        shift, scale, gate = (jnp.where(is_text, m[:, 1, i], m[:, 0, i]) for i in range(3))
        h = _layer_norm(x, w, bias, cfg.norm_eps) * (1.0 + scale) + shift
        return h.astype(dt), gate.astype(dt)

    def block(x, p):
        with jax.named_scope(scopes.DIT_ADALN):
            h, gate = modulated(x, p["ln1_w"], p["ln1_b"], p["mod1"], p["mod1_b"])
        with jax.named_scope(scopes.DIT_QKV):
            q, k, v = (dense(h, p[f"w{n}"], p[f"w{n}_b"]).reshape(b, l, nh, dh)
                       for n in "qkv")
        with jax.named_scope(scopes.DIT_QK_ROPE):
            q = _qk_norm_rope(q, p["q_norm_w"], p["q_norm_b"], cos, sin).astype(dt)
            k = _qk_norm_rope(k, p["k_norm_w"], p["k_norm_b"], cos, sin).astype(dt)
        with jax.named_scope(scopes.DIT_ATTENTION):
            a = joint_attention(q, k, v)
        with jax.named_scope(scopes.DIT_ATTN_OUT):
            x = x + gate * dense(a.reshape(b, l, d), p["wo"], p["wo_b"])
        with jax.named_scope(scopes.DIT_ADALN):
            h, gate = modulated(x, p["ln2_w"], p["ln2_b"], p["mod2"], p["mod2_b"])
        with jax.named_scope(scopes.DIT_MLP):
            f = jax.nn.gelu(dense(h, p["w_up"], p["w_up_b"]).astype(jnp.float32),
                            approximate=True)
            x = x + gate * dense(f, p["w_down"], p["w_down_b"])
        return x, 0

    with jax.named_scope(scopes.DIT_LAYERS):
        x, _ = jax.lax.scan(block, x, params["layers"])
    with jax.named_scope(scopes.DIT_FINAL):
        # the final LayerNorm acts per token: over the joint stream, its
        # video rows are these
        h = _layer_norm(x[:, lc:], params["final_norm_w"], params["final_norm_b"],
                        cfg.norm_eps)
        shift, scale = jnp.split(dense(se, params["final_mod"], params["final_mod_b"])
                                 .astype(jnp.float32)[:, None], 2, axis=-1)
        h = _layer_norm(h, params["out_norm_w"], params["out_norm_b"],
                        cfg.norm_eps) * (1.0 + scale) + shift
        return dense(h, params["x_out"], params["x_out_b"]).astype(jnp.float32)


def ddim_denoise(cfg: DiTConfig, params: dict, noise: Array, cond: Array,
                 num_steps: int, key: Optional[Array] = None,
                 grid: Optional[Tuple[int, int, int]] = None) -> Array:
    """Multi-step denoising loop (the Diffuse stage's runtime body).

    DDIM with a linear alpha-bar schedule; deterministic (eta=0).  With
    guidance, ``cond`` is (2B, Lc, cond_dim), the prompts' rows and then
    the empty prompts'; each step runs the network once on ``[x; x]`` and
    takes ``e_u + s (e_c - e_u)``.
    """
    with jax.named_scope(scopes.DDIM):
        betas = jnp.linspace(1e-4, 0.02, 1000, dtype=jnp.float32)
        alpha_bar = jnp.cumprod(1.0 - betas)
        ts = jnp.linspace(999, 0, num_steps).astype(jnp.int32)
    rope_grid = {} if grid is None else {"grid": grid}   # for the block that reads it

    def step(i, x):
        with jax.named_scope(scopes.DDIM):
            t = ts[i]
            t_next = jnp.where(i + 1 < num_steps,
                               ts[jnp.minimum(i + 1, num_steps - 1)], -1)
            ab_t = alpha_bar[t]
            ab_n = jnp.where(t_next >= 0, alpha_bar[jnp.maximum(t_next, 0)], 1.0)
            tb = jnp.full((x.shape[0],), t, jnp.float32)
        if cfg.guidance_scale == 1.0:
            eps = forward(cfg, params, x, tb, cond, **rope_grid)
        else:
            both = forward(cfg, params, jnp.concatenate([x, x]),
                           jnp.concatenate([tb, tb]), cond, **rope_grid)
            with jax.named_scope(scopes.DDIM):
                e_c, e_u = jnp.split(both, 2)
                eps = e_u + cfg.guidance_scale * (e_c - e_u)
        with jax.named_scope(scopes.DDIM):
            x0 = (x - jnp.sqrt(1 - ab_t) * eps) / jnp.sqrt(ab_t)
            return jnp.sqrt(ab_n) * x0 + jnp.sqrt(1 - ab_n) * eps

    return jax.lax.fori_loop(0, num_steps, step, noise)


# ---------------------------------------------------------------------------
# AE-KL latent decoder (Decode stage) — conv upsampler, memory-bound
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    name: str
    latent_channels: int
    base_channels: int = 512
    num_upsamples: int = 3        # 8x spatial upscale
    res_blocks: int = 2           # residual conv blocks per level
    out_channels: int = 3
    dtype: Any = jnp.bfloat16
    source: str = ""


def init_decoder(cfg: DecoderConfig, key: Array) -> dict:
    nconv = 1 + cfg.num_upsamples * (1 + cfg.res_blocks) + 1
    ks = common.split_keys(key, nconv + 1)
    ch = cfg.base_channels
    params = {"conv_in": dense_init(ks[0], (3, 3, cfg.latent_channels, ch), cfg.dtype)}
    ki = 1
    for i in range(cfg.num_upsamples):
        cin = max(ch // (2 ** i), 32)
        cout = max(ch // (2 ** (i + 1)), 32)
        params[f"up{i}_in"] = dense_init(ks[ki], (3, 3, cin, cout), cfg.dtype); ki += 1
        for r in range(cfg.res_blocks):
            params[f"up{i}_res{r}"] = dense_init(ks[ki], (3, 3, cout, cout), cfg.dtype); ki += 1
    cfin = max(ch // (2 ** cfg.num_upsamples), 32)
    params["conv_out"] = dense_init(ks[ki], (3, 3, cfin, cfg.out_channels), cfg.dtype)
    return params


def _conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def decode_latent(cfg: DecoderConfig, params: dict, z: Array) -> Array:
    """z: (B, h, w, latent_channels) -> pixels (B, 8h, 8w, 3).

    Video pipelines fold frames into the batch dim (the profiler's cost
    model accounts for the heavier 3D-conv + temporal-upsample cost of the
    real AE; see DESIGN.md §assumptions).
    """
    with jax.named_scope(scopes.DECODER_CONV_IN):
        x = _conv(z.astype(cfg.dtype), params["conv_in"])
    for i in range(cfg.num_upsamples):
        with jax.named_scope(scopes.decoder_up(i)):
            b, hh, ww, c = x.shape
            x = jax.nn.silu(x.astype(jnp.float32)).astype(cfg.dtype)
            x = jax.image.resize(x, (b, hh * 2, ww * 2, c), "nearest")
            x = _conv(x, params[f"up{i}_in"])
            for r in range(cfg.res_blocks):
                h = jax.nn.silu(x.astype(jnp.float32)).astype(cfg.dtype)
                x = x + _conv(h, params[f"up{i}_res{r}"])
    with jax.named_scope(scopes.DECODER_CONV_OUT):
        x = jax.nn.silu(x.astype(jnp.float32)).astype(cfg.dtype)
        return jnp.tanh(_conv(x, params["conv_out"]).astype(jnp.float32))
