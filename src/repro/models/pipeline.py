"""The three-stage Diffusion Pipeline: Encode -> Diffuse -> Decode.

This is the model object the serving system deploys.  Each stage is an
independent parameter pytree + apply function, so a *placement* can load any
subset of stages onto a worker, and a *dispatch plan* can run a stage on its
own device group — exactly the paper's stage-level abstraction.

Resolution/duration -> latent token geometry follows the 8x-VAE, patch-2
convention (image: (res/16)^2 tokens; video adds frames/4 temporal tokens),
matching Table 2's l_proc ranges.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common, diffusion, scopes, transformer
from repro.models.common import Array, ModelConfig


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    name: str
    encoder: ModelConfig              # bidirectional text encoder (stage E)
    dit: diffusion.DiTConfig          # denoiser (stage D)
    decoder: diffusion.DecoderConfig  # AE-KL latent decoder (stage C)
    num_steps: int                    # denoising steps (Table 5)
    max_cond_len: int = 128
    is_video: bool = False
    clip_frames: int = 0              # a video of fixed length, in frames; 0: from seconds
    aspect: float = 1.0               # width over height
    source: str = ""

    def latent_grid(self, resolution: int, seconds: float = 0.0) -> Tuple[int, int, int]:
        """(frames, h, w) latent geometry of a ``resolution``-px-high output.
        8x VAE + patch 2 -> /16 per side.  Video: a clip of ``clip_frames``
        keeps its first frame and compresses the rest 4x in time (a causal
        VAE: 49 frames -> 13); without it, 4x compression of ``seconds`` at
        16 fps."""
        h = max(2, resolution // 16)
        w = max(2, round(resolution * self.aspect) // 16)
        if self.clip_frames:
            frames = (self.clip_frames - 1) // 4 + 1
        else:
            frames = max(1, int(seconds * 16) // 4) if self.is_video else 1
        return frames, h, w

    def latent_tokens(self, resolution: int, seconds: float = 0.0) -> int:
        f, h, w = self.latent_grid(resolution, seconds)
        return f * h * w


def init(cfg: PipelineConfig, key: Array) -> Dict[str, Any]:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "encode": transformer.init(cfg.encoder, k1),
        "diffuse": diffusion.init(cfg.dit, k2),
        "decode": diffusion.init_decoder(cfg.decoder, k3),
    }


# --- Stage apply functions (each independently dispatchable) ---------------

def encode(cfg: PipelineConfig, params: Dict, tokens: Array) -> Array:
    """Stage E: prompt tokens (B, Lc) -> condition embeddings (B, Lc, D_enc).

    With guidance the empty prompt (all pad, id 0) is encoded after the
    prompts: (2B, Lc, D_enc), the condition ``ddim_denoise`` takes."""
    ecfg = cfg.encoder
    if cfg.dit.cond_batch == 2:
        tokens = jnp.concatenate([tokens, jnp.zeros_like(tokens)])
    x = transformer.embed_tokens(ecfg, params["encode"], tokens)
    b, l, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32)[None], (b, l))
    x, _, _ = transformer._run_segments(ecfg, params["encode"], x, positions,
                                        None, "train", 0)
    return common.rms_norm(x, params["encode"]["final_norm"], ecfg.norm_eps)


def diffuse(cfg: PipelineConfig, params: Dict, cond: Array, latent_shape,
            key: Array, num_steps: Optional[int] = None,
            grid: Optional[Tuple[int, int, int]] = None) -> Array:
    """Stage D: T-step denoising from Gaussian noise in latent space.  The
    cogvideox block places its rotary positions on the latent ``grid``."""
    steps = num_steps or cfg.num_steps
    with jax.named_scope(scopes.DDIM):
        noise = jax.random.normal(key, latent_shape, jnp.float32)
    return diffusion.ddim_denoise(cfg.dit, params["diffuse"], noise, cond, steps,
                                  grid=grid)


def decode(cfg: PipelineConfig, params: Dict, latents: Array,
           grid: Tuple[int, int, int]) -> Array:
    """Stage C: latent tokens (B, L, C) -> pixel frames (B*F, 8h*2, 8w*2, 3).

    Tokens are un-patchified (patch 2 over an 8x-VAE grid) then decoded.
    """
    f, h, w = grid
    b, l, c = latents.shape
    assert l == f * h * w, (l, grid)
    cl = cfg.decoder.latent_channels
    # (B, F, h, w, patch2*cl) -> (B*F, 2h, 2w, cl)
    z = latents.reshape(b * f, h, w, 2, 2, cl).transpose(0, 1, 3, 2, 4, 5)
    z = z.reshape(b * f, 2 * h, 2 * w, cl)
    return diffusion.decode_latent(cfg.decoder, params["decode"], z)


def generate(cfg: PipelineConfig, params: Dict, tokens: Array, resolution: int,
             seconds: float, key: Array, num_steps: Optional[int] = None) -> Array:
    """End-to-end E->D->C (the co-located ⟨EDC⟩ execution path)."""
    grid = cfg.latent_grid(resolution, seconds)
    ltokens = cfg.latent_tokens(resolution, seconds)
    cond = encode(cfg, params, tokens)
    b = tokens.shape[0]
    lat_dim = cfg.dit.latent_dim
    latents = diffuse(cfg, params, cond, (b, ltokens, lat_dim), key, num_steps,
                      grid=grid)
    return decode(cfg, params, latents, grid)


# --- Workload geometry helpers (used by the profiler & dispatcher) ---------

def stage_proc_len(cfg: PipelineConfig, stage: str, resolution: int,
                   seconds: float, cond_len: int = 77) -> int:
    """The paper's l_proc per stage (Table 2 semantics)."""
    if stage == "E":
        return cond_len
    return cfg.latent_tokens(resolution, seconds) + (cond_len if stage == "D" else 0)
