"""The CogVideoX family (text-to-video): what differs from ``dit_t5_aekl``.

E and C are sd3's and flux's (a T5-XXL-width encoder, the AE-KL decoder);
D is the cogvideox block of ``repro.models.diffusion`` with classifier-free
guidance.  This module overrides only what that changes:

* the latent grid of a class, from the program's ``PipelineConfig``: a
  clip of ``frames`` frames at ``frame_width`` / ``frame_height``, so the
  480-px class is 13 x 30 x 45 latent tokens;
* the stage functions: E encodes the prompt and the empty prompt in one
  batch inside ``stage_E_<len>``, D places its rotary positions on the
  grid, C decodes every latent frame;
* the work: E and D run on the guided batch of two, C on every frame;
  ``attention_flops`` and ``qk_rope_bytes`` feed the cell's own per-layer
  metrics;
* the weights' scales, no leaf drawn as zero (a zero modulation would make
  every block the identity and the check empty);
* the check, against ``cogvideox_ref.py``, and its float8 control.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from . import dit_t5_aekl

WRONG = dit_t5_aekl.WRONG
OFFSET_STD = 0.1      # every bias and norm-gain offset but q's and k's gains
# q's and k's norm gains: with offsets of 0.1 the scores spread by about
# one and the attention is nearly uniform, so where a token sits hardly
# moves the result; gains of 1 +- 1.7 make it select, as trained attention
# does, and a wrong rotation shows in the check
QK_GAIN_STD = 1.0


class Family(dit_t5_aekl.Family):
    def __init__(self, cfg: dict):
        super().__init__(cfg)
        self.rows = 1 if cfg["guidance_scale"] == 1.0 else 2
        self._pcfg = None

    # --- the program ------------------------------------------------------

    def program_config(self):
        if self._pcfg is None:
            c = self.cfg
            base = super().program_config()
            dit = dataclasses.replace(
                base.dit, block="cogvideox", norm_eps=c["dit_norm_eps"],
                guidance_scale=c["guidance_scale"])
            self._pcfg = dataclasses.replace(
                base, dit=dit, max_cond_len=self.cond_len, clip_frames=c["frames"],
                aspect=c["frame_width"] / c["frame_height"])
        return self._pcfg

    def grid(self, res: int):
        return self.program_config().latent_grid(res)

    def latent_tokens(self, res: int) -> int:
        return math.prod(self.grid(res))

    def stage_fns(self, pcfg, res: int):
        from repro.models import pipeline as pl

        grid = pcfg.latent_grid(res)
        lat = (1, math.prod(grid), self.cfg["dit_latent_dim"])

        def e(p, tokens):
            return pl.encode(pcfg, {"encode": p}, tokens)

        def d(p, cond, key):
            return pl.diffuse(pcfg, {"diffuse": p}, cond, lat, key, grid=grid)

        def c(p, latents):
            return pl.decode(pcfg, {"decode": p}, latents, grid)

        return {"E": (f"stage_E_{self.cond_len}", e),
                "D": (f"stage_D_{res}", d),
                "C": (f"stage_C_{res}", c)}

    # --- weights ------------------------------------------------------------

    def weight_std(self, path: tuple, shape: tuple) -> float:
        """As sd3's and flux's, and for D's biases (``<weight>_b``) and norm
        gains (``<norm>_w``, offsets from 1) ``OFFSET_STD``, for q's and k's
        gains ``QK_GAIN_STD``."""
        if path[0] == "diffuse" and path[-1] in ("q_norm_w", "k_norm_w"):
            return QK_GAIN_STD
        if path[0] == "diffuse" and path[-1].endswith(("_b", "_w")):
            return OFFSET_STD
        return super().weight_std(path, shape)

    # --- work, from widths and shapes --------------------------------------

    def flops(self, stage: str, res: int) -> float:
        """As ``dit_t5_aekl``: products only, so a floor.  E encodes two
        prompts; D runs the guided batch of two each step, the text's
        projection in every evaluation and the modulations on one vector
        per sample; C decodes every frame of the clip."""
        c = self.cfg
        if stage == "E":
            return self.rows * super().flops("E", res)
        if stage == "D":
            d, e, lat = c["dit_d_model"], c["dit_time_embed_dim"], c["dit_latent_dim"]
            lx = self.latent_tokens(res)
            l = lx + self.cond_len
            layer = 2 * l * (4 * d * d + 2 * d * c["dit_d_ff"]) + 4 * l * l * d
            layer += 2 * e * 12 * d                       # two modulations
            step = c["dit_layers"] * layer
            step += 2 * lx * lat * d * 2                  # patches in, noise out
            step += 2 * self.cond_len * c["encoder_d_model"] * d
            step += 2 * (d * e + e * e) + 2 * e * 2 * d   # time MLP, final AdaLN
            return self.rows * self.steps * step
        if stage == "C":
            f, h, w = self.grid(res)
            # the square count of a res x res image is over (res / 8)^2 pixels
            # per level; a frame is 2h x 2w latent pixels, 2h = res / 8
            return f * (w / h) * super().flops("C", res)
        raise ValueError(stage)

    def bytes(self, stage: str, res: int, shapes) -> float:
        """Weights once, inputs and outputs, as ``dit_t5_aekl``, with the
        guided batch's condition and every frame."""
        c = self.cfg
        import jax
        tree = {"E": "encode", "D": "diffuse", "C": "decode"}[stage]
        leaves = jax.tree_util.tree_flatten_with_path(shapes[tree])[0]
        w = sum(int(np.prod(x.shape)) * x.dtype.itemsize for p, x in leaves
                if getattr(p[-1], "key", None) != "embed")
        item = np.dtype(c["dtype"]).itemsize
        cond = self.rows * self.cond_len * c["encoder_d_model"] * item
        lat = self.latent_tokens(res) * c["dit_latent_dim"] * 4
        if stage == "E":
            return w + 2 * cond + self.cond_len * 4
        if stage == "D":
            return w + cond + lat
        f, h, w_lat = self.grid(res)
        return w + lat + f * (16 * h) * (16 * w_lat) * 3 * 4

    def attention_flops(self, res: int) -> float:
        """D's attention core for one request: ``4 l^2 d`` per layer, step
        and guided sample (scores and weighted sum; the softmax left out)."""
        c = self.cfg
        l = self.latent_tokens(res) + self.cond_len
        return 4.0 * l * l * c["dit_d_model"] * c["dit_layers"] * self.steps * self.rows

    def qk_rope_bytes(self, res: int) -> float:
        """Bytes the q/k norms and rotary positions must move for one
        request: q and k read and written once in the program's precision,
        and the (cos, sin) tables in float32 read once, per layer and step,
        for the guided batch."""
        c = self.cfg
        l = self.latent_tokens(res) + self.cond_len
        d, dh = c["dit_d_model"], c["dit_d_model"] // c["dit_heads"]
        item = np.dtype(c["dtype"]).itemsize
        per = 2 * 2 * self.rows * l * d * item + 2 * l * dh * 4
        return per * c["dit_layers"] * self.steps

    # --- the comparison -----------------------------------------------------

    def reference_sizes(self, res: int):
        c = self.cfg
        f, h, w = self.grid(res)
        enc = (c["encoder_heads"], c["encoder_head_dim"], 1e-6, 10000.0)
        dit = (c["dit_heads"], c["dit_norm_eps"], (f, h, w), c["guidance_scale"])
        dec = ((1, h, w), c["decoder_upsamples"], c["decoder_res_blocks"],
               c["decoder_latent_channels"])
        return enc, dit, dec

    def check(self, params, items, control=False):
        """Worst relative error of each stage over the sampled ``items``, as
        ``dit_t5_aekl.Family.check`` computes it, against
        ``cogvideox_ref.py``: E's condition of both prompts, D's latents
        after the guided steps, C's frames."""
        import jax.numpy as jnp

        from . import cogvideox_ref as ref

        worst = {"e_rel_err": 0.0, "d_rel_err": 0.0, "c_rel_err": 0.0}
        for it in items:
            enc, dit, dec = self.reference_sizes(it["res"])
            if control:
                it = self._served_by(ref.FP8, params, it)
            cond = jnp.asarray(it["cond"])
            lat = jnp.asarray(it["latents"])
            d_ref, d_noise = ref.diffuse(dit, ref.F32, self.steps, params["diffuse"],
                                         cond, lat.shape, it["key"])
            got = {
                "e_rel_err": (ref.encode(enc, ref.F32, params["encode"], it["tokens"]),
                              cond, 0.0),
                "d_rel_err": (d_ref, lat, d_noise),
                "c_rel_err": (ref.decode(dec, ref.F32, params["decode"], lat),
                              it["image"], 0.0),
            }
            for k, (want, have, base) in sorted(got.items()):
                want = np.asarray(want, np.float64)
                have = np.asarray(have, np.float64)
                if have.shape != want.shape or not np.isfinite(have).all():
                    worst[k] = WRONG
                    continue
                scale = np.linalg.norm(want - np.asarray(base, np.float64))
                worst[k] = max(worst[k], float(np.linalg.norm(have - want) / scale))
        return worst

    def _served_by(self, arith, params, it):
        """``it`` with its outputs made by the reference in ``arith``."""
        from . import cogvideox_ref as ref

        enc, dit, dec = self.reference_sizes(it["res"])
        cond = ref.encode(enc, arith, params["encode"], it["tokens"])
        shape = (1, self.latent_tokens(it["res"]), self.cfg["dit_latent_dim"])
        lat, _ = ref.diffuse(dit, arith, self.steps, params["diffuse"], cond,
                             shape, it["key"])
        image = np.asarray(ref.decode(dec, arith, params["decode"], lat))
        return dict(it, cond=cond, latents=lat, image=image)
