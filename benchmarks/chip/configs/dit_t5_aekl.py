"""The DiT + T5-encoder + AE-KL family (sd3, flux): program, weights, work.

A configuration file names this family under ``family``; the harness finds
this module by that name.  It holds what belongs to the family and not to
the harness:

* ``Family.program_config``: the program's ``PipelineConfig`` with every
  size taken from the configuration file;
* ``Family.stage_fns``: the program's stage entry points (``encode``,
  ``diffuse``, ``decode`` of ``repro.models.pipeline``) for one shape, under
  stable names (``stage_E_<len>``, ``stage_D_<res>``, ``stage_C_<res>``) that
  the trace reduction finds them by;
* ``Family.weight_spec``: the scale of every weight leaf, from which the
  harness draws the weights on the device;
* ``flops`` and ``bytes``: the work each stage needs, computed from widths
  and request shapes;
* ``check``: each sampled request's stages against the plain reference in
  ``dit_t5_aekl_ref.py``, for the program or for the control in its place.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

PATCH = 2           # latent tokens are 2x2 patches of an 8x-VAE grid
VAE_SCALE = 8
WRONG = 1e30          # the error of an output of the wrong shape, or not finite


class Family:
    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.cond_len = cfg["cond_len"]
        self.steps = cfg["num_steps"]

    # --- the program ------------------------------------------------------

    def program_config(self):
        import jax.numpy as jnp

        import repro.configs as rc
        c = self.cfg
        dtype = jnp.dtype(c["dtype"])
        base = rc.get(c["pipeline"])
        enc = dataclasses.replace(
            base.encoder, num_layers=c["encoder_layers"],
            d_model=c["encoder_d_model"], num_heads=c["encoder_heads"],
            num_kv_heads=c["encoder_heads"], head_dim=c["encoder_head_dim"],
            d_ff=c["encoder_d_ff"], vocab_size=c["encoder_vocab"], dtype=dtype)
        dit = dataclasses.replace(
            base.dit, num_layers=c["dit_layers"], d_model=c["dit_d_model"],
            num_heads=c["dit_heads"], d_ff=c["dit_d_ff"],
            latent_dim=c["dit_latent_dim"], cond_dim=c["encoder_d_model"],
            time_embed_dim=c["dit_time_embed_dim"], dtype=dtype)
        dec = dataclasses.replace(
            base.decoder, latent_channels=c["decoder_latent_channels"],
            base_channels=c["decoder_base_channels"],
            num_upsamples=c["decoder_upsamples"],
            res_blocks=c["decoder_res_blocks"], dtype=dtype)
        return dataclasses.replace(base, encoder=enc, dit=dit, decoder=dec,
                                   num_steps=self.steps)

    def latent_side(self, res: int) -> int:
        return res // (VAE_SCALE * PATCH)

    def latent_tokens(self, res: int) -> int:
        return self.latent_side(res) ** 2

    def stage_fns(self, pcfg, res: int):
        """{stage: (name, fn)} for one request shape.  ``fn`` takes the
        stage's own weights and the request's device inputs."""
        from repro.models import pipeline as pl

        lat = (1, self.latent_tokens(res), self.cfg["dit_latent_dim"])
        grid = (1, self.latent_side(res), self.latent_side(res))

        def e(p, tokens):
            return pl.encode(pcfg, {"encode": p}, tokens)

        def d(p, cond, key):
            return pl.diffuse(pcfg, {"diffuse": p}, cond, lat, key)

        def c(p, latents):
            return pl.decode(pcfg, {"decode": p}, latents, grid)

        return {"E": (f"stage_E_{self.cond_len}", e),
                "D": (f"stage_D_{res}", d),
                "C": (f"stage_C_{res}", c)}

    # --- weights ------------------------------------------------------------

    def param_shapes(self, pcfg):
        """The program's weight tree as shapes: {"encode", "diffuse",
        "decode"}.  E reads no LM head, so none is made."""
        import jax

        from repro.models import diffusion, transformer
        key = jax.ShapeDtypeStruct((2,), np.uint32)
        enc = jax.eval_shape(lambda k: transformer.init(pcfg.encoder, k), key)
        enc = {k: v for k, v in enc.items() if k != "lm_head"}
        return {
            "encode": enc,
            "diffuse": jax.eval_shape(lambda k: diffusion.init(pcfg.dit, k), key),
            "decode": jax.eval_shape(
                lambda k: diffusion.init_decoder(pcfg.decoder, k), key),
        }

    def weight_std(self, path: tuple, shape: tuple) -> float:
        """Standard deviation of one weight leaf.  Projections read as
        1/sqrt(fan-in); the projections that write into a residual stream
        are scaled down by the number of writers, so the stream stays of
        order one through the depth; norm weights are small offsets of the
        ``1 + w`` gain.  The decoder's first convolution is scaled by the
        size of the latents DDIM ends with, and its last one so that the
        ``tanh`` works in its graded range, where a wrong pixel shows."""
        c = self.cfg
        name = path[-1]
        stage = path[0]
        if name in ("ln1", "ln2", "final_norm"):
            return 0.1
        if name == "embed":
            return 1.0
        if stage == "decode":
            fan_in = int(np.prod(shape[:-1]))
            std = 1.0 / math.sqrt(fan_in)
            if name == "conv_in":
                return std / self.final_latent_rms()
            if "_res" in name:
                return std / math.sqrt(2 * c["decoder_res_blocks"])
            if name == "conv_out":
                return std * c["decoder_out_gain"]
            return std
        fan_in = shape[-2]
        std = 1.0 / math.sqrt(fan_in)
        if name in ("wo", "w_down"):
            depth = c["encoder_layers"] if stage == "encode" else c["dit_layers"]
            return std / math.sqrt(2 * depth)
        return std

    def final_latent_rms(self) -> float:
        """RMS of DDIM's result when the predicted noise is of unit size and
        independent of the latents, as with random weights: the variance
        recursion of x' = a x + b e over this schedule."""
        betas = np.linspace(1e-4, 0.02, 1000, dtype=np.float32)
        ab = np.cumprod(1.0 - betas.astype(np.float64))
        ts = np.linspace(999, 0, self.steps).astype(np.int32)
        var = 1.0
        for i, t in enumerate(ts):
            ab_n = ab[ts[i + 1]] if i + 1 < len(ts) else 1.0
            a = math.sqrt(ab_n / ab[t])
            b = math.sqrt(1 - ab_n) - a * math.sqrt(1 - ab[t])
            var = a * a * var + b * b
        return math.sqrt(var)

    # --- work, from widths and shapes --------------------------------------

    def flops(self, stage: str, res: int) -> float:
        """Operations the stage needs for one call: two per multiply-add of
        each projection, attention's two products, and each convolution.
        Norms, activations and the DDIM update are left out, so the count
        is a floor of what the program computes."""
        c = self.cfg
        if stage == "E":
            l, d = self.cond_len, c["encoder_d_model"]
            hd = c["encoder_heads"] * c["encoder_head_dim"]
            per = 2 * l * (4 * d * hd + 3 * d * c["encoder_d_ff"]) + 4 * l * l * hd
            return c["encoder_layers"] * per
        if stage == "D":
            d, lat = c["dit_d_model"], c["dit_latent_dim"]
            lx = self.latent_tokens(res)
            l = lx + self.cond_len
            layer = 2 * l * (4 * d * d + 2 * d * c["dit_d_ff"]) + 4 * l * l * d
            layer += 2 * d * 6 * d                   # modulation: one vector
            step = c["dit_layers"] * layer
            step += 2 * lx * lat * d * 2             # latents in and out
            step += 2 * (c["dit_time_embed_dim"] * d + d * d) + 2 * d * 2 * d
            once = 2 * self.cond_len * c["encoder_d_model"] * d   # condition
            return self.steps * step + once
        if stage == "C":
            total = 0.0
            for (h, cin, cout) in self._convs(res):
                total += 2 * h * h * 9 * cin * cout
            return total
        raise ValueError(stage)

    def _convs(self, res: int):
        """(side, in channels, out channels) of each decoder convolution."""
        c = self.cfg
        ch = c["decoder_base_channels"]
        side = res // VAE_SCALE
        out = [(side, c["decoder_latent_channels"], ch)]
        for i in range(c["decoder_upsamples"]):
            side *= 2
            cin, cout = max(ch // 2 ** i, 32), max(ch // 2 ** (i + 1), 32)
            out.append((side, cin, cout))
            out += [(side, cout, cout)] * c["decoder_res_blocks"]
        out.append((side, max(ch // 2 ** c["decoder_upsamples"], 32), 3))
        return out

    def bytes(self, stage: str, res: int, shapes) -> float:
        """Bytes the stage must move through HBM for one call: its weights
        once (E: the embedding rows of the prompt only), its inputs and its
        outputs."""
        c = self.cfg
        tree = {"E": "encode", "D": "diffuse", "C": "decode"}[stage]
        import jax
        leaves = jax.tree_util.tree_flatten_with_path(shapes[tree])[0]
        w = sum(int(np.prod(x.shape)) * x.dtype.itemsize for p, x in leaves
                if getattr(p[-1], "key", None) != "embed")
        item = np.dtype(c["dtype"]).itemsize
        if stage == "E":
            rows = self.cond_len * c["encoder_d_model"] * item
            return w + 2 * rows + self.cond_len * 4
        lat = self.latent_tokens(res) * c["dit_latent_dim"] * 4
        if stage == "D":
            cond = self.cond_len * c["encoder_d_model"] * item
            return w + cond + lat
        return w + lat + res * res * 3 * 4

    # --- the comparison -----------------------------------------------------

    def reference_sizes(self, res: int):
        c = self.cfg
        enc = (c["encoder_heads"], c["encoder_head_dim"], 1e-6, 10000.0)
        dit = (c["dit_heads"], c["dit_time_embed_dim"], 1e-6)
        grid = (1, self.latent_side(res), self.latent_side(res))
        dec = (grid, c["decoder_upsamples"], c["decoder_res_blocks"],
               c["decoder_latent_channels"])
        return enc, dit, dec

    def check(self, params, items, control=False):
        """Worst relative error of each stage over the sampled ``items``.

        Each item holds the request's device inputs (``tokens``, ``key``)
        and what the timed path produced (``cond``, ``latents``, ``image``
        on the host).  With ``control``, the control takes the program's
        place: the reference computed in float8 serves each request, E -> D
        -> C from the same inputs, and its outputs are judged in the same
        way.  Each stage of the float32 reference takes the input that the
        judged stage took, so a fault shows in the stage that made it.  An
        error is the norm of the difference from the reference over the
        norm of the reference; for D, over the norm of what the network
        added to the scaled starting noise, which is most of DDIM's result
        and would hide a wrong step."""
        import jax.numpy as jnp

        from . import dit_t5_aekl_ref as ref

        worst = {"e_rel_err": 0.0, "d_rel_err": 0.0, "c_rel_err": 0.0}
        for it in items:
            enc, dit, dec = self.reference_sizes(it["res"])
            if control:
                it = self._served_by(ref.FP8, params, it)
            cond = jnp.asarray(it["cond"])
            lat = jnp.asarray(it["latents"])
            d_ref, d_noise = ref.diffuse(dit, ref.F32, self.steps,
                                         params["diffuse"], cond, lat.shape,
                                         it["key"])
            got = {
                "e_rel_err": (ref.encode(enc, ref.F32, params["encode"],
                                         it["tokens"]), cond, 0.0),
                "d_rel_err": (d_ref, lat, d_noise),
                "c_rel_err": (ref.decode(dec, ref.F32, params["decode"], lat),
                              it["image"], 0.0),
            }
            for k, (want, have, base) in sorted(got.items()):
                want = np.asarray(want, np.float64)
                have = np.asarray(have, np.float64)
                if have.shape != want.shape or not np.isfinite(have).all():
                    worst[k] = WRONG     # no number: above every limit
                    continue
                scale = np.linalg.norm(want - np.asarray(base, np.float64))
                err = np.linalg.norm(have - want) / scale
                worst[k] = max(worst[k], float(err))
        return worst

    def _served_by(self, arith, params, it):
        """``it`` with its outputs made by the reference in ``arith``."""
        from . import dit_t5_aekl_ref as ref

        enc, dit, dec = self.reference_sizes(it["res"])
        cond = ref.encode(enc, arith, params["encode"], it["tokens"])
        shape = (1, self.latent_tokens(it["res"]), self.cfg["dit_latent_dim"])
        lat, _ = ref.diffuse(dit, arith, self.steps, params["diffuse"], cond,
                             shape, it["key"])
        image = np.asarray(ref.decode(dec, arith, params["decode"], lat))
        return dict(it, cond=cond, latents=lat, image=image)
