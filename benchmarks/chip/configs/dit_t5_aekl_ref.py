"""Plain reference of the DiT + T5-encoder + AE-KL pipeline (sd3, flux).

Written from the architecture as the configuration files state it, in
straightforward ``jax.numpy``; it imports nothing of the program under test.
It reads the weights the benchmark made (they are benchmark data) and keeps
them in bfloat16 on the device; each layer's weights are widened inside the
layer, so the reference fits next to them on one chip.

Stages:

* E: a bidirectional T5-style encoder: token embedding, per layer an RMS
  norm with weight ``1 + w``, rotary positions on queries and keys, full
  softmax attention, an output projection, an RMS norm and a SwiGLU MLP,
  each added to the residual stream; a final RMS norm.
* D: a single-stream DiT: condition and latent tokens projected and joined
  (condition first), a sin/cos position code with learned frequencies, a
  timestep embedding through a two-layer SiLU MLP, per layer AdaLN
  modulation (scale, shift and gate for attention and for a tanh-GELU MLP)
  from that one vector per sample, a modulated final norm and an output
  projection of the latent positions.  Sampling is deterministic DDIM
  (eta 0) over a linear beta schedule of 1000 steps from 1e-4 to 0.02,
  starting from ``jax.random.normal(key)``.
* C: an AE-KL style decoder: patches of 2x2 unfolded to the latent grid,
  a 3x3 convolution in, three levels of (SiLU, nearest 2x upsampling,
  convolution, residual SiLU-convolution blocks), SiLU, a 3x3 convolution
  out and ``tanh``.

Every matrix product and convolution goes through ``Arith.dot`` /
``Arith.conv``, and every tensor that the configuration holds in its own
precision between operations (the residual streams, the condition, the
predicted noise, the decoder's activations) through ``Arith.keep``.
``F32`` computes in float32 at the highest matmul precision and keeps
float32: the reference.  ``FP8`` rounds each product's operands and each
kept tensor to float8 e4m3 (one scale per tensor) and accumulates in
float32: the control, one precision below the bfloat16 the
configurations state.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32_MAX_FP8 = 448.0


class Arith:
    """How products are computed: ``quant`` rounds an operand, or not."""

    def __init__(self, name: str, fp8: bool):
        self.name = name
        self.fp8 = fp8

    def q(self, x):
        x = x.astype(jnp.float32)
        if not self.fp8:
            return x
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F32_MAX_FP8
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s

    def keep(self, x):
        return self.q(x)

    def dot(self, eq, a, b):
        return jnp.einsum(eq, self.q(a), self.q(b),
                          precision=jax.lax.Precision.HIGHEST)

    def conv(self, x, w):
        return jax.lax.conv_general_dilated(
            self.q(x), self.q(w), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)


F32 = Arith("f32", fp8=False)
FP8 = Arith("fp8", fp8=True)


def _f32(tree):
    return jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), tree)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _attention(ar, q, k, v, block=1024):
    """Softmax attention over all keys, in blocks of query rows."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    outs = []
    for i in range(0, q.shape[1], block):
        s = ar.dot("bqhd,bkhd->bhqk", q[:, i:i + block], k) * scale
        p = jax.nn.softmax(s, axis=-1)
        outs.append(ar.dot("bhqk,bkhd->bqhd", p, v))
    return jnp.concatenate(outs, axis=1)


def _rope(x, theta):
    """Rotary positions: halves (x1, x2) rotated by pos * theta^(-2i/dh)."""
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# --- E -----------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("sizes", "ar"))
def encode(sizes, ar, p, tokens):
    """tokens (B, L) int32 -> condition (B, L, d), float32."""
    heads, dh, eps, theta = sizes
    x = ar.keep(p["embed"][tokens])
    b, l, _ = x.shape

    def layer(x, w):
        w = _f32(w)
        h = _rms(x, eps) * (1.0 + w["ln1"])
        q = _rope(ar.dot("bld,de->ble", h, w["wq"]).reshape(b, l, heads, dh), theta)
        k = _rope(ar.dot("bld,de->ble", h, w["wk"]).reshape(b, l, heads, dh), theta)
        v = ar.dot("bld,de->ble", h, w["wv"]).reshape(b, l, heads, dh)
        a = _attention(ar, q, k, v).reshape(b, l, heads * dh)
        x = ar.keep(x + ar.dot("ble,ed->bld", a, w["wo"]))
        h = _rms(x, eps) * (1.0 + w["ln2"])
        g = jax.nn.silu(ar.dot("bld,df->blf", h, w["w_gate"]))
        u = ar.dot("bld,df->blf", h, w["w_up"])
        return ar.keep(x + ar.dot("blf,fd->bld", g * u, w["w_down"])), None

    for block in p["blocks"]:
        for stacked in block:
            x, _ = jax.lax.scan(layer, x, stacked)
    return ar.keep(_rms(x, eps) * (1.0 + p["final_norm"].astype(jnp.float32)))


# --- D -----------------------------------------------------------------------

def _timestep_embedding(t, dim):
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    a = t[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(a), jnp.sin(a)], axis=-1)


def _dit(sizes, ar, p, x_lat, t, c):
    """One network evaluation: predicted noise (B, Lx, latent)."""
    heads, time_dim, eps = sizes
    b, lx, _ = x_lat.shape
    lc = c.shape[1]
    d = p["x_in"].shape[1]
    dh = d // heads
    x = jnp.concatenate([c, ar.dot("blc,cd->bld", x_lat, p["x_in"])], axis=1)
    l = lx + lc
    pos = jnp.arange(l, dtype=jnp.float32)[:, None]
    pf = p["pos_freq"].astype(jnp.float32)
    x = ar.keep(x + jnp.concatenate([jnp.sin(pos * pf[0]), jnp.cos(pos * pf[1])], -1)[None])
    tc = ar.dot("be,ed->bd", _timestep_embedding(t, time_dim), p["t_mlp1"])
    tc = ar.dot("bd,de->be", jax.nn.silu(tc), p["t_mlp2"])

    def layer(x, w):
        w = _f32(w)
        m = ar.dot("bd,de->be", tc, w["mod"]).reshape(b, 6, 1, d)
        h = _rms(x, eps) * (1.0 + m[:, 0]) + m[:, 1]
        q = ar.dot("bld,de->ble", h, w["wq"]).reshape(b, l, heads, dh)
        k = ar.dot("bld,de->ble", h, w["wk"]).reshape(b, l, heads, dh)
        v = ar.dot("bld,de->ble", h, w["wv"]).reshape(b, l, heads, dh)
        a = _attention(ar, q, k, v).reshape(b, l, d)
        x = ar.keep(x + m[:, 2] * ar.dot("bld,de->ble", a, w["wo"]))
        h = _rms(x, eps) * (1.0 + m[:, 3]) + m[:, 4]
        f = jax.nn.gelu(ar.dot("bld,df->blf", h, w["w_up"]), approximate=True)
        return ar.keep(x + m[:, 5] * ar.dot("blf,fd->bld", f, w["w_down"])), None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    fm = ar.dot("bd,de->be", tc, p["final_mod"]).reshape(b, 2, 1, d)
    x = _rms(x, eps) * (1.0 + fm[:, 0]) + fm[:, 1]
    return ar.keep(ar.dot("bld,dc->blc", x[:, lc:], p["x_out"]))


@functools.partial(jax.jit, static_argnames=("sizes", "ar"))
def _cond_in(sizes, ar, p, cond):
    return ar.keep(ar.dot("blc,cd->bld", cond, p["cond_in"]))


@functools.partial(jax.jit, static_argnames=("sizes", "ar"))
def _ddim_step(sizes, ar, p, x, c, t, ab_t, ab_n):
    e = _dit(sizes, ar, p, x, jnp.full((x.shape[0],), t, jnp.float32), c)
    x0 = (x - jnp.sqrt(1.0 - ab_t) * e) / jnp.sqrt(ab_t)
    return jnp.sqrt(ab_n) * x0 + jnp.sqrt(1.0 - ab_n) * e


def ddim_schedule(steps):
    """[(t, alpha_bar[t], alpha_bar[t_next])] for each of ``steps`` steps;
    alpha_bar after the last step is 1."""
    betas = jnp.linspace(1e-4, 0.02, 1000, dtype=jnp.float32)
    alpha_bar = jnp.cumprod(1.0 - betas)
    ts = [int(v) for v in jnp.linspace(999, 0, steps).astype(jnp.int32)]
    nxt = [alpha_bar[t] for t in ts[1:]] + [jnp.float32(1.0)]
    return [(jnp.float32(t), alpha_bar[t], n) for t, n in zip(ts, nxt)]


def diffuse(sizes, ar, steps, p, cond, latent_shape, key):
    """cond (B, Lc, cond_dim) -> (latents after ``steps`` DDIM steps, the
    latents the same steps give with no predicted noise).  The second is
    the starting noise scaled by 1 / sqrt(alpha_bar) of the first step:
    what the network contributes is the difference."""
    x = jax.random.normal(key, latent_shape, jnp.float32)
    sched = ddim_schedule(steps)
    noise_only = x / jnp.sqrt(sched[0][1])
    c = _cond_in(sizes, ar, p, cond.astype(jnp.float32))
    for t, ab_t, ab_n in sched:
        x = _ddim_step(sizes, ar, p, x, c, t, ab_t, ab_n)
    return x, noise_only


# --- C -----------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("sizes", "ar"))
def decode(sizes, ar, p, latents):
    """latents (B, F*h*w, 4*cl) -> pixels (B*F, 16h, 16w, 3) in [-1, 1]."""
    grid, levels, res_blocks, cl = sizes
    f, h, w = grid
    b = latents.shape[0]
    z = latents.astype(jnp.float32).reshape(b * f, h, w, 2, 2, cl)
    z = z.transpose(0, 1, 3, 2, 4, 5).reshape(b * f, 2 * h, 2 * w, cl)
    x = ar.keep(ar.conv(z, p["conv_in"]))
    for i in range(levels):
        x = jax.nn.silu(x)
        x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
        x = ar.keep(ar.conv(x, p[f"up{i}_in"]))
        for r in range(res_blocks):
            x = ar.keep(x + ar.conv(jax.nn.silu(x), p[f"up{i}_res{r}"]))
    return jnp.tanh(ar.conv(jax.nn.silu(x), p["conv_out"]))
