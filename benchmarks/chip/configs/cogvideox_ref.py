"""Plain reference of CogVideoX-5B text-to-video as the benchmark serves it.

Written from the published model (arXiv:2408.06072; the CogVideoX-5B
transformer of ``THUDM/CogVideoX-5b``) in straightforward ``jax.numpy``; it
imports nothing of the program under test.  E and C are the references of
``dit_t5_aekl_ref.py`` (the same T5-XXL-width encoder and AE-KL decoder as
sd3 and flux), and so is the arithmetic: every product goes through
``Arith.dot`` and every tensor the configuration keeps in its own precision
(the text and video streams, the predicted noise) through ``Arith.keep``,
in float32 at the highest matmul precision for ``F32`` and in float8 for
the control ``FP8``.

* E: the prompt and the empty prompt (all pad, id 0), in one batch.
* D: the text (226 tokens) projected 4096 -> 3072 and the video patches
  (2 x 2 x 16 channels, in the latent tokens' (row, column, channel) order)
  projected to 3072, both with biases, no position table.  The time
  embedding: sin/cos of the timestep over 3072 channels (cosines first),
  3072 -> 512, SiLU, 512 -> 512.  Per block, twice (before the attention
  and before the MLP): shift, scale and gate for the video and for the
  text from one projection of SiLU(e), one affine LayerNorm (eps 1e-5) for
  both streams; the attention over the text and then the video, q, k and
  v with biases, a per-head affine LayerNorm of q and k (eps 1e-6), the
  video's q and k rotated by 3-D rotary positions (head dims 16 / 24 / 24
  over frame, row and column, interleaved pairs, theta 10000, integer grid
  positions), softmax attention, the output projection with its bias; the
  MLP 3072 -> 12288 -> 3072 with biases and tanh-GELU over both streams.
  Each stream adds its gated part.  After the blocks the final LayerNorm
  over both streams, the video's part through an AdaLN (LayerNorm, then
  shift and scale from SiLU(e)) and 3072 -> 64 with a bias.  Sampling is
  the repo's deterministic DDIM with classifier-free guidance: each step
  the network on the prompt's and on the empty prompt's condition, and
  e_u + s (e_c - e_u).
* C: each latent frame decoded alone, as the program's 2-D decoder does.

Departures from the released model, the same in the program: norm gains
are stored as offsets from 1 (weights are random), the repo's
epsilon-prediction DDIM over a linear beta schedule stands for CogVideoX's
v-prediction schedule, the condition is the T5 encoder's output without a
relative position bias, and C decodes frames alone.  The attention is
computed in blocks of query rows, so the whole score matrix never exists.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import dit_t5_aekl_ref as base
from .dit_t5_aekl_ref import F32, FP8  # noqa: F401  (the arithmetic this module offers)

QK_EPS = 1e-6
THETA = 10000.0
QUERY_BLOCK = 128


def _f32(tree):
    return jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), tree)


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * (1.0 + w) + b


def _linear(ar, x, w, b):
    return ar.dot("...i,io->...o", x, w) + b


def rotary_tables(grid, head_dim):
    """(cos, sin), each (frames*rows*cols, head_dim): for each part of the
    head (frame, row, column), the part's frequencies at the grid's integer
    positions, each repeated for the two dims of its pair; frame-major."""
    def part(dim, n):
        freqs = 1.0 / THETA ** (np.arange(0, dim, 2)[: dim // 2] / dim)
        ang = np.outer(np.arange(n, dtype=np.float64), freqs)
        return np.repeat(ang, 2, axis=1)

    f, h, w = grid
    dims = (head_dim // 4, head_dim // 8 * 3, head_dim // 8 * 3)
    t, r, c = (part(d, n) for d, n in zip(dims, grid))
    ang = np.concatenate([
        np.broadcast_to(t[:, None, None], (f, h, w, dims[0])),
        np.broadcast_to(r[None, :, None], (f, h, w, dims[1])),
        np.broadcast_to(c[None, None, :], (f, h, w, dims[2]))], axis=-1)
    ang = ang.reshape(f * h * w, head_dim)
    return jnp.asarray(np.cos(ang), jnp.float32), jnp.asarray(np.sin(ang), jnp.float32)


def _rotate(x, cos, sin):
    """x (B, S, H, D): each pair (x0, x1) -> (x0 cos - x1 sin, x1 cos + x0 sin)."""
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    turned = jnp.stack([-pairs[..., 1], pairs[..., 0]], axis=-1).reshape(x.shape)
    return x * cos[None, :, None] + turned * sin[None, :, None]


def _attention(ar, q, k, v):
    """Softmax attention of every query over every key, (B, L, H, D), in
    blocks of ``QUERY_BLOCK`` query rows."""
    b, l, h, dh = q.shape
    n = -(-l // QUERY_BLOCK)
    qb = jnp.pad(q, ((0, 0), (0, n * QUERY_BLOCK - l), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(b, n, QUERY_BLOCK, h, dh), 1, 0)

    def one(qq):
        s = ar.dot("bqhd,bkhd->bhqk", qq, k) / np.sqrt(dh)
        return ar.dot("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out = jnp.moveaxis(jax.lax.map(one, qb), 0, 1).reshape(b, n * QUERY_BLOCK, h, dh)
    return out[:, :l]


@functools.partial(jax.jit, static_argnames=("sizes", "ar"))
def _net(sizes, ar, p, x_lat, t, text):
    """One evaluation: the video's predicted noise (B, F*h*w, 64)."""
    heads, eps, grid, _ = sizes
    b, lc = text.shape[:2]
    d = p["x_in"].shape[1]
    dh = d // heads
    cos, sin = rotary_tables(grid, dh)
    emb = _linear(ar, base._timestep_embedding(t, d), p["t_mlp1"].astype(jnp.float32),
                  p["t_mlp1_b"].astype(jnp.float32))
    emb = _linear(ar, jax.nn.silu(emb), p["t_mlp2"].astype(jnp.float32),
                  p["t_mlp2_b"].astype(jnp.float32))
    act = jax.nn.silu(emb)
    video = ar.keep(_linear(ar, x_lat, p["x_in"].astype(jnp.float32),
                            p["x_in_b"].astype(jnp.float32)))
    text = ar.keep(_linear(ar, text, p["text_in"].astype(jnp.float32),
                           p["text_in_b"].astype(jnp.float32)))

    def heads_of(x):
        return x.reshape(b, -1, heads, dh)

    def layer(carry, w):
        text, video = carry
        w = _f32(w)
        m = _linear(ar, act, w["mod1"], w["mod1_b"])[:, None]
        shift, scale, gate, t_shift, t_scale, t_gate = jnp.split(m, 6, axis=-1)
        nv = _layer_norm(video, w["ln1_w"], w["ln1_b"], eps) * (1 + scale) + shift
        nt = _layer_norm(text, w["ln1_w"], w["ln1_b"], eps) * (1 + t_scale) + t_shift
        joint = jnp.concatenate([nt, nv], axis=1)
        q = _layer_norm(heads_of(_linear(ar, joint, w["wq"], w["wq_b"])),
                        w["q_norm_w"], w["q_norm_b"], QK_EPS)
        k = _layer_norm(heads_of(_linear(ar, joint, w["wk"], w["wk_b"])),
                        w["k_norm_w"], w["k_norm_b"], QK_EPS)
        v = heads_of(_linear(ar, joint, w["wv"], w["wv_b"]))
        q = jnp.concatenate([q[:, :lc], _rotate(q[:, lc:], cos, sin)], axis=1)
        k = jnp.concatenate([k[:, :lc], _rotate(k[:, lc:], cos, sin)], axis=1)
        a = _linear(ar, _attention(ar, q, k, v).reshape(b, -1, d), w["wo"], w["wo_b"])
        video = ar.keep(video + gate * a[:, lc:])
        text = ar.keep(text + t_gate * a[:, :lc])

        m = _linear(ar, act, w["mod2"], w["mod2_b"])[:, None]
        shift, scale, gate, t_shift, t_scale, t_gate = jnp.split(m, 6, axis=-1)
        nv = _layer_norm(video, w["ln2_w"], w["ln2_b"], eps) * (1 + scale) + shift
        nt = _layer_norm(text, w["ln2_w"], w["ln2_b"], eps) * (1 + t_scale) + t_shift
        joint = jnp.concatenate([nt, nv], axis=1)
        f = jax.nn.gelu(_linear(ar, joint, w["w_up"], w["w_up_b"]), approximate=True)
        f = _linear(ar, f, w["w_down"], w["w_down_b"])
        video = ar.keep(video + gate * f[:, lc:])
        text = ar.keep(text + t_gate * f[:, :lc])
        return (text, video), None

    (text, video), _ = jax.lax.scan(layer, (text, video), p["layers"])
    top = _f32({k: p[k] for k in ("final_norm_w", "final_norm_b", "final_mod",
                                  "final_mod_b", "out_norm_w", "out_norm_b",
                                  "x_out", "x_out_b")})
    joint = _layer_norm(jnp.concatenate([text, video], axis=1), top["final_norm_w"],
                        top["final_norm_b"], eps)
    shift, scale = jnp.split(_linear(ar, act, top["final_mod"], top["final_mod_b"])[:, None],
                             2, axis=-1)
    video = _layer_norm(joint[:, lc:], top["out_norm_w"], top["out_norm_b"], eps)
    video = video * (1 + scale) + shift
    return ar.keep(_linear(ar, video, top["x_out"], top["x_out_b"]))


@jax.jit
def _ddim_update(x, e_c, e_u, s, ab_t, ab_n):
    e = e_u + s * (e_c - e_u)
    x0 = (x - jnp.sqrt(1.0 - ab_t) * e) / jnp.sqrt(ab_t)
    return jnp.sqrt(ab_n) * x0 + jnp.sqrt(1.0 - ab_n) * e


def diffuse(sizes, ar, steps, p, cond, latent_shape, key):
    """cond (2, 226, 4096), the prompt's and the empty prompt's -> (latents
    after ``steps`` guided DDIM steps, the starting noise scaled by
    1 / sqrt(alpha_bar) of the first step).  The two conditions run one
    after the other, each as a batch of one."""
    guidance = sizes[3]
    x = jax.random.normal(key, latent_shape, jnp.float32)
    sched = base.ddim_schedule(steps)
    noise_only = x / jnp.sqrt(sched[0][1])
    cond = cond.astype(jnp.float32)
    for t, ab_t, ab_n in sched:
        tb = jnp.full((x.shape[0],), t, jnp.float32)
        e_c = _net(sizes, ar, p, x, tb, cond[:1])
        e_u = _net(sizes, ar, p, x, tb, cond[1:])
        x = _ddim_update(x, e_c, e_u, jnp.float32(guidance), ab_t, ab_n)
    return x, noise_only


def encode(sizes, ar, p, tokens):
    """tokens (1, 226) -> the condition (2, 226, 4096): the prompt's, then
    the empty prompt's."""
    both = jnp.concatenate([tokens, jnp.zeros_like(tokens)])
    return base.encode(sizes, ar, p, both)


def decode(sizes, ar, p, latents):
    """latents (1, F*h*w, 64) -> frames (F, 16h, 16w, 3), each latent
    frame decoded alone; ``sizes`` are those of one frame."""
    (_, h, w), *rest = sizes
    one = ((1, h, w), *rest)
    frames = latents.reshape(-1, 1, h * w, latents.shape[-1])
    return jnp.concatenate([base.decode(one, ar, p, z) for z in frames])
