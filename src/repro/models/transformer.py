"""Generic decoder/encoder transformer over heterogeneous layer segments.

One implementation serves every assigned architecture: the config's
``layer_pattern`` is tiled and merged into homogeneous *segments*, each
executed with a single ``lax.scan`` over stacked per-layer params — this
keeps HLO size O(#segments), not O(#layers), which bounds both compile time
and the SPMD partitioner's work on the 512-device dry-run mesh.

Three entry modes share the layer code:
  * ``forward``  — training / encoder pass, no cache.
  * ``prefill``  — full-sequence pass that fills a KV/state cache.
  * ``decode``   — single-token step against the cache (``serve_step``).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models import common, moe as moe_lib, scopes, ssm as ssm_lib
from repro.models.common import (ATTN, ATTN_BIDIR, ATTN_CHUNKED, ATTN_KINDS,
                                 ATTN_LOCAL, FFN_MOE, MAMBA2, RWKV6, Array,
                                 ModelConfig, dense_init, embed_init)

PyTree = Any

FFN_NONE = "none"

# Optional PartitionSpec for the residual stream during training
# (Megatron-style sequence sharding; set by the launcher before lowering).
# Saved scan-carry residuals then shard over seq x batch instead of batch
# only, cutting the dominant peak-memory term by the model-axis size.
_ACTIVATION_SPEC = None


def set_activation_sharding(spec) -> None:
    global _ACTIVATION_SPEC
    _ACTIVATION_SPEC = spec


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------

def _init_attn_layer(cfg: ModelConfig, key: Array) -> dict:
    d, dh = cfg.d_model, cfg.resolved_head_dim
    ks = common.split_keys(key, 4)
    scale_o = 1.0 / max(1, cfg.num_layers) ** 0.5
    p = {
        "ln1": jnp.zeros((d,), jnp.float32),
        "wq": dense_init(ks[0], (d, cfg.num_heads * dh), cfg.dtype),
        "wk": dense_init(ks[1], (d, cfg.num_kv_heads * dh), cfg.dtype),
        "wv": dense_init(ks[2], (d, cfg.num_kv_heads * dh), cfg.dtype),
        "wo": dense_init(ks[3], (cfg.num_heads * dh, d), cfg.dtype, scale=scale_o),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((dh,), jnp.float32)
        p["k_norm"] = jnp.zeros((dh,), jnp.float32)
    return p


def _init_ffn(cfg: ModelConfig, ffn: str, key: Array) -> dict:
    if ffn == FFN_NONE:
        return {}
    d = cfg.d_model
    if ffn == FFN_MOE:
        return {"ln2": jnp.zeros((d,), jnp.float32), "moe": moe_lib.init_moe(cfg, key)}
    ks = common.split_keys(key, 3)
    scale_o = 1.0 / max(1, cfg.num_layers) ** 0.5
    return {
        "ln2": jnp.zeros((d,), jnp.float32),
        "w_gate": dense_init(ks[0], (d, cfg.d_ff), cfg.dtype),
        "w_up": dense_init(ks[1], (d, cfg.d_ff), cfg.dtype),
        "w_down": dense_init(ks[2], (cfg.d_ff, d), cfg.dtype, scale=scale_o),
    }


def _init_layer(cfg: ModelConfig, kind: Tuple[str, str], key: Array) -> dict:
    mixer, ffn = kind
    k1, k2 = jax.random.split(key)
    if mixer in ATTN_KINDS:
        p = _init_attn_layer(cfg, k1)
    elif mixer == MAMBA2:
        p = {"ln1": jnp.zeros((cfg.d_model,), jnp.float32),
             "mamba": ssm_lib.init_mamba2(cfg, k1)}
    elif mixer == RWKV6:
        p = {"ln1": jnp.zeros((cfg.d_model,), jnp.float32),
             "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
             "rwkv": ssm_lib.init_rwkv6(cfg, k1)}
    else:
        raise ValueError(f"unknown mixer {mixer!r}")
    if mixer != RWKV6:  # rwkv6 carries its own channel-mix as the ffn
        p.update(_init_ffn(cfg, ffn, k2))
    return p


def init(cfg: ModelConfig, key: Array) -> dict:
    """Build the full parameter pytree.

    ``params["blocks"][bi][pi]`` holds the stacked (repeat, ...) params of
    pattern position ``pi`` in scan-plan block ``bi`` (see
    ``ModelConfig.scan_plan``).
    """
    plan = cfg.scan_plan()
    keys = common.split_keys(key, 4 + len(plan))
    params: Dict[str, Any] = {
        "embed": embed_init(keys[0], (cfg.vocab_size, cfg.d_model), cfg.dtype),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[1], (cfg.d_model, cfg.vocab_size), cfg.dtype)
    if cfg.modality == "vision":
        params["vision_proj"] = dense_init(
            keys[2], (cfg.vision_embed_dim, cfg.d_model), cfg.dtype)
    if cfg.modality == "audio_codec":
        params["codebook_embed"] = embed_init(
            keys[2], (cfg.num_codebooks, cfg.vocab_size, cfg.d_model), cfg.dtype)
        params["codebook_head"] = dense_init(
            keys[3], (cfg.num_codebooks, cfg.d_model, cfg.vocab_size), cfg.dtype)
    blocks = []
    for (cycle, repeat), k in zip(plan, keys[4:]):
        pkeys = common.split_keys(k, len(cycle))
        block = []
        for kind, pk in zip(cycle, pkeys):
            lkeys = jnp.stack(common.split_keys(pk, repeat))
            block.append(jax.vmap(lambda kk, _kind=kind: _init_layer(cfg, _kind, kk))(lkeys))
        blocks.append(block)
    params["blocks"] = blocks
    return params


# ---------------------------------------------------------------------------
# KV / state caches
# ---------------------------------------------------------------------------

def cache_capacity(cfg: ModelConfig, mixer: str, max_len: int) -> int:
    if mixer == ATTN:
        return max_len
    if mixer == ATTN_LOCAL:
        return min(cfg.window_size, max_len)
    if mixer == ATTN_CHUNKED:
        return min(cfg.chunk_size, max_len)
    return 0


def _cache_entry(cfg: ModelConfig, mixer: str, count: int, batch: int,
                 max_len: int):
    dh = cfg.resolved_head_dim
    if mixer in (ATTN, ATTN_LOCAL, ATTN_CHUNKED):
        cap = cache_capacity(cfg, mixer, max_len)
        return {
            "k": jnp.zeros((count, batch, cap, cfg.num_kv_heads, dh), cfg.dtype),
            "v": jnp.zeros((count, batch, cap, cfg.num_kv_heads, dh), cfg.dtype),
            # absolute position held in each slot; -1 = empty
            "pos": jnp.full((count, batch, cap), -1, jnp.int32),
        }
    if mixer == ATTN_BIDIR:
        raise ValueError("encoder segments have no decode cache")
    if mixer == MAMBA2:
        st = ssm_lib.init_mamba2_state(cfg, batch, cfg.dtype)
    elif mixer == RWKV6:
        st = ssm_lib.init_rwkv6_state(cfg, batch, cfg.dtype)
    else:
        raise ValueError(mixer)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (count,) + x.shape), st)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> list:
    """Per scan-plan block/position stacked caches: caches[bi][pi]."""
    return [
        [_cache_entry(cfg, mixer, repeat, batch, max_len)
         for (mixer, _ffn) in cycle]
        for cycle, repeat in cfg.scan_plan()
    ]


# ---------------------------------------------------------------------------
# Attention sublayer
# ---------------------------------------------------------------------------

def _project_qkv(cfg, p, x, positions):
    b, l, _ = x.shape
    dh = cfg.resolved_head_dim
    q = jnp.einsum("bld,de->ble", x, p["wq"]).reshape(b, l, cfg.num_heads, dh)
    k = jnp.einsum("bld,de->ble", x, p["wk"]).reshape(b, l, cfg.num_kv_heads, dh)
    v = jnp.einsum("bld,de->ble", x, p["wv"]).reshape(b, l, cfg.num_kv_heads, dh)
    if cfg.qk_norm:
        q = common.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = common.rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attn_nocache(cfg, p, x, mixer, positions):
    """Training / prefill attention over the in-flight sequence only."""
    b, l, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x, positions)
    n_rep = cfg.num_heads // cfg.num_kv_heads
    kr = common.repeat_kv(k, n_rep)
    vr = common.repeat_kv(v, n_rep)
    pos = positions[0] if positions.ndim > 1 else positions
    window = cfg.window_size if mixer == ATTN_LOCAL else 0
    if cfg.use_flash and mixer != ATTN_BIDIR:
        out = kops.flash_attention(q, kr, vr, causal=True, window=window,
                                   softcap=cfg.attn_softcap, use_kernel=True)
    elif (l >= cfg.attn_block_threshold
          and l % cfg.attn_block_size == 0):
        # long sequences: online-softmax blocked attention (never builds
        # the (L, L) score matrix — required to fit HBM at 4k-500k tokens)
        out = common.attention_blocked(q, kr, vr, pos, pos, mixer,
                                       cfg.window_size, cfg.chunk_size,
                                       cfg.attn_softcap, cfg.attn_block_size)
    else:
        mask = common.make_attention_mask(pos, pos, mixer, cfg.window_size,
                                          cfg.chunk_size)
        out = common.attention(q, kr, vr, mask, cfg.attn_softcap)
    out = out.reshape(b, l, cfg.num_heads * cfg.resolved_head_dim)
    return jnp.einsum("ble,ed->bld", out, p["wo"]), (k, v)


def _attn_decode(cfg, p, x, mixer, offset, cache):
    """Single-token attention against the ring cache.

    cache: {"k","v": (B, S, Hkv, Dh), "pos": (B, S)}; offset: scalar int32 =
    number of tokens already processed (the new token's position).
    """
    b, l, _ = x.shape  # l == 1
    positions = jnp.full((b, l), offset, jnp.int32)
    q, k_new, v_new = _project_qkv(cfg, p, x, positions)
    cap = cache["k"].shape[1]
    slot = offset % cap
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype),
                                     (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype),
                                     (0, slot, 0, 0))
    pos = jax.lax.dynamic_update_slice(
        cache["pos"], jnp.full((b, 1), offset, jnp.int32), (0, slot))

    n_rep = cfg.num_heads // cfg.num_kv_heads
    valid = (pos >= 0) & (pos <= offset)
    if mixer == ATTN_LOCAL:
        valid &= pos > offset - cfg.window_size
    elif mixer == ATTN_CHUNKED:
        valid &= (pos // cfg.chunk_size) == (offset // cfg.chunk_size)

    scale = 1.0 / (cfg.resolved_head_dim ** 0.5)
    if cfg.gqa_grouped_decode:
        # grouped form: never materializes the n_rep-expanded KV (reads the
        # cache once instead of n_rep times — decode is cache-bandwidth
        # bound, so this is a direct memory-term win)
        dh = cfg.resolved_head_dim
        qg = q.reshape(b, l, cfg.num_kv_heads, n_rep, dh)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                            preferred_element_type=jnp.float32) * scale
        scores = common.softcap(scores, cfg.attn_softcap)
        scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(v.dtype), v)
        out = out.reshape(b, l, cfg.num_heads * dh)
    else:
        kr = common.repeat_kv(k, n_rep)
        vr = common.repeat_kv(v, n_rep)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                            preferred_element_type=jnp.float32) * scale
        scores = common.softcap(scores, cfg.attn_softcap)
        scores = jnp.where(valid[:, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(vr.dtype), vr)
        out = out.reshape(b, l, cfg.num_heads * cfg.resolved_head_dim)
    return jnp.einsum("ble,ed->bld", out, p["wo"]), {"k": k, "v": v, "pos": pos}


def _fill_cache_from_prefill(cfg, mixer, k, v, positions, cap):
    """Write the last ``cap`` tokens of prefill K/V into a fresh ring cache."""
    b, l = k.shape[0], k.shape[1]
    take = min(cap, l)
    ks = k[:, l - take:, :, :]
    vs = v[:, l - take:, :, :]
    ps = jnp.broadcast_to(positions[:, l - take:], (b, take))
    if take < cap:
        pad = cap - take
        ks = jnp.pad(ks, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, pad), (0, 0), (0, 0)))
        ps = jnp.pad(ps, ((0, 0), (0, pad)), constant_values=-1)
        return {"k": ks, "v": vs, "pos": ps}
    # ring layout: token at absolute position p sits in slot p % cap
    slots = ps[0] % cap
    inv = jnp.zeros((cap,), jnp.int32).at[slots].set(jnp.arange(cap))
    return {"k": ks[:, inv], "v": vs[:, inv], "pos": ps[:, inv]}


# ---------------------------------------------------------------------------
# Layer forward (one layer; used inside the per-segment scan)
# ---------------------------------------------------------------------------

def _ffn_apply(cfg, ffn_kind, p, x):
    if ffn_kind == FFN_NONE:
        return x, 0.0
    h = common.rms_norm(x, p["ln2"], cfg.norm_eps)
    if ffn_kind == FFN_MOE:
        out, aux = moe_lib.moe_ffn(cfg, p["moe"], h)
        return x + out, aux
    return x + common.swiglu(h, p["w_gate"], p["w_up"], p["w_down"]), 0.0


def _layer_fwd(cfg, kind, p, x, positions, cache, mode, offset):
    """Returns (x, new_cache, aux)."""
    mixer, ffn_kind = kind
    aux = 0.0
    if mixer in ATTN_KINDS:
        with jax.named_scope(scopes.ENCODER_ATTENTION):
            h = common.rms_norm(x, p["ln1"], cfg.norm_eps)
            if mode == "decode":
                out, new_cache = _attn_decode(cfg, p, h, mixer, offset, cache)
            else:
                out, (k, v) = _attn_nocache(cfg, p, h, mixer, positions)
                new_cache = None
                if mode == "prefill":
                    cap = cache["k"].shape[1]
                    new_cache = _fill_cache_from_prefill(cfg, mixer, k, v,
                                                         positions, cap)
            x = x + out
        with jax.named_scope(scopes.ENCODER_MLP):
            x, aux = _ffn_apply(cfg, ffn_kind, p, x)
    elif mixer == MAMBA2:
        h = common.rms_norm(x, p["ln1"], cfg.norm_eps)
        if mode == "decode":
            out, new_cache = ssm_lib.mamba2_decode(cfg, p["mamba"], h, cache)
        else:
            out, new_cache = ssm_lib.mamba2_forward(
                cfg, p["mamba"], h, cache if mode == "prefill" else None)
            if mode != "prefill":
                new_cache = None
        x = x + out
        x, aux = _ffn_apply(cfg, ffn_kind, p, x)
    elif mixer == RWKV6:
        h = common.rms_norm(x, p["ln1"], cfg.norm_eps)
        st = cache if mode != "train" else None
        out, s_new, shift_tm = ssm_lib.rwkv6_timemix(
            cfg, p["rwkv"], h, st, decode=(mode == "decode"))
        x = x + out
        h2 = common.rms_norm(x, p["ln2"], cfg.norm_eps)
        out2, shift_cm = ssm_lib.rwkv6_channelmix(cfg, p["rwkv"], h2, st)
        x = x + out2
        new_cache = (None if mode == "train" else
                     {"ssm": s_new, "shift_tm": shift_tm.astype(cfg.dtype),
                      "shift_cm": shift_cm.astype(cfg.dtype)})
    else:
        raise ValueError(mixer)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Segment runners
# ---------------------------------------------------------------------------

def _run_segments(cfg, params, x, positions, caches, mode, offset):
    """Run every scan-plan block; each block is one lax.scan whose body
    applies the whole pattern cycle once."""
    new_caches = []
    total_aux = jnp.float32(0.0)
    for bi, (cycle, repeat) in enumerate(cfg.scan_plan()):
        p_blk = params["blocks"][bi]
        c_blk = caches[bi] if caches is not None else None

        def body(carry, xs, _cycle=cycle, _has_cache=c_blk is not None):
            xc, auxc = carry
            if _ACTIVATION_SPEC is not None and mode == "train":
                xc = jax.lax.with_sharding_constraint(xc, _ACTIVATION_SPEC)
            if _has_cache:
                p_cyc, c_cyc = xs
            else:
                p_cyc, c_cyc = xs, [None] * len(_cycle)
            ncs = []
            for kind, p_l, c_l in zip(_cycle, p_cyc, c_cyc):
                xc, nc, aux = _layer_fwd(cfg, kind, p_l, xc, positions, c_l,
                                         mode, offset)
                auxc = auxc + aux
                ncs.append(nc if nc is not None else 0)
            return (xc, auxc), ncs

        if mode == "train" and cfg.remat:
            body = jax.checkpoint(body)  # recompute in bwd; no stacked stash
        xs = (p_blk, c_blk) if c_blk is not None else p_blk
        with jax.named_scope(scopes.ENCODER_LAYERS):
            (x, total_aux), ys = jax.lax.scan(body, (x, total_aux), xs)
        new_caches.append(ys if c_blk is not None else None)
    return x, new_caches, total_aux


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_tokens(cfg: ModelConfig, params: dict, tokens: Array,
                 prefix_embeds: Optional[Array] = None) -> Array:
    """tokens: (B, L) int32 — or (B, K, L) for audio_codec.
    prefix_embeds: (B, Tv, Dv) vision/audio stub embeddings, projected and
    prepended (the modality-frontend carve-out)."""
    if cfg.modality == "audio_codec" and tokens.ndim == 3:
        # sum the K codebook embeddings per frame [arXiv:2306.05284]
        x = jnp.sum(jax.vmap(
            lambda emb, tok: emb[tok], in_axes=(0, 1), out_axes=1
        )(params["codebook_embed"], tokens), axis=1)
    else:
        x = params["embed"][tokens]
    if cfg.name and getattr(cfg, "embed_scale", False):
        x = x * (cfg.d_model ** 0.5)
    if prefix_embeds is not None:
        proj = params.get("vision_proj")
        pe = (jnp.einsum("btv,vd->btd", prefix_embeds.astype(cfg.dtype), proj)
              if proj is not None else prefix_embeds.astype(cfg.dtype))
        x = jnp.concatenate([pe, x], axis=1)
    return x


def lm_logits(cfg: ModelConfig, params: dict, x: Array) -> Array:
    x = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.modality == "audio_codec":
        logits = jnp.einsum("bld,kdv->blkv", x, params["codebook_head"])
    elif cfg.tie_embeddings:
        logits = jnp.einsum("bld,vd->blv", x, params["embed"])
    else:
        logits = jnp.einsum("bld,dv->blv", x, params["lm_head"])
    return common.softcap(logits.astype(jnp.float32), cfg.logit_softcap)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, params: dict, tokens: Array,
            prefix_embeds: Optional[Array] = None) -> Tuple[Array, Array]:
    """Training/encoder pass: (logits (B, L', Vf32), aux_loss)."""
    x = embed_tokens(cfg, params, tokens, prefix_embeds)
    b, l, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32)[None], (b, l))
    x, _, aux = _run_segments(cfg, params, x, positions, None, "train", 0)
    return lm_logits(cfg, params, x), aux


def prefill(cfg: ModelConfig, params: dict, tokens: Array, max_len: int,
            prefix_embeds: Optional[Array] = None) -> Tuple[Array, list, Array]:
    """Returns (last-token logits, cache, offset). Cache sized for max_len."""
    x = embed_tokens(cfg, params, tokens, prefix_embeds)
    b, l, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32)[None], (b, l))
    caches = init_cache(cfg, b, max_len)
    x, new_caches, _ = _run_segments(cfg, params, x, positions, caches, "prefill", 0)
    logits = lm_logits(cfg, params, x[:, -1:, :])
    return logits, new_caches, jnp.int32(l)


def decode_step(cfg: ModelConfig, params: dict, tokens: Array, caches: list,
                offset: Array) -> Tuple[Array, list]:
    """serve_step: ONE new token (B, 1) [or (B, K, 1) audio] against the cache."""
    x = embed_tokens(cfg, params, tokens)
    # decode positions derive from offset inside the layers
    b = x.shape[0]
    pos = jnp.full((b, 1), offset, jnp.int32)
    x, new_caches, _ = _run_segments(cfg, params, x, pos, caches, "decode", offset)
    return lm_logits(cfg, params, x), new_caches
