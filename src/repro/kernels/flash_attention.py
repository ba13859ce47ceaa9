"""Flash attention as a Pallas TPU kernel.

TPU-native tiling: the (B*H, Lq, D) query stream is blocked (block_q, D) into
VMEM; D is kept whole per block (<= 256 for every config in the zoo).  Both
products take their operands in the input dtype with a float32 result; the
online-softmax state (m, l, acc) stays float32 in VMEM scratch, and the
probabilities are cast to the input dtype before the product with V, as
``repro.models.common.attention`` does.

Two schedules over the keys:

* non-causal (the DiT's joint sequence): one grid step per (head, query
  block) holds the head's whole K and V in VMEM and walks them in chunks of
  ``block_k`` inside the kernel, so the grid has few steps and K/V are read
  from HBM once per head.  The sequence is padded to a multiple of 128; the
  padded keys are masked by a static ``kv_len`` compare on the last chunk
  alone, and the padded query rows are sliced off.
* causal (decoder LLMs, with sliding window and score softcap): the keys are
  a grid axis of ``block_k`` blocks, so blocks above the diagonal or outside
  the window are skipped.

Oracle: ``repro.kernels.ref.attention_ref``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1e30
LANES = 128
# the non-causal schedule's default tiles, timed on a TPU v5e against
# 256-1024 rows (PERF.md, benchmarks/attention_bench.py --sweep)
BLOCK_Q = 512
BLOCK_K = 512
# scoped VMEM the compiler grants a kernel unless asked for more
DEFAULT_VMEM = 16 * 2 ** 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _lanes(x: Array, n: int) -> Array:
    """A (rows, 128) array whose lanes hold one value per row, as (rows, n)."""
    if n == LANES:
        return x
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    if n < LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, window: int, softcap: float,
               kv_len: int, block_k: int, q_offset: int):
    block_q = q_ref.shape[1]
    block_kv = k_ref.shape[1]          # the whole padded length when non-causal
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def chunk(start, size: int, masked: bool):
        q = q_ref[0]                                   # (block_q, D)
        k = k_ref[0, pl.ds(start, size), :]            # (size, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        if masked:
            k_pos = (ki * block_kv + start
                     + jax.lax.broadcasted_iota(jnp.int32, (block_q, size), 1))
            mask = k_pos < kv_len
            if causal:
                q_pos = (qi * block_q + q_offset
                         + jax.lax.broadcasted_iota(jnp.int32, (block_q, size), 0))
                mask = jnp.logical_and(mask, k_pos <= q_pos)
                if window:
                    mask = jnp.logical_and(mask, k_pos > q_pos - window)
            s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]        # (block_q, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, size))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0, pl.ds(start, size), :]
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * _lanes(alpha, acc_ref.shape[1]) + pv

    if causal:
        # skip blocks strictly above the diagonal / outside the window
        first_q = qi * block_q + q_offset
        needed = ki * block_kv <= first_q + block_q - 1
        if window:
            needed = jnp.logical_and(needed, (ki + 1) * block_kv - 1 > first_q - window)
        pl.when(needed)(lambda: chunk(0, block_kv, masked=True))
    else:
        n_full, tail = divmod(block_kv, block_k)
        last = tail or block_k             # the chunk that holds the padded keys
        n_clean = n_full - (0 if tail else 1)

        def body(i, carry):
            chunk(pl.multiple_of(i * block_k, block_k), block_k, masked=False)
            return carry

        jax.lax.fori_loop(0, n_clean, body, 0)
        chunk(n_clean * block_k, last, masked=kv_len < block_kv)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)             # fully-masked rows -> 0 output
        o_ref[0] = (acc_ref[...] / _lanes(l, acc_ref.shape[1])).astype(o_ref.dtype)


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: int = 0, softcap: float = 0.0,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool = False) -> Array:
    """q: (B, Lq, H, D); k/v: (B, Lkv, H, D) with H already GQA-expanded.

    Block sizes default to 128 for the causal schedule and to ``BLOCK_Q`` /
    ``BLOCK_K`` (query rows balanced over the blocks) for the non-causal one.
    """
    b, lq, h, d = q.shape
    lkv = k.shape[1]
    q_offset = lkv - lq  # decode/extend: queries sit at the end of kv

    qt = jnp.moveaxis(q, 2, 1).reshape(b * h, lq, d)
    kt = jnp.moveaxis(k, 2, 1).reshape(b * h, lkv, d)
    vt = jnp.moveaxis(v, 2, 1).reshape(b * h, lkv, d)
    if causal:
        block_q = min(block_q or 128, max(8, lq))
        block_k = min(block_k or 128, max(8, lkv))
        lq_p, lkv_p, block_kv = _round_up(lq, block_q), _round_up(lkv, block_k), block_k
    else:
        nq = -(-lq // (block_q or BLOCK_Q))
        block_q = _round_up(-(-lq // nq), 16)
        lq_p = nq * block_q
        lkv_p = block_kv = _round_up(lkv, min(block_k or BLOCK_K, LANES))
        block_k = min(block_k or BLOCK_K, block_kv)
    out = _flash_padded(
        _pad_rows(qt, lq_p), _pad_rows(kt, lkv_p), _pad_rows(vt, lkv_p),
        kv_len=lkv, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, block_kv=block_kv, q_offset=q_offset,
        interpret=interpret)
    out = out[:, :lq, :].reshape(b, h, lq, d)
    return jnp.moveaxis(out, 1, 2)


def _pad_rows(x: Array, rows: int) -> Array:
    return jnp.pad(x, ((0, 0), (0, rows - x.shape[1]), (0, 0))) if rows > x.shape[1] else x


def _flash_padded(qt: Array, kt: Array, vt: Array, *, kv_len: int, causal: bool,
                  window: int, softcap: float, block_q: int, block_k: int,
                  block_kv: int, q_offset: int, interpret: bool) -> Array:
    """The kernel over padded (B*H, L, D) streams: keys at ``kv_len`` and
    beyond are masked, whatever they hold."""
    bh, lq_p, d = qt.shape
    lkv_p = kt.shape[1]
    grid = (bh, lq_p // block_q, lkv_p // block_kv)
    kernel = functools.partial(
        _fa_kernel, scale=1.0 / math.sqrt(d), causal=causal, window=window,
        softcap=softcap, kv_len=kv_len, block_k=block_k, q_offset=q_offset)
    item = qt.dtype.itemsize
    # double-buffered q, k, v, o blocks, the state, the scores and probabilities
    vmem = (2 * item * d * (2 * block_q + 2 * block_kv)
            + 4 * block_q * (2 * LANES + d) + 12 * block_q * block_k)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=max(DEFAULT_VMEM, _round_up(vmem * 5 // 4, 2 ** 20)))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq_p, d), qt.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
