"""Public jit'd entry points for the Pallas kernels, with jnp fallbacks.

Every op takes ``use_kernel``: False routes to the pure-jnp oracle in
``ref.py`` (the CPU-correct path used by smoke tests and the serving
examples); True routes to the Pallas TPU kernel, compiled for the chip.
Interpret mode runs only when the caller passes ``interpret=True`` (the
test suite does, on the CPU); without it a kernel call on the CPU raises.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref

Array = jax.Array


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: int = 0, softcap: float = 0.0,
                    use_kernel: bool = False, interpret: bool = False) -> Array:
    """q: (B, Lq, H, D); k/v: (B, Lkv, H, D). GQA must be expanded upstream."""
    if not use_kernel:
        lq, lkv = q.shape[1], k.shape[1]
        mask = None
        if causal or window:
            qpos = jnp.arange(lq) + (lkv - lq)
            kpos = jnp.arange(lkv)
            mask = kpos[None, :] <= qpos[:, None]
            if window:
                mask &= kpos[None, :] > qpos[:, None] - window
        return ref.attention_ref(q, k, v, mask, softcap)
    from repro.kernels import flash_attention as fa
    return fa.flash_attention(q, k, v, causal=causal, window=window, softcap=softcap,
                              interpret=interpret)


# ---------------------------------------------------------------------------
# Gated linear-attention scan (Mamba2 / RWKV6)
# ---------------------------------------------------------------------------

def linear_scan(q: Array, k: Array, v: Array, decay: Array, *,
                bonus: Optional[Array] = None, initial_state: Optional[Array] = None,
                use_kernel: bool = False, interpret: bool = False,
                chunk: int = 32) -> Tuple[Array, Array]:
    """(B,H,L,K) inputs -> (out (B,H,L,V), final_state (B,H,K,V))."""
    if not use_kernel:
        return ref.chunked_linear_scan_ref(q, k, v, decay, bonus, initial_state, chunk)
    from repro.kernels import ssm_scan
    return ssm_scan.ssm_scan(q, k, v, decay, bonus=bonus, initial_state=initial_state,
                             chunk=chunk, interpret=interpret)


def linear_scan_decode(q: Array, k: Array, v: Array, decay: Array, state: Array,
                       *, bonus: Optional[Array] = None) -> Tuple[Array, Array]:
    """Single-token recurrence; always the jnp path (it is a matvec)."""
    return ref.linear_scan_decode_ref(q, k, v, decay, state, bonus)


# ---------------------------------------------------------------------------
# AdaLN-modulated RMSNorm (DiT)
# ---------------------------------------------------------------------------

def adaln_rmsnorm(x: Array, scale: Array, shift: Array, *, eps: float = 1e-6,
                  use_kernel: bool = False, interpret: bool = False) -> Array:
    if not use_kernel:
        return ref.adaln_rmsnorm_ref(x, scale, shift, eps)
    from repro.kernels import adaln_rmsnorm as ar
    return ar.adaln_rmsnorm(x, scale, shift, eps=eps, interpret=interpret)
