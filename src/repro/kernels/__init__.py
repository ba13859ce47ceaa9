"""Pallas TPU kernels for the compute hot spots, with pure-jnp oracles.

Kernels (each: <name>.py = pl.pallas_call + BlockSpec; ops.py = jit'd
wrappers; ref.py = oracle):

* ``flash_attention`` — tiled online-softmax attention (causal / sliding-
  window / softcap, and the DiT's padded non-causal joint sequence), which
  serves the Diffuse stage's long joint lengths on a TPU.
* ``ssm_scan`` — chunked gated linear-attention scan shared by Mamba2 and
  RWKV6 (data-dependent decay, bonus-u path).
* ``adaln_rmsnorm`` — AdaLN-Zero modulated RMSNorm fusion (DiT blocks).

Validated against the oracles with ``interpret=True`` on CPU; compiled for
TPU with MXU-aligned (multiple-of-128) tiles.
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
