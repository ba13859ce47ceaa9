"""Names of the named scopes in the stage programs.

Each stage program runs its blocks under ``jax.named_scope`` with one of
these names.  A scope changes only the compiled program's metadata (the
``op_name`` of each HLO instruction reads ``.../dit/attention/dot_general``),
never what it computes; ``repro.roofline.hlo.op_scopes`` reads the names
back from the compiled text, and the chip benchmark reduces device time by
them.

* D (``diffusion.forward`` and ``ddim_denoise``): the input projections,
  positions and time embedding; the layer loop's own work (each layer's
  weights sliced out of the stacked arrays, the counter); per layer the
  modulation and both modulated norms, the q/k/v projections, the attention
  core alone (scores, softmax, weighted sum: whatever implements it stays
  inside ``dit/attention``), the output projection with its gated residual,
  the MLP with its gated residual; the final modulation and output
  projection; the sampler's starting noise and update.
* E (``transformer._run_segments`` and ``_layer_fwd``): the layer loop's own
  work, each attention sublayer, each MLP.
* C (``diffusion.decode_latent``): the first convolution, each upsampling
  level, the last convolution.
"""
from __future__ import annotations

DIT_EMBED = "dit/embed"
DIT_LAYERS = "dit/layers"
DIT_ADALN = "dit/adaln"
DIT_QKV = "dit/qkv"
DIT_ATTENTION = "dit/attention"
DIT_ATTN_OUT = "dit/attn_out"
DIT_MLP = "dit/mlp"
DIT_FINAL = "dit/final"
DDIM = "ddim"
DIFFUSE = (DIT_EMBED, DIT_LAYERS, DIT_ADALN, DIT_QKV, DIT_ATTENTION,
           DIT_ATTN_OUT, DIT_MLP, DIT_FINAL, DDIM)

ENCODER_LAYERS = "encoder/layers"
ENCODER_ATTENTION = "encoder/attention"
ENCODER_MLP = "encoder/mlp"
ENCODE = (ENCODER_LAYERS, ENCODER_ATTENTION, ENCODER_MLP)

DECODER_CONV_IN = "decoder/conv_in"
DECODER_CONV_OUT = "decoder/conv_out"


def decoder_up(level: int) -> str:
    return f"decoder/up{level}"


def decode(num_upsamples: int) -> tuple:
    return (DECODER_CONV_IN, *(decoder_up(i) for i in range(num_upsamples)),
            DECODER_CONV_OUT)


def names(num_upsamples: int) -> tuple:
    """Every scope of the three stage programs, for a decoder of
    ``num_upsamples`` levels."""
    return ENCODE + DIFFUSE + decode(num_upsamples)
