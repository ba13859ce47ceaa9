"""Ulysses sequence parallelism via shard_map (the paper's SP mechanism).

DeepSpeed-Ulysses [arXiv:2309.14509]: activations enter sharded on the
*sequence* dim; an all-to-all re-shards them on the *head* dim for the
attention core (each device holds H/k full-length heads), and a second
all-to-all restores sequence sharding.  On TPU both all-to-alls map 1:1
onto ``jax.lax.all_to_all`` over the model axis — this is the φ_s =
"ulysses" parallel config a dispatch plan requests.

For the attention-free SSM architectures (rwkv6, mamba2) Ulysses is
inapplicable; ``scan_chunk_parallel`` is the substitute: devices hold
sequence chunks and chain recurrent states with a ppermute ladder.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ops as kops
from repro.kernels import ref

Array = jax.Array


def ulysses_attention(q: Array, k: Array, v: Array, mesh: Mesh,
                      axis: str = "model", causal: bool = False,
                      softcap: float = 0.0) -> Array:
    """q/k/v: (B, L, H, D) sharded on L over ``axis``; H % axis_size == 0.

    L need not divide the axis size: the sequence is padded to a multiple
    of it, padded keys are masked out, and padded queries are dropped.
    Returns attention output (B, L, H, D) sharded on L again.
    """
    n = mesh.shape[axis]
    assert q.shape[2] % n == 0, f"heads {q.shape[2]} % {n} != 0"
    l = q.shape[1]
    pad = (-l) % n
    if pad:
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = (jnp.pad(x, widths) for x in (q, k, v))

    def body(qs, ks, vs):
        # (B, L/n, H, D) -> all-to-all -> (B, L, H/n, D)
        a2a = lambda x: jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                           tiled=True)
        qh, kh, vh = a2a(qs), a2a(ks), a2a(vs)
        pos = jnp.arange(l + pad)
        mask = None
        if pad:
            mask = pos[None, :] < l          # no query attends to a padded key
        if causal:
            tri = pos[None, :] <= pos[:, None]
            mask = tri if mask is None else mask & tri
        out = ref.attention_ref(qh, kh, vh, mask, softcap)
        # (B, L, H/n, D) -> back to sequence sharding (B, L/n, H, D)
        return jax.lax.all_to_all(out, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    spec = P(None, axis, None, None)
    out = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=spec)(q, k, v)
    return out[:, :l] if pad else out


def scan_chunk_parallel(q: Array, k: Array, v: Array, decay: Array,
                        mesh: Mesh, axis: str = "model",
                        bonus: Optional[Array] = None) -> Tuple[Array, Array]:
    """Sequence-chunk parallel gated linear scan (SSM SP substitute).

    Inputs (B, H, L, K) sharded on L.  Each device runs the chunked scan on
    its local chunk from a zero state, then states are corrected with a
    sequential ppermute ladder: device i receives the accumulated state of
    devices < i, decayed by its chunk's total decay product.
    """
    n = mesh.shape[axis]

    def body(qs, ks, vs, ws):
        bb, hh, _, kk = qs.shape
        vv = vs.shape[-1]
        zero = jax.lax.pcast(jnp.zeros((bb, hh, kk, vv), jnp.float32), (axis,),
                             to="varying")
        _, s_local = kops.linear_scan(qs, ks, vs, ws, bonus=bonus,
                                      initial_state=zero)
        # total decay of the local chunk per (B, H, K)
        dtot = jnp.exp(jnp.sum(jnp.log(jnp.clip(ws.astype(jnp.float32),
                                                1e-30)), axis=2))
        # prefix ladder: prefix_i = dtot_{i-1} * prefix_{i-1} + S_{i-1};
        # telescoped with n-1 right-shifts (device 0 receives zeros)
        carry = jnp.zeros_like(s_local)
        perm = [(i, i + 1) for i in range(n - 1)]
        for _ in range(max(0, n - 1)):
            msg = dtot[..., None] * carry + s_local
            carry = jax.lax.ppermute(msg, axis, perm=perm)
        # redo the local scan seeded with the exact prefix state
        out, s_final = kops.linear_scan(qs, ks, vs, ws, bonus=bonus,
                                        initial_state=carry)
        return out, s_final[None]

    spec_l = P(None, None, axis, None)
    out, s = jax.shard_map(body, mesh=mesh,
                           in_specs=(spec_l, spec_l, spec_l, spec_l),
                           out_specs=(spec_l, P(axis, None, None, None, None)))(
        q, k, v, decay)
    return out, s[-1]
