"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import adaln_rmsnorm as ar
from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref
from repro.kernels import ssm_scan
from repro.models import common


@pytest.mark.parametrize("b,lq,lkv,h,d", [
    (2, 64, 64, 2, 32), (1, 100, 100, 3, 64), (2, 1, 128, 2, 32),
    (1, 128, 128, 1, 128), (1, 17, 17, 2, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(b, lq, lkv, h, d, dtype):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, lq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, lkv, h, d), dtype)
    v = jax.random.normal(ks[2], (b, lkv, h, d), dtype)
    out = fa.flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                             interpret=True)
    want = ops.flash_attention(q, k, v, causal=True, use_kernel=False)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window,softcap,causal", [
    (48, 0.0, True), (0, 50.0, True), (16, 30.0, True), (0, 0.0, False),
])
def test_flash_attention_variants(window, softcap, causal):
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    b, l, h, d = 2, 96, 2, 32
    q = jax.random.normal(ks[0], (b, l, h, d))
    k = jax.random.normal(ks[1], (b, l, h, d))
    v = jax.random.normal(ks[2], (b, l, h, d))
    out = fa.flash_attention(q, k, v, causal=causal, window=window,
                             softcap=softcap, block_q=32, block_k=32,
                             interpret=True)
    want = ops.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


def _qkv(key, shape, dtype=jnp.bfloat16):
    return [jax.random.normal(k, shape, dtype) for k in jax.random.split(key, 3)]


def _rel_err(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("l,h,d", [
    (141, 2, 64), (333, 2, 64), (1024, 1, 64), (1101, 2, 64), (2117, 1, 128),
])
def test_flash_attention_joint_sequence(l, h, d):
    """The DiT's non-causal joint sequence in bf16, at its default tiles:
    lengths that need padding to 128 (all but 1024) and a tail chunk."""
    q, k, v = _qkv(jax.random.PRNGKey(l), (1, l, h, d))
    out = fa.flash_attention(q, k, v, causal=False, interpret=True)
    want = common.attention(q, k, v, None)
    assert out.shape == want.shape and out.dtype == want.dtype
    assert _rel_err(out, want) < 1e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=2e-2, rtol=2e-2)


def test_flash_attention_masks_padded_keys():
    """Whatever the padded key and value rows hold, the output is the same."""
    l, lp, d = 333, 384, 64
    q, k, v = _qkv(jax.random.PRNGKey(7), (2, l, d))
    pad = lambda x, fill: jnp.concatenate(
        [x, jnp.full((2, lp - l, d), fill, x.dtype)], axis=1)
    run = lambda fill: fa._flash_padded(
        pad(q, 0), pad(k, fill), pad(v, fill), kv_len=l, causal=False, window=0,
        softcap=0.0, block_q=lp, block_k=128, block_kv=lp, q_offset=0,
        interpret=True)[:, :l]
    clean = run(0.0)
    np.testing.assert_array_equal(np.asarray(run(1e30), np.float32),
                                  np.asarray(clean, np.float32))
    heads_minor = lambda x: x.swapaxes(0, 1)[None]           # (1, L, H, D)
    want = common.attention(*map(heads_minor, (q, k, v)), None)[0].swapaxes(0, 1)
    assert _rel_err(clean, want) < 1e-2


@pytest.mark.parametrize("b,h,l,dk,dv,bonus", [
    (2, 2, 100, 16, 32, False), (1, 3, 64, 32, 32, True),
    (2, 1, 33, 8, 8, True), (1, 2, 16, 64, 64, False),
    (1, 1, 7, 4, 4, True),
])
def test_ssm_scan_vs_sequential(b, h, l, dk, dv, bonus):
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (b, h, l, dk))
    k = jax.random.normal(ks[1], (b, h, l, dk))
    v = jax.random.normal(ks[2], (b, h, l, dv))
    decay = jnp.maximum(jnp.exp(-jnp.exp(jax.random.normal(ks[3], (b, h, l, dk)))),
                        np.exp(-ssm_scan.MAX_NEG_LOGW))
    s0 = jax.random.normal(ks[4], (b, h, dk, dv))
    u = jax.random.normal(ks[5], (h, dk)) if bonus else None
    o1, s1 = ssm_scan.ssm_scan(q, k, v, decay, bonus=u, initial_state=s0,
                               interpret=True)
    o2, s2 = ref.linear_scan_ref(q, k, v, decay, u, s0)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=3e-3, rtol=3e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=3e-3, rtol=3e-3)


def test_chunked_ref_matches_sequential():
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 6)
    b, h, l, dk, dv = 2, 3, 130, 8, 16
    q = jax.random.normal(ks[0], (b, h, l, dk))
    k = jax.random.normal(ks[1], (b, h, l, dk))
    v = jax.random.normal(ks[2], (b, h, l, dv))
    decay = jax.nn.sigmoid(jax.random.normal(ks[3], (b, h, l, dk))) * 0.3 + 0.7
    s0 = jax.random.normal(ks[4], (b, h, dk, dv))
    bonus = jax.random.normal(ks[5], (h, dk))
    for bn in (None, bonus):
        o1, s1 = ref.linear_scan_ref(q, k, v, decay, bn, s0)
        o2, s2 = ref.chunked_linear_scan_ref(q, k, v, decay, bn, s0, chunk=32)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("b,l,d,rows", [(2, 100, 64, 32), (1, 7, 128, 256),
                                        (4, 256, 32, 64)])
def test_adaln_rmsnorm(b, l, d, rows):
    key = jax.random.PRNGKey(4)
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (b, l, d), jnp.float32)
    s = jax.random.normal(ks[1], (b, d)) * 0.1
    t = jax.random.normal(ks[2], (b, d)) * 0.1
    out = ar.adaln_rmsnorm(x, s, t, block_rows=rows, interpret=True)
    want = ref.adaln_rmsnorm_ref(x, s, t)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_decode_step_matches_scan():
    """Recurrent single-step == one-step full scan (both oracle paths)."""
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 6)
    b, h, dk, dv = 2, 4, 8, 16
    q = jax.random.normal(ks[0], (b, h, dk))
    k = jax.random.normal(ks[1], (b, h, dk))
    v = jax.random.normal(ks[2], (b, h, dv))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, h, dk)))
    s0 = jax.random.normal(ks[4], (b, h, dk, dv))
    u = jax.random.normal(ks[5], (h, dk))
    o1, s1 = ref.linear_scan_decode_ref(q, k, v, w, s0, u)
    o2, s2 = ref.linear_scan_ref(q[:, :, None], k[:, :, None], v[:, :, None],
                                 w[:, :, None], u, s0)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2[:, :, 0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-5)
