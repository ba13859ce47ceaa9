"""Sharding rules + sequence parallelism (multi-device via subprocess)."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

import repro.configs as C
from repro.models import transformer as tf
from repro.sharding import partition

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.parametrize("arch", list(C.ARCH_IDS))
def test_param_specs_cover_tree(arch):
    cfg = C.get(arch)
    shapes = jax.eval_shape(lambda k: tf.init(cfg, k), jax.random.PRNGKey(0))
    specs = partition.param_specs(cfg, shapes)
    flat_shapes = jax.tree_util.tree_leaves(shapes)
    flat_specs = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_shapes) == len(flat_specs)
    for sh, sp in zip(flat_shapes, flat_specs):
        assert len(sp) <= sh.ndim


@pytest.mark.parametrize("arch", ["gemma2-9b", "yi-34b", "deepseek-moe-16b",
                                  "rwkv6-3b"])
def test_divisibility_validation(arch):
    """After validation every sharded dim divides the mesh axis size."""
    cfg = C.get(arch)
    shapes = jax.eval_shape(lambda k: tf.init(cfg, k), jax.random.PRNGKey(0))
    specs = partition.param_specs(cfg, shapes)

    class FakeMesh:
        shape = {"model": 16, "data": 16}

    fixed = partition.validate_divisibility(specs, shapes, FakeMesh())
    flat_sh = jax.tree_util.tree_leaves(shapes)
    flat_sp = jax.tree_util.tree_leaves(fixed,
                                        is_leaf=lambda x: isinstance(x, P))
    for sh, sp in zip(flat_sh, flat_sp):
        for dim, ax in enumerate(tuple(sp)):
            if ax is not None:
                axes = (ax,) if isinstance(ax, str) else ax
                size = 1
                for a in axes:
                    size *= FakeMesh.shape[a]
                assert sh.shape[dim] % size == 0, (arch, sp, sh.shape)


def _run_subprocess(code: str):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=REPO_SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]


def test_ulysses_matches_reference_4dev():
    _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.sharding import sequence_parallel as sp
        from repro.kernels import ops
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("model",))
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (2, 32, 8, 16))
        k = jax.random.normal(ks[1], (2, 32, 8, 16))
        v = jax.random.normal(ks[2], (2, 32, 8, 16))
        out = sp.ulysses_attention(q, k, v, mesh, causal=True)
        ref = ops.flash_attention(q, k, v, causal=True, use_kernel=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    """)


def test_ulysses_uneven_length_masks_padding_4dev():
    """A length the SP degree does not divide (the DiT's 4096 + 77 joint
    sequence is one) is padded inside and the padded keys are masked."""
    _run_subprocess("""
        import jax, numpy as np
        from repro.sharding import sequence_parallel as sp
        from repro.kernels import ops
        from repro.launch.mesh import make_mesh
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (1, 30, 8, 16)) for kk in ks)
        for n in (2, 4):
            mesh = make_mesh((n,), ("model",), devices=jax.devices()[:n])
            for causal in (False, True):
                out = sp.ulysses_attention(q, k, v, mesh, causal=causal)
                ref = ops.flash_attention(q, k, v, causal=causal,
                                          use_kernel=False)
                assert out.shape == ref.shape
                np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                           atol=2e-5)
    """)


def test_scan_chunk_parallel_matches_reference_4dev():
    _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.sharding import sequence_parallel as sp
        from repro.kernels import ref
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("model",))
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (2, 3, 64, 8))
        k = jax.random.normal(ks[1], (2, 3, 64, 8))
        v = jax.random.normal(ks[2], (2, 3, 64, 8))
        w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (2, 3, 64, 8)) * 0.3))
        o1, s1 = sp.scan_chunk_parallel(q, k, v, w, mesh)
        o2, s2 = ref.linear_scan_ref(q, k, v, w)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   atol=3e-3, rtol=3e-3)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   atol=3e-3, rtol=3e-3)
    """)


def test_sharded_train_step_runs_8dev():
    """A reduced model trains under pjit on a 4x2 mesh (data x model)."""
    _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        import repro.configs as C
        from repro.data import pipeline as dp
        from repro.launch.mesh import make_mesh
        from repro.sharding import partition
        from repro.training import loop
        from jax.sharding import NamedSharding, PartitionSpec as P
        cfg = C.get_smoke("deepseek-moe-16b")
        mesh = make_mesh((2, 2), ("data", "model"))
        state = loop.init_state(cfg, jax.random.PRNGKey(0))
        sspec = partition.state_specs(cfg, jax.eval_shape(lambda: state))
        sspec = partition.validate_divisibility(
            sspec, jax.eval_shape(lambda: state), mesh)
        shard = partition.named(sspec, mesh)
        state = jax.device_put(state, shard)
        dcfg = dp.DataConfig(batch=4, seq_len=16)
        batch = {k: jax.device_put(jnp.asarray(v), NamedSharding(
                     mesh, P("data", *([None] * (v.ndim - 1)))))
                 for k, v in dp.synthetic_batch(cfg, dcfg, 0).items()}
        step = jax.jit(loop.make_train_step(cfg), in_shardings=(shard, None))
        with mesh:
            state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
    """)
