"""Roofline machinery: trip-count-aware HLO parsing + dry-run smoke."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.roofline import hlo

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_scan_flops_match_unrolled():
    """cost_analysis counts while bodies once; our parser must not."""
    def body(c, _):
        return c @ c, None

    def scanned(x):
        return jax.lax.scan(body, x, None, length=8)[0]

    def unrolled(x):
        for _ in range(8):
            x = x @ x
        return x

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    fl = {}
    for name, fn in (("scan", scanned), ("unroll", unrolled)):
        c = jax.jit(fn).lower(x).compile()
        fl[name] = hlo.module_costs(c.as_text(), 1).flops
    assert fl["scan"] == fl["unroll"] == 8 * 2 * 128 ** 3


def test_nested_scan_multipliers():
    def inner(c, _):
        return c @ c, None

    def outer(c, _):
        c, _ = jax.lax.scan(inner, c, None, length=3)
        return c, None

    def fn(x):
        return jax.lax.scan(outer, x, None, length=4)[0]

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = jax.jit(fn).lower(x).compile()
    mc = hlo.module_costs(c.as_text(), 1)
    assert mc.flops == 12 * 2 * 64 ** 3


def test_dot_flops_with_batch_dims():
    def fn(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    a = jax.ShapeDtypeStruct((4, 32, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 64, 16), jnp.float32)
    c = jax.jit(fn).lower(a, b).compile()
    mc = hlo.module_costs(c.as_text(), 1)
    assert mc.flops == 2 * 4 * 32 * 64 * 16


def test_collective_parsing_smoke():
    text = """
HloModule m

ENTRY %main (p: f32[16,16]) -> f32[16,16] {
  %p = f32[16,16]{1,0} parameter(0)
  %ar = f32[16,16]{1,0} all-reduce(%p), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %ag = f32[16,16]{1,0} all-gather(%ar), replica_groups={{0,1}}, dimensions={0}
}
"""
    mc = hlo.module_costs(text, 4)
    assert mc.collective_counts == {"all-reduce": 1, "all-gather": 1}
    # AR: 2*(3/4)*1024B; AG: (1/2)*1024B
    assert abs(mc.collective_wire_bytes - (2 * 0.75 * 1024 + 0.5 * 1024)) < 1


@pytest.mark.slow
def test_dryrun_single_combo_subprocess():
    """A small arch x decode compiles on a 64-device mesh in-process."""
    code = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=64"
        import jax
        import repro.configs as C
        from repro.launch import specs as specs_lib, dryrun
        from repro.launch.mesh import make_mesh
        spec = specs_lib.input_specs("internvl2-2b", "decode_32k")
        mesh = make_mesh((8, 8), ("data", "model"))
        cfg = C.get("internvl2-2b")
        in_sh = dryrun.shardings_for(spec, cfg, mesh, False)
        with mesh:
            compiled = jax.jit(spec.fn, in_shardings=in_sh,
                               donate_argnums=(2,)).lower(*spec.args).compile()
        from repro.roofline import hlo
        mc = hlo.module_costs(compiled.as_text(), 64)
        assert mc.flops > 0 and mc.hbm_bytes > 0
        print("DRYRUN_OK")
    """
    env = dict(os.environ, PYTHONPATH=REPO_SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0 and "DRYRUN_OK" in out.stdout, out.stderr[-2000:]


def test_long500k_skip_reasons():
    from repro.launch import specs as specs_lib
    import repro.configs as C
    expected_skip = {"yi-34b", "yi-9b", "internvl2-2b", "deepseek-moe-16b",
                     "musicgen-medium"}
    for arch in C.ARCH_IDS:
        spec = specs_lib.input_specs(arch, "long_500k")
        if arch in expected_skip:
            assert spec.skipped, arch
        else:
            assert not spec.skipped, arch
