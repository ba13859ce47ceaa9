"""No hidden device use: importing and planning touch no backend, the
compile cache goes where it is placed, and a Pallas kernel on the CPU
runs in interpret mode only when asked to."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.launch import compile_cache

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_cpu(code: str, **env) -> str:
    full = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO_SRC, **env)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=full, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_import_initialises_no_backend():
    """Importing the model, the simulator, the kernels, or any other module
    of the package leaves every backend uninitialised."""
    out = _run_cpu("""
        import importlib, pkgutil
        from jax._src import xla_bridge
        import repro.models.pipeline, repro.core.simulator, repro.kernels.ops
        print("INIT", xla_bridge.backends_are_initialized())
        import repro
        for m in pkgutil.walk_packages(repro.__path__, "repro."):
            importlib.import_module(m.name)
            if xla_bridge.backends_are_initialized():
                print("INITIALISED BY", m.name)
                break
    """)
    assert "INIT False" in out
    assert "INITIALISED BY" not in out, out


def test_planning_initialises_no_backend():
    """Profiler, Orchestrator and Dispatcher on full sd3 stay off devices:
    a process that plans never takes the chip."""
    out = _run_cpu("""
        import repro.configs as C
        from jax._src import xla_bridge
        from repro.core.dispatcher import Dispatcher
        from repro.core.orchestrator import Orchestrator
        from repro.core.profiler import Profiler
        from repro.core.request import Request
        prof = Profiler(C.get("sd3"))
        reqs = [Request("sd3", 512), Request("sd3", 1024)]
        for r in reqs:
            r.deadline = 2.5 * prof.pipeline_time(r)
        plan = Orchestrator(prof, num_chips=1).generate(reqs)
        idle = set(range(plan.num_units))
        got = Dispatcher(prof).dispatch(reqs, plan, idle, {g: 0.0 for g in idle}, 0.0)
        assert got, "nothing dispatched"
        print("INIT", xla_bridge.backends_are_initialized())
    """)
    assert "INIT False" in out


@pytest.mark.parametrize("op", ["flash_attention", "adaln_rmsnorm"])
def test_kernel_on_cpu_without_interpret_raises(op):
    x = jnp.ones((1, 128, 2, 64))
    with pytest.raises(ValueError, match="interpret"):
        if op == "flash_attention":
            ops.flash_attention(x, x, x, causal=True, use_kernel=True)
        else:
            h = jnp.ones((1, 128, 128))
            m = jnp.ones((1, 128))
            ops.adaln_rmsnorm(h, m, m, use_kernel=True)


def test_compile_cache_goes_where_placed(tmp_path):
    placed = tmp_path / "cache"
    out = _run_cpu("""
        import os, jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        print("DIR", enable_compile_cache())
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
        print("FILES", len(os.listdir(os.environ["JAX_COMPILATION_CACHE_DIR"])))
    """, JAX_COMPILATION_CACHE_DIR=str(placed),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert f"DIR {placed}" in out
    assert "FILES 0" not in out


def test_compile_cache_default_is_fixed_repo_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(compile_cache.REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert (compile_cache.REPO_ROOT / "chip_smoke.py").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
