import os
import pathlib
import sys

# CPU only: these tests rehearse the harness and check its arithmetic;
# nothing here measures the chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="session")
def _own_compile_cache(tmp_path_factory):
    """CPU programs go to a cache of the test session's own, not to the
    checkout's, which holds the chip's."""
    from benchmarks.chip import harness
    saved = harness.CACHE_DIR
    harness.CACHE_DIR = tmp_path_factory.mktemp("jax_cache")
    yield
    harness.CACHE_DIR = saved
