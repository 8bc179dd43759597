"""The benchmark's own checks run on the CPU, at small sizes:

    python -m pytest bench/tests

They are not part of the repository's test suite.  Each test gets its
own compile caches."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import pytest  # noqa: E402

# Sizes a CPU runs in seconds; the shapes' structure is the cell's own.
SMALL = {"gemm.wide": {"traffic": {"n": 8, "K": 2}},
         "pagerank.uniform": {"cfg": {"scale": 8}}}


@pytest.fixture
def fresh_caches(tmp_path, monkeypatch):
    """A compile store of the test's own and JAX's persistent cache off,
    so a program changed by a test is compiled, never found."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from repro.core import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    compile_cache.set_default_cache(
        compile_cache.CompileCache(root=tmp_path / "jax" / "repro"))
    yield
    compile_cache.set_default_cache(None)
