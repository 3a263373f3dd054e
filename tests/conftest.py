"""Test harness: an 8-device fake CPU pod.

Mirrors the reference's answer to "multi-node testing without a cluster"
(docker demo network on localhost; SURVEY.md §4): stations are mesh slices,
so N fake CPU devices give an N-slot pod in CI.

XLA_FLAGS is set before jax is imported (the CPU backend reads it when it
initializes): eight devices and the backend's optimisation level 1. Each
process keeps jax's persistent compilation cache in a directory of its own.
`jax.config.update("jax_platforms", "cpu")` holds the suite to the CPU
whatever the environment says: these tests must never take the chip.
"""
import os
import shutil
import sys
import tempfile

# make the suite runnable from any cwd without pip-installing the package:
# the repo root (parent of tests/) is the import root for vantage6_tpu
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

# The suite is bound by compiling, not by running. At backend optimisation
# level 1 (XLA's default is 3) a tiny GPT-2 cell's case takes 14.0 s, not
# 21.6, and a CNN's training 20.2 s, not 26.1; level 0 compiles no faster
# and runs that training ten times slower. Each flag is added only where
# the environment has not set it.
TEST_XLA_FLAGS = {
    "xla_force_host_platform_device_count": "8",
    "xla_backend_optimization_level": "1",
}
flags = os.environ.get("XLA_FLAGS", "")
for name, value in TEST_XLA_FLAGS.items():
    if name not in flags:
        flags += f" --{name}={value}"
os.environ["XLA_FLAGS"] = flags.strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# A test process compiles the same programs again and again (each benchmark
# cell's cases build its round and reference anew), so jax's persistent
# cache keeps every program, the small ones too, in a directory of the
# process's own that goes at the session's end: the GPT-2 packed cell's
# cases take 37.9 s, not 73.8 (58.4 where only compiles of 1 s and more are
# kept). An environment that places the cache keeps its place.
_COMPILE_CACHE = None
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _COMPILE_CACHE = tempfile.mkdtemp(prefix="jax-cache-")
    jax.config.update("jax_compilation_cache_dir", _COMPILE_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

import pytest  # noqa: E402


def pytest_sessionfinish(session):
    if _COMPILE_CACHE:
        shutil.rmtree(_COMPILE_CACHE, ignore_errors=True)


@pytest.fixture
def fresh_compiles():
    """One test with the persistent cache off. The names a program gives its
    operations are in no cache key, so a test that reads them from a
    compiled program must compile it, not load an earlier build of the same
    text under other names."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake CPU devices, got {devs}"
    return devs


@pytest.fixture(scope="session")
def fresh():
    """``fresh(tree)``: a copy of a state. ``FedAvg.run_rounds`` and
    ``run_rounds_async`` consume the state they are handed, so a test that
    steps more than once from one init hands each call a copy."""
    return lambda tree: jax.tree.map(jnp.copy, tree)
