"""Test harness: an 8-device fake CPU pod.

Mirrors the reference's answer to "multi-node testing without a cluster"
(docker demo network on localhost; SURVEY.md §4): stations are mesh slices,
so N fake CPU devices give an N-slot pod in CI.

XLA_FLAGS is set before jax is imported (the CPU backend reads it when it
initializes), and `jax.config.update("jax_platforms", "cpu")` holds the
suite to the CPU whatever the environment says: these tests must never take
the chip.
"""
import os
import sys

# make the suite runnable from any cwd without pip-installing the package:
# the repo root (parent of tests/) is the import root for vantage6_tpu
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


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
