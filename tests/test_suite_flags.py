"""What `conftest.py` sets up for the test processes before jax starts: the
XLA flags (eight CPU devices, the backend's optimisation level 1), each only
where the environment has not named that flag itself, and jax's persistent
compilation cache in a directory of the process's own, unless the
environment places the cache."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

CONFTEST = Path(__file__).resolve().parent / "conftest.py"
DEVICES = "--xla_force_host_platform_device_count=8"
LEVEL = "--xla_backend_optimization_level=1"


def _after_conftest(**env) -> dict:
    """XLA_FLAGS and the cache's directory as a fresh process holds them once
    the conftest has run, and whether that directory is there after the
    session's end."""
    script = (
        "import json, os, runpy, jax\n"
        "conftest = runpy.run_path(%r)\n"
        "cache = jax.config.jax_compilation_cache_dir\n"
        "made = cache is not None and os.path.isdir(cache)\n"
        "conftest['pytest_sessionfinish'](None)\n"
        "print(json.dumps({'flags': os.environ['XLA_FLAGS'], 'cache': cache,"
        " 'made': made, 'kept': cache is not None and os.path.isdir(cache)}))"
        % str(CONFTEST))
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=300, env={**{k: v for k, v in os.environ.items()
                             if k != "JAX_COMPILATION_CACHE_DIR"},
                          "XLA_FLAGS": "", **env})
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_test_process_carries_both_flags():
    flags = os.environ["XLA_FLAGS"].split()
    assert DEVICES in flags and LEVEL in flags
    assert len(jax.devices()) == 8


@pytest.mark.parametrize("mine, added", [
    ("--xla_backend_optimization_level=2", DEVICES),
    ("--xla_force_host_platform_device_count=4", LEVEL),
])
def test_a_flag_set_in_the_environment_is_kept(mine, added):
    assert _after_conftest(XLA_FLAGS=mine)["flags"].split() == [mine, added]


def test_an_environment_naming_both_flags_is_left_alone():
    mine = ("--xla_force_host_platform_device_count=2 "
            "--xla_backend_optimization_level=3")
    assert _after_conftest(XLA_FLAGS=mine)["flags"] == mine


def test_the_compile_cache_is_the_process_own_and_goes_at_the_end():
    seen = _after_conftest()
    repo = CONFTEST.parents[1]
    assert seen["made"] and not seen["kept"]
    assert repo not in Path(seen["cache"]).resolve().parents
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_a_cache_placed_by_the_environment_is_kept(tmp_path):
    seen = _after_conftest(JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert seen["cache"] == str(tmp_path)
    assert seen["made"] and seen["kept"]
