"""Each cell's loop at a tiny size on the CPU, through the harness: it agrees
with its plain reference, the reference in the next precision down fails, and
so does the timed path with a fault planted under it. The harness's look for
a chip is skipped here and tested apart."""
from __future__ import annotations

import dataclasses
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from perfbench import compare, harness
from perfbench_tiny import CELL_NAMES, ROOT, tiny_cell

SEED = 2**31 + 12345


def _run(name, seconds=0.05):
    return harness.run_cell(tiny_cell(name), SEED, seconds, trace=False,
                            require_chip=False)


@pytest.mark.parametrize("name", CELL_NAMES)
def test_cell_agrees_with_its_reference(name, capsys):
    result = _run(name)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["rounds"] >= 1
    compared = [r for r in result["checks"].values() if r["limit"] is not None]
    assert compared, "a cell compares at least one number"
    assert set(result["metrics"]) >= {"setup_s"} and len(result["metrics"]) >= 2
    assert all(m["value"] > 0 for m in result["metrics"].values())
    harness.print_result(result)
    out, err = capsys.readouterr()
    assert out.strip().splitlines()[-1].startswith('{"correct": true')
    assert list(result)[-1] == "checks" and "check " in err


@pytest.mark.parametrize("name", CELL_NAMES)
def test_reference_in_the_next_precision_down_fails(name):
    cell = tiny_cell(name)
    module = cell.reference_module()
    key = harness.key_from_seed(SEED)
    n = cell.traffic["follow_dispatches"] * cell.traffic.get(
        "rounds_per_dispatch", 1)
    fn = getattr(module, cell.traffic["reference"])

    def follow(**kw):
        inputs = module.make_inputs(cell.config, cell.traffic, key)
        return fn(cell.config, cell.traffic, inputs, n, **kw)

    control = follow(precision=cell.traffic["control_precision"])
    correct, rows = compare.judge(
        compare.numbers(control, follow()), cell.limits)
    assert not correct, rows


@pytest.mark.parametrize("name", CELL_NAMES)
def test_the_probe_judges_control_and_faults_by_the_cells_limits(name):
    """`probe.py`, which proves the shipped limits on the chip, at the tiny
    size: the program reads correct, the control and every fault do not."""
    from perfbench import probe

    lines = []
    as_expected = probe.probe(
        tiny_cell(name), seeds=1, control_seeds=1, first_seed=SEED + 1,
        faults=["half_batch", "no_exchange"], write=lines.append,
        require_chip=False)
    assert as_expected, lines
    assert [l["correct"] for l in lines] == [True, False, False, False]
    assert all(l["over_its_limit"] for l in lines[1:])


def test_the_worst_leaf_and_the_median_leaf_are_numbers_of_their_own():
    """One leaf a tenth off fails ``grad_norm`` and leaves
    ``grad_norm_median`` alone; every leaf a hundredth off fails the median's
    limit under the worst leaf's."""
    ref = {"losses": [2.0], "grad_norms": {"a": 1.0, "b": 2.0, "c": 4.0},
           "change_norms": {"a": 1.0, "b": 1.0, "c": 1.0}}
    one_leaf = {**ref, "grad_norms": {"a": 1.0, "b": 2.0, "c": 4.4}}
    found = compare.numbers(one_leaf, ref)
    assert found["grad_norm"] == pytest.approx(0.1)
    assert found["grad_norm_median"] == 0.0
    every_leaf = {**ref, "grad_norms": {"a": 1.01, "b": 2.02, "c": 4.04}}
    found = compare.numbers(every_leaf, ref)
    assert found["grad_norm"] == pytest.approx(0.01)
    assert found["grad_norm_median"] == pytest.approx(0.01)
    limits = {"loss_1": None, "grad_norm": 0.05, "grad_norm_median": 0.003,
              "change_norm": 0.03}
    assert not compare.judge(found, limits)[0]
    assert compare.judge(compare.numbers(ref, ref), limits)[0]
    # a tree of one leaf has no median leaf, and a limit with no number fails
    single = {"losses": [2.0], "grad_norms": {"update": 1.0},
              "change_norms": {"w": 1.0}}
    assert "grad_norm_median" not in compare.numbers(single, single)
    assert not compare.judge(compare.numbers(single, single), limits)[0]


def test_the_control_rounds_operands_forward_and_cotangents_backward():
    import jax.numpy as jnp

    from perfbench.precision import cotangent_rounder, rounder

    x = jnp.array([1.0, 1.0 + 2.0**-10, 3.0])
    before, after = rounder("bfloat16"), cotangent_rounder("bfloat16")
    assert before(x).tolist() == [1.0, 1.0, 3.0]          # rounded forward
    assert after(x).tolist() == x.tolist()                # untouched forward
    g = jax.grad(lambda v: jnp.sum(before(v) * x))(x)
    assert g.tolist() == x.tolist()                       # untouched backward
    g = jax.grad(lambda v: jnp.sum(after(v) * x))(x)
    assert g.tolist() == [1.0, 1.0, 3.0]                  # rounded backward
    for precision in ("float8", "bfloat16"):
        q = rounder(precision)(x)
        assert float(jnp.max(jnp.abs(q - x))) > 0
    assert rounder("float32")(x) is x
    with pytest.raises(ValueError):
        rounder("float4")


@pytest.mark.parametrize("name", CELL_NAMES)
def test_a_traced_runs_line(name, monkeypatch, capsys):
    """The traced branch of a run, which the CPU cannot reach by itself (it
    has no device plane): the tiny cell runs under the profiler, and the
    trace read back is one recorded on the chip."""
    from perfbench import trace

    recorded = str(Path(__file__).parent / "data" / "engine_v5e_cut.xplane.pb")
    monkeypatch.setattr(trace, "find_xplane", lambda log_dir: recorded)
    result = harness.run_cell(tiny_cell(name), SEED, 0.05, trace=True,
                              require_chip=False)
    assert result["correct"], result["checks"]
    reported = {m["name"] for m in tiny_cell(name).per_layer}
    assert set(result["metrics"]) <= reported
    device = result["device"]
    assert 0 < device["busy_s"] <= device["window_s"]
    assert {"memory_peak_bytes", "memory_reserved_bytes"} <= set(device)
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    scopes = result["scopes"]
    assert scopes["rows"] >= len(scopes["top"]) > 1
    assert scopes["sum_s"] <= device["busy_s"]
    harness.print_result(result)
    assert list(result)[-1] == "checks"
    assert capsys.readouterr().out.strip().splitlines()[-1].startswith(
        '{"correct": true')
    assert not harness.TRACE_DIR.exists()


# ------------------------------------------------------------------ faults
def _unchanged_state(monkeypatch):
    """The round runs and its state comes back as it went in: a copy taken
    before the call, since a round that donates its state deletes what it
    was handed."""
    from vantage6_tpu.fed.fedavg import FedAvg
    from vantage6_tpu.workloads.fed_transformer import FedTransformer

    def copy(tree):
        return jax.tree.map(jnp.copy, tree)

    whole_round = FedTransformer.round

    def round_(self, params, opt_state, tokens, mask):
        kept = copy((params, opt_state))
        return *kept, whole_round(self, params, opt_state, tokens, mask)[2]

    whole_run = FedAvg.run_rounds

    def run_rounds(self, params, *a, opt_state=None, **kw):
        kept = copy((params, opt_state))
        return *kept, *whole_run(self, params, *a, opt_state=opt_state,
                                 **kw)[2:]

    monkeypatch.setattr(FedTransformer, "round", round_)
    monkeypatch.setattr(FedAvg, "run_rounds", run_rounds)


def _donated_state(monkeypatch):
    """What `donate_argnums` on the state does to a caller: once the round
    has returned, the buffers it was handed are gone."""
    from vantage6_tpu.fed.fedavg import FedAvg
    from vantage6_tpu.workloads.fed_transformer import FedTransformer

    def delete(tree):
        for leaf in jax.tree.leaves(tree):
            if not leaf.is_deleted():
                leaf.delete()

    whole_round = FedTransformer.round

    def round_(self, params, opt_state, tokens, mask):
        out = jax.block_until_ready(
            whole_round(self, params, opt_state, tokens, mask))
        delete((params, opt_state))
        return out

    whole_run = FedAvg.run_rounds

    def run_rounds(self, params, *a, opt_state=None, **kw):
        out = jax.block_until_ready(
            whole_run(self, params, *a, opt_state=opt_state, **kw))
        delete((params, opt_state))
        return out

    monkeypatch.setattr(FedTransformer, "round", round_)
    monkeypatch.setattr(FedAvg, "run_rounds", run_rounds)


def _half_batch(monkeypatch):
    from vantage6_tpu.fed.fedavg import FedAvg
    from vantage6_tpu.workloads.fed_transformer import FedTransformer

    shard = FedTransformer.shard_tokens
    monkeypatch.setattr(
        FedTransformer, "shard_tokens",
        lambda self, t: shard(self, t[:, : t.shape[1] // 2]))
    init = FedAvg.__init__
    monkeypatch.setattr(
        FedAvg, "__init__",
        lambda self, mesh, spec: init(self, mesh, dataclasses.replace(
            spec, batch_size=spec.batch_size // 2)))


def _no_exchange(monkeypatch):
    from vantage6_tpu.fed import collectives, fedavg

    def first_station_only(stacked, weights=None, mask=None):
        return jax.tree.map(lambda x: x[0], stacked)

    monkeypatch.setattr(collectives, "fed_mean", first_station_only)
    monkeypatch.setattr(fedavg, "fed_mean", first_station_only)


FAULTS = {"state_unchanged": _unchanged_state, "half_batch": _half_batch,
          "no_exchange": _no_exchange}
PLANTED = [(name, fault) for name in CELL_NAMES for fault in sorted(FAULTS)]


@pytest.mark.parametrize("name,fault", PLANTED)
def test_a_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    result = _run(name)
    assert not result["correct"], (fault, result["checks"])


@pytest.mark.parametrize("name", CELL_NAMES)
def test_an_unchanged_state_reads_not_correct_where_the_round_donates_it(
        name, monkeypatch):
    """The fault hands back a copy taken before the call, so it is read as
    `correct: false`, and not as a deleted buffer, once the program donates
    `params` and `opt_state`."""
    _donated_state(monkeypatch)
    assert _run(name)["correct"]      # the harness touches no donated buffer
    _unchanged_state(monkeypatch)
    result = _run(name)
    assert not result["correct"], result["checks"]


# ---------------------------------------------------------------- no chip
def test_a_run_that_finds_no_tpu_fails_and_prints_no_metric():
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL_NAMES[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(ROOT / ".perfbench")},
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no result" in proc.stderr


def test_fewer_chips_than_the_cell_asks_for_is_no_chip():
    with pytest.raises(harness.NoChip):
        harness.find_devices(len(jax.devices()) + 1, require_chip=False)
    with pytest.raises(harness.NoChip):
        harness.find_devices(1, require_chip=True)  # the tests run on the CPU


def test_alone_in_a_directory_the_command_fails(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL_NAMES[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_seeds_beyond_32_bits_make_different_keys():
    a, b = harness.key_from_seed(5), harness.key_from_seed(2**32 + 5)
    assert not (jax.random.key_data(a) == jax.random.key_data(b)).all()


# ------------------------------------------- the cache and a traced run's names
def test_a_traced_run_never_loads_a_program_compiled_under_other_names(
        tmp_path, monkeypatch):
    """The scopes are in no cache key, so an untraced run loads what a tree
    with other scope names compiled, names and all; a traced run, whose
    per-layer metrics read those names, keys its programs by them and
    compiles its own."""
    from jax.experimental.compilation_cache import compilation_cache

    from perfbench import cells
    from perfbench.meter import CompileMeter

    def program(scope):  # two trees' programs: alike but for a scope's name
        def f(x):
            with jax.named_scope(scope):
                return jnp.sin(x) * 2.0 + 1.0
        return jax.jit(f)

    x = jnp.arange(8.0)

    def loaded_from_the_cache(*scopes):
        """Each program in turn, from one line: with the names in the key,
        the lines of the calling frames are in it too."""
        found = []
        for scope in scopes:
            jax.clear_caches()
            before = meter.compiles, meter.cache_hits
            program(scope)(x).block_until_ready()
            assert meter.compiles - before[0] == 1   # built or loaded: once
            found.append(meter.cache_hits - before[1] == 1)
        return found

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cells, "ROOT", tmp_path)
    kept = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_compilation_cache_include_metadata_in_key",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    meter = CompileMeter()
    try:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        harness.enable_compile_cache()
        other = "attention_of_another_tree"
        # untraced: the other tree's program is served under stale names
        assert loaded_from_the_cache("attention", "attention", other) == [
            False, True, True]
        harness.enable_compile_cache(traced=True)
        # traced: nothing an untraced run left, then its own entry, and
        # never the other tree's
        assert loaded_from_the_cache(
            "attention", "attention", other, "attention") == [
            False, True, False, True]
        assert (tmp_path / ".jax_cache").is_dir()
    finally:
        meter.close()
        for name, value in kept.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
        jax.clear_caches()
