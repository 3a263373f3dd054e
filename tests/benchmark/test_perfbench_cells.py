"""Each cell's loop at a tiny size on the CPU, through the harness: it agrees
with its plain reference, the reference in the next precision down fails, and
so does the timed path with a fault planted under it. The harness's look for
a chip is skipped here and tested apart."""
from __future__ import annotations

import dataclasses
import subprocess
import sys

import jax
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


# ------------------------------------------------------------------ faults
def _unchanged_state(monkeypatch):
    from vantage6_tpu.fed.fedavg import FedAvg
    from vantage6_tpu.workloads.fed_transformer import FedTransformer

    whole_round = FedTransformer.round

    def round_(self, params, opt_state, tokens, mask):
        return params, opt_state, whole_round(
            self, params, opt_state, tokens, mask)[2]

    whole_run = FedAvg.run_rounds

    def run_rounds(self, params, *a, opt_state=None, **kw):
        out = whole_run(self, params, *a, opt_state=opt_state,
                        **{**kw, "donate": False})
        return (params, opt_state) + tuple(out[2:])

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
