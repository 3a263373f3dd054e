"""What PR 21 (the chip bring-up) added, on the CPU: the smoke's own CPU
mode end to end, its refusal to run without a TPU, a failed check turning
into a non-zero exit, the compile-cache placement, the peaks table, the
one-compile contract of the round engine, and the bench parent's "a device
leg runs once, on the chip, or fails" rule.

The chip itself is not here: `chip_smoke.py` without `--cpu-tiny` is run
through the builder's chip tool (CHANGES.md, PR 21 has both runs).
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import bench  # noqa: E402
import chip_smoke  # noqa: E402


def _smoke(*args: str, env: dict[str, str], timeout: float = 600):
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=timeout, cwd=REPO,
        env={**os.environ, **env},
    )


def test_cpu_tiny_mode_passes_end_to_end(tmp_path):
    """Every phase — collectives included, on 4 virtual devices — at tiny
    size, kernels interpreted; the cache goes where the environment says."""
    cache = tmp_path / "cache"
    proc = _smoke("--cpu-tiny", env={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "JAX_COMPILATION_CACHE_DIR": str(cache),
    })
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    # the last line is the result the driver parses: these keys, no others
    assert json.loads(proc.stdout.rstrip().splitlines()[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }
    summary = lines[-2]
    assert summary["phase"] == "summary" and summary["ok"] is True
    assert summary["phases"] == {p: "ok" for p in chip_smoke.PHASES}
    assert summary["claim"] is None
    assert summary["compile_cache"]["dir"] == str(cache)
    assert cache.is_dir()  # written THERE, and no other directory was set
    assert not (tmp_path / ".jax_cache").exists()
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert all(ln["platform"] == "cpu" for ln in phases.values())
    for name in chip_smoke.PHASES:
        assert phases[name]["ok"] and all(phases[name]["checks"].values())
        assert phases[name]["setup_seconds"] >= 0
    # the flash kernel ran interpreted here, and says so
    assert phases["transformer_flash"]["kernel"]["compiled"] is False


def test_without_the_flag_no_tpu_is_refused():
    """`JAX_PLATFORMS=cpu python chip_smoke.py`: non-zero, and no result."""
    proc = _smoke(env={"JAX_PLATFORMS": "cpu"}, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_alone_in_a_directory_is_refused(tmp_path):
    """A directory that holds `chip_smoke.py` and nothing else of the repo:
    non-zero and no result, whatever jax would find."""
    lone = tmp_path / "chip_smoke.py"
    lone.write_text((REPO / "chip_smoke.py").read_text())
    for args in ([], ["--cpu-tiny"]):
        proc = subprocess.run(
            [sys.executable, str(lone), *args], capture_output=True,
            text=True, timeout=120, cwd=tmp_path,
        )
        assert proc.returncode != 0
        assert proc.stdout == ""
        assert "not found next to this script" in proc.stderr


@pytest.mark.parametrize("broken", ["check", "exception", "nan"])
def test_failed_phase_exits_nonzero(monkeypatch, capsys, broken):
    """A check that does not hold, an exception and a non-finite value each
    fail their phase AND the process; the other phases still run and the
    summary says which one failed. (Phases faked at the table: the real
    ones run in the end-to-end test above.)"""
    def bad(sz, meter, shared):
        if broken == "exception":
            raise RuntimeError("mosaic says no")
        value = float("nan") if broken == "nan" else 1.0
        return {"checks": {"finite": chip_smoke._finite([value]),
                           "holds": broken != "check"}}

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent-unused")
    monkeypatch.setitem(chip_smoke.PHASES, "fedavg_cnn", bad)
    monkeypatch.setitem(
        chip_smoke.PHASES, "task_plane",
        lambda sz, meter, shared: {"checks": {"fine": True}},
    )
    rc = chip_smoke.main(["--cpu-tiny", "--only", "fedavg_cnn,task_plane"])
    out = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
           if ln.startswith("{")]
    assert rc == 1
    assert out[-1] == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu",
                   "count": len(jax.devices())},
    }
    assert out[-2]["ok"] is False and out[-2]["claim"] is None
    assert out[-2]["phases"] == {"fedavg_cnn": "failed", "task_plane": "ok"}
    failed = next(ln for ln in out if ln.get("phase") == "fedavg_cnn")
    assert failed["ok"] is False and failed["platform"] == "cpu"
    assert ("mosaic says no" if broken == "exception" else "checks failed") \
        in failed["error"]


def test_compile_cache_placed_from_outside(monkeypatch):
    from vantage6_tpu.core import compile_cache as cc

    seen = []
    monkeypatch.setattr(
        cc.jax.config, "update", lambda k, v: seen.append((k, v))
    )
    monkeypatch.setenv(cc.ENV_VAR, "/some/dir")
    assert cc.enable_compile_cache() == "/some/dir"
    assert seen == []  # set: nothing is set in code
    monkeypatch.delenv(cc.ENV_VAR)
    assert cc.enable_compile_cache() == str(REPO / ".jax_cache")
    assert seen == [("jax_compilation_cache_dir", str(REPO / ".jax_cache"))]


def test_unknown_device_kind_is_an_error():
    assert bench.device_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError, match="no peaks recorded"):
        bench.device_peaks("TPU v99")


def test_device_leg_refuses_the_cpu(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent-unused")
    monkeypatch.delenv("BENCH_FORCE_CPU", raising=False)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        bench._worker_setup(device_leg=True)
    monkeypatch.setenv("BENCH_FORCE_CPU", "1")
    with pytest.raises(RuntimeError, match="does not run on the CPU"):
        bench._worker_setup(device_leg=True)


def test_second_run_rounds_compiles_nothing(devices):
    """Fed by the first call's outputs, the second `run_rounds` must hit
    the first one's executable: one signature, one compile (on jax 0.9 a
    fresh array and a round's output differ in committed sharding). Server
    Adam, so the optimizer state has leaves that are born unplaced."""
    import jax.numpy as jnp
    import numpy as np
    import optax
    from vantage6_tpu.core.mesh import FederationMesh
    from vantage6_tpu.fed.fedavg import FedAvg, FedAvgSpec

    mesh = FederationMesh(8, devices=devices)
    engine = FedAvg(mesh, FedAvgSpec(
        loss_fn=lambda p, bx, by, w: jnp.mean((bx @ p["w"] - by) ** 2),
        batch_size=4, server_optimizer=optax.adam(1e-2),
    ))
    rng = np.random.default_rng(0)
    sx = mesh.shard_stacked(rng.normal(size=(8, 16, 3)).astype(np.float32))
    sy = mesh.shard_stacked(rng.normal(size=(8, 16)).astype(np.float32))
    counts = np.full((8,), 16.0, np.float32)  # unplaced, like a caller's
    key = jax.random.key(0)
    p, o, _, _ = engine.run_rounds(
        {"w": jnp.zeros(3)}, sx, sy, counts, key, 2
    )
    assert all(
        len(a.sharding.device_set) == 8 for a in jax.tree.leaves((p, o))
    )
    engine.run_rounds(
        p, sx, sy, counts, jax.random.fold_in(key, 2), 2, opt_state=o
    )
    stats = engine._run.stats()
    assert (stats["compiles"], stats["retraces"], stats["fallbacks"]) == (
        1, 0, 0
    ), stats["last_compile"].get("changed")


def test_compile_refusal_raises_once():
    """A program the compiler refuses raises out of the observed dispatch —
    it is not compiled a second time behind a fallback."""
    from vantage6_tpu.runtime.profiling import observed_jit

    calls = []

    def refuse(x):
        calls.append(1)
        raise RuntimeError("mosaic says no")

    f = observed_jit("t.refused", refuse)
    with pytest.raises(RuntimeError, match="mosaic says no"):
        f(jax.numpy.ones(3))
    assert len(calls) == 1 and f.stats()["fallbacks"] == 0


# ------------------------------------------------- bench parent (no jax)
_TPU = {"platform": "tpu", "device_kind": "TPU v5 lite", "n_devices": 1}


def _fake_worker_json(mode: str) -> dict:
    if mode == "spmd":
        return {**_TPU, "rounds_per_sec": 30.0, "round_time_ms": 33.0,
                "run_times_s": [0.16], "n_stations": 32, "rounds_trained": 5,
                "accuracy": 0.8}
    if mode == "fused":
        return {**_TPU, "fused_rounds_per_sec": 31.0, "fused_speedup": 1.5,
                "n_stations": 32}
    if mode == "baseline":
        return {"platform": "cpu", "rounds_per_sec": 0.01, "rounds": 5,
                "rounds_trained": 5, "accuracy": 0.79}
    if mode == "transformer":
        return {**_TPU, "step_time_ms": 150.0, "tokens_per_sec": 1e5,
                "achieved_tflops": 70.0, "attention": "recompute",
                "config": {}, "flops_per_step": 1.1e13}
    if mode == "fedoverhead":
        return {**_TPU, "n_stations": 4, "s1_step_ms": 1.0, "round_ms": 5.0,
                "per_station_ms_in_round": 1.2, "fed_overhead_pct": 20.0,
                "achieved_tflops": 0.1, "config": {}, "flops_per_round": 1e9}
    return {"ok": True, "mode": mode}  # legs stored whole


DEVICE_LEGS = {"spmd", "fused", "agg", "compression", "transformer",
               "fedoverhead"}


@pytest.mark.parametrize("no_tpu", [False, True])
def test_bench_device_legs_run_once_or_fail(monkeypatch, tmp_path, capsys,
                                            no_tpu):
    """Workers faked at the subprocess seam: every leg is started exactly
    once; only the host legs are pinned to the CPU; a device leg that finds
    no TPU fails, stays failed (no CPU retry) and makes the exit non-zero,
    while every other leg still lands, checkpointed after each one."""
    ckpt = tmp_path / "ckpt.json"
    monkeypatch.setenv("BENCH_CHECKPOINT", str(ckpt))
    calls = []

    def fake_run(cmd, capture_output, text, timeout, env):
        mode = cmd[cmd.index("--worker") + 1]
        calls.append((mode, env.get("BENCH_FORCE_CPU")))
        if no_tpu and mode in DEVICE_LEGS:
            return subprocess.CompletedProcess(
                cmd, 1, stdout="",
                stderr="RuntimeError: device leg needs a TPU, jax found 'cpu'",
            )
        return subprocess.CompletedProcess(
            cmd, 0, stdout=json.dumps(_fake_worker_json(mode)) + "\n",
            stderr="",
        )

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    with pytest.raises(SystemExit) as e:
        bench.main()
    lines = [json.loads(ln)
             for ln in capsys.readouterr().out.strip().splitlines()]
    out = lines[-1]
    modes = [m for m, _ in calls]
    assert len(modes) == len(set(modes)) == 13  # once each, no retries
    assert {m for m, cpu in calls if cpu == "1"} == set(modes) - DEVICE_LEGS
    assert out["partial"] is False and json.loads(ckpt.read_text()) == out
    seen = 0  # the cumulative line grows leg by leg
    for doc in lines:
        assert len(doc["legs_done"]) >= seen
        seen = len(doc["legs_done"])
    if no_tpu:
        assert e.value.code == 1
        assert set(out["legs_failed"]) == {
            "spmd", "fused", "agg", "compression", "transformer",
            "fedoverhead",
        }
        assert out["value"] is None and "needs a TPU" in out["error"]
        assert "host_parallel" in out["legs_done"]
        assert "mfu_vs_v5e_bf16_peak" not in out
    else:
        assert e.value.code == 0 and out["legs_failed"] == []
        assert out["value"] == 30.0 and out["platform"] == "tpu"
        assert 0 < out["mfu_vs_v5e_bf16_peak"] < 1
