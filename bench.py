"""Benchmark: federated rounds/sec, 32-station FedAvg CNN (BASELINE.md),
plus an MXU-utilization metric on the federated transformer.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras},
re-printed (cumulative) after every finished leg.

- SPMD path: the FedAvg engine — all 32 stations' local training + weighted
  aggregation as one jitted SPMD program, multi-round via lax.scan. It is a
  DEVICE leg, like fused, agg, compression, transformer and fedoverhead: a
  device leg runs on a TPU whose `device_kind` is in DEVICE_PEAKS, or it
  FAILS. Nothing retries it on the CPU, and no CPU number is ever written
  under a device metric's name.
- Baseline: the reference's execution shape (SURVEY.md §3.2) emulated
  *generously* on CPU — sequential per-station local training through JSON
  payload (de)serialization per hop, but NO docker container lifecycle, NO
  HTTPS, NO polling intervals. The reference's real per-round cost is
  dominated by exactly those omitted parts, so the reported speedup is a
  conservative lower bound. It is a HOST leg, like hostparallel,
  controlplane, cpscale, observability, wireformat and autopilot: the host
  is their deployment platform, so they pin JAX to the CPU.
- Transformer: one federated training step of the long-context workload at
  an MXU-friendly size (bf16, d_model 1024, seq 1024) with analytic FLOPs —
  the metric where "TPU-native" means hardware utilization, not just
  "faster than a sequential CPU loop" (VERDICT r2 weak #2).

Accuracy parity (BASELINE.md criterion): both FedAvg paths train the same
number of rounds and evaluate their final model on the SAME held-out set;
both accuracies and their gap are reported.

Timing protocol: every measurement compiles once, runs once warm, then
times TIMED_RUNS executions — each chained from the previous one's outputs
and ended by `block_until_ready` (dispatch is asynchronous; without it the
clock measures the enqueue) — and reports the median. Derived MFU is
sanity-checked: mfu > 1 is physically impossible and flips "timing_valid"
to false instead of publishing an impossible number.

Process architecture: a chip belongs to ONE process at a time, so the
parent NEVER initializes a JAX backend; every leg runs in its own worker
subprocess, one at a time, under a hard timeout.

Budget protocol (VERDICT r4 weak #1 — BENCH_r04 was rc=124/empty): the
whole run fits ONE overall wall-clock budget (BENCH_BUDGET_S, default
3000 s). Per-leg timeouts are derived as min(leg nominal, time remaining),
a leg whose remaining window is too small is SKIPPED with a diagnostic
instead of started, and the cumulative result JSON is re-printed after
EVERY completed leg — the driver parses the LAST valid line, so a kill at
any moment preserves every leg that finished. The exit code is non-zero
when any leg that was started failed.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

N_STATIONS = 32
N_PER_STATION = 256
LOCAL_STEPS = 10
BATCH = 32
LR = 0.05
# Rounds per timed execution AND the accuracy-parity leg. 5 keeps the CPU
# baseline inside its budget: its per-round cost is ~140 s compute + ~230 s
# compile on this host (phase_seconds in the worker output), so 5 rounds +
# 5 hop-instrumented timing rounds + eval ~= 1000 s < WORKER_TIMEOUT_S.
# On TPU a timed run is then ~180 ms — ample resolution.
SPMD_ROUNDS = 5
# Synthetic-task difficulty for BOTH FedAvg legs and the eval set. At the
# historical 0.7 both paths saturate at accuracy 1.0 after 5 rounds and the
# parity check proves nothing (VERDICT r3 weak #2). Calibrated on an
# 8-station CPU proxy of the bench config (same local steps/batch/lr/
# rounds/Dirichlet): noise 2.0 -> 0.81, 3.0 -> 0.51, 4.0 -> 0.26 five-round
# accuracy; 2.0 lands in the 0.7-0.9 band where a real aggregation bug has
# room to move the gap. Ignored when real MNIST files exist.
SYNTH_NOISE = 2.0
TIMED_RUNS = 3          # median of this many post-discard executions
BASELINE_TIMING_ROUNDS = 5   # >= 5 measured rounds (VERDICT r1/r2)
BASELINE_TIMING_STATIONS = 4  # hop-instrumented stations per timing round
BASELINE_MAX_S = 900.0  # stop the baseline accuracy loop after this much
WORKER_TIMEOUT_S = 1500
# Overall wall-clock budget for the WHOLE bench (VERDICT r4 weak #1: the
# r4 leg budgets summed to ~7900 s worst case, any driver window was
# exceeded, and the one end-of-main print meant rc=124 erased everything).
# Per-leg timeouts are derived from what remains of this budget; the
# BUDGET_MARGIN_S reserve guarantees the final JSON line gets printed.
BENCH_BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "3000"))
BUDGET_MARGIN_S = 60.0
MIN_LEG_S = 45.0        # don't even start a leg with less than this left
# agg_modes leg (sharded server update): 3 modes x (compile + warm +
# timed chain) of a tiny 8-station/2-round config — ~2-4 min on this host.
AGG_TIMEOUT_S = 600
# host_parallel leg (station executor pool): sequential vs pooled host-path
# rounds/sec at HOST_STATIONS stations with a sleep-padded partial — pure
# scheduling comparison, seconds of wall-clock, CPU only.
HOST_TIMEOUT_S = 240
# control_plane leg (control-plane fast path PR): one in-process server +
# CP_DAEMONS real node daemons over HTTP, CP_TASKS small partial tasks
# submitted back to back, measured twice — per-run endpoints + fixed-
# interval polling (the pre-PR shape) vs batched claim/report + long-poll
# event wakeups. Reports submit→result-visible p50/p95, run dispatch
# (assigned→started) p50/p95, tasks/sec, REST calls/task, and a
# cross-arm results-parity flag. Host CPU only by design.
CONTROL_TIMEOUT_S = 420
CP_DAEMONS = 8
CP_TASKS = 40
CP_WIDTH = 2          # organizations targeted per task
# control_plane_scale leg (horizontal scale-out PR): 1 vs 2 STATELESS
# server replica PROCESSES over one shared sqlite+wal store, same daemon
# fleet (in the worker process) + same task load on each arm. The client
# pipelines CPS_TASKS tiny partials (create all, then collect), daemons
# spread their primary api_url round-robin across the replicas and only
# fail over on connection errors. Reports tasks/sec per arm, the 1->2
# speedup, a zero-double-dispatch count (activation CAS losers + won-vs-
# expected mismatch), cross-arm results parity, and per-replica request
# attribution read off each replica's own V6T_TRACE_FILE span sink.
CPSCALE_TIMEOUT_S = 900
CPS_REPLICAS = 2      # scaled arm size (arms are 1 vs CPS_REPLICAS)
CPS_DAEMONS = 8
CPS_TASKS = 1000
CPS_WIDTH = 1         # one org per task: runs == tasks, pure throughput
# observability leg (tracing + telemetry PR): the control_plane mini
# topology run with distributed tracing OFF vs ON (same transport, same
# tasks), arms ALTERNATED to decorrelate machine noise and best-of per
# arm compared — the instrumentation must never become the bottleneck it
# measures (< 5% tasks/sec overhead). The traced arm additionally proves
# one task's trace covers create→dispatch→claim→exec→report→aggregate,
# exports valid Perfetto trace_event JSON, and parses GET /metrics.
OBS_TIMEOUT_S = 540
OBS_DAEMONS = 4
OBS_TASKS = 24
OBS_REPS = 2          # off/trace/ops triples (alternated)
OBS_OVERHEAD_PCT = 5.0
# watchdog/flight extension (ops-plane PR): a THIRD alternated arm runs
# the full ops plane (tracing + watchdog at an operator cadence +
# structured JSON logging + flight-recorder taps). overhead_pct keeps its
# PR-5 meaning (tracing vs bare); ops_overhead_pct isolates what the ops
# plane adds ON TOP of tracing, against the same <5% budget. After the
# overhead arms, a fault-injection smoke kills one daemon mid-round and
# wedges one run past its deadline: the watchdog must raise daemon_lapsed
# + stuck_run within one evaluation interval, /api/health must flip to
# degraded, and a flight dump must doctor into a trace-correlated
# timeline naming the stuck run.
OBS_WD_ARM_INTERVAL = 2.0  # watchdog cadence in the ON overhead arm — a
                           # fast-but-plausible operator setting (default
                           # 5 s); the whole topology shares one python
                           # process in this bench, so the smoke's 0.4 s
                           # detection cadence would bill GIL contention
                           # no multi-process deployment pays
OBS_WD_INTERVAL = 0.4      # watchdog eval cadence in the fault smoke
OBS_WD_DEADLINE = 1.0      # stuck-run deadline in the smoke
OBS_WD_PING_WINDOW = 1.2   # daemon_lapsed window in the smoke
OBS_FLEET_PUSH_S = 0.5     # daemon fleet-push cadence in the fleet arm —
                           # deliberately 30x the production default (15 s,
                           # V6T_FLEET_PUSH_INTERVAL) so the <5%
                           # fleet_overhead_pct budget is measured against
                           # a HARDER duty cycle than any real deployment
                           # pays
# wire_format leg (binary wire PR): v1 JSON+base64 vs v2 framed-binary
# (de)serialization throughput + on-wire bytes on model-weight pytrees and a
# DataFrame stats table, plus single-pass broadcast encryption cost when the
# cryptography package is present (4096-bit keygen is seconds; AES of the
# payloads is milliseconds). Pure host CPU work.
WIRE_TIMEOUT_S = 300
WIRE_MB_SIZES = (1, 10, 32)   # pytree payload sizes (MiB of f32 weights)
WIRE_REPS = 3                 # timed reps per measurement (median-free mean)
WIRE_BROADCAST_N = 8          # acceptance: broadcast-to-8 within 2x single
# compression leg (gradient-compression PR, the wire leg's extension):
# dense vs compressed (stochastic int8 + top-k + error feedback) delta
# exchange on the FedAvg-CNN run — the acceptance numbers are >=4x on-wire
# delta reduction at accuracy parity, with the jitted compress/decompress
# cost (device.compress spans) under 10% of round time. Sized like the
# agg_modes leg: small local compute, the DELTA EXCHANGE is the subject.
COMPRESS_TIMEOUT_S = 600
# autopilot leg (robustness PR): buffered-async straggler resilience —
# one V6T_FAULTS-delayed station of AP_STATIONS, sync rounds crater to
# ~1/delay while run_buffered (quorum S-1, over-select 1) must hold >=
# AP_RESILIENCE_PCT of the clean sync rounds/sec at aggregate parity —
# plus the closed-loop smoke: a label-flip-poisoned station is
# auto-masked by the autopilot (anomalous_station -> mask_station),
# accuracy recovers hands-off, and the mask reverts on alert clear.
AP_TIMEOUT_S = 420
AP_STATIONS = 8
AP_ROUNDS = 6
AP_RESILIENCE_PCT = 80.0
COMPRESS_STATIONS = 8
COMPRESS_TOPK = 0.1           # keep 10% of coordinates
COMPRESS_ACC_TOL = 0.08       # lossy exchange: wider than ACC_TOLERANCE
COMPRESS_COST_PCT = 10.0      # device.compress budget vs round time
HOST_STATIONS = 4
HOST_ROUNDS = 6
HOST_PAD_S = 0.05
# fused leg (fused multi-round device program PR): ONE K-round lax.scan
# dispatch + one host pull vs K per-round dispatches each ending in a
# host pull of the loss (the pre-PR `Federation.run` driver shape), at the
# headline 32-station config.
FUSED_TIMEOUT_S = 600
FUSED_ROUNDS = 32           # K rounds per fused dispatch
ACC_TOLERANCE = 0.05    # |acc_spmd - acc_baseline| for "accuracy_parity"
# Published per-chip peaks, keyed by the `device_kind` jax reports. MFU
# figures are taken against the bf16 peak (the CNN runs f32 on data this
# small — the bf16 peak is the honest *upper* reference either way). A
# device that is not in the table is an error, never a default.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e" (one chip)
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def device_peaks(device_kind: str) -> dict:
    """The peaks row of ``device_kind``; an unknown kind raises."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks recorded for device_kind {device_kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}) — add its published peaks and their "
            "source to bench.DEVICE_PEAKS"
        ) from None

# MXU-friendly transformer bench shape (single chip). Batch 16 measured
# best on the v5e (B8: 34.5% MFU, B16: 37.7%, B32: OOM).
TF_D, TF_LAYERS, TF_HEADS, TF_SEQ, TF_BATCH, TF_VOCAB = 1024, 8, 8, 1024, 16, 4096

# Federation-overhead shape (VERDICT r3 weak #4): the transformer at a size
# where FO_STATIONS stations pack onto ONE chip (stations_per_slot>1), so
# the same model can be timed as an S-station federated round AND as a
# plain S=1 step — the ratio round_time / (S * step_time) is what the
# federated packing + fed_mean aggregation actually cost at MXU scale.
FO_STATIONS = 4
FO = dict(d=512, layers=4, heads=8, seq=512, batch=8, vocab=4096)


def cnn_train_flops_per_round(n_stations: int = N_STATIONS) -> float:
    """Analytic FLOPs of one federated round (all stations).

    Per-example forward FLOPs of models/cnn.py on 28x28x1 input
    (SAME-padded 3x3 convs, 2 FLOPs per MAC):
      conv1: 28*28 positions * 32 ch * (3*3*1) MACs * 2
      conv2: 14*14 positions * 64 ch * (3*3*32) MACs * 2
      dense1: (7*7*64) * 128 * 2
      dense2: 128 * 10 * 2
    A training step costs ~3x forward (backward ~= 2x forward); pooling/relu/
    softmax are bandwidth-bound noise at this scale and are excluded.
    """
    conv1 = 28 * 28 * 32 * (3 * 3 * 1) * 2
    conv2 = 14 * 14 * 64 * (3 * 3 * 32) * 2
    dense1 = (7 * 7 * 64) * 128 * 2
    dense2 = 128 * 10 * 2
    fwd_per_example = conv1 + conv2 + dense1 + dense2
    return 3.0 * fwd_per_example * BATCH * LOCAL_STEPS * n_stations


def transformer_train_flops(
    d: int, n_layers: int, seq: int, batch: int, vocab: int
) -> float:
    """Analytic FLOPs of one training step (fwd*3), model FLOPs only.

    Per token forward:
      qkv proj     2 * d * 3d           = 6 d^2
      out proj     2 * d * d            = 2 d^2
      mlp          2 * d * 4d * 2       = 16 d^2
      attention    causal QK^T + PV: avg (T+1)/2 keys/query, 2*2d per key
                                        = 2 d (T+1)
      (per layer: 24 d^2 + 2 d (T+1))
      lm head      2 * d * vocab
    Causal attention counts the REQUIRED (T+1)/2 average context, not the
    full T the kernel may compute — conservative for MFU.
    """
    per_layer = 24.0 * d * d + 2.0 * d * (seq + 1)
    fwd_per_token = n_layers * per_layer + 2.0 * d * vocab
    return 3.0 * fwd_per_token * batch * seq


from statistics import median as _median


# --------------------------------------------------------------- subprocess
def _run_worker(mode: str, *, force_cpu: bool, timeout_s: float,
                extra_env: dict[str, str] | None = None
                ) -> tuple[dict | None, str]:
    """Run `python bench.py --worker <mode>` and parse its last stdout line.

    Returns (parsed json or None, diagnostic). force_cpu is for the HOST
    legs only: it adds the fake-pod XLA flag and tells the worker to pin
    jax_platforms=cpu before any device touch. A device leg is never run
    with it.
    """
    env = dict(os.environ)
    if extra_env:
        env.update(extra_env)
    if force_cpu:
        env["BENCH_FORCE_CPU"] = "1"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--worker", mode],
            capture_output=True, text=True, timeout=timeout_s, env=env,
        )
    except subprocess.TimeoutExpired:
        return None, f"{mode}: timeout after {timeout_s:.0f}s"
    if proc.returncode != 0:
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()[-3:]
        return None, f"{mode}: rc={proc.returncode}: {' | '.join(tail)}"
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            return json.loads(line), "ok"
        except json.JSONDecodeError:
            continue
    return None, f"{mode}: no json in output"


# ------------------------------------------------------------------ workers
def _worker_setup(device_leg: bool = False):
    """Every worker's first call. A host leg arrives with BENCH_FORCE_CPU
    and is pinned to the CPU; a DEVICE leg must find a TPU that is in the
    peaks table, and fails here — before any measurement — when it does
    not. Compiled programs go to the one compile cache."""
    import jax

    if os.environ.get("BENCH_FORCE_CPU"):
        if device_leg:
            raise RuntimeError("a device leg does not run on the CPU")
        jax.config.update("jax_platforms", "cpu")
    from vantage6_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    if device_leg:
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            raise RuntimeError(
                f"device leg needs a TPU, jax found {dev.platform!r}"
            )
        device_peaks(dev.device_kind)
    return jax


def _eval_data():
    """The held-out evaluation set BOTH FedAvg paths are scored on: the real
    MNIST test split when files exist, else fresh draws (seed disjoint from
    every training seed) from the same synthetic template task."""
    from vantage6_tpu.utils import datasets as D

    real = D.load_mnist(split="test")
    if real is not None:
        x, y = real
        return x[:4096], y[:4096]
    return D.synthetic_image_classes(2048, seed=777, noise=SYNTH_NOISE)


def _timed(jax, step, state, n: int = TIMED_RUNS):
    """`n` timed executions of `step(state, i) -> (state, out)`: each run's
    inputs chain from the previous run's outputs, and each ends in
    `block_until_ready` (dispatch is asynchronous — without it the clock
    measures the enqueue). Callers run the program once, warm, before.

    Returns (final_state, per-run seconds).
    """
    times = []
    for i in range(n):
        t0 = time.perf_counter()
        state, out = step(state, i)
        jax.block_until_ready((state, out))
        times.append(time.perf_counter() - t0)
    return state, times


def _fresh(jax, tree):
    """A copy of ``tree``: the fused programs consume the ``params`` and
    ``opt_state`` they are handed, so a state that is stepped from more
    than once is copied first."""
    return jax.tree.map(jax.numpy.copy, tree)


def worker_spmd() -> None:
    """rounds/sec of the one-program SPMD FedAvg path + final accuracy.

    AOT: `.lower().compile()` once, then warm + TIMED_RUNS timed executions
    of the SAME executable (median reported) — no second trace/compile for
    a different round count."""
    jax = _worker_setup(device_leg=True)

    from vantage6_tpu.core.mesh import FederationMesh
    from vantage6_tpu.workloads import fedavg_mnist as W

    rounds = SPMD_ROUNDS
    mesh = FederationMesh(N_STATIONS)
    engine = W.make_engine(
        mesh, local_steps=LOCAL_STEPS, batch_size=BATCH, local_lr=LR,
        learning_stats=False,  # pure-throughput leg: no discarded stats
    )
    sx, sy, counts = W.make_federated_data(
        N_STATIONS, n_per_station=N_PER_STATION, mesh=mesh,
        noise=SYNTH_NOISE,
    )
    key = jax.random.key(0)
    params = W.init_params(jax.random.fold_in(key, 1))
    # placed as the engine's own entry places them: the executable is
    # compiled for the shardings its outputs come back in
    params, opt_state, counts, mask, key = engine._place(
        params, engine.init(params), counts, jax.numpy.ones_like(counts), key
    )
    args = (params, opt_state, sx, sy, counts, mask, key)
    t0 = time.perf_counter()
    compiled = engine._run.lower(*args, n_rounds=rounds).compile()
    compile_s = time.perf_counter() - t0
    # warm (buffer placement)
    jax.block_until_ready(compiled(*_fresh(jax, args[:2]), *args[2:]))

    def step(state, i):
        p, o = state
        p, o, losses, _ = compiled(
            p, o, sx, sy, counts, mask, jax.random.fold_in(key, 100 + i)
        )
        return (p, o), losses

    _, times = _timed(jax, step, _fresh(jax, (params, opt_state)))
    dt = _median(times)
    # the timed chain's final params are TIMED_RUNS * rounds deep into
    # training; evaluate a FRESH acc-leg run from init instead so both
    # paths are compared at the same round count
    p_acc, _, losses, _ = compiled(
        params, opt_state, sx, sy, counts, mask, key
    )
    ex, ey = _eval_data()
    acc = W.evaluate(p_acc, ex, ey)
    print(json.dumps({
        "rounds_per_sec": rounds / dt,
        "round_time_ms": 1e3 * dt / rounds,
        "rounds_measured": rounds,
        "run_times_s": [round(t, 4) for t in times],
        "compile_seconds": round(compile_s, 1),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": len(jax.devices()),
        "n_stations": N_STATIONS,
        "final_loss": float(losses[-1]),
        "accuracy": round(acc, 4),
        "rounds_trained": rounds,
    }))


def worker_fused() -> None:
    """Fused multi-round device program vs per-round dispatch.

    The sequential arm is the per-round driver shape: K dispatches of the
    public `engine.round()` (observed_jit dispatch, history hook, inner
    local-steps lax.scan), each followed by a host pull of the loss. The
    fused arm is ONE `run_rounds` executable (scan form) for all K rounds
    with a single host pull.

    Correctness in-leg: the fused program is compared with K sequential
    `round()` calls from the same init/key — bit-identity is recorded
    (`fp32_identical_scan_form`), the largest difference reported."""
    jax = _worker_setup(device_leg=True)
    import numpy as np
    import jax.numpy as jnp

    from vantage6_tpu.core.mesh import FederationMesh
    from vantage6_tpu.workloads import fedavg_mnist as W

    k_rounds = FUSED_ROUNDS
    mesh = FederationMesh(N_STATIONS)
    engine = W.make_engine(
        mesh, local_steps=LOCAL_STEPS, batch_size=BATCH, local_lr=LR,
        learning_stats=False,
    )
    sx, sy, counts = W.make_federated_data(
        N_STATIONS, n_per_station=N_PER_STATION, mesh=mesh,
        noise=SYNTH_NOISE,
    )
    key = jax.random.key(0)
    params = W.init_params(jax.random.fold_in(key, 1))
    params, opt_state, counts, mask, key = engine._place(
        params, engine.init(params), counts, jnp.ones_like(counts), key
    )

    t0 = time.perf_counter()
    fused = engine._run.lower(
        params, opt_state, sx, sy, counts, mask, key, n_rounds=k_rounds,
    ).compile()
    compile_s = time.perf_counter() - t0

    # identity oracle: K PUBLIC round() calls from the same init, over the
    # same key stream run_rounds derives
    key_id = jax.random.fold_in(key, 2)
    ps, os_ = params, opt_state
    seq_losses = []
    for rk in jax.random.split(key_id, k_rounds):
        ps, os_, loss, _ = engine.round(
            ps, os_, sx, sy, counts, rk, mask=mask
        )
        seq_losses.append(float(loss))
    pf, _, losses_f, _ = fused(
        *_fresh(jax, (params, opt_state)), sx, sy, counts, mask, key_id
    )
    identical = all(
        bool(jnp.array_equal(a, b))
        for a, b in zip(jax.tree.leaves(pf), jax.tree.leaves(ps))
    ) and bool(np.array_equal(
        np.asarray(losses_f), np.asarray(seq_losses, np.float32)
    ))
    max_diff = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(pf), jax.tree.leaves(ps))
    )
    ex, ey = _eval_data()
    acc_fused = W.evaluate(pf, ex, ey)
    acc_seq = W.evaluate(ps, ex, ey)

    jax.block_until_ready(
        fused(*_fresh(jax, (params, opt_state)), sx, sy, counts, mask, key)
    )

    def fused_step(state, i):
        p, o = state
        p, o, losses, _ = fused(
            p, o, sx, sy, counts, mask, jax.random.fold_in(key, 100 + i)
        )
        return (p, o), losses

    def seq_step(state, i):
        p, o = state
        loss = None
        for rk in jax.random.split(jax.random.fold_in(key, 100 + i), k_rounds):
            p, o, loss, _ = engine.round(p, o, sx, sy, counts, rk, mask=mask)
            float(loss)  # per-round host pull: the per-round driver shape
        return (p, o), loss

    _, f_times = _timed(jax, fused_step, _fresh(jax, (params, opt_state)))
    _, s_times = _timed(jax, seq_step, (params, opt_state))
    fused_dt, seq_dt = _median(f_times), _median(s_times)
    print(json.dumps({
        "fused_rounds_per_sec": k_rounds / fused_dt,
        "sequential_rounds_per_sec": k_rounds / seq_dt,
        "fused_speedup": seq_dt / fused_dt,
        "rounds_per_dispatch": k_rounds,
        "fused_round_time_ms": round(1e3 * fused_dt / k_rounds, 4),
        "sequential_round_time_ms": round(1e3 * seq_dt / k_rounds, 4),
        "host_pulls_fused": 1,
        "host_pulls_sequential": k_rounds,
        "fp32_identical_scan_form": identical,
        "fused_vs_rounds_max_abs_diff": max_diff,
        "accuracy_fused": round(acc_fused, 4),
        "accuracy_sequential": round(acc_seq, 4),
        "run_times_fused_s": [round(t, 4) for t in f_times],
        "run_times_sequential_s": [round(t, 4) for t in s_times],
        "compile_seconds": round(compile_s, 1),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": len(jax.devices()),
        "n_stations": N_STATIONS,
        "local_steps": LOCAL_STEPS,
        "batch": BATCH,
        "final_loss": float(losses_f[-1]),
    }))


def worker_transformer() -> None:
    """MXU-utilization metric: one federated transformer training step at an
    MXU-friendly size (bf16 compute, f32 master weights). Attention is
    `recompute` (the shape of the one prior chip row) unless BENCH_FLASH=1
    selects the compiled Pallas kernel; whichever is selected runs or the
    leg fails — neither gives way to the other."""
    jax = _worker_setup(device_leg=True)
    import jax.numpy as jnp

    from vantage6_tpu.workloads import fed_transformer as FT

    d, layers, heads, vocab = TF_D, TF_LAYERS, TF_HEADS, TF_VOCAB
    batch = int(os.environ.get("BENCH_TF_BATCH", TF_BATCH))
    seq = int(os.environ.get("BENCH_TF_SEQ", TF_SEQ))
    attention = (
        "flash" if os.environ.get("BENCH_FLASH") == "1" else "recompute"
    )
    # BENCH_TF_REMAT=1: per-layer rematerialization — activation memory
    # O(1) in depth, ~+1/3 FLOPs; the knob that lets larger batch/seq fit
    # (B32 OOMed without it at the default shape)
    remat = os.environ.get("BENCH_TF_REMAT", "0") == "1"

    cfg = FT.TransformerConfig(
        vocab=vocab, d_model=d, n_heads=heads, n_layers=layers,
        max_len=seq, dtype=jnp.bfloat16, attention=attention, remat=remat,
    )
    eng = FT.make_engine(n_stations=1, seq_devices=1, cfg=cfg, lr=1e-3)
    tokens = eng.shard_tokens(
        FT.make_federated_tokens(1, batch=batch, seq_len=seq, vocab=vocab)
    )
    params, opt = eng.init(jax.random.key(0))
    mask = jnp.ones(1)
    t0 = time.perf_counter()
    # compile + warm; a round consumes its state, so go on from its outputs
    params, opt, _ = jax.block_until_ready(
        eng.round(params, opt, tokens, mask))
    compile_s = time.perf_counter() - t0

    def step(state, i):
        p, o = state
        p, o, loss = eng.round(p, o, tokens, mask)
        return (p, o), loss

    (p, opt), times = _timed(jax, step, (params, opt))
    _, _, loss = eng.round(p, opt, tokens, mask)
    dt = _median(times)
    flops = transformer_train_flops(d, layers, seq, batch, vocab)
    print(json.dumps({
        "step_time_ms": round(1e3 * dt, 3),
        "run_times_s": [round(t, 4) for t in times],
        "tokens_per_sec": round(batch * seq / dt, 1),
        "flops_per_step": flops,
        "achieved_tflops": round(flops / dt / 1e12, 2),
        "compile_seconds": round(compile_s, 1),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "attention": attention,
        "final_loss": float(loss),
        "config": {"d_model": d, "n_layers": layers, "n_heads": heads,
                   "seq": seq, "batch": batch, "vocab": vocab,
                   "dtype": "bfloat16", "remat": remat},
    }))


def worker_fedoverhead() -> None:
    """Federation overhead at MXU scale (VERDICT r3 weak #4).

    Times the SAME transformer twice on one chip: (a) an S=FO_STATIONS
    federated round — stations packed on the chip via stations_per_slot,
    per-station local step under fed_map, count-weighted fed_mean merge —
    and (b) a plain S=1 training step. Overhead = t_round / (S * t_step)
    - 1: everything the federated structure adds beyond S independent
    steps' worth of compute (vmap packing inefficiency + aggregation).
    """
    jax = _worker_setup(device_leg=True)
    import jax.numpy as jnp

    from vantage6_tpu.workloads import fed_transformer as FT

    shape = FO
    cfg = FT.TransformerConfig(
        vocab=shape["vocab"], d_model=shape["d"], n_heads=shape["heads"],
        n_layers=shape["layers"], max_len=shape["seq"],
        dtype=jnp.bfloat16, attention="recompute",
    )

    # BOTH legs pinned to ONE device slot: the S-station round packs every
    # station onto it (stations_per_slot, inner vmap), so the ratio
    # round/(S*step) isolates packing + aggregation overhead — on a
    # multi-device host an unpinned S-round would parallelize and the
    # ratio would measure speedup instead
    one_slot = jax.devices()[:1]

    def timed(n_stations: int) -> float:
        eng = FT.make_engine(
            n_stations=n_stations, seq_devices=1, cfg=cfg, lr=1e-3,
            devices=one_slot,
        )
        tokens = eng.shard_tokens(
            FT.make_federated_tokens(
                n_stations, batch=shape["batch"], seq_len=shape["seq"],
                vocab=shape["vocab"],
            )
        )
        params, opt = eng.init(jax.random.key(0))
        mask = jnp.ones(n_stations)
        # warm; a round consumes its state, so go on from its outputs
        params, opt, _ = jax.block_until_ready(
            eng.round(params, opt, tokens, mask))

        def step(state, i):
            p, o = state
            p, o, loss = eng.round(p, o, tokens, mask)
            return (p, o), loss

        _, times = _timed(jax, step, (params, opt))
        return _median(times)

    t1 = timed(1)
    ts = timed(FO_STATIONS)
    per_station_flops = transformer_train_flops(
        shape["d"], shape["layers"], shape["seq"], shape["batch"],
        shape["vocab"],
    )
    overhead = ts / (FO_STATIONS * t1) - 1.0
    print(json.dumps({
        "n_stations": FO_STATIONS,
        "s1_step_ms": round(1e3 * t1, 3),
        "round_ms": round(1e3 * ts, 3),
        "per_station_ms_in_round": round(1e3 * ts / FO_STATIONS, 3),
        "fed_overhead_pct": round(100 * overhead, 2),
        "achieved_tflops": round(
            FO_STATIONS * per_station_flops / ts / 1e12, 2
        ),
        "flops_per_round": FO_STATIONS * per_station_flops,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "config": {**shape, "dtype": "bfloat16"},
    }))


def worker_agg() -> None:
    """agg_modes leg: the server-update aggregation strategies compared on
    the SAME federation — replicated (fed_mean all-reduce), scattered
    (reduce-scatter + ZeRO-1 sharded optax + all-gather), scattered+bf16
    (bf16 on-wire deltas). Reports, per mode: rounds/sec, estimated
    collective bytes/round for the server update, measured per-device
    aggregation-state bytes (moments, from the executed program's actual
    shardings), device peak memory when the backend exposes it, and the
    final-param divergence vs replicated (parity evidence).

    Sized small (local_steps=1, batch 8, 32 rows/station): the leg measures
    AGGREGATION strategies, not local training throughput — the config just
    has to make the update path a visible fraction of the round.
    """
    jax = _worker_setup(device_leg=True)
    import jax.numpy as jnp
    import optax

    from vantage6_tpu.core.mesh import FederationMesh
    from vantage6_tpu.fed.collectives import flat_size, padded_flat_size
    from vantage6_tpu.runtime.metrics import device_peak_bytes
    from vantage6_tpu.workloads import fedavg_mnist as W

    n_st = int(os.environ.get("BENCH_AGG_STATIONS", "8"))
    rounds = int(os.environ.get("BENCH_AGG_ROUNDS", "2"))
    mesh = FederationMesh(n_st)
    d = mesh.station_axis_size
    sx, sy, counts = W.make_federated_data(
        n_st, n_per_station=32, mesh=mesh, noise=SYNTH_NOISE
    )
    key = jax.random.key(0)
    p0 = W.init_params(jax.random.fold_in(key, 1))
    mask = jnp.ones_like(counts)
    n_params = flat_size(p0)
    n_pad = padded_flat_size(n_params, d)

    def est_collective_bytes(mode: str) -> int:
        """Per-device on-wire bytes/round of the SERVER UPDATE collectives
        (ring algorithm: each of reduce-scatter / all-gather moves
        (D-1)/D * N elements per device; an all-reduce is both halves)."""
        half = (d - 1) / d * n_pad
        if mode == "replicated":
            return int(2 * half * 4)  # f32 all-reduce of the mean delta
        wire = 2 if mode == "scattered_bf16" else 4
        return int(half * wire + half * 4)  # rs(comm_dtype) + ag(f32 params)

    def per_device_state_bytes(opt_state) -> int:
        per: dict = {}
        for leaf in jax.tree.leaves(opt_state):
            if not hasattr(leaf, "addressable_shards"):
                continue
            for sh in leaf.addressable_shards:
                key_ = getattr(sh.device, "id", sh.device)
                per[key_] = per.get(key_, 0) + sh.data.nbytes
        return max(per.values()) if per else 0

    modes = [
        ("replicated", {}),
        ("scattered", dict(shard_server_update=True)),
        ("scattered_bf16",
         dict(shard_server_update=True, comm_dtype=jnp.bfloat16)),
    ]
    per_mode: dict = {}
    final_params: dict = {}
    for name, kw in modes:
        eng = W.make_engine(
            mesh, local_steps=1, batch_size=8, local_lr=LR,
            server_optimizer=optax.adam(1e-2), learning_stats=False, **kw,
        )
        # placed as the engine's own entry places them (see worker_spmd)
        p_in, opt0, c_in, m_in, k_in = eng._place(
            _fresh(jax, p0), eng.init(p0), counts, mask, key
        )
        args = (p_in, opt0, sx, sy, c_in, m_in, k_in)
        # memory_stats() peaks are PROCESS-LIFETIME monotonic: a per-mode
        # absolute reading would inherit earlier modes' high-water mark, so
        # report the delta (0 = this mode never exceeded the prior peak);
        # the sharding comparison itself rests on agg_state_bytes_per_device,
        # which is measured from each program's own output shardings.
        peak_before = device_peak_bytes()
        t0 = time.perf_counter()
        compiled = eng._run.lower(*args, n_rounds=rounds).compile()
        compile_s = time.perf_counter() - t0
        # warm; o1 carries the PROGRAM's shardings
        p1, o1, _, _ = compiled(*args)
        jax.block_until_ready(o1)

        def step(state, i):
            p, o = state
            p, o, losses, _ = compiled(
                p, o, sx, sy, c_in, m_in, jax.random.fold_in(k_in, 100 + i)
            )
            return (p, o), losses

        _, times = _timed(jax, step, _fresh(jax, (p1, o1)))
        dt = _median(times)
        # the warm call already ran this deterministic program on `args`
        final_params[name] = p1
        peak_after = device_peak_bytes()
        per_mode[name] = {
            "rounds_per_sec": round(rounds / dt, 3),
            "round_time_ms": round(1e3 * dt / rounds, 3),
            "run_times_s": [round(t, 4) for t in times],
            "compile_seconds": round(compile_s, 1),
            "est_collective_bytes_per_round": est_collective_bytes(name),
            "agg_state_bytes_per_device": per_device_state_bytes(o1),
            "device_peak_bytes_delta": (
                None if peak_before is None or peak_after is None
                else peak_after - peak_before
            ),
        }

    def max_param_diff(a, b) -> float:
        return max(
            float(jnp.max(jnp.abs(x - y)))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
        )

    rep = per_mode["replicated"]
    scat = per_mode["scattered"]
    print(json.dumps({
        "n_stations": n_st,
        "station_axis_size": d,
        "rounds_per_exec": rounds,
        "n_params": n_params,
        "modes": per_mode,
        "param_maxdiff_scattered_vs_replicated": max_param_diff(
            final_params["replicated"], final_params["scattered"]
        ),
        "param_maxdiff_bf16_vs_replicated": max_param_diff(
            final_params["replicated"], final_params["scattered_bf16"]
        ),
        # acceptance probes: scattered must not be slower than replicated
        # (CPU mesh) and must cut per-device aggregation-state memory D>1
        "scattered_not_slower": bool(
            scat["rounds_per_sec"] >= rep["rounds_per_sec"] * 0.95
        ),
        "agg_state_memory_cut": round(
            rep["agg_state_bytes_per_device"]
            / max(scat["agg_state_bytes_per_device"], 1), 2
        ),
        "platform": jax.devices()[0].platform,
    }))


def worker_hostparallel() -> None:
    """host_parallel leg: station executor pool vs sequential host dispatch.

    The SAME federation + task sequence runs twice — executor_workers=0
    (the historical synchronous path) and executor_workers=n_stations — on
    a sleep-padded partial (sleep(pad) + a small pandas aggregate), so the
    measured win is SCHEDULING (max-over-stations vs sum-over-stations per
    round), not compute luck. Reports rounds/sec for both, the speedup, the
    max-vs-sum round-time decomposition from per-run timestamps, and a
    bit-exactness parity flag over the two paths' results.
    """
    _worker_setup()
    import pandas as pd

    from vantage6_tpu.algorithm.decorators import data
    from vantage6_tpu.runtime.federation import federation_from_datasets
    from vantage6_tpu.runtime.metrics import round_decomposition

    n_st = int(os.environ.get("BENCH_HOST_STATIONS", str(HOST_STATIONS)))
    rounds = int(os.environ.get("BENCH_HOST_ROUNDS", str(HOST_ROUNDS)))
    pad = float(os.environ.get("BENCH_HOST_PAD_S", str(HOST_PAD_S)))

    @data(1)
    def padded_partial(df, pad_s=0.0):
        time.sleep(pad_s)
        return {"sum": float(df["x"].sum()), "n": int(len(df))}

    frames = [
        pd.DataFrame({"x": [float(i * 100 + j) for j in range(64)]})
        for i in range(n_st)
    ]
    algo = {"padded_partial": padded_partial}

    def timed(workers: int):
        fed = federation_from_datasets(
            frames, {"bench-host": algo}, executor_workers=workers
        )
        results, per_round, last_task = [], [], None
        t0 = time.perf_counter()
        for _ in range(rounds):
            r0 = time.perf_counter()
            last_task = fed.create_task(
                "bench-host",
                {"method": "padded_partial", "kwargs": {"pad_s": pad}},
            )
            results.append(fed.wait_for_results(last_task.id))
            per_round.append(time.perf_counter() - r0)
        dt = time.perf_counter() - t0
        decomp = round_decomposition(last_task.runs)
        fed.close()
        return rounds / dt, _median(per_round), results, decomp

    seq_rps, seq_round_s, seq_results, seq_decomp = timed(0)
    pool_rps, pool_round_s, pool_results, pool_decomp = timed(n_st)
    print(json.dumps({
        "n_stations": n_st,
        "rounds": rounds,
        "pad_s_per_station": pad,
        "sequential_rounds_per_sec": round(seq_rps, 3),
        "pooled_rounds_per_sec": round(pool_rps, 3),
        "sequential_round_time_s": round(seq_round_s, 4),
        "pooled_round_time_s": round(pool_round_s, 4),
        "speedup_pooled_vs_sequential": round(pool_rps / seq_rps, 2),
        # max-vs-sum decomposition of the LAST round's runs: the sequential
        # path pays ~sum_exec_s of wall-clock, the pooled path ~max_exec_s
        "round_decomposition": {
            "sequential": {
                k: round(v, 4) if isinstance(v, float) else v
                for k, v in seq_decomp.items()
            },
            "pooled": {
                k: round(v, 4) if isinstance(v, float) else v
                for k, v in pool_decomp.items()
            },
        },
        "results_parity": bool(seq_results == pool_results),
    }))


def worker_controlplane() -> None:
    """control_plane leg: batched+event-driven vs per-run+polled dispatch.

    The SAME server build serves both arms (old endpoints stay live —
    mixed-version is an acceptance criterion); only the DAEMON/CLIENT
    transport policy differs: the legacy arm pins `transport="per-run"`,
    `event_wait=0` and a fixed 0.25 s client poll (the pre-PR shape), the
    fast arm uses the batched claim/report endpoints and long-poll event
    wakeups. Tasks are tiny pandas partials so the measured time IS
    control-plane latency, not compute. Parity asserts per arm (every
    task completed, exactly one run per targeted org) and across arms
    (identical results for identical inputs — no lost/duplicated runs).
    """
    _worker_setup()
    import statistics
    import tempfile

    import numpy as np
    import pandas as pd

    from vantage6_tpu.client import UserClient
    from vantage6_tpu.common.enums import TaskStatus
    from vantage6_tpu.common.rest import REST_STATS
    from vantage6_tpu.node.daemon import NodeDaemon
    from vantage6_tpu.server.app import ServerApp

    n_daemons = int(os.environ.get("BENCH_CP_DAEMONS", str(CP_DAEMONS)))
    n_tasks = int(os.environ.get("BENCH_CP_TASKS", str(CP_TASKS)))
    image, module = "v6-average-py", "vantage6_tpu.workloads.average"

    tmp = tempfile.mkdtemp(prefix="v6t-cp-bench-")
    rng = np.random.default_rng(7)
    csvs = []
    for i in range(n_daemons):
        path = os.path.join(tmp, f"s{i:02d}.csv")
        pd.DataFrame(
            {"age": rng.uniform(20, 80, 32).round(1)}
        ).to_csv(path, index=False)
        csvs.append(path)

    def arm(fast: bool) -> dict:
        srv = ServerApp()
        srv.ensure_root(password="rootpass123")
        http = srv.serve(port=0, background=True)
        client = UserClient(http.url)
        if not fast:
            client._event_push = False  # pin the fixed-interval poll
        client.authenticate("root", "rootpass123")
        orgs, daemons = [], []
        for i in range(n_daemons):
            org = client.organization.create(name=f"cp{i:02d}")
            orgs.append(org)
        collab = client.collaboration.create(
            name="cp", organization_ids=[o["id"] for o in orgs]
        )
        for i, org in enumerate(orgs):
            ni = client.node.create(
                organization_id=org["id"], collaboration_id=collab["id"]
            )
            d = NodeDaemon(
                api_url=http.url,
                api_key=ni["api_key"],
                algorithms={image: module},
                databases=[
                    {"label": "default", "type": "csv", "uri": csvs[i]}
                ],
                mode="inline",
                poll_interval=0.25,
                transport="batched" if fast else "per-run",
                event_wait=2.0 if fast else 0.0,
            )
            d.start()
            daemons.append(d)
        org_ids = [o["id"] for o in orgs]
        stats0 = REST_STATS.snapshot()
        latencies, dispatch, results, parity = [], [], [], True
        t_all0 = time.perf_counter()
        for i in range(n_tasks):
            targets = [org_ids[(i + k) % n_daemons] for k in range(CP_WIDTH)]
            t0 = time.perf_counter()
            t = client.task.create(
                collaboration=collab["id"],
                organizations=targets,
                image=image,
                input_={"method": "partial_average",
                        "kwargs": {"column": "age"}},
            )
            res = client.wait_for_results(
                t["id"], interval=0.25, timeout=120.0
            )
            latencies.append(time.perf_counter() - t0)
            results.append(res)
            runs = client.run.from_task(t["id"])
            run_orgs = [r["organization"]["id"] for r in runs]
            parity &= sorted(run_orgs) == sorted(targets)
            parity &= all(
                TaskStatus(r["status"]) == TaskStatus.COMPLETED for r in runs
            )
            for r in runs:
                if r["started_at"] and r["assigned_at"]:
                    dispatch.append(r["started_at"] - r["assigned_at"])
        total_s = time.perf_counter() - t_all0
        stats1 = REST_STATS.snapshot()
        for d in daemons:
            d.stop()
        http.stop()
        srv.close()
        lat = sorted(latencies)
        dsp = sorted(dispatch)

        def pct(xs, p):
            return xs[min(len(xs) - 1, int(round(p / 100 * (len(xs) - 1))))]

        return {
            "task_p50_s": round(statistics.median(lat), 4),
            "task_p95_s": round(pct(lat, 95), 4),
            "dispatch_p50_s": round(statistics.median(dsp), 4),
            "dispatch_p95_s": round(pct(dsp, 95), 4),
            "tasks_per_sec": round(n_tasks / total_s, 3),
            "rest_calls": int(stats1["calls"] - stats0["calls"]),
            "rest_calls_per_task": round(
                (stats1["calls"] - stats0["calls"]) / n_tasks, 1
            ),
            "rest_bytes": int(
                stats1["bytes_sent"] + stats1["bytes_received"]
                - stats0["bytes_sent"] - stats0["bytes_received"]
            ),
            "stale_retries": int(
                stats1["stale_retries"] - stats0["stale_retries"]
            ),
            "parity_ok": bool(parity),
            "results": results,
        }

    legacy = arm(fast=False)
    fast = arm(fast=True)
    cross_parity = legacy.pop("results") == fast.pop("results")
    print(json.dumps({
        "n_daemons": n_daemons,
        "n_tasks": n_tasks,
        "width": CP_WIDTH,
        "per_run_polled": legacy,
        "batched_pushed": fast,
        "speedup_task_p95": round(
            legacy["task_p95_s"] / fast["task_p95_s"], 2
        ) if fast["task_p95_s"] > 0 else None,
        "speedup_dispatch_p95": round(
            legacy["dispatch_p95_s"] / fast["dispatch_p95_s"], 2
        ) if fast["dispatch_p95_s"] > 0 else None,
        "speedup_tasks_per_sec": round(
            fast["tasks_per_sec"] / legacy["tasks_per_sec"], 2
        ),
        "rest_calls_reduction": round(
            legacy["rest_calls"] / fast["rest_calls"], 2
        ) if fast["rest_calls"] else None,
        # no lost/duplicated runs in either arm AND identical results for
        # identical inputs across arms
        "results_parity": bool(
            legacy["parity_ok"] and fast["parity_ok"] and cross_parity
        ),
    }))


def worker_replica() -> None:
    """control_plane_scale child: ONE stateless server replica process over
    the shared store named by V6T_CPS_URI. Prints a {"url", "replica_id"}
    line once serving, then blocks until its stdin closes — the parent's
    shutdown signal (portable, no signal handling needed)."""
    _worker_setup()
    from vantage6_tpu.server.app import ServerApp

    srv = ServerApp(
        uri=os.environ["V6T_CPS_URI"],
        jwt_secret=os.environ["V6T_CPS_SECRET"],
    )
    if os.environ.get("V6T_CPS_ENSURE_ROOT") == "1":
        srv.ensure_root(password=os.environ["V6T_CPS_ROOT_PW"])
    http = srv.serve(port=0, background=True)
    print(json.dumps(
        {"url": http.url, "replica_id": srv.replica_id}
    ), flush=True)
    try:
        sys.stdin.read()
    finally:
        http.stop()
        srv.close()


def worker_cpscale() -> None:
    """control_plane_scale leg: horizontal scale-out of the control plane.

    1 vs CPS_REPLICAS stateless server replicas — SEPARATE OS processes
    (spawned via `--worker replica`) sharing ONE sqlite+wal store — serve
    the same fleet of CPS_DAEMONS node daemons and the same pipelined load
    of CPS_TASKS tiny pandas partials. Daemons take comma-separated
    api_url lists with their PRIMARY round-robined across replicas (the
    list is failover, not load-balancing), so steady-state REST traffic
    splits evenly. Acceptance: >= 1.6x tasks/sec at 2 replicas, ZERO
    double-dispatch (every run's activation CAS won exactly once — the
    store-level claim guard, counted at the daemons), cross-arm results
    parity, and per-replica request attribution visible in each replica's
    own trace file (summarize()['replicas'])."""
    _worker_setup()
    import tempfile

    import numpy as np
    import pandas as pd

    from vantage6_tpu.client import UserClient
    from vantage6_tpu.common.enums import TaskStatus
    from vantage6_tpu.node.daemon import NodeDaemon
    from vantage6_tpu.runtime.tracing import read_spans, summarize

    n_replicas = int(os.environ.get("BENCH_CPS_REPLICAS", str(CPS_REPLICAS)))
    n_daemons = int(os.environ.get("BENCH_CPS_DAEMONS", str(CPS_DAEMONS)))
    n_tasks = int(os.environ.get("BENCH_CPS_TASKS", str(CPS_TASKS)))
    image, module = "v6-average-py", "vantage6_tpu.workloads.average"
    root_pw = "cps-rootpass-123"

    tmp = tempfile.mkdtemp(prefix="v6t-cps-bench-")
    rng = np.random.default_rng(11)
    csvs = []
    for i in range(n_daemons):
        path = os.path.join(tmp, f"s{i:02d}.csv")
        pd.DataFrame(
            {"age": rng.uniform(20, 80, 32).round(1)}
        ).to_csv(path, index=False)
        csvs.append(path)

    def spawn_replica(uri: str, rid: str, ensure_root: bool,
                      trace_file: str):
        env = dict(os.environ)
        env.update({
            "V6T_CPS_URI": uri,
            "V6T_CPS_SECRET": "cps-shared-jwt-secret",
            "V6T_CPS_ENSURE_ROOT": "1" if ensure_root else "0",
            "V6T_CPS_ROOT_PW": root_pw,
            "V6T_REPLICA_ID": rid,
            "V6T_TRACE_FILE": trace_file,
            "BENCH_FORCE_CPU": "1",
        })
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--worker", "replica"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, env=env,
        )
        line = proc.stdout.readline()
        try:
            info = json.loads(line)
        except json.JSONDecodeError:
            proc.kill()
            raise RuntimeError(
                f"replica {rid} failed to boot: {line!r} / "
                f"{proc.stderr.read()[-2000:]}"
            )
        return proc, info["url"]

    def arm(n_reps: int) -> dict:
        # fresh store per arm: the 1-replica arm must not inherit the
        # scaled arm's backlog (or vice versa)
        uri = "sqlite+wal:///" + os.path.join(tmp, f"cp-{n_reps}.db")
        traces = [
            os.path.join(tmp, f"trace-{n_reps}rep-r{r}.jsonl")
            for r in range(n_reps)
        ]
        procs, urls = [], []
        for r in range(n_reps):
            proc, url = spawn_replica(
                uri, f"replica-{r}", ensure_root=(r == 0),
                trace_file=traces[r],
            )
            procs.append(proc)
            urls.append(url)
        daemons = []
        try:
            client = UserClient(urls[0])
            client.authenticate("root", root_pw)
            orgs = [
                client.organization.create(name=f"cps{i:02d}")
                for i in range(n_daemons)
            ]
            collab = client.collaboration.create(
                name="cps", organization_ids=[o["id"] for o in orgs]
            )
            for i, org in enumerate(orgs):
                ni = client.node.create(
                    organization_id=org["id"],
                    collaboration_id=collab["id"],
                )
                # primary replica round-robined; the rest are failover
                ordered = urls[i % n_reps:] + urls[:i % n_reps]
                d = NodeDaemon(
                    api_url=",".join(ordered),
                    api_key=ni["api_key"],
                    algorithms={image: module},
                    databases=[
                        {"label": "default", "type": "csv",
                         "uri": csvs[i]}
                    ],
                    mode="inline",
                    poll_interval=0.25,
                    transport="batched",
                    event_wait=2.0,
                )
                d.start()
                daemons.append(d)
            org_ids = [o["id"] for o in orgs]
            # concurrent submitters — users behind a dumb round-robin LB.
            # Each thread owns its clients (UserClient is not built for
            # cross-thread sharing): tasks are CREATED on one replica and
            # AWAITED through the next one over, so results reported via
            # any replica must become visible — and wake long-polls —
            # through every other (the shared-store event bus at work).
            from concurrent.futures import ThreadPoolExecutor

            n_threads = int(os.environ.get("BENCH_CPS_CLIENTS", "8"))
            thread_clients = []
            for k in range(n_threads):
                a = UserClient(urls[k % n_reps])
                a.authenticate("root", root_pw)
                if n_reps == 1:
                    thread_clients.append((a, a))
                    continue
                b = UserClient(urls[(k + 1) % n_reps])
                b.authenticate("root", root_pw)
                thread_clients.append((a, b))

            results: list = [None] * n_tasks
            parity_per_thread = [True] * n_threads

            def drive(k: int) -> None:
                create_cl, wait_cl = thread_clients[k]
                ok = True
                for i in range(k, n_tasks, n_threads):
                    t = create_cl.task.create(
                        collaboration=collab["id"],
                        organizations=[
                            org_ids[(i + j) % n_daemons]
                            for j in range(CPS_WIDTH)
                        ],
                        image=image,
                        input_={"method": "partial_average",
                                "kwargs": {"column": "age"}},
                    )
                    results[i] = wait_cl.wait_for_results(
                        t["id"], interval=0.25, timeout=300.0
                    )
                    runs = wait_cl.run.from_task(t["id"])
                    ok &= len(runs) == CPS_WIDTH
                    ok &= all(
                        TaskStatus(r["status"]) == TaskStatus.COMPLETED
                        for r in runs
                    )
                parity_per_thread[k] = ok

            t0 = time.perf_counter()
            with ThreadPoolExecutor(n_threads) as ex:
                list(ex.map(drive, range(n_threads)))
            total_s = time.perf_counter() - t0
            parity = all(parity_per_thread) and None not in results
            won = sum(d.activations_won for d in daemons)
            lost = sum(d.activations_lost for d in daemons)
            # ground-truth per-replica request counts off each replica's
            # own /api/metrics (spans only cover TRACED hops; the counter
            # sees every request including daemon claim/report polls)
            import urllib.request as _ur

            served = {}
            for u in urls:
                try:
                    body = _ur.urlopen(
                        u + "/api/metrics", timeout=10
                    ).read().decode()
                except Exception:
                    body = ""
                n_req = 0
                for ln in body.splitlines():
                    if ln.startswith("v6t_http_requests_total"):
                        n_req = int(float(ln.split()[-1]))
                served[u] = n_req
        finally:
            for d in daemons:
                d.stop()
            for p in procs:
                try:
                    p.stdin.close()
                    p.wait(timeout=30)
                except Exception:
                    p.kill()
        # span-level attribution off each replica's own sink: only TRACED
        # hops (client task ops, unbatched reports) appear here — the
        # trace_view per-replica table the operators read
        spans = []
        for path in traces:
            try:
                spans.extend(read_spans(path))
            except OSError:
                pass
        rep_summary = (summarize(spans) or {}).get("replicas") or {}
        expected = n_tasks * CPS_WIDTH
        return {
            "n_replicas": n_reps,
            "tasks_per_sec": round(n_tasks / total_s, 3),
            "total_s": round(total_s, 3),
            # double-dispatch = a run activated by 2 daemons (CAS loser
            # seen) OR won a different number of times than runs exist
            "activations_won": int(won),
            "activations_lost": int(lost),
            "double_dispatch": int(lost + abs(won - expected)),
            "parity_ok": bool(parity),
            "requests_per_replica": [served[u] for u in urls],
            "traced_spans_per_replica": {
                rid: row["count"]
                for rid, row in (
                    rep_summary.get("by_replica") or {}
                ).items()
            },
            "results": results,
        }

    one = arm(1)
    many = arm(n_replicas)
    cross_parity = one.pop("results") == many.pop("results")
    print(json.dumps({
        "n_daemons": n_daemons,
        "n_tasks": n_tasks,
        "width": CPS_WIDTH,
        "single": one,
        "scaled": many,
        # distinct from the control_plane leg's speedup_tasks_per_sec so
        # bench_trend's flattener never conflates the two headline rows
        "scaleout_speedup_tasks_per_sec": round(
            many["tasks_per_sec"] / one["tasks_per_sec"], 2
        ) if one["tasks_per_sec"] > 0 else None,
        "double_dispatch": int(
            one["double_dispatch"] + many["double_dispatch"]
        ),
        # every replica in the scaled arm actually served real traffic
        "all_replicas_served": bool(
            len(many["requests_per_replica"]) == n_replicas
            and min(many["requests_per_replica"]) > 0
        ),
        "results_parity": bool(
            one["parity_ok"] and many["parity_ok"] and cross_parity
        ),
    }))


def worker_observability() -> None:
    """observability leg: bare vs tracing vs full ops plane, alternated.

    The guardrail for the tracing PR, extended by the watchdog, device-
    observatory, learning-plane and fleet-fabric PRs: six arms per rep —
    "off" (bare), "trace" (distributed tracing, the PR-5 configuration,
    so overhead_pct keeps its historical meaning), "ops" (tracing +
    watchdog at an operator cadence + structured JSON logging + flight
    taps), "obsy" (ops + device observatory), "learn" (ops + learning
    plane: per-task round recording + /api/rounds), "fleet" (ops +
    daemon fleet pushes at a 30x-production cadence + the store-backed
    SLO engine evaluating on every watchdog tick). Arms alternate and
    compare best-of so a host-load spike doesn't masquerade as
    instrumentation overhead; ops_overhead_pct (ops vs trace) is the
    watchdog PR's <5% acceptance, learning_overhead_pct (learn vs ops)
    the learning-plane PR's, fleet_overhead_pct (fleet vs ops) the
    fleet-fabric PR's. The fleet arm also asserts the cross-host census:
    every daemon AND the server itself must appear as fresh sources in
    GET /api/fleet after the timed window. The learning_anomaly smoke seeds a
    label-flipped station in an engine run and asserts anomalous_station
    names it within one watchdog interval, with fp32-identical stats
    between replicated and scattered update paths.
    The traced arm also asserts the OBSERVABILITY acceptance: one task's
    trace covers client create → server dispatch → daemon claim → runner
    exec → result upload → aggregation, exports valid Perfetto
    trace_event JSON, and the server's /metrics parses with the absorbed
    series. A fault-injection smoke then proves the watchdog DETECTS: a
    daemon killed mid-round and a run wedged past its deadline must raise
    their alerts within one evaluation interval, flip /api/health to
    degraded, and produce a flight dump that tools/doctor.py renders as a
    trace-correlated timeline naming the stuck run.
    """
    _worker_setup()
    import tempfile

    import numpy as np
    import pandas as pd

    from vantage6_tpu.client import UserClient
    from vantage6_tpu.common.enums import TaskStatus
    from vantage6_tpu.common.log import disable_json_sink, enable_json_sink
    from vantage6_tpu.node.daemon import NodeDaemon
    from vantage6_tpu.runtime.learning import LEARNING, update_stats_host
    from vantage6_tpu.runtime.profiling import DEVICE_OBS
    from vantage6_tpu.runtime.tracing import (
        TRACER, summarize, to_trace_events,
    )
    from vantage6_tpu.runtime.watchdog import WATCHDOG
    from vantage6_tpu.server.app import ServerApp

    n_daemons = int(os.environ.get("BENCH_OBS_DAEMONS", str(OBS_DAEMONS)))
    n_tasks = int(os.environ.get("BENCH_OBS_TASKS", str(OBS_TASKS)))
    image, module = "v6-average-py", "vantage6_tpu.workloads.average"

    tmp = tempfile.mkdtemp(prefix="v6t-obs-bench-")
    rng = np.random.default_rng(11)
    csvs = []
    for i in range(n_daemons):
        path = os.path.join(tmp, f"s{i:02d}.csv")
        pd.DataFrame(
            {"age": rng.uniform(20, 80, 32).round(1)}
        ).to_csv(path, index=False)
        csvs.append(path)

    def boot_stack(tag: str, n: int, **daemon_kw):
        """Server + authed root client + n orgs/nodes/daemons — the ONE
        topology bring-up shared by the overhead arms and the fault
        smoke, so a daemon-construction change can't silently leave the
        smoke testing a different stack than the arms measure."""
        srv = ServerApp()
        srv.ensure_root(password="rootpass123")
        http = srv.serve(port=0, background=True)
        client = UserClient(http.url)
        client.authenticate("root", "rootpass123")
        orgs = [
            client.organization.create(name=f"{tag}-{i:02d}")
            for i in range(n)
        ]
        collab = client.collaboration.create(
            name=tag, organization_ids=[o["id"] for o in orgs],
        )
        daemons = []
        for i, org in enumerate(orgs):
            ni = client.node.create(
                organization_id=org["id"], collaboration_id=collab["id"]
            )
            d = NodeDaemon(
                api_url=http.url,
                api_key=ni["api_key"],
                algorithms={image: module},
                databases=[
                    {"label": "default", "type": "csv", "uri": csvs[i]}
                ],
                mode="inline",
                **daemon_kw,
            )
            d.start()
            daemons.append(d)
        return srv, http, client, orgs, collab, daemons

    def arm(mode: str, arm_tag: str) -> dict:
        # five alternated arms: "off" (no instrumentation), "trace"
        # (distributed tracing — the PR-5 configuration, so overhead_pct
        # keeps its historical meaning), "ops" (tracing + watchdog at an
        # operator cadence + JSON logging + flight taps — the full ops
        # plane; ops_overhead_pct vs the trace arm isolates what THIS
        # layer adds), "obsy" (ops + the device observatory armed —
        # observatory_overhead_pct vs the ops arm isolates the device-
        # plane instrumentation, the observatory PR's <5% acceptance),
        # "learn" (ops + the learning plane armed: per-task round
        # recording into LEARNING + the /api/rounds surface —
        # learning_overhead_pct vs the ops arm isolates the learning-
        # plane instrumentation, the learning-plane PR's <5% acceptance),
        # "fleet" (ops + every daemon pushing telemetry snapshots at
        # OBS_FLEET_PUSH_S + the server self-ingesting and the SLO burn-
        # rate engine evaluating store-backed history on each watchdog
        # tick — fleet_overhead_pct vs the ops arm isolates the fleet
        # fabric, the fleet-fabric PR's <5% acceptance)
        tracing_on = mode != "off"
        TRACER.configure(enabled=tracing_on, sample=1.0)
        TRACER.clear()
        DEVICE_OBS.configure(enabled=mode == "obsy")
        if mode == "learn":
            LEARNING.clear()
        if mode in ("ops", "obsy", "learn", "fleet"):
            WATCHDOG.configure(interval=OBS_WD_ARM_INTERVAL)
            enable_json_sink(os.path.join(tmp, f"log-{arm_tag}.jsonl"))
        else:
            WATCHDOG.configure(interval=60.0)  # effectively idle
            disable_json_sink()
        daemon_kw: dict = {"poll_interval": 0.25}
        if mode == "fleet":
            daemon_kw["fleet_push_interval"] = OBS_FLEET_PUSH_S
        srv, http, client, orgs, collab, daemons = boot_stack(
            f"obs-{arm_tag}", n_daemons, **daemon_kw,
        )
        org_ids = [o["id"] for o in orgs]
        parity = True
        last_trace = None
        last_learn_task = None
        t_all0 = time.perf_counter()
        for i in range(n_tasks):
            targets = [org_ids[(i + k) % n_daemons] for k in range(2)]
            t = client.task.create(
                collaboration=collab["id"],
                organizations=targets,
                image=image,
                input_={"method": "partial_average",
                        "kwargs": {"column": "age"}},
            )
            res = client.wait_for_results(
                t["id"], interval=0.25, timeout=120.0
            )
            ctx = client.trace_context(t["id"])
            with TRACER.span(
                "aggregate", kind="aggregate", service="client",
                parent=ctx, require_parent=True,
            ):
                total = sum(r["sum"] for r in res)
                count = sum(r["count"] for r in res)
                parity &= count == 64 and total > 0
                if mode == "learn":
                    # learning plane armed: the per-station result
                    # vectors are this round's "updates" — stats + a
                    # RoundHistory record per task (the learning.round
                    # span joins the ambient aggregate span)
                    flat = np.array(
                        [[r["sum"], r["count"]] for r in res], np.float32
                    )
                    LEARNING.history(t["id"]).record_stats(
                        update_stats_host(flat)
                    )
                    last_learn_task = t["id"]
            runs = client.run.from_task(t["id"])
            parity &= sorted(
                r["organization"]["id"] for r in runs
            ) == sorted(targets)
            parity &= all(
                TaskStatus(r["status"]) == TaskStatus.COMPLETED
                for r in runs
            )
            if ctx is not None:
                last_trace = ctx.trace_id
        total_s = time.perf_counter() - t_all0
        out = {
            "tasks_per_sec": round(n_tasks / total_s, 3),
            "parity_ok": bool(parity),
        }
        if mode == "learn" and last_learn_task is not None:
            # outside the timed window: the /api/rounds surface serves
            # what the arm recorded (route + registry acceptance)
            rr = client.util.rounds(last_learn_task)
            idx = client.util.rounds()
            out["rounds_endpoint_ok"] = (
                rr.get("task_id") == last_learn_task
                and len(rr.get("rounds") or []) >= 1
            )
            out["rounds_index_ok"] = any(
                t2.get("task") == last_learn_task
                for t2 in idx.get("tasks") or []
            )
        if mode == "fleet":
            # outside the timed window: the cross-host census acceptance —
            # every daemon's pushes AND the server's self-ingested snapshot
            # must read back as fresh sources from GET /api/fleet
            view = client.util.fleet()
            srcs = view.get("sources") or []
            n_daemon_srcs = sum(
                1 for s in srcs if s.get("service") == "daemon"
            )
            metrics_text = client.util.metrics()
            out["fleet_sources"] = len(srcs)
            out["fleet_daemon_sources"] = n_daemon_srcs
            out["fleet_census_ok"] = (
                n_daemon_srcs == n_daemons
                and any(s.get("service") == "server" for s in srcs)
                and not any(s.get("stale") for s in srcs)
            )
            out["slo_engine_ok"] = (
                "v6t_slo_evaluations_total" in metrics_text
                and "v6t_fleet_ingests_total" in metrics_text
            )
        if tracing_on and last_trace is not None:
            spans = TRACER.drain(last_trace)
            names = {s["name"] for s in spans}
            required = {
                "client.task_create", "server.dispatch", "daemon.claim",
                "daemon.exec", "runner.exec", "daemon.report",
                "client.wait_results", "aggregate",
            }
            perfetto = to_trace_events(spans)
            x_events = [
                e for e in perfetto["traceEvents"] if e.get("ph") == "X"
            ]
            metrics_text = client.util.metrics()
            out.update({
                "trace_id": last_trace,
                "n_spans": len(spans),
                "span_coverage_ok": required.issubset(names),
                "missing_spans": sorted(required - names),
                "perfetto_ok": bool(x_events) and all(
                    "ts" in e and "dur" in e and "pid" in e
                    for e in x_events
                ),
                "per_hop": {
                    k: v for k, v in summarize(spans)["spans"].items()
                    if not k.startswith(("http ", "rest "))
                },
                "metrics_ok": all(
                    s in metrics_text
                    for s in (
                        "v6t_wire_encode_bytes_total",
                        "v6t_rest_calls_total",
                        "v6t_executor_inflight_items",
                        "v6t_event_hub_buffer_len",
                        "v6t_auth_cache_hits_total",
                    )
                ),
            })
        for d in daemons:
            d.stop()
        http.stop()
        srv.close()
        return out

    def fault_smoke() -> dict:
        """Kill one daemon mid-round + wedge one run past its deadline;
        measure detection latency, the health flip, and the post-mortem
        path (flight dump → doctor timeline naming the stuck run)."""
        import subprocess

        from vantage6_tpu.common.flight import FLIGHT, read_bundle

        TRACER.configure(enabled=True, sample=1.0)
        # fast eval cadence now, but RELAXED thresholds until the healthy
        # baseline round is in the books — on a loaded host a >1s healthy
        # round against the smoke deadlines would raise alerts before any
        # fault is injected, poisoning healthy_status
        WATCHDOG.configure(
            interval=OBS_WD_INTERVAL,
            run_deadline_s=300.0,
            ping_window_s=60.0,
        )
        enable_json_sink(os.path.join(tmp, "log-fault.jsonl"))
        FLIGHT.clear()
        srv, http, client, orgs, collab, daemons = boot_stack(
            "obs-fault", 2, poll_interval=0.1, sync_interval=2.0,
            ping_interval=0.3, event_wait=0.5,
        )
        out: dict = {}
        try:
            # one healthy round first: traces + flight content to dump
            t_ok = client.task.create(
                collaboration=collab["id"],
                organizations=[o["id"] for o in orgs],
                image=image,
                input_={"method": "partial_average",
                        "kwargs": {"column": "age"}},
            )
            client.wait_for_results(t_ok["id"], interval=0.1, timeout=60.0)
            out["healthy_status"] = client.util.health()["status"]
            # healthy evidence recorded — NOW arm the smoke thresholds
            WATCHDOG.configure(
                run_deadline_s=OBS_WD_DEADLINE,
                ping_window_s=OBS_WD_PING_WINDOW,
            )
            # FAULT 1 — daemon killed mid-round: stop the victim's threads
            # WITHOUT the offline handshake (a crash, not a shutdown); its
            # node stays "online" at the server and the pings stop
            victim = daemons[1]
            victim._stop.set()
            # a real crash: listen/sync threads die, the worker pool dies,
            # NO offline handshake reaches the server. Join before the
            # wedge task exists so no victim thread can pick it up.
            for th in (victim._thread, victim._sync_thread):
                if th is not None:
                    th.join(timeout=10)
            victim._pool.shutdown(wait=False, cancel_futures=True)
            # FAULT 2 — wedged run: a task for the dead daemon's org,
            # claimed ACTIVE (the victim's last act before dying) and
            # never finished
            t_bad = client.task.create(
                collaboration=collab["id"],
                organizations=[orgs[1]["id"]],
                image=image,
                input_={"method": "partial_average",
                        "kwargs": {"column": "age"}},
            )
            runs = client.run.from_task(t_bad["id"])
            rid = runs[0]["id"]
            victim.request(
                "PATCH", f"run/{rid}",
                {"status": "active", "started_at": time.time()},
            )
            wedged_at = time.monotonic()
            want = {"stuck_run", "daemon_lapsed"}
            seen: set = set()
            deadline = wedged_at + OBS_WD_DEADLINE + 12.0
            while time.monotonic() < deadline and not want <= seen:
                seen = {
                    a["rule"] for a in client.util.alerts()["active"]
                }
                if want <= seen:
                    break
                time.sleep(0.1)
            detect_s = time.monotonic() - wedged_at
            # "within one evaluation interval" of the deadline passing
            # (+1 interval of poll slack for this probe loop itself)
            budget_s = OBS_WD_DEADLINE + 2 * OBS_WD_INTERVAL + 0.5
            health = client.util.health()
            dump = client.util.debug_dump()
            doctor = subprocess.run(
                [sys.executable, os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "tools", "doctor.py",
                ), dump["path"], "--trace", t_bad["trace_id"][:8]],
                capture_output=True, text=True, timeout=60,
            )
            # the torn-tail-tolerant reader, not raw json.loads — a dump
            # racing a writer must still yield the records that DID land
            bundle = read_bundle(dump["path"])
            bundle_spans = [
                r for r in bundle if r.get("type") == "span"
                and r.get("trace_id") == t_bad["trace_id"]
            ]
            bundle_logs = [
                r for r in bundle if r.get("type") == "log"
                and r.get("trace_id") == t_bad["trace_id"]
            ]
            out.update({
                "alerts_seen": sorted(seen),
                "alerts_ok": want <= seen,
                "detect_s": round(detect_s, 2),
                "detect_budget_s": round(budget_s, 2),
                "within_one_interval": detect_s <= budget_s,
                "health_degraded": health["status"] == "degraded",
                "failing_components_or_alerts": {
                    "alerts": health.get("alerts"),
                },
                "flight_bundle": dump["path"],
                "bundle_spans_for_stuck_task": len(bundle_spans),
                "bundle_trace_correlated_logs": len(bundle_logs),
                "doctor_ok": (
                    doctor.returncode == 0
                    and f"run {rid}" in doctor.stdout
                    and "stuck_run" in doctor.stdout
                ),
                "stuck_run_id": rid,
            })
        finally:
            for d in daemons:
                try:
                    d.stop()
                except Exception:
                    pass
            http.stop()
            srv.close()
        return out

    def retrace_storm_smoke() -> dict:
        """Seed a retrace storm (shape-perturbed re-dispatch of one
        observed function) and prove the observatory NAMES it three ways:
        the recompile_storm alert (within one watchdog interval of the
        storm), the device.compile spans (retrace + signature diff +
        XLA memory/cost introspection), and the doctor perf digest of a
        flight dump."""
        import subprocess

        import jax
        import jax.numpy as jnp

        from vantage6_tpu.common.flight import FLIGHT
        from vantage6_tpu.runtime.profiling import observed_jit

        TRACER.configure(enabled=True, sample=1.0)
        TRACER.clear()
        DEVICE_OBS.configure(enabled=True)
        DEVICE_OBS.clear()
        FLIGHT.clear()
        WATCHDOG.configure(interval=OBS_WD_INTERVAL)
        WATCHDOG.start()
        out: dict = {}
        try:
            quiet_before = not any(
                a["rule"] == "recompile_storm"
                for a in WATCHDOG.evaluate()
            )
            time.sleep(2 * OBS_WD_INTERVAL)  # baseline history on the books
            storm_fn = observed_jit(
                "bench.storm_fn", lambda x: jnp.tanh(x @ x.T).sum()
            )
            with TRACER.span("bench.retrace_storm", kind="bench") as root:
                storm_trace = root.context.trace_id
                # the classic storm: a data-dependent dimension wobbling
                # per dispatch, every call a fresh abstract signature
                for i in range(6):
                    jax.block_until_ready(storm_fn(jnp.ones((8 + i, 4))))
            storm_done = time.monotonic()
            detect_deadline = storm_done + 4 * OBS_WD_INTERVAL + 2.0
            alert = None
            while time.monotonic() < detect_deadline and alert is None:
                alert = next(
                    (a for a in WATCHDOG.active_alerts()
                     if a["rule"] == "recompile_storm"), None,
                )
                if alert is None:
                    time.sleep(0.05)
            detect_s = time.monotonic() - storm_done
            budget_s = 2 * OBS_WD_INTERVAL + 0.5  # one interval + poll slack
            spans = TRACER.drain(storm_trace)
            compile_spans = [
                s for s in spans if s["name"] == "device.compile"
            ]
            retrace_spans = [
                s for s in compile_spans if s["attrs"].get("retrace")
            ]
            dump_path = FLIGHT.dump(reason="bench-storm")
            doctor = subprocess.run(
                [sys.executable, os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "tools", "doctor.py",
                ), dump_path],
                capture_output=True, text=True, timeout=60,
            )
            diffs = [
                s["attrs"].get("changed") for s in retrace_spans
                if s["attrs"].get("changed")
            ]
            out = {
                "quiet_before_storm": quiet_before,
                "n_compiles": len(compile_spans),
                "n_retrace_spans": len(retrace_spans),
                "spans_carry_xla_introspection": bool(compile_spans) and all(
                    "compile_ms" in s["attrs"]
                    and "temp_bytes" in s["attrs"]
                    and "flops" in s["attrs"]
                    for s in compile_spans
                ),
                "signature_diffs": diffs[:3],
                "alert_raised": alert is not None,
                "alert_names_function": bool(
                    alert and "bench.storm_fn" in alert["message"]
                ),
                "alert_message": alert["message"] if alert else None,
                "detect_s": round(detect_s, 2),
                "detect_budget_s": round(budget_s, 2),
                "within_one_interval": alert is not None
                and detect_s <= budget_s,
                "flight_bundle": dump_path,
                "doctor_names_function_and_diff": (
                    doctor.returncode == 0
                    and "bench.storm_fn" in doctor.stdout
                    and any(d in doctor.stdout for d in diffs)
                ),
            }
        finally:
            WATCHDOG.stop()
        return out

    def learning_anomaly_smoke() -> dict:
        """Seed an anomalous station — label-flipped data on 1 of 8
        stations of a FedAvg engine run, so its local updates point
        AGAINST the pooled delta — and prove the learning plane NAMES it:
        the `anomalous_station` alert (within one watchdog interval of
        the rounds being recorded, message carrying the station and the
        offending stat) and the doctor learning digest of a flight dump.
        Also asserts the in-round stats are fp32-IDENTICAL between the
        replicated and scattered (ZeRO-1) update paths."""
        import subprocess

        import jax
        import jax.numpy as jnp

        from vantage6_tpu.common.flight import FLIGHT
        from vantage6_tpu.core.mesh import FederationMesh
        from vantage6_tpu.fed.fedavg import FedAvg, FedAvgSpec

        TRACER.configure(enabled=True, sample=1.0)
        WATCHDOG.configure(interval=OBS_WD_INTERVAL)
        LEARNING.clear()
        FLIGHT.clear()
        S, n_rows, d = 8, 32, 16
        seeded = 5
        rng2 = np.random.default_rng(7)
        x = rng2.standard_normal((S, n_rows, d)).astype(np.float32)
        beta = rng2.standard_normal(d).astype(np.float32)
        y = (x @ beta + 0.05 * rng2.standard_normal(
            (S, n_rows)
        )).astype(np.float32)
        y[seeded] = -y[seeded]  # the label flip

        def loss_fn(p, bx, by, w):
            pred = bx @ p
            return jnp.sum(w * (pred - by) ** 2) / jnp.maximum(
                jnp.sum(w), 1.0
            )

        mesh = FederationMesh(S)
        kw = dict(
            loss_fn=loss_fn, local_steps=2, batch_size=16, local_lr=0.02
        )
        counts = jnp.full((S,), float(n_rows))
        p0 = jnp.zeros(d)
        key = jax.random.key(3)
        rounds = 6
        rep_eng = FedAvg(mesh, FedAvgSpec(**kw))
        scat_eng = FedAvg(mesh, FedAvgSpec(**kw, shard_server_update=True))
        _, _, losses_rep, stats_rep = rep_eng.run_rounds(
            jnp.copy(p0), jnp.asarray(x), jnp.asarray(y), counts, key,
            rounds,
        )
        _, _, _, stats_scat = scat_eng.run_rounds(
            jnp.copy(p0), jnp.asarray(x), jnp.asarray(y), counts, key,
            rounds,
        )
        fp32_identical = all(
            np.array_equal(
                np.asarray(stats_rep[k]), np.asarray(stats_scat[k])
            )
            for k in stats_rep
        )
        WATCHDOG.start()
        out: dict = {}
        try:
            quiet_before = not any(
                a["rule"] == "anomalous_station"
                for a in WATCHDOG.evaluate()
            )
            history = LEARNING.history("bench-anomaly")
            with TRACER.span("bench.learning_anomaly", kind="bench"):
                history.record_engine(losses_rep, stats_rep)
            recorded_at = time.monotonic()
            deadline = recorded_at + 4 * OBS_WD_INTERVAL + 2.0
            alert = None
            while time.monotonic() < deadline and alert is None:
                alert = next(
                    (a for a in WATCHDOG.active_alerts()
                     if a["rule"] == "anomalous_station"), None,
                )
                if alert is None:
                    time.sleep(0.05)
            detect_s = time.monotonic() - recorded_at
            budget_s = 2 * OBS_WD_INTERVAL + 0.5  # 1 interval + poll slack
            dump_path = FLIGHT.dump(reason="bench-anomaly")
            doctor = subprocess.run(
                [sys.executable, os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "tools", "doctor.py",
                ), dump_path],
                capture_output=True, text=True, timeout=60,
            )
            seeded_cos = float(
                np.asarray(stats_rep["station_cos"])[-1][seeded]
            )
            out = {
                "quiet_before": quiet_before,
                "seeded_station": seeded,
                "rounds_recorded": rounds,
                "fp32_identical": bool(fp32_identical),
                "seeded_station_cos_last_round": round(seeded_cos, 4),
                "alert_raised": alert is not None,
                "alert_names_station": bool(
                    alert
                    and alert["labels"].get("station") == seeded
                    and f"station {seeded}" in alert["message"]
                ),
                "alert_names_stat": bool(
                    alert and (
                        "cosine" in alert["message"]
                        or "norm" in alert["message"]
                    )
                ),
                "alert_message": alert["message"] if alert else None,
                "anomaly_detect_s": round(detect_s, 2),
                "detect_budget_s": round(budget_s, 2),
                "within_one_interval": alert is not None
                and detect_s <= budget_s,
                "flight_bundle": dump_path,
                "doctor_names_station": (
                    doctor.returncode == 0
                    and "anomalous_station" in doctor.stdout
                    and f"station {seeded}" in doctor.stdout
                ),
            }
        finally:
            WATCHDOG.stop()
        return out

    try:
        offs, ons, opss, obsys, learns, fleets = [], [], [], [], [], []
        traced: dict = {}
        for rep in range(max(1, int(os.environ.get(
            "BENCH_OBS_REPS", str(OBS_REPS)
        )))):
            offs.append(arm("off", f"off{rep}"))
            on = arm("trace", f"on{rep}")
            traced = on  # keep the freshest traced-arm evidence
            ons.append(on)
            opss.append(arm("ops", f"ops{rep}"))
            obsys.append(arm("obsy", f"obsy{rep}"))
            learns.append(arm("learn", f"learn{rep}"))
            fleets.append(arm("fleet", f"fleet{rep}"))
        watchdog_smoke = fault_smoke()
        storm_smoke = retrace_storm_smoke()
        anomaly_smoke = learning_anomaly_smoke()
    finally:
        TRACER.configure(enabled=True, sample=1.0)
        disable_json_sink()
        DEVICE_OBS.configure(enabled=True)
        WATCHDOG.configure(
            interval=5.0, run_deadline_s=300.0, ping_window_s=60.0,
        )
    best_off = max(a["tasks_per_sec"] for a in offs)
    best_on = max(a["tasks_per_sec"] for a in ons)
    best_ops = max(a["tasks_per_sec"] for a in opss)
    best_obsy = max(a["tasks_per_sec"] for a in obsys)
    best_learn = max(a["tasks_per_sec"] for a in learns)
    best_fleet = max(a["tasks_per_sec"] for a in fleets)
    overhead_pct = round(100.0 * (best_off - best_on) / best_off, 2)
    # what the WATCHDOG PR adds on top of tracing (the "<5% watchdog +
    # JSON logging" acceptance): ops arm vs trace arm, best-of each
    ops_overhead_pct = round(100.0 * (best_on - best_ops) / best_on, 2)
    # what the DEVICE OBSERVATORY adds on top of the full ops plane
    # (the observatory PR's <5% acceptance): observatory arm vs ops arm
    observatory_overhead_pct = round(
        100.0 * (best_ops - best_obsy) / best_ops, 2
    )
    # what the LEARNING PLANE adds on top of the full ops plane (the
    # learning-plane PR's <5% acceptance): learn arm vs ops arm
    learning_overhead_pct = round(
        100.0 * (best_ops - best_learn) / best_ops, 2
    )
    # what the FLEET FABRIC adds on top of the full ops plane (this PR's
    # <5% acceptance): fleet arm (pushes at 30x-production cadence + SLO
    # engine reading store history every tick) vs ops arm, best-of each
    fleet_overhead_pct = round(
        100.0 * (best_ops - best_fleet) / best_ops, 2
    )
    print(json.dumps({
        "n_daemons": n_daemons,
        "n_tasks": n_tasks,
        "reps": len(offs),
        "tasks_per_sec_tracing_off": best_off,
        "tasks_per_sec_tracing_on": best_on,
        "tasks_per_sec_ops_plane": best_ops,
        "tasks_per_sec_observatory": best_obsy,
        "overhead_pct": overhead_pct,
        "overhead_ok": overhead_pct < OBS_OVERHEAD_PCT,
        "ops_overhead_pct": ops_overhead_pct,
        "ops_overhead_ok": ops_overhead_pct < OBS_OVERHEAD_PCT,
        "tasks_per_sec_learning_plane": best_learn,
        "observatory_overhead_pct": observatory_overhead_pct,
        "observatory_overhead_ok": (
            observatory_overhead_pct < OBS_OVERHEAD_PCT
        ),
        "learning_overhead_pct": learning_overhead_pct,
        "learning_overhead_ok": learning_overhead_pct < OBS_OVERHEAD_PCT,
        "tasks_per_sec_fleet_plane": best_fleet,
        "fleet_overhead_pct": fleet_overhead_pct,
        "fleet_overhead_ok": fleet_overhead_pct < OBS_OVERHEAD_PCT,
        "overhead_budget_pct": OBS_OVERHEAD_PCT,
        "ops_plane_in_ops_arm": ["tracing", "watchdog", "json_logging",
                                 "flight_taps"],
        "observatory_in_obsy_arm": ["ops_plane", "device_observatory"],
        "learning_plane_in_learn_arm": [
            "ops_plane", "round_recording", "rounds_api",
        ],
        "fleet_fabric_in_fleet_arm": [
            "ops_plane", "daemon_fleet_push", "server_self_ingest",
            "slo_burn_rate_engine",
        ],
        "fleet_push_interval_s": OBS_FLEET_PUSH_S,
        "fleet_census_ok": all(a.get("fleet_census_ok") for a in fleets),
        "fleet_slo_engine_ok": all(
            a.get("slo_engine_ok") for a in fleets
        ),
        "fleet_sources_last_arm": fleets[-1].get("fleet_sources"),
        "rounds_endpoint_ok": all(
            a.get("rounds_endpoint_ok") and a.get("rounds_index_ok")
            for a in learns
        ),
        "parity_ok": all(
            a["parity_ok"]
            for a in offs + ons + opss + obsys + learns + fleets
        ),
        "trace": {
            k: traced.get(k)
            for k in (
                "trace_id", "n_spans", "span_coverage_ok",
                "missing_spans", "perfetto_ok", "metrics_ok", "per_hop",
            )
        },
        "watchdog": watchdog_smoke,
        "retrace_storm": storm_smoke,
        "learning_anomaly": anomaly_smoke,
    }))


def worker_wireformat() -> None:
    """wire_format leg: v1 (JSON + base64 .npy) vs v2 (framed binary) wire.

    Serialization: model-weight-like f32 pytrees at WIRE_MB_SIZES MiB and a
    DataFrame stats table through serialize+deserialize in BOTH formats —
    reports encode+decode throughput, on-wire bytes, and the reduction; the
    parity block asserts v2 round-trips bit-identically AND that v1 blobs
    still decode through the auto-detecting deserialize.

    Encryption (cryptography-gated, skipped with a marker otherwise): one
    RSA keypair, then single-recipient encrypt vs `encrypt_bytes_broadcast`
    to WIRE_BROADCAST_N recipients vs N naive full passes on the 10 MiB
    payload; also decrypts a legacy '$'-format blob with the v2-capable
    cryptor (cross-format compat).
    """
    _worker_setup()
    import numpy as np

    from vantage6_tpu.common.serialization import deserialize, serialize

    rng = np.random.default_rng(0)

    def pytree_payload(mib: float) -> dict:
        """4-layer weight pytree totalling ~mib MiB of f32."""
        n = int(mib * (1 << 20) / 4)
        quarter = max(1, n // 4)
        return {
            "round": 7,
            "layers": {
                f"layer_{i}": {
                    "w": rng.standard_normal(quarter, dtype=np.float32),
                    "b": rng.standard_normal(
                        max(1, quarter // 64), dtype=np.float32
                    ),
                }
                for i in range(4)
            },
        }

    def tree_equal(a, b) -> bool:
        if isinstance(a, dict):
            return (isinstance(b, dict) and a.keys() == b.keys()
                    and all(tree_equal(a[k], b[k]) for k in a))
        if isinstance(a, np.ndarray):
            return (isinstance(b, np.ndarray) and a.dtype == b.dtype
                    and a.shape == b.shape
                    and bool(np.array_equal(a, b, equal_nan=True)))
        return type(a) is type(b) and a == b

    def timed(fn) -> float:
        t0 = time.perf_counter()
        for _ in range(WIRE_REPS):
            fn()
        return (time.perf_counter() - t0) / WIRE_REPS

    sizes_out = []
    parity_all = True
    for mib in WIRE_MB_SIZES:
        payload = pytree_payload(mib)
        v1 = serialize(payload, format="v1")
        v2 = serialize(payload, format="v2")
        enc1 = timed(lambda: serialize(payload, format="v1"))
        enc2 = timed(lambda: serialize(payload, format="v2"))
        dec1 = timed(lambda: deserialize(v1))
        dec2 = timed(lambda: deserialize(v2))
        payload_mb = mib  # nominal f32 MiB
        parity = (
            tree_equal(deserialize(v2), payload)   # v2 bit-identical
            and tree_equal(deserialize(v1), payload)  # v1 still decodes
        )
        parity_all = parity_all and parity
        sizes_out.append({
            "payload_mib": payload_mb,
            "v1_bytes": len(v1),
            "v2_bytes": len(v2),
            "on_wire_reduction": round(1.0 - len(v2) / len(v1), 4),
            "v1_encode_s": round(enc1, 5), "v1_decode_s": round(dec1, 5),
            "v2_encode_s": round(enc2, 5), "v2_decode_s": round(dec2, 5),
            "roundtrip_speedup_v2_vs_v1": round(
                (enc1 + dec1) / max(enc2 + dec2, 1e-9), 1
            ),
            "v2_roundtrip_mb_per_s": round(
                2 * payload_mb / max(enc2 + dec2, 1e-9), 1
            ),
            "parity": parity,
        })

    # DataFrame stats table (per-station summary shape)
    import pandas as pd

    df = pd.DataFrame({
        "feature": [f"f{i}" for i in range(200)],
        "mean": rng.standard_normal(200),
        "std": rng.standard_normal(200) ** 2,
        "count": rng.integers(0, 10**6, 200),
    })
    df_payload = {"stats": df, "n": 200}
    df_ok = True
    for fmt in ("v1", "v2"):
        out = deserialize(serialize(df_payload, format=fmt))
        try:
            # to_json carries 10 decimal digits (both formats — DataFrames
            # ride the header): near-exact, not bit-exact, by design
            pd.testing.assert_frame_equal(
                out["stats"], df, check_exact=False, rtol=1e-9
            )
            df_ok = df_ok and out["n"] == 200
        except AssertionError:
            df_ok = False

    # headline acceptance numbers come from the >=10 MiB payload
    big = next(s for s in sizes_out if s["payload_mib"] >= 10)

    # ---- encryption: single vs single-pass broadcast ------------------
    crypto: dict = {}
    try:
        import cryptography  # noqa: F401
        have_crypto = True
    except ImportError:
        have_crypto = False
        crypto["skipped"] = "cryptography not installed"
    if have_crypto:
        from vantage6_tpu.common.encryption import RSACryptor

        t0 = time.perf_counter()
        kp = RSACryptor(RSACryptor.create_new_rsa_key())
        keygen_s = time.perf_counter() - t0
        pub = kp.public_key_str
        data = serialize(pytree_payload(10), format="v2")
        t_single = timed(lambda: kp.encrypt_bytes(data, pub))
        t_bcast = timed(
            lambda: kp.encrypt_bytes_broadcast(data, [pub] * WIRE_BROADCAST_N)
        )
        t_naive = timed(
            lambda: [kp.encrypt_bytes(data, pub)
                     for _ in range(WIRE_BROADCAST_N)]
        )
        blob_bin = kp.encrypt_bytes(data, pub)
        wire_v2_str = kp.encrypt_bytes_to_str(data, pub)
        legacy_str = kp._encrypt_legacy_str(data, pub)
        compat = (
            kp.decrypt_bytes(blob_bin) == data
            and kp.decrypt_str_to_bytes(wire_v2_str) == data
            and kp.decrypt_bytes(legacy_str) == data      # v1 encrypted blob
        )
        # legacy double-encoding comparison on the STRING wire: v1 payload
        # inside the legacy cryptor vs v2 payload inside the binary framing
        v1_payload = serialize(pytree_payload(10), format="v1")
        legacy_wire_len = len(kp._encrypt_legacy_str(v1_payload, pub))
        crypto = {
            "keygen_s": round(keygen_s, 2),
            "payload_bytes": len(data),
            "single_encrypt_s": round(t_single, 4),
            f"broadcast_{WIRE_BROADCAST_N}_s": round(t_bcast, 4),
            f"naive_{WIRE_BROADCAST_N}x_s": round(t_naive, 4),
            "broadcast_cost_vs_single": round(
                t_bcast / max(t_single, 1e-9), 2
            ),
            "naive_cost_vs_single": round(t_naive / max(t_single, 1e-9), 2),
            "encrypted_wire_bytes_v2_str": len(wire_v2_str),
            "encrypted_wire_bytes_v1_str": legacy_wire_len,
            "encrypted_wire_reduction": round(
                1.0 - len(wire_v2_str) / legacy_wire_len, 4
            ),
            "cross_format_compat": compat,
            "broadcast_within_2x": bool(
                t_bcast / max(t_single, 1e-9) <= 2.0
            ),
        }
        parity_all = parity_all and compat

    checks = {
        "on_wire_reduction_ge_25pct": bool(
            big["on_wire_reduction"] >= 0.25
        ),
        "throughput_ge_3x": bool(big["roundtrip_speedup_v2_vs_v1"] >= 3.0),
        "parity": bool(parity_all and df_ok),
        "broadcast_within_2x": crypto.get("broadcast_within_2x"),
    }
    print(json.dumps({
        "sizes": sizes_out,
        "dataframe_roundtrip_ok": df_ok,
        "broadcast_encryption": crypto,
        "checks": checks,
    }))


def worker_compression() -> None:
    """compression leg (wire-leg extension, gradient-compression PR).

    The SAME FedAvg-CNN federation trains twice from one init: dense delta
    exchange vs the compressed stack (stochastic int8 + top-k(COMPRESS_TOPK)
    + per-station error feedback, docs/compression.md). Reports, per arm:
    rounds/sec, final accuracy on the shared held-out set, and — the
    acceptance numbers — the on-wire delta bytes/round (dense 4N f32 per
    station vs the compressed frame), the reduction ratio (>=4x bar), the
    accuracy gap (parity within COMPRESS_ACC_TOL), and a compression-cost
    probe: the SAME jitted compress/decompress kernels run standalone
    under ``device.compress``/``device.decompress`` trace spans, their
    total time compared against the measured round time (<10% bar).
    The probe executes one full round's exchange (S compress + 1
    decompress) SEQUENTIALLY on the host — an upper bound: on a pod each
    station's compress runs on its own device concurrently.
    """
    jax = _worker_setup(device_leg=True)
    import jax.numpy as jnp
    import numpy as np

    from vantage6_tpu.core.mesh import FederationMesh
    from vantage6_tpu.fed import compression as comp
    from vantage6_tpu.fed.collectives import flat_size
    from vantage6_tpu.fed.compression import CompressorSpec
    from vantage6_tpu.runtime.tracing import TRACER, summarize
    from vantage6_tpu.workloads import fedavg_mnist as W

    n_st = int(os.environ.get("BENCH_COMPRESS_STATIONS",
                              str(COMPRESS_STATIONS)))
    topk = float(os.environ.get("BENCH_COMPRESS_TOPK", str(COMPRESS_TOPK)))
    # the headline training config (meaningful accuracy at 5 rounds): the
    # subject is the DELTA EXCHANGE, both arms train identically
    local_steps, batch, n_per = LOCAL_STEPS, BATCH, N_PER_STATION
    rounds = int(os.environ.get("BENCH_COMPRESS_ROUNDS", str(SPMD_ROUNDS)))
    mesh = FederationMesh(n_st)
    sx, sy, counts = W.make_federated_data(
        n_st, n_per_station=n_per, mesh=mesh, noise=SYNTH_NOISE
    )
    key = jax.random.key(0)
    p0 = W.init_params(jax.random.fold_in(key, 1))
    mask = jnp.ones_like(counts)
    n_params = flat_size(p0)
    spec = CompressorSpec(topk_ratio=topk, int8=True)
    ex, ey = _eval_data()

    per_arm: dict = {}
    for name, compressor in (("dense", None), ("compressed", spec)):
        eng = W.make_engine(
            mesh, local_steps=local_steps, batch_size=batch, local_lr=LR,
            compressor=compressor, learning_stats=False,
        )
        # placed as the engine's own entry places them (see worker_spmd)
        p_in, opt0, c_in, m_in, k_in = eng._place(
            _fresh(jax, p0), eng.init(p0), counts, mask, key
        )
        args = (p_in, opt0, sx, sy, c_in, m_in, k_in)
        t0 = time.perf_counter()
        compiled = eng._run.lower(*args, n_rounds=rounds).compile()
        compile_s = time.perf_counter() - t0
        p1, o1, losses, _ = compiled(*args)  # warm (deterministic on args)
        jax.block_until_ready(losses)

        def step(state, i):
            p, o = state
            p, o, ls, _ = compiled(
                p, o, sx, sy, c_in, m_in, jax.random.fold_in(k_in, 50 + i)
            )
            return (p, o), ls

        _, times = _timed(jax, step, _fresh(jax, (p1, o1)))
        dt = _median(times)
        per_arm[name] = {
            "rounds_per_sec": round(rounds / dt, 3),
            "round_time_ms": round(1e3 * dt / rounds, 3),
            "run_times_s": [round(t, 4) for t in times],
            "compile_seconds": round(compile_s, 1),
            "final_loss": float(losses[-1]),
            # both arms score the ROUND-rounds-deep warm-run model on the
            # same held-out set — the accuracy-parity comparison
            "accuracy": round(W.evaluate(p1, ex, ey), 4),
        }

    # ---- on-wire delta accounting (static, metadata-only) -------------
    raw_per_round = 4 * n_params * n_st
    wire_per_round = spec.wire_nbytes(n_params) * n_st
    reduction = raw_per_round / wire_per_round

    # ---- compression-cost probe (device.compress spans) ---------------
    rng = np.random.default_rng(5)
    delta = jnp.asarray(rng.normal(size=n_params).astype(np.float32))
    ef = jnp.zeros(n_params)
    # warm the standalone jit executables OUTSIDE the traced probe
    payload, _, _ = comp.compress_delta(spec, delta, ef,
                                        key=jax.random.key(0))
    comp.decompress_delta(spec, payload, n_params)
    with TRACER.span("bench.compress_probe", kind="bench") as root:
        for s in range(n_st):
            payload, _, _ = comp.compress_delta(
                spec, delta, ef, key=jax.random.key(s), station=s
            )
        comp.decompress_delta(spec, payload, n_params)
        trace_id = root.context.trace_id
    spans = TRACER.drain(trace_id)
    table = summarize(spans)["spans"]
    probe_ms = (
        table.get("device.compress", {}).get("total_ms", 0.0)
        + table.get("device.decompress", {}).get("total_ms", 0.0)
    )
    round_ms = per_arm["compressed"]["round_time_ms"]
    cost_pct = round(100.0 * probe_ms / round_ms, 2) if round_ms else None

    gap = abs(per_arm["dense"]["accuracy"]
              - per_arm["compressed"]["accuracy"])
    print(json.dumps({
        "n_stations": n_st,
        "rounds_per_exec": rounds,
        "n_params": n_params,
        "config": {"local_steps": local_steps, "batch": batch,
                   "n_per_station": n_per},
        "spec": {"topk_ratio": topk, "int8": True, "chunk": spec.chunk},
        "arms": per_arm,
        "delta_raw_bytes_per_round": raw_per_round,
        "delta_wire_bytes_per_round": wire_per_round,
        "on_wire_reduction": round(reduction, 2),
        "reduction_ok": bool(reduction >= 4.0),
        "accuracy_gap": round(gap, 4),
        "accuracy_tolerance": COMPRESS_ACC_TOL,
        "accuracy_parity": bool(gap <= COMPRESS_ACC_TOL),
        "compress_probe": {
            "device_compress": table.get("device.compress"),
            "device_decompress": table.get("device.decompress"),
            "probe_total_ms": round(probe_ms, 3),
            "pct_of_round": cost_pct,
            "cost_ok": bool(cost_pct is not None
                            and cost_pct < COMPRESS_COST_PCT),
            "note": "S sequential host-side compresses + 1 decompress vs "
                    "one round — upper bound (stations compress "
                    "concurrently on a pod)",
        },
        "platform": jax.devices()[0].platform,
    }))


def worker_autopilot() -> None:
    """autopilot leg: robustness PR acceptance, two arms.

    Straggler resilience: the SAME 8-station host federation runs mean
    rounds three ways — clean sync (all stations, wait=True), sync with a
    V6T_FAULTS delay pinning station 0 at ~10x the clean round time
    (every round waits for the straggler: rounds/sec craters toward
    1/delay), and buffered-async via Federation.run_buffered (quorum 7,
    over-select 1: first-7 completions aggregate, the straggler is
    killed at quorum by the terminal-sticky kill_task). Acceptance:
    async holds >= AP_RESILIENCE_PCT of clean sync rounds/sec, at
    aggregate parity (the one excluded station moves an 8-station mean
    well under 2%).

    Autopilot smoke: a FedAvg engine run with FAULTS.poison_labels
    label-flipping one station of 8 records into the learning plane; the
    anomalous_station alert fires and the attached Autopilot
    (ArrayActuator) auto-masks the station HANDS-OFF; re-running under
    the actuator's participation mask recovers accuracy; clearing the
    learning history clears the alert and the mask REVERTS. The flight
    dump's doctor digest must show both the action and the revert.
    """
    _worker_setup()
    import subprocess

    import jax
    import jax.numpy as jnp
    import numpy as np
    import pandas as pd

    from vantage6_tpu.algorithm.decorators import data
    from vantage6_tpu.common.enums import TaskStatus
    from vantage6_tpu.common.faults import FAULTS
    from vantage6_tpu.common.flight import FLIGHT
    from vantage6_tpu.core.mesh import FederationMesh
    from vantage6_tpu.fed.fedavg import AsyncRoundSpec, FedAvg, FedAvgSpec
    from vantage6_tpu.runtime.autopilot import ArrayActuator, Autopilot
    from vantage6_tpu.runtime.federation import federation_from_datasets
    from vantage6_tpu.runtime.learning import LEARNING
    from vantage6_tpu.runtime.tracing import TRACER
    from vantage6_tpu.runtime.watchdog import WATCHDOG

    S = int(os.environ.get("BENCH_AP_STATIONS", str(AP_STATIONS)))
    rounds = int(os.environ.get("BENCH_AP_ROUNDS", str(AP_ROUNDS)))

    # ---- straggler arm ------------------------------------------------
    @data(1)
    def local_mean(df):
        return {"sum": float(df["x"].sum()), "n": int(len(df))}

    rng = np.random.default_rng(5)
    frames = [
        pd.DataFrame({"x": rng.normal(10.0, 1.0, 128)}) for _ in range(S)
    ]
    fed = federation_from_datasets(
        frames, {"bench-ap": {"local_mean": local_mean}},
        executor_workers=S,
    )

    def sync_round() -> float:
        t = fed.create_task("bench-ap", {"method": "local_mean"})
        rs = [
            r.result for r in t.runs if r.status == TaskStatus.COMPLETED
        ]
        total = sum(r["sum"] for r in rs)
        n = sum(r["n"] for r in rs)
        return total / max(n, 1)

    FAULTS.clear()
    t0 = time.perf_counter()
    vals_clean = [sync_round() for _ in range(rounds)]
    clean_dt = time.perf_counter() - t0
    rps_clean = rounds / clean_dt
    # the "10x-slow station": pin the delay to ~9 extra clean-round times
    # (clamped so degraded hosts still finish inside the leg timeout)
    delay_s = min(1.0, max(0.2, 9.0 * clean_dt / rounds))
    FAULTS.configure(f"delay:station=0,seconds={delay_s:.3f}")

    sync_straggler_rounds = max(2, rounds // 3)
    t0 = time.perf_counter()
    for _ in range(sync_straggler_rounds):
        sync_round()
    rps_sync_straggler = sync_straggler_rounds / (
        time.perf_counter() - t0
    )

    spec = AsyncRoundSpec(
        quorum=S - 1, over_select=1, staleness_discount=0.5,
        deadline_s=max(5.0, 4.0 * delay_s),
    )
    vals_async, killed_total, max_staleness = [], 0, 0.0
    t0 = time.perf_counter()
    for _ in range(rounds):
        res = fed.run_buffered(
            "bench-ap", {"method": "local_mean"}, spec,
            rng=np.random.default_rng(0),
        )
        accepted = set(res["accepted"])
        rs = [
            r.result for r in res["task"].runs
            if r.station_index in accepted
        ]
        total = sum(r["sum"] for r in rs)
        n = sum(r["n"] for r in rs)
        vals_async.append(total / max(n, 1))
        killed_total += len(res["killed"])
        max_staleness = max(max_staleness, float(max(res["staleness"])))
    rps_async = rounds / (time.perf_counter() - t0)
    fault_snapshot = FAULTS.snapshot()
    FAULTS.clear()
    staleness_after = fed.station_staleness()
    fed.close()

    resilience = 100.0 * rps_async / rps_clean if rps_clean > 0 else 0.0
    mean_clean = float(np.mean(vals_clean))
    mean_async = float(np.mean(vals_async))
    agg_rel_err = abs(mean_async - mean_clean) / max(abs(mean_clean), 1e-9)

    # ---- autopilot closed-loop smoke ---------------------------------
    TRACER.configure(enabled=True, sample=1.0)
    WATCHDOG.configure(interval=OBS_WD_INTERVAL)
    LEARNING.clear()
    FLIGHT.clear()
    S2, n_rows, d = 8, 32, 16
    seeded = 5
    rng2 = np.random.default_rng(7)
    x = rng2.standard_normal((S2, n_rows, d)).astype(np.float32)
    beta = rng2.standard_normal(d).astype(np.float32)
    y_clean = (x @ beta + 0.05 * rng2.standard_normal(
        (S2, n_rows)
    )).astype(np.float32)
    # the poisoning goes through the fault harness, not hand-rolled
    # flipping: the same V6T_FAULTS spec a deployment would smoke with
    FAULTS.configure(f"flip:station={seeded},fraction=1.0")
    y = y_clean.copy()
    y[seeded] = FAULTS.poison_labels(y[seeded], seeded)
    flip_applied = not np.array_equal(y[seeded], y_clean[seeded])
    FAULTS.clear()

    def loss_fn(p, bx, by, w):
        pred = bx @ p
        return jnp.sum(w * (pred - by) ** 2) / jnp.maximum(
            jnp.sum(w), 1.0
        )

    mesh = FederationMesh(S2)
    eng = FedAvg(mesh, FedAvgSpec(
        loss_fn=loss_fn, local_steps=2, batch_size=16, local_lr=0.02
    ))
    counts = jnp.full((S2,), float(n_rows))
    p0 = jnp.zeros(d)
    key = jax.random.key(3)
    sm_rounds = 6
    _, _, losses_poisoned, stats = eng.run_rounds(
        jnp.copy(p0), jnp.asarray(x), jnp.asarray(y), counts, key,
        sm_rounds,
    )
    _, _, losses_clean, _ = eng.run_rounds(
        jnp.copy(p0), jnp.asarray(x), jnp.asarray(y_clean), counts, key,
        sm_rounds,
    )

    actuator = ArrayActuator(S2)
    pilot = Autopilot(actuator=actuator, listener_key="bench-autopilot")
    pilot.attach()
    WATCHDOG.start()
    out_smoke: dict = {}
    try:
        history = LEARNING.history("bench-autopilot")
        with TRACER.span("bench.autopilot_smoke", kind="bench"):
            history.record_engine(losses_poisoned, stats)
        recorded_at = time.monotonic()
        deadline = recorded_at + 4 * OBS_WD_INTERVAL + 2.0
        while time.monotonic() < deadline and not actuator.masked[seeded]:
            time.sleep(0.05)
        mask_detect_s = time.monotonic() - recorded_at
        auto_masked = bool(actuator.masked[seeded])
        # hands-off recovery: rerun under the mask the AUTOPILOT set
        mask = jnp.asarray(actuator.participation_mask())
        _, _, losses_masked, _ = eng.run_rounds(
            jnp.copy(p0), jnp.asarray(x), jnp.asarray(y), counts, key,
            sm_rounds, mask=mask,
        )
        # alert clear -> revert: with the poisoned history gone the
        # anomalous_station rule proposes nothing and the engaged mask
        # must come back off by itself
        LEARNING.clear()
        revert_deadline = time.monotonic() + 4 * OBS_WD_INTERVAL + 2.0
        while (
            time.monotonic() < revert_deadline and actuator.masked[seeded]
        ):
            time.sleep(0.05)
        mask_reverted = not bool(actuator.masked[seeded])
        digest = pilot.digest()
        dump_path = FLIGHT.dump(reason="bench-autopilot")
        doctor = subprocess.run(
            [sys.executable, os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "tools", "doctor.py",
            ), dump_path, "--tail", "0"],
            capture_output=True, text=True, timeout=60,
        )
        poisoned_loss = float(np.asarray(losses_poisoned)[-1])
        masked_loss = float(np.asarray(losses_masked)[-1])
        clean_loss = float(np.asarray(losses_clean)[-1])
        out_smoke = {
            "flip_applied": flip_applied,
            "seeded_station": seeded,
            "autopilot_auto_masked": auto_masked,
            "autopilot_mask_detect_s": round(mask_detect_s, 2),
            "mask_detect_budget_s": round(2 * OBS_WD_INTERVAL + 0.5, 2),
            "poisoned_final_loss": round(poisoned_loss, 5),
            "masked_final_loss": round(masked_loss, 5),
            "clean_final_loss": round(clean_loss, 5),
            "accuracy_recovers": bool(
                masked_loss < poisoned_loss
                and masked_loss <= max(clean_loss * 1.5, clean_loss + 0.05)
            ),
            "mask_reverted_on_clear": mask_reverted,
            "autopilot_digest": digest,
            "flight_bundle": dump_path,
            "doctor_shows_action_and_revert": bool(
                doctor.returncode == 0
                and "autopilot digest" in doctor.stdout
                and "mask_station" in doctor.stdout
                and "reverted" in doctor.stdout
            ),
        }
    finally:
        pilot.detach()
        WATCHDOG.stop()
        FAULTS.clear()

    print(json.dumps({
        "n_stations": S,
        "rounds": rounds,
        "straggler_delay_s": round(delay_s, 3),
        "clean_rounds_per_sec": round(rps_clean, 3),
        "sync_straggler_rounds_per_sec": round(rps_sync_straggler, 3),
        "async_rounds_per_sec": round(rps_async, 3),
        "straggler_resilience_pct": round(resilience, 1),
        "resilience_ok": bool(resilience >= AP_RESILIENCE_PCT),
        "sync_craters": bool(rps_sync_straggler <= 0.5 * rps_clean),
        "stragglers_killed": killed_total,
        "straggler_max_staleness": max_staleness,
        "staleness_after": [int(v) for v in staleness_after],
        "aggregate_rel_err": round(agg_rel_err, 5),
        "aggregate_parity_ok": bool(agg_rel_err < 0.02),
        "fault_snapshot": fault_snapshot,
        **out_smoke,
    }))


def worker_baseline() -> None:
    """Reference-shaped rounds: sequential stations + JSON payload hops.

    Timing: a full 32-station hop-instrumented round costs minutes on this
    host, so each of the BASELINE_TIMING_ROUNDS timing rounds routes
    BASELINE_TIMING_STATIONS stations through the complete serialize ->
    train -> deserialize path sequentially, times them, and scales by
    S/BASELINE_TIMING_STATIONS (per-station hop cost is independent of the
    station index; the method is recorded in "timing_method"). This is what
    lets the measurement honor both the >=5-rounds requirement and the time
    budget (VERDICT r2 weak #4).

    Accuracy: training runs the full reference maths for BENCH_ACC_ROUNDS
    rounds — every round aggregates ALL stations' sequential-semantics
    updates (executed batched via vmap: the identical per-station program
    with the same seeds; each timing round, the first hop-instrumented
    station is cross-checked against its batched result to loose f32
    tolerance — vmap only reassociates floating-point reductions, it cannot
    change the maths) — and the final model is scored on the same held-out
    set as the SPMD worker (VERDICT r2 missing #4).
    """
    jax = _worker_setup()
    import jax.numpy as jnp
    import numpy as np

    from vantage6_tpu.common.serialization import deserialize, serialize
    from vantage6_tpu.workloads import fedavg_mnist as W

    acc_rounds = int(os.environ.get("BENCH_ACC_ROUNDS", str(SPMD_ROUNDS)))
    n_st = N_STATIONS
    cpu = jax.devices("cpu")[0]
    key = jax.random.key(0)
    with jax.default_device(cpu):
        # SAME shards and weighting as the SPMD leg — accuracy_parity must
        # compare IMPLEMENTATIONS, not data partitionings: Dirichlet
        # non-iid shards, padded with true counts, count-weighted mean
        sx_np, sy_np, counts = W.make_federated_data(
            n_st, n_per_station=N_PER_STATION, noise=SYNTH_NOISE
        )
        sx, sy = jnp.asarray(sx_np), jnp.asarray(sy_np)
        counts = jnp.asarray(counts)
        params = W.init_params(jax.random.fold_in(key, 1))

        def local_train(params, sx, sy, count, k):
            safe = jnp.maximum(count.astype(jnp.int32), 1)

            def step(p, sk):
                idx = jax.random.randint(sk, (BATCH,), 0, safe)
                bx, by = jnp.take(sx, idx, axis=0), jnp.take(sy, idx, axis=0)
                g = jax.grad(
                    lambda q: W.weighted_ce_loss(q, bx, by, jnp.ones(BATCH))
                )(p)
                return jax.tree.map(lambda a, gg: a - LR * gg, p, g), None

            out, _ = jax.lax.scan(step, params,
                                  jax.random.split(k, LOCAL_STEPS))
            return out

        local_train = jax.jit(local_train)

        # SAME RNG chain as the SPMD engine (fed/fedavg.py _run_impl /
        # _local_update): round keys = split(key(0), rounds), station key =
        # fold_in(round_key, station_id), step keys = split(., LOCAL_STEPS).
        # With identical batch draws the accuracy-parity comparison isolates
        # the IMPLEMENTATIONS, not two sampling streams.
        round_keys = jax.random.split(jax.random.key(0), acc_rounds)
        station_ids = jnp.arange(n_st)

        def station_keys(r):
            return jax.vmap(
                lambda s: jax.random.fold_in(round_keys[r], s)
            )(station_ids)

        # all-stations round for the accuracy leg: lax.map compiles the
        # station body ONCE and loops (vmap of 32 stations took minutes of
        # XLA compile on this host), preserving per-station sequential
        # semantics exactly
        @jax.jit
        def batched_train(params, sx, sy, counts, keys):
            return jax.lax.map(
                lambda t: local_train(params, t[0], t[1], t[2], t[3]),
                (sx, sy, counts, keys),
            )

        def weighted_mean(stacked_tree):
            wn = counts / jnp.sum(counts)
            return jax.tree.map(
                lambda t: jnp.einsum("s,s...->...", wn, t), stacked_tree
            )

        # warm both executables outside the timed region
        t0 = time.perf_counter()
        jax.block_until_ready(local_train(params, sx[0], sy[0],
                                          counts[0], station_keys(0)[0]))
        jax.block_until_ready(
            batched_train(params, sx, sy, counts, station_keys(0))
        )
        compile_s = time.perf_counter() - t0

        # never index past the (possibly shrunken) federation; scaling by
        # the float ratio stays exact for non-multiples
        k_timed = min(BASELINE_TIMING_STATIONS, n_st)
        per_round_est: list[float] = []
        batched_round_s: list[float] = []
        t_start = time.perf_counter()
        done = 0
        for r in range(acc_rounds):
            keys_r = station_keys(r)
            if r < BASELINE_TIMING_ROUNDS:
                # hop-instrumented sequential path for k stations, timed
                t0 = time.perf_counter()
                hop_results = []
                for s in range(k_timed):
                    blob = serialize({"params": params})
                    p_in = deserialize(blob)["params"]
                    p_in = jax.tree.map(jnp.asarray, p_in)
                    new_p = local_train(
                        p_in, sx[s], sy[s], counts[s], keys_r[s]
                    )
                    hop_results.append(
                        deserialize(serialize({"params": new_p}))["params"]
                    )
                jax.block_until_ready(jax.tree.leaves(hop_results[-1])[0])
                per_round_est.append(
                    (time.perf_counter() - t0) * n_st / k_timed
                )
            t0 = time.perf_counter()
            stacked = batched_train(params, sx, sy, counts, keys_r)
            jax.block_until_ready(stacked)
            batched_round_s.append(time.perf_counter() - t0)
            if r < BASELINE_TIMING_ROUNDS:
                # the hop path and the batched path are the same maths; the
                # tolerance absorbs vmap's reassociated f32 reductions
                # amplified over LOCAL_STEPS sgd steps
                for a, b in zip(
                    jax.tree.leaves(hop_results[0]),
                    jax.tree.leaves(jax.tree.map(lambda t: t[0], stacked)),
                ):
                    np.testing.assert_allclose(
                        np.asarray(a), np.asarray(b), atol=5e-3, rtol=5e-2
                    )
            params = weighted_mean(stacked)
            jax.block_until_ready(jax.tree.leaves(params)[0])
            done = r + 1
            if (
                time.perf_counter() - t_start > BASELINE_MAX_S
                and len(per_round_est) >= BASELINE_TIMING_ROUNDS
            ):
                break
        med = _median(per_round_est)
        t0 = time.perf_counter()
        ex, ey = _eval_data()
        acc = W.evaluate(params, ex, ey)
        eval_s = time.perf_counter() - t0
    print(json.dumps({
        "rounds_per_sec": 1.0 / med,
        "rounds": len(per_round_est),
        "round_time_s_median": round(med, 2),
        "round_time_s_all": [round(t, 2) for t in per_round_est],
        "timing_method": (
            f"{k_timed}-of-{n_st} stations hop-instrumented "
            f"sequentially per round, scaled x{n_st / k_timed:g}"
        ),
        "accuracy": round(acc, 4),
        "rounds_trained": done,
        "phase_seconds": {
            "compile_warm": round(compile_s, 1),
            "batched_rounds": [round(t, 1) for t in batched_round_s],
            "eval": round(eval_s, 1),
        },
    }))


# --------------------------------------------------------------------- main
def main() -> None:
    t_start = time.monotonic()
    deadline = t_start + BENCH_BUDGET_S - BUDGET_MARGIN_S

    def remaining() -> float:
        return deadline - time.monotonic()

    out: dict = {
        "metric": "fedavg_rounds_per_sec_32stations_cnn",
        "value": None,
        "unit": "rounds/sec",
        "vs_baseline": None,
        "budget_s": BENCH_BUDGET_S,
    }
    legs_done: list[str] = []
    legs_failed: list[str] = []
    bench_notes: list[dict] = []

    def leg_note(kind: str, leg: str, **fields) -> None:
        """One flight-note-shaped record (`{"type": "note", ts, kind,
        ...}` — the flight recorder's on-disk shape, built by hand
        because the bench parent must never import the package, whose
        __init__ pulls jax). `v6t_bench_leg_*` kinds classify WHY a leg
        has no number."""
        bench_notes.append({
            "type": "note", "ts": round(time.time(), 3),
            "kind": kind, "leg": leg, **fields,
        })

    def run_leg(name: str, mode: str, nominal_s: float, *, host: bool,
                extra_env: dict[str, str] | None = None
                ) -> tuple[dict | None, str]:
        """Run one leg in its own worker — once. `host=True` pins the
        worker to the CPU, the deployment platform of a host leg; a device
        leg runs on the chip, and when it fails that IS its result. A leg
        the budget has no room for is skipped, never started. The outcome
        lands in legs_done as ok / ':skipped' / ':failed' and as a
        v6t_bench_leg_* note (a timeout told apart from a crash), so the
        artifact does not conflate 'investigate this' with 'expected
        budget behavior'."""
        if remaining() <= MIN_LEG_S:
            result, diag = None, f"skipped: {remaining():.0f}s left in budget"
        else:
            result, diag = _run_worker(
                mode, force_cpu=host, extra_env=extra_env,
                timeout_s=max(1.0, min(nominal_s, remaining())),
            )
        if result is not None:
            leg_note("v6t_bench_leg_ok", name)
            legs_done.append(name)
        elif diag.startswith("skipped"):
            leg_note("v6t_bench_leg_skipped", name, diag=diag)
            legs_done.append(name + ":skipped")
        else:
            kind = ("v6t_bench_leg_timeout" if "timeout after" in diag
                    else "v6t_bench_leg_failed")
            leg_note(kind, name, diag=diag)
            legs_done.append(name + ":failed")
            legs_failed.append(name)
        return result, diag

    ckpt_path = os.environ.get("BENCH_CHECKPOINT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_CHECKPOINT.json"
    )
    notes_path = os.environ.get("BENCH_FLIGHT_NOTES") or os.path.join(
        os.path.dirname(ckpt_path), "BENCH_FLIGHT.jsonl"
    )

    def emit(partial: bool = True) -> None:
        """Print the CUMULATIVE result after every leg — the driver parses
        the LAST valid JSON line, so a kill at any moment preserves every
        leg that already finished (VERDICT r4 weak #1) — AND checkpoint the
        same JSON to disk (BENCH_CHECKPOINT, atomic tmp+rename): a SIGKILL
        mid-leg or a driver that loses our stdout still leaves every
        finished leg's numbers on disk. Fail-soft: a full disk must
        degrade the checkpoint, never the bench."""
        out["elapsed_s"] = round(time.monotonic() - t_start, 1)
        out["legs_done"] = list(legs_done)
        out["legs_failed"] = list(legs_failed)
        # why a leg has no number, in the artifact itself: counts per
        # v6t_bench_leg_* kind, the non-ok legs by name, and the notes
        # (flight-note-shaped; also mirrored to a doctor-readable JSONL)
        by_kind: dict[str, int] = {}
        for n in bench_notes:
            by_kind[n["kind"]] = by_kind.get(n["kind"], 0) + 1
        out["bench_health"] = {
            "by_kind": by_kind,
            "degraded_legs": sorted({
                n["leg"] for n in bench_notes
                if n["kind"] != "v6t_bench_leg_ok"
            }),
            "notes": bench_notes,
            "flight_notes_path": notes_path,
        }
        out["partial"] = partial
        line = json.dumps(out)
        print(line, flush=True)
        try:
            tmp = ckpt_path + ".tmp"
            with open(tmp, "w") as fh:
                fh.write(line + "\n")
                fh.flush()
                os.fsync(fh.fileno())
            os.replace(tmp, ckpt_path)
        except OSError:
            pass
        try:
            # the same notes as a flight-bundle-shaped JSONL, so
            # `tools/doctor.py BENCH_FLIGHT.jsonl` renders the round's
            # story with the tooling operators already know.
            # Fail-soft like the checkpoint.
            with open(notes_path, "w") as fh:
                for rec in bench_notes:
                    fh.write(json.dumps(rec) + "\n")
        except OSError:
            pass

    emit()  # a kill during the first leg still leaves a parseable line

    # ---- headline: the one-program SPMD FedAvg round (device) ---------
    spmd, spmd_diag = run_leg("spmd", "spmd", WORKER_TIMEOUT_S, host=False)
    flops_round = cnn_train_flops_per_round(N_STATIONS)
    out["stations"] = N_STATIONS
    out["model_flops_per_round"] = flops_round
    out["timing_valid"] = True
    if spmd is not None:
        rps = spmd["rounds_per_sec"]
        out["value"] = round(rps, 3)
        out["platform"] = spmd["platform"]
        out["device_kind"] = spmd["device_kind"]
        out["n_devices"] = spmd["n_devices"]
        out["round_time_ms"] = round(spmd["round_time_ms"], 3)
        out["run_times_s"] = spmd.get("run_times_s")
        achieved = rps * flops_round
        out["achieved_flops_per_sec"] = round(achieved, 1)
        out["accuracy_tpu_path"] = spmd.get("accuracy")
        peak = device_peaks(spmd["device_kind"])["bf16_flops"]
        mfu = achieved / (peak * spmd["n_devices"])
        out["mfu_vs_v5e_bf16_peak"] = round(mfu, 6)
        if mfu > 1.0:  # physically impossible => the timing is wrong
            out["timing_valid"] = False
    else:
        out["error"] = f"spmd: {spmd_diag}"
    emit()

    # ---- fused multi-round device program (one dispatch per K rounds) --
    fu, fu_diag = run_leg("fused", "fused", FUSED_TIMEOUT_S, host=False)
    if fu is not None:
        out["fused"] = fu
        out["fused_rounds_per_sec"] = round(fu["fused_rounds_per_sec"], 3)
        out["fused_speedup_vs_per_round_dispatch"] = round(
            fu["fused_speedup"], 2
        )
        fu_mfu = (
            fu["fused_rounds_per_sec"]
            * cnn_train_flops_per_round(fu["n_stations"])
            / (device_peaks(fu["device_kind"])["bf16_flops"]
               * fu["n_devices"])
        )
        out["fused_mfu_vs_v5e_bf16_peak"] = round(fu_mfu, 6)
        if fu_mfu > 1.0:
            out["timing_valid"] = False
    else:
        out["fused_error"] = fu_diag
    emit()

    # ---- reference-shaped baseline (host) -----------------------------
    base, base_diag = run_leg(
        "baseline", "baseline", WORKER_TIMEOUT_S, host=True,
        extra_env={"BENCH_ACC_ROUNDS": str(
            spmd["rounds_trained"] if spmd else SPMD_ROUNDS
        )},
    )
    if base is not None:
        out["baseline_rounds_per_sec"] = round(base["rounds_per_sec"], 4)
        out["baseline_rounds"] = base["rounds"]
        out["baseline_timing_method"] = base.get("timing_method")
        out["accuracy_baseline_path"] = base.get("accuracy")
        if spmd is not None:
            out["vs_baseline"] = round(
                spmd["rounds_per_sec"] / base["rounds_per_sec"], 2
            )
            if (
                spmd.get("accuracy") is not None
                and base.get("accuracy") is not None
                and spmd.get("rounds_trained") == base.get("rounds_trained")
            ):
                gap = abs(spmd["accuracy"] - base["accuracy"])
                out["accuracy_gap"] = round(gap, 4)
                out["accuracy_tolerance"] = ACC_TOLERANCE
                out["accuracy_parity"] = bool(gap <= ACC_TOLERANCE)
    else:
        out["baseline_error"] = base_diag
    emit()

    # ---- legs whose result is stored whole -----------------------------
    # device: server-update aggregation modes (sharded update PR) and the
    # dense vs int8+top-k+EF delta exchange; host (CPU is the deployment
    # platform, no tensor throughput is measured): the host-path executor
    # pool, the control-plane fast path and its 1-vs-N replica scale-out,
    # the tracing/ops overhead guardrail, wire format v1 vs v2, and the
    # buffered-async + autopilot loop.
    for name, mode, key_, nominal_s, host in (
        ("agg", "agg", "agg_modes", AGG_TIMEOUT_S, False),
        ("host_parallel", "hostparallel", "host_parallel",
         HOST_TIMEOUT_S, True),
        ("control_plane", "controlplane", "control_plane",
         CONTROL_TIMEOUT_S, True),
        ("control_plane_scale", "cpscale", "control_plane_scale",
         CPSCALE_TIMEOUT_S, True),
        ("observability", "observability", "observability",
         OBS_TIMEOUT_S, True),
        ("wire_format", "wireformat", "wire_format", WIRE_TIMEOUT_S, True),
        ("compression", "compression", "compression",
         COMPRESS_TIMEOUT_S, False),
        ("autopilot", "autopilot", "autopilot", AP_TIMEOUT_S, True),
    ):
        result, diag = run_leg(name, mode, nominal_s, host=host)
        if result is not None:
            out[key_] = result
        else:
            out[key_ + "_error"] = diag
        emit()

    # ---- MXU utilization metric (transformer, device) ------------------
    tf, tf_diag = run_leg(
        "transformer", "transformer", WORKER_TIMEOUT_S, host=False
    )
    if tf is not None:
        out["transformer_step_time_ms"] = tf["step_time_ms"]
        out["transformer_tokens_per_sec"] = tf["tokens_per_sec"]
        out["transformer_achieved_tflops"] = tf["achieved_tflops"]
        out["transformer_attention"] = tf["attention"]
        out["transformer_config"] = tf["config"]
        out["transformer_platform"] = tf["platform"]
        tf_mfu = tf["flops_per_step"] / (
            tf["step_time_ms"] / 1e3
        ) / device_peaks(tf["device_kind"])["bf16_flops"]
        out["transformer_mfu_vs_v5e_bf16_peak"] = round(tf_mfu, 4)
        if tf_mfu > 1.0:
            out["timing_valid"] = False
    else:
        out["transformer_error"] = tf_diag
    emit()

    # ---- federation overhead at MXU scale (device) ---------------------
    fo, fo_diag = run_leg(
        "fedoverhead", "fedoverhead", WORKER_TIMEOUT_S, host=False
    )
    if fo is not None:
        out["fed_overhead"] = {
            k: fo[k]
            for k in (
                "n_stations", "s1_step_ms", "round_ms",
                "per_station_ms_in_round", "fed_overhead_pct",
                "achieved_tflops", "platform", "config",
            )
        }
        out["fed_overhead"]["mfu_vs_v5e_bf16_peak"] = round(
            fo["flops_per_round"]
            / (fo["round_ms"] / 1e3)
            / device_peaks(fo["device_kind"])["bf16_flops"],
            4,
        )
    else:
        out["fed_overhead_error"] = fo_diag
    emit(partial=False)
    sys.exit(1 if legs_failed else 0)


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--worker":
        {"spmd": worker_spmd,
         "fused": worker_fused,
         "agg": worker_agg,
         "baseline": worker_baseline,
         "hostparallel": worker_hostparallel,
         "controlplane": worker_controlplane,
         "cpscale": worker_cpscale,
         "replica": worker_replica,
         "observability": worker_observability,
         "wireformat": worker_wireformat,
         "compression": worker_compression,
         "autopilot": worker_autopilot,
         "transformer": worker_transformer,
         "fedoverhead": worker_fedoverhead}[sys.argv[2]]()
    else:
        main()
