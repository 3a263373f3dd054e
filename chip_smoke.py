"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py                 # on a machine with TPU chip(s)
    python chip_smoke.py --cpu-tiny      # here, to debug the command itself

ONE process drives the main path once through the entry points a user
calls, at the full width of the models the repo benches, on ALL local
devices, with random weights made from a seed:

- ``fedavg_cnn``        the headline FedAvg-CNN (bench.py sizes) through
                        ``make_engine`` -> ``FedAvg.run_rounds`` with the
                        shipped defaults, K rounds and K again;
- ``transformer_flash`` the Pallas flash kernel COMPILED, alone against
                        ``ops.flash_attention.reference`` (forward and
                        grad), then ``FedTransformer.round`` with
                        ``attention="flash"`` at the bench shape, then 4
                        stations packed on one chip (the kernel under vmap
                        inside shard_map);
- ``task_plane``        server + client + node daemon in this process, one
                        ``task.create(engine="device")`` ending on the
                        device; then ``Federation`` + ``central_fedavg``,
                        what ``v6t run`` does;
- ``collectives``       (more than one device) ZeRO-1 scattered update,
                        bf16 on-wire deltas, ``secure_sum``, ring attention
                        over the devices and one station per device,
                        against the replicated and the one-device results.

Each phase prints one JSON line (set-up = trace + lower + compile seconds,
wall seconds, losses, checks). Any failed check, exception or non-finite
value makes the process exit non-zero. After the phases comes the summary
line ``{"phase": "summary", "ok": ..., "phases": {...}, ..., "claim": null}``
(the times are there for the next issue to read — this script claims no
speed), and the LAST stdout line is the result the driver parses, these keys
and no others:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without a TPU, or in a directory that does not hold the repo, the script
exits non-zero before any phase and prints no result line.

Without ``--cpu-tiny`` the script refuses any platform but a TPU whose
``device_kind`` is in bench.py's peaks table. ``--cpu-tiny`` is the only way
onto the CPU (tiny sizes, kernels interpreted); it is chosen by the flag,
never by detection, and every line then says ``"platform": "cpu"``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback
from typing import Any

# Tolerances. The flash ones follow from the dtype (bf16 keeps 8 bits: a
# product of O(1) values is off by ~4e-3, and the kernel rounds p to bf16
# before the PV matmul; measured on the v5e: 9.4e-3 forward, 3.9e-3 grad).
# The parameter ones bound what two compilations of the same f32 math may
# differ by after K rounds of SGD on weights of O(0.1) — measured 0.0 for
# fused vs rounds on the v5e (CHANGES.md, PR 21) — and the bf16 one what
# rounding the summed deltas to 8 bits on the wire may add.
TOL = {
    "flash_fwd_abs": 5e-2,        # |kernel - f32 reference|, outputs O(1)
    "flash_grad_rel": 5e-2,       # max |g - g_ref| / max |g_ref|
    "fused_vs_rounds_abs": 1e-2,  # run_rounds(K) vs K x round(), params
    "packed_vs_separate_abs": 1e-2,  # one gather vs two, several devices
    "scattered_vs_replicated_abs": 1e-2,
    "bf16_vs_replicated_abs": 5e-2,
    "one_chip_vs_replicated_abs": 1e-2,
    "flash_vs_recompute_loss_rel": 2e-2,
    "sharded_vs_packed_loss_rel": 2e-2,
    "ring_vs_one_device_loss_rel": 5e-2,
}
CHANCE_ACCURACY = 0.1  # ten balanced classes


class Failed(Exception):
    """A check of the smoke did not hold."""


class CompileMeter:
    """Every trace / lowering / backend compile of this process, from jax's
    own monitoring events — plain ``jax.jit``, ``observed_jit`` and eager
    ops alike. A persistent-cache hit still passes through the backend
    compile event (its duration is then the retrieval), so ``compiles``
    counts programs BUILT OR LOADED and ``cache_hits`` says how many were
    loaded."""

    _DURATIONS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self) -> None:
        import jax.monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_: Any) -> None:
        if event in self._DURATIONS:
            self.seconds += seconds
            if event == self._DURATIONS[-1]:
                self.compiles += 1

    def _event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def _max_abs_diff(a: Any, b: Any) -> float:
    import jax
    import jax.numpy as jnp

    return max(
        float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def _finite(values: Any) -> bool:
    import numpy as np

    return bool(np.all(np.isfinite(np.asarray(values, np.float64))))


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def _sizes(tiny: bool) -> dict[str, Any]:
    """Full sizes come from bench.py (the one place they are defined); the
    tiny ones only have to make every code path run on the CPU."""
    import bench
    import jax.numpy as jnp

    if tiny:
        small_tf = dict(d=32, layers=1, heads=2, seq=32, batch=2, vocab=64)
        return {
            "cnn": dict(stations=4, n_per=32, local_steps=2, batch=8,
                        rounds=2, noise=0.3),
            "kernel": dict(b=1, h=2, t=64, d=16),
            "tf": small_tf,
            "fo": dict(small_tf, stations=4),
            "dtype": jnp.float32,
            "tiny": True,
        }
    return {
        "cnn": dict(stations=bench.N_STATIONS, n_per=bench.N_PER_STATION,
                    local_steps=bench.LOCAL_STEPS, batch=bench.BATCH,
                    rounds=bench.SPMD_ROUNDS, noise=bench.SYNTH_NOISE),
        "kernel": dict(b=bench.TF_BATCH, h=bench.TF_HEADS, t=bench.TF_SEQ,
                       d=bench.TF_D // bench.TF_HEADS),
        "tf": dict(d=bench.TF_D, layers=bench.TF_LAYERS, heads=bench.TF_HEADS,
                   seq=bench.TF_SEQ, batch=bench.TF_BATCH,
                   vocab=bench.TF_VOCAB),
        "fo": dict(bench.FO, stations=bench.FO_STATIONS),
        "dtype": jnp.bfloat16,
        "tiny": False,
    }


# ------------------------------------------------------------- fedavg_cnn
def _cnn_setup(sz: dict[str, Any], devices: Any = None, **engine_kw: Any):
    """(mesh, engine, stacked x, stacked y, counts) at the bench's CNN size
    on seeded SYNTHETIC data — ``synthetic_image_classes`` directly, so a
    ``./data/mnist`` under the cwd cannot change what runs."""
    import bench
    from vantage6_tpu.core.mesh import FederationMesh
    from vantage6_tpu.utils.datasets import synthetic_image_classes
    from vantage6_tpu.workloads import fedavg_mnist as W

    c = sz["cnn"]
    mesh = FederationMesh(c["stations"], devices=devices)
    engine = W.make_engine(
        mesh, local_steps=c["local_steps"], batch_size=c["batch"],
        local_lr=bench.LR, **engine_kw,
    )
    x, y = synthetic_image_classes(
        c["stations"] * c["n_per"], seed=0, noise=c["noise"]
    )
    sx, sy, counts = W.federate(x, y, c["stations"], mesh=mesh)
    return mesh, engine, sx, sy, counts


def _cnn_keys():
    """(params seed key, the key of the first K-round dispatch)."""
    import jax

    key = jax.random.key(0)
    return jax.random.fold_in(key, 1), jax.random.fold_in(key, 2)


def phase_fedavg_cnn(sz, meter, shared) -> dict[str, Any]:
    import bench
    import jax
    import numpy as np
    from vantage6_tpu.common.telemetry import REGISTRY
    from vantage6_tpu.utils.datasets import synthetic_image_classes
    from vantage6_tpu.workloads import fedavg_mnist as W

    k = sz["cnn"]["rounds"]
    mesh, engine, sx, sy, counts = _cnn_setup(sz)
    pkey, rkey = _cnn_keys()

    # K x round(): the reference the fused program must reproduce
    p = W.init_params(pkey)
    o = engine.init(p)
    seq_losses = []
    for rk in jax.random.split(rkey, k):
        p, o, loss, _ = engine.round(p, o, sx, sy, counts, rk)
        seq_losses.append(float(loss))
    p_seq = p

    # the shipped fast path, cold: K rounds in one dispatch
    t0 = time.perf_counter()
    p1, o1, losses1, stats1 = engine.run_rounds(
        W.init_params(pkey), sx, sy, counts, rkey, k
    )
    jax.block_until_ready(p1)
    first_s = time.perf_counter() - t0
    diff = _max_abs_diff(p1, p_seq)
    loss_diff = float(np.max(np.abs(np.asarray(losses1) - seq_losses)))
    shared["cnn_replicated"] = jax.device_get(p1)  # before p1 is donated

    # which gather the engine built (one over rows that carry their label,
    # or the two of x and y), and the other one beside it: a second engine
    # steered to the separate path here, since the program has no option
    gather_path = engine.gather_path(sx, sy)
    separate = W.make_engine(
        mesh, local_steps=sz["cnn"]["local_steps"],
        batch_size=sz["cnn"]["batch"], local_lr=bench.LR,
    )
    separate.gather_path = lambda x, y: "separate"
    p_sep, _, losses_sep, _ = separate.run_rounds(
        W.init_params(pkey), sx, sy, counts, rkey, k
    )
    gather_diff = _max_abs_diff(p1, p_sep)
    gather_loss_diff = float(
        np.max(np.abs(np.asarray(losses1) - np.asarray(losses_sep)))
    )

    # ... and K again from the returned state: the steady state
    run = engine._run
    before = (run.stats()["compiles"], meter.compiles)
    t0 = time.perf_counter()
    p2, o2, losses2, _ = engine.run_rounds(
        p1, sx, sy, counts, jax.random.fold_in(rkey, 1), k, opt_state=o1
    )
    jax.block_until_ready(p2)
    steady_s = time.perf_counter() - t0
    st = run.stats()
    after = (st["compiles"], meter.compiles)
    losses = [float(v) for v in np.asarray(losses1)] + [
        float(v) for v in np.asarray(losses2)
    ]
    ex, ey = synthetic_image_classes(
        2048, seed=777, noise=sz["cnn"]["noise"]
    )
    acc = W.evaluate(p2, ex, ey)
    snap = REGISTRY.snapshot()

    checks = {
        "losses_finite": _finite(losses) and _finite(seq_losses),
        "losses_falling": losses[-1] < losses[0],
        "accuracy_above_chance": acc > 2 * CHANCE_ACCURACY,
        "stats_returned":
            set(stats1) >= {"station_norm", "station_cos", "update_norm"}
            and _finite(stats1["update_norm"]),
        "fused_matches_rounds":
            diff <= TOL["fused_vs_rounds_abs"]
            and loss_diff <= TOL["fused_vs_rounds_abs"],
        "fused_matches_rounds_bit_for_bit": diff == 0.0 and loss_diff == 0.0,
        "packed_gather_built": gather_path == "packed",
        # the two programs feed loss_fn the same bits; on one chip they came
        # out identical, on four one ulp apart after one round (3e-8) and
        # 1.1e-3 after five (PERF.md section 6, PR 27): exact on one device,
        # a tolerance across devices
        "packed_matches_separate":
            max(gather_diff, gather_loss_diff) <= (
                0.0 if len(jax.devices()) == 1
                else TOL["packed_vs_separate_abs"]),
        "one_compile_of_fused_program": st["compiles"] == 1,
        "no_compile_after_first_dispatch": after == before,
        "no_retrace":
            st["retraces"] == 0
            and snap.get("v6t_jit_retraces_total", 0) == 0,
        "no_jit_fallback":
            st["fallbacks"] == 0
            and snap.get("v6t_jit_fallbacks_total", 0) == 0,
        "inputs_on_all_devices": all(
            len(a.sharding.device_set) == len(jax.devices())
            for a in (sx, sy, counts)
        ),
    }
    return {
        "checks": checks,
        "mesh": repr(mesh),
        "rounds_per_dispatch": k,
        "first_dispatch_seconds": round(first_s, 3),
        "steady_dispatch_seconds": round(steady_s, 4),
        "steady_round_ms": round(1e3 * steady_s / k, 3),
        "losses": [round(v, 4) for v in losses],
        "accuracy": round(acc, 4),
        "fused_vs_rounds_max_abs_diff": diff,
        "fused_vs_rounds_loss_max_abs_diff": loss_diff,
        "gather": gather_path,
        "packed_vs_separate_max_abs_diff": gather_diff,
        "packed_vs_separate_loss_max_abs_diff": gather_loss_diff,
    }


# ------------------------------------------------------ transformer_flash
def _tf_rounds(shape, sz, meter, attention, n_steps, n_stations=1,
               seq_devices=1, devices=None) -> dict[str, Any]:
    """A fresh FedTransformer (seeded weights and tokens) stepped
    ``n_steps`` rounds: its losses, the seconds of each step, the programs
    compiled after the first step, and how many devices hold the tokens."""
    import jax
    import jax.numpy as jnp
    from vantage6_tpu.workloads import fed_transformer as FT

    cfg = FT.TransformerConfig(
        vocab=shape["vocab"], d_model=shape["d"], n_heads=shape["heads"],
        n_layers=shape["layers"], max_len=shape["seq"], dtype=sz["dtype"],
        attention=attention, flash_interpret=sz["tiny"],
    )
    eng = FT.make_engine(
        n_stations=n_stations, seq_devices=seq_devices, cfg=cfg, lr=1e-3,
        devices=devices,
    )
    tokens = eng.shard_tokens(FT.make_federated_tokens(
        n_stations, batch=shape["batch"], seq_len=shape["seq"],
        vocab=shape["vocab"],
    ))
    params, opt = eng.init(jax.random.key(0))
    mask = jnp.ones(n_stations)
    losses, seconds, late = [], [], 0
    for i in range(n_steps):
        before = meter.compiles
        t0 = time.perf_counter()
        params, opt, loss = eng.round(params, opt, tokens, mask)
        losses.append(float(jax.block_until_ready(loss)))
        seconds.append(round(time.perf_counter() - t0, 4))
        if i:
            late += meter.compiles - before
    return {
        "losses": losses, "step_seconds": seconds,
        "compiled_after_first": late,
        "token_devices": len(tokens.sharding.device_set),
    }


def _falling(losses: list[float]) -> bool:
    return _finite(losses) and losses[-1] < losses[0]


def _flash_vs_reference(sz) -> dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from vantage6_tpu.ops.flash_attention import flash_attention, reference

    kz = sz["kernel"]
    shape = (kz["b"], kz["h"], kz["t"], kz["d"])
    q, k, v, w = (
        jax.random.normal(jax.random.key(i), shape, jnp.float32)
        for i in range(4)
    )
    qc, kc, vc = (a.astype(sz["dtype"]) for a in (q, k, v))

    def kernel_loss(q, k, v):
        o = flash_attention(
            q, k, v, causal=True, interpret=sz["tiny"]
        )
        return jnp.sum(o.astype(jnp.float32) * w), o

    def ref_loss(q, k, v):
        o = reference(q, k, v, causal=True)
        return jnp.sum(o * w), o

    grad_kernel = jax.jit(jax.grad(kernel_loss, argnums=(0, 1, 2),
                                   has_aux=True))
    t0 = time.perf_counter()
    g, o = jax.block_until_ready(grad_kernel(qc, kc, vc))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(grad_kernel(qc, kc, vc))
    steady_s = time.perf_counter() - t0
    # the oracle sees the SAME (rounded) inputs, in f32 at full precision
    with jax.default_matmul_precision("highest"):
        g_ref, o_ref = jax.block_until_ready(jax.jit(jax.grad(
            ref_loss, argnums=(0, 1, 2), has_aux=True
        ))(*(a.astype(jnp.float32) for a in (qc, kc, vc))))
    fwd_err = _max_abs_diff(o, o_ref)
    grad_err = max(
        _max_abs_diff(a, b) / float(jnp.max(jnp.abs(b)))
        for a, b in zip(g, g_ref)
    )
    return {
        "shape": list(shape), "dtype": jnp.dtype(sz["dtype"]).name,
        "compiled": not sz["tiny"],
        "fwd_max_abs_err": fwd_err, "grad_max_rel_err": grad_err,
        "fwd_bwd_first_seconds": round(first_s, 3),
        "fwd_bwd_steady_ms": round(1e3 * steady_s, 3),
        "finite": _finite(o) and all(_finite(a) for a in g),
    }


def phase_transformer_flash(sz, meter, shared) -> dict[str, Any]:
    import jax

    kern = _flash_vs_reference(sz)
    # FedTransformer.round at the bench shape, flash, one station
    one_st = _tf_rounds(sz["tf"], sz, meter, "flash", 3)
    # 4 stations packed on ONE chip: pallas_call under vmap in shard_map;
    # and the same round with attention="recompute", for its first loss
    fo = sz["fo"]
    on_one = dict(n_stations=fo["stations"], devices=jax.devices()[:1])
    packed = _tf_rounds(fo, sz, meter, "flash", 3, **on_one)
    recompute = _tf_rounds(fo, sz, meter, "recompute", 1, **on_one)
    shared["fo_packed_first_loss"] = packed["losses"][0]
    checks = {
        "kernel_finite": kern["finite"],
        "kernel_fwd_matches_reference":
            kern["fwd_max_abs_err"] <= TOL["flash_fwd_abs"],
        "kernel_grad_matches_reference":
            kern["grad_max_rel_err"] <= TOL["flash_grad_rel"],
        "round_losses_finite_falling": _falling(one_st["losses"]),
        "round_no_compile_after_first": one_st["compiled_after_first"] == 0,
        "packed_losses_finite_falling": _falling(packed["losses"]),
        "packed_no_compile_after_first": packed["compiled_after_first"] == 0,
        "packed_flash_matches_recompute":
            _rel(packed["losses"][0], recompute["losses"][0])
            <= TOL["flash_vs_recompute_loss_rel"],
    }
    return {
        "checks": checks,
        "kernel": kern,
        "round": {"config": sz["tf"], "attention": "flash", **one_st},
        "packed": {"config": fo, "attention": "flash", **packed,
                   "recompute_first_loss": recompute["losses"][0]},
    }


# -------------------------------------------------------------- task_plane
def _device_engine_task(tmp: str) -> dict[str, Any]:
    """server + researcher + one inline node daemon whose device engine is
    this process's devices; one engine="device" task."""
    import numpy as np
    import pandas as pd
    from vantage6_tpu.client import UserClient
    from vantage6_tpu.node.daemon import NodeDaemon
    from vantage6_tpu.server.app import ServerApp

    vals = np.random.default_rng(7).uniform(20, 80, 500).round(1)
    pd.DataFrame({"age": vals}).to_csv(f"{tmp}/s0.csv", index=False)
    srv = ServerApp()
    http = daemon = None
    try:
        srv.ensure_root(password="rootpass123")
        http = srv.serve(port=0, background=True)
        client = UserClient(http.url)
        client.authenticate("root", "rootpass123")
        org = client.organization.create(name="chip_org")
        collab = client.collaboration.create(
            name="chip", organization_ids=[org["id"]]
        )
        node = client.node.create(
            organization_id=org["id"], collaboration_id=collab["id"]
        )
        daemon = NodeDaemon(
            api_url=http.url,
            api_key=node["api_key"],
            algorithms={
                "device-engine": "vantage6_tpu.workloads.device_engine"
            },
            databases=[
                {"label": "default", "type": "csv", "uri": f"{tmp}/s0.csv"}
            ],
            mode="inline",
            poll_interval=0.1,
            device_engine={},  # local devices only
        )
        daemon.start()
        t0 = time.perf_counter()
        task = client.task.create(
            collaboration=collab["id"],
            organizations=[org["id"]],
            image="device-engine",
            input_={
                "method": "device_column_stats",
                "kwargs": {"column": "age", "pad_to": 512},
            },
            databases=[{"label": "default"}],
            engine="device",
        )
        result = client.wait_for_results(
            task_id=task["id"], interval=0.2, timeout=300
        )[0]
        task_s = time.perf_counter() - t0
        statuses = [r["status"] for r in client.run.from_task(task["id"])]
    finally:
        if daemon is not None:
            daemon.stop()
        if http is not None:
            http.stop()
        srv.close()
    return {
        "result": result,
        "expected": {"mean": float(vals.mean()), "std": float(vals.std()),
                     "count": len(vals)},
        "run_statuses": statuses,
        "task_seconds": round(task_s, 3),
    }


def _central_fedavg(sz) -> dict[str, Any]:
    """Federation + central_fedavg for two rounds — what `v6t run
    --image v6-fedavg-mnist --method central_fedavg` does."""
    import numpy as np
    from vantage6_tpu.runtime.federation import federation_from_datasets
    from vantage6_tpu.utils.datasets import synthetic_image_classes
    from vantage6_tpu.workloads import fedavg_mnist as W

    c = sz["cnn"]
    n, per = c["stations"], c["n_per"]
    x, y = synthetic_image_classes(n * per, seed=5, noise=c["noise"])
    datasets = [
        {"x": x[i * per:(i + 1) * per], "y": y[i * per:(i + 1) * per],
         "count": np.float32(per), "sid": np.int32(i)}
        for i in range(n)
    ]
    fed = federation_from_datasets(datasets, {"v6-fedavg-mnist": W})
    try:
        t0 = time.perf_counter()
        task = fed.create_task(
            "v6-fedavg-mnist",
            {"method": "central_fedavg",
             "kwargs": {"n_rounds": 2, "local_steps": c["local_steps"],
                        "batch_size": c["batch"]}},
            organizations=[fed.organization_ids()[0]],
        )
        (res,) = fed.wait_for_results(task.id, timeout=600)
        seconds = time.perf_counter() - t0
        statuses = sorted({
            r.status.value for t in fed.tasks.values() for r in t.runs
        })
    finally:
        fed.close()
    return {
        "losses": [float(v) for v in res["losses"]],
        "run_statuses": statuses,
        "n_tasks": len(fed.tasks),
        "seconds": round(seconds, 3),
    }


def phase_task_plane(sz, meter, shared) -> dict[str, Any]:
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        de = _device_engine_task(tmp)
    cf = _central_fedavg(sz)
    got, want = de["result"], de["expected"]
    checks = {
        "device_task_matches_numpy":
            abs(got["mean"] - want["mean"]) < 1e-3
            and abs(got["std"] - want["std"]) < 1e-3
            and got["count"] == want["count"],
        # a crashed device step is caught inside the runtime: the run's
        # status is what says it did not crash
        "device_task_completed": de["run_statuses"] == ["completed"],
        "central_fedavg_losses_finite":
            len(cf["losses"]) == 2 and _finite(cf["losses"]),
        "central_fedavg_all_runs_completed":
            cf["run_statuses"] == ["completed"] and cf["n_tasks"] == 3,
    }
    return {"checks": checks, "device_engine": de, "central_fedavg": cf}


# ------------------------------------------------------------- collectives
def phase_collectives(sz, meter, shared) -> dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from vantage6_tpu.fed import collectives as C
    from vantage6_tpu.runtime.metrics import device_memory_all
    from vantage6_tpu.workloads import fedavg_mnist as W

    devs = jax.devices()
    k = sz["cnn"]["rounds"]
    pkey, rkey = _cnn_keys()

    def k_rounds(devices=None, **kw):
        mesh, engine, sx, sy, counts = _cnn_setup(sz, devices=devices, **kw)
        p, _, losses, _ = engine.run_rounds(
            W.init_params(pkey), sx, sy, counts, rkey, k
        )
        placed = all(
            len(a.sharding.device_set) == len(mesh.mesh.devices.flat)
            for a in (sx, sy, counts)
        )
        return mesh, jax.device_get(p), np.asarray(losses), placed

    replicated = shared.get("cnn_replicated")
    if replicated is None:  # fedavg_cnn was not among the phases run
        _, replicated, _, _ = k_rounds()
    mesh, scattered, s_losses, s_placed = k_rounds(shard_server_update=True)
    _, bf16, b_losses, _ = k_rounds(
        shard_server_update=True, comm_dtype=jnp.bfloat16
    )
    mem = device_memory_all()
    _, one_chip, _, _ = k_rounds(devices=devs[:1])
    diffs = {
        "scattered_vs_replicated": _max_abs_diff(scattered, replicated),
        "bf16_vs_replicated": _max_abs_diff(bf16, replicated),
        "one_chip_vs_replicated": _max_abs_diff(one_chip, replicated),
    }

    # secure_sum: pairwise masks cancel EXACTLY in int32, across devices
    s = mesh.n_stations
    vals = np.random.default_rng(3).normal(0, 1, (s, 4096)).astype(np.float32)
    stacked = mesh.shard_stacked(vals)
    scale = 2.0**16
    secure = jax.jit(C.secure_sum, static_argnums=2)(
        stacked, jax.random.key(11), scale
    )
    plain = jax.jit(
        lambda x: C.dequantize(jnp.sum(C.quantize(x, scale), axis=0), scale)
    )(stacked)

    # the transformer over the devices: ring attention (ppermute), and one
    # station per device, against one device
    fo = sz["fo"]
    ring = _tf_rounds(fo, sz, meter, "ring", 2, seq_devices=len(devs))
    base = _tf_rounds(fo, sz, meter, "recompute", 1, devices=devs[:1])
    sharded = _tf_rounds(fo, sz, meter, "flash", 2, n_stations=len(devs))
    packed = shared.get("fo_packed_first_loss")

    checks = {
        "mesh_uses_every_device":
            mesh.station_axis_size * mesh.devices_per_station == len(devs),
        "inputs_on_all_devices": s_placed,
        "losses_finite": _finite(s_losses) and _finite(b_losses),
        **{name + "_agree": d <= TOL[name + "_abs"]
           for name, d in diffs.items()},
        # CPU devices report no memory stats; on the chip every device
        # must hold bytes after the rounds above
        "bytes_on_every_device":
            sz["tiny"] and not mem
            or len(mem) == len(devs)
            and all((d["bytes_in_use"] or 0) > 0 for d in mem),
        "secure_sum_masks_cancel_exactly":
            bool(jnp.array_equal(secure, plain)),
        "secure_sum_close_to_float_sum":
            float(np.max(np.abs(np.asarray(secure) - vals.sum(0))))
            <= s / scale,
        "transformer_tokens_on_all_devices":
            ring["token_devices"] == sharded["token_devices"] == len(devs),
        "ring_losses_finite_falling": _falling(ring["losses"]),
        "ring_matches_one_device":
            _rel(ring["losses"][0], base["losses"][0])
            <= TOL["ring_vs_one_device_loss_rel"],
        "sharded_losses_finite_falling": _falling(sharded["losses"]),
        "no_compile_after_first":
            ring["compiled_after_first"] == 0
            and sharded["compiled_after_first"] == 0,
    }
    if packed is not None and len(devs) == fo["stations"]:
        # same stations, params and tokens as the one-chip packed round
        checks["sharded_matches_packed"] = (
            _rel(sharded["losses"][0], packed)
            <= TOL["sharded_vs_packed_loss_rel"]
        )
    return {
        "checks": checks,
        "mesh": repr(mesh),
        "param_max_abs_diff": diffs,
        "device_memory": mem,
        "ring": {"seq_devices": len(devs), **ring,
                 "one_device_first_loss": base["losses"][0]},
        "sharded": {"n_stations": len(devs), **sharded,
                    "packed_first_loss": packed},
    }


# -------------------------------------------------------------------- main
PHASES = {
    "fedavg_cnn": phase_fedavg_cnn,
    "transformer_flash": phase_transformer_flash,
    "task_plane": phase_task_plane,
    "collectives": phase_collectives,  # only with more than one device
}


def _cache_entries(directory: str) -> int:
    try:
        return len(os.listdir(directory))
    except FileNotFoundError:
        return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-tiny", action="store_true",
        help="debug mode: CPU, tiny sizes, kernels interpreted",
    )
    ap.add_argument(
        "--only", default="",
        help="comma-separated phases to run (debugging; default: all of "
        + ", ".join(PHASES) + ")",
    )
    args = ap.parse_args(argv)
    only = tuple(p for p in args.only.split(",") if p)
    unknown = set(only) - set(PHASES)
    if unknown:
        ap.error(f"unknown phase(s) {sorted(unknown)}")
    here = os.path.dirname(os.path.abspath(__file__))
    absent = [n for n in ("bench.py", "vantage6_tpu")
              if not os.path.exists(os.path.join(here, n))]
    if absent:  # the program under test is the checkout this file is in
        print(f"chip_smoke: {absent} not found next to this script; run it "
              "from the root of a checkout", file=sys.stderr)
        return 2
    if args.cpu_tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"

    t_start = time.perf_counter()
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(json.dumps({"phase": "device", **device}), flush=True)
    if args.cpu_tiny:
        if device["platform"] != "cpu":
            print("chip_smoke: --cpu-tiny but jax is already on "
                  f"{device['platform']!r}", file=sys.stderr)
            return 2
    else:
        if device["platform"] != "tpu":
            print(f"chip_smoke: no TPU (platform {device['platform']!r}); "
                  "pass --cpu-tiny to debug the command on the CPU",
                  file=sys.stderr)
            return 2
        import bench

        bench.device_peaks(device["kind"])  # an unknown kind is an error

    from vantage6_tpu.core.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    entries_before = _cache_entries(cache_dir)
    print(json.dumps({
        "phase": "compile_cache", "platform": device["platform"],
        "dir": cache_dir, "placed_by_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "entries_before": entries_before,
    }), flush=True)

    meter = CompileMeter()
    sz = _sizes(args.cpu_tiny)
    selected = [
        p for p in PHASES
        if (not only or p in only)
        and (p != "collectives" or len(devs) > 1)
    ]
    outcome: dict[str, str] = {}
    setup: dict[str, float] = {}
    shared: dict[str, Any] = {}
    for name in selected:
        s0, c0, h0 = meter.seconds, meter.compiles, meter.cache_hits
        t0 = time.perf_counter()
        line: dict[str, Any] = {"phase": name,
                                "platform": device["platform"]}
        try:
            line.update(PHASES[name](sz, meter, shared))
            line["checks"] = {k: bool(v) for k, v in line["checks"].items()}
            failed = [k for k, ok in line["checks"].items() if not ok]
            if failed:
                raise Failed(f"checks failed: {failed}")
            line["ok"] = True
        except Exception as e:  # the smoke's own boundary: report, go on
            traceback.print_exc()
            line["ok"] = False
            line["error"] = f"{type(e).__name__}: {str(e)[:2000]}"
        line["setup_seconds"] = setup[name] = round(meter.seconds - s0, 3)
        line["programs_compiled"] = meter.compiles - c0
        line["cache_hits"] = meter.cache_hits - h0
        line["wall_seconds"] = round(time.perf_counter() - t0, 3)
        outcome[name] = "ok" if line["ok"] else "failed"
        print(json.dumps(line, default=str), flush=True)
    meter.close()

    from vantage6_tpu.common.telemetry import REGISTRY

    snap = REGISTRY.snapshot()
    jit = {"retraces": snap.get("v6t_jit_retraces_total", 0),
           "fallbacks": snap.get("v6t_jit_fallbacks_total", 0)}
    ok = (
        bool(outcome) and all(v == "ok" for v in outcome.values())
        and not any(jit.values())  # over the whole run, every phase
    )
    print(json.dumps({
        "phase": "summary",
        "platform": device["platform"],
        "ok": ok,
        "phases": outcome,
        "jit": jit,
        "only": list(only) or None,
        "cpu_tiny": args.cpu_tiny,
        "compile_cache": {
            "dir": cache_dir, "entries_before": entries_before,
            "entries_after": _cache_entries(cache_dir),
            "hits": meter.cache_hits,
        },
        "setup_seconds": setup,
        "wall_seconds": round(time.perf_counter() - t_start, 3),
        "claim": None,
    }), flush=True)
    # the result line: exactly these keys, the device as jax reports it
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
