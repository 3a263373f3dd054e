"""`v6t` — the operator CLI.

Parity: the reference's `v6` CLI (SURVEY.md §2 item 26): instance
management for nodes/servers/stores (`new/start/stop/list/files`), a
one-machine demo network (`v6t dev`), algorithm boilerplate
(`v6t algorithm create`), and a smoke test (`v6t test`). The reference
spins every instance up as a docker container; here instances are local
processes (pid files under the instance data dir) — same lifecycle verbs,
no docker dependency.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import click
import yaml

from vantage6_tpu.common.context import (
    ConfigurationError,
    NodeContext,
    ServerContext,
    StoreContext,
)


class _FriendlyGroup(click.Group):
    """Operator errors (bad/missing configs) print one line, not tracebacks."""

    def invoke(self, ctx: click.Context):
        try:
            return super().invoke(ctx)
        except ConfigurationError as e:
            raise click.ClickException(str(e)) from None


@click.group(name="v6t", cls=_FriendlyGroup)
@click.version_option(package_name="vantage6-tpu")
def cli() -> None:
    """vantage6-tpu: TPU-native federated analysis."""


# ------------------------------------------------------------------ helpers


# image name -> importable module, for demo networks and `v6t run`
BUILTIN_ALGORITHMS = {
    "v6-average-py": "vantage6_tpu.workloads.average",
    "v6-summary-py": "vantage6_tpu.workloads.summary",
    "v6-logistic-regression-py": "vantage6_tpu.workloads.logistic_regression",
    "v6-kaplan-meier-py": "vantage6_tpu.workloads.survival",
    "v6-fedavg-mnist": "vantage6_tpu.workloads.fedavg_mnist",
    "v6-secure-average": "vantage6_tpu.workloads.secure_average",
    "v6-glm-py": "vantage6_tpu.workloads.glm",
    "v6-crosstab-py": "vantage6_tpu.workloads.stats",
    "v6-correlation-py": "vantage6_tpu.workloads.stats",
    "v6-preprocess-py": "vantage6_tpu.workloads.preprocess",
    "v6-quantiles-py": "vantage6_tpu.workloads.quantiles",
    "v6-vertical-lr-py": "vantage6_tpu.workloads.vertical",
    "v6-device-engine": "vantage6_tpu.workloads.device_engine",
}


def _pid_file(ctx) -> Path:
    return ctx.data_dir / "instance.pid"


def _read_pid(pidfile: Path) -> int:
    """0 = no live pid recorded (empty/garbled files count as stale)."""
    try:
        return int(pidfile.read_text().strip() or 0)
    except (OSError, ValueError):
        return 0


def _alive(pid: int) -> bool:
    if pid <= 0:  # os.kill(0, ...) would signal our own process group
        return False
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True  # EPERM: exists, owned by another user


def _owns_chip(ctx) -> bool:
    """Whether the instance is configured to use the accelerator: a node
    that is a device-engine mesh member, or one that hands the chip to its
    sandboxed algorithms (policies: {accelerator: true}). Servers, stores
    and every other node are host-plane processes."""
    if ctx.kind != "node":
        return False
    cfg = ctx.config
    return cfg.get("device_engine") is not None or bool(
        (cfg.get("policies") or {}).get("accelerator")
    )


def _start_detached(ctx, runner_arg: str) -> int:
    pidfile = _pid_file(ctx)
    if pidfile.exists() and _alive(_read_pid(pidfile)):
        raise click.ClickException(f"{ctx.kind} {ctx.name!r} already running")
    logfile = ctx.log_dir / "stdout.log"
    # a chip belongs to ONE process at a time: every instance inherits this
    # environment, so the first one to touch jax would take the chip and
    # the rest fail or hang at start-up. Only an instance configured to own
    # the chip keeps the environment's platform; all others are pinned to
    # the CPU (docs/OPERATOR_GUIDE.md "One process for each chip").
    env = dict(os.environ)
    if not _owns_chip(ctx):
        env["JAX_PLATFORMS"] = "cpu"
    with open(logfile, "ab") as out:
        proc = subprocess.Popen(
            [sys.executable, "-m", "vantage6_tpu.cli.main", runner_arg, ctx.name],
            stdout=out,
            stderr=subprocess.STDOUT,
            start_new_session=True,
            env=env,
        )
    pidfile.write_text(str(proc.pid))
    return proc.pid


def _stop_instance(ctx) -> bool:
    pidfile = _pid_file(ctx)
    if not pidfile.exists():
        return False
    pid = _read_pid(pidfile)
    if not _alive(pid):
        pidfile.unlink(missing_ok=True)  # stale
        return False

    def _signal(sig: int) -> None:
        try:
            os.kill(pid, sig)
        except ProcessLookupError:
            pass  # exited between the liveness check and the signal
        except PermissionError:
            raise click.ClickException(
                f"pid {pid} belongs to another user (recycled pid?); "
                f"remove {pidfile} by hand if this instance is gone"
            ) from None

    _signal(signal.SIGTERM)
    for _ in range(50):
        if not _alive(pid):
            break
        time.sleep(0.1)
    else:
        _signal(signal.SIGKILL)  # did not honor SIGTERM in 5s
        for _ in range(20):
            if not _alive(pid):
                break
            time.sleep(0.1)
    if _alive(pid):
        raise click.ClickException(
            f"{ctx.kind} {ctx.name!r} (pid {pid}) survived SIGKILL"
        )
    pidfile.unlink(missing_ok=True)  # only after confirmed dead
    return True


def _status_row(ctx_cls, name: str) -> tuple[str, str]:
    try:
        ctx = ctx_cls(name)
    except ConfigurationError:
        return name, "broken config"
    pid = _read_pid(_pid_file(ctx)) if _pid_file(ctx).exists() else 0
    if _alive(pid):
        return name, f"running (pid {pid})"
    return name, "stopped"


# --------------------------------------------------------------------- node


@cli.group()
def node() -> None:
    """Manage data-station nodes."""


@node.command("new")
@click.option("--name", prompt=True)
@click.option("--api-url", prompt="Server API url")
@click.option("--api-key", prompt=True)
@click.option(
    "--database",
    "databases",
    multiple=True,
    help="label:type:uri triple, e.g. default:csv:/data/x.csv",
)
def node_new(name: str, api_url: str, api_key: str, databases: tuple[str]) -> None:
    """Create a node instance config."""
    dbs = []
    for spec in databases:
        parts = spec.split(":", 2)
        if len(parts) != 3 or not parts[2]:
            raise click.ClickException(
                f"--database {spec!r}: expected label:type:uri "
                "(e.g. default:csv:/data/x.csv)"
            )
        label, typ, uri = parts
        dbs.append({"label": label or "default", "type": typ or "csv", "uri": uri})
    ctx = NodeContext.create(
        name,
        {"api_url": api_url, "api_key": api_key, "databases": dbs},
    )
    click.echo(f"node config written to {ctx.config_path}")


@node.command("start")
@click.argument("name")
@click.option("--attach", is_flag=True, help="run in the foreground")
def node_start(name: str, attach: bool) -> None:
    """Start a node daemon."""
    ctx = NodeContext(name)
    if attach:
        _run_node(name)
        return
    pid = _start_detached(ctx, "_run-node")
    click.echo(f"node {name!r} started (pid {pid})")


@node.command("stop")
@click.argument("name")
def node_stop(name: str) -> None:
    ctx = NodeContext(name)
    click.echo(
        f"node {name!r} " + ("stopped" if _stop_instance(ctx) else "was not running")
    )


@node.command("list")
def node_list() -> None:
    for name in NodeContext.available_configurations():
        n, status = _status_row(NodeContext, name)
        click.echo(f"{n:30s} {status}")


@node.command("files")
@click.argument("name")
def node_files(name: str) -> None:
    """Print the instance's file locations (reference: `v6 node files`)."""
    ctx = NodeContext(name)
    click.echo(f"config: {ctx.config_path}")
    click.echo(f"data:   {ctx.data_dir}")
    click.echo(f"log:    {ctx.log_dir}")


@node.command("attach")
@click.argument("name")
def node_attach(name: str) -> None:
    """Tail the node's log (reference: `v6 node attach`)."""
    ctx = NodeContext(name)
    logfile = ctx.log_dir / "stdout.log"
    if not logfile.exists():
        raise click.ClickException(f"no log at {logfile}")
    with open(logfile, "rb") as f:  # tail without loading a multi-GB log
        f.seek(max(0, logfile.stat().st_size - 4096))
        click.echo(f.read().decode(errors="replace"), nl=False)


@node.command("clean")
@click.argument("name")
@click.confirmation_option(prompt="Remove this node's config and data?")
def node_clean(name: str) -> None:
    ctx = NodeContext(name)
    _stop_instance(ctx)
    import shutil

    shutil.rmtree(ctx.data_dir, ignore_errors=True)
    ctx.config_path.unlink(missing_ok=True)
    click.echo(f"node {name!r} removed")


@cli.command("_run-node", hidden=True)
@click.argument("name")
def _run_node_cmd(name: str) -> None:
    _run_node(name)


def _run_node(name: str) -> None:
    from vantage6_tpu.core.compile_cache import enable_compile_cache
    from vantage6_tpu.node.daemon import NodeDaemon

    enable_compile_cache()
    ctx = NodeContext(name)
    daemon = NodeDaemon.from_context(ctx)
    daemon.start(background=False)


# ------------------------------------------------------------------- server


@cli.group()
def server() -> None:
    """Manage control-plane servers."""


@server.command("new")
@click.option("--name", prompt=True)
@click.option("--port", default=ServerContext.DEFAULT_PORT, show_default=True)
def server_new(name: str, port: int) -> None:
    ctx = ServerContext.create(name, {"port": port})
    click.echo(f"server config written to {ctx.config_path}")


@server.command("start")
@click.argument("name")
@click.option("--attach", is_flag=True)
def server_start(name: str, attach: bool) -> None:
    ctx = ServerContext(name)
    if attach:
        _run_server(name)
        return
    pid = _start_detached(ctx, "_run-server")
    click.echo(f"server {name!r} started on port {ctx.port} (pid {pid})")


@server.command("stop")
@click.argument("name")
def server_stop(name: str) -> None:
    ctx = ServerContext(name)
    click.echo(
        f"server {name!r} "
        + ("stopped" if _stop_instance(ctx) else "was not running")
    )


@server.command("list")
def server_list() -> None:
    for name in ServerContext.available_configurations():
        n, status = _status_row(ServerContext, name)
        click.echo(f"{n:30s} {status}")


@server.command("import")
@click.argument("name")
@click.argument("entities_file", type=click.Path(exists=True))
def server_import(name: str, entities_file: str) -> None:
    """Seed organizations/collaborations/users from YAML
    (reference: `v6 server import`)."""
    ctx = ServerContext(name)
    with open(entities_file) as f:
        entities = yaml.safe_load(f) or {}
    from vantage6_tpu.server.app import ServerApp

    app = ServerApp(uri=ctx.uri)
    try:
        summary = _import_entities(app, entities)
    finally:
        app.close()
    click.echo(json.dumps(summary))


def _import_entities(app, entities: dict) -> dict:
    from vantage6_tpu.server import models as m

    created = {"organizations": 0, "collaborations": 0, "users": 0, "nodes": []}

    def org_by_name(name: str | None) -> "m.Organization | None":
        # orgs may come from this file OR already exist in the database
        return m.Organization.first(name=name) if name else None

    # validate EVERY reference up front: a failure mid-import would strand
    # partially-seeded entities and lose already-generated node api keys
    file_orgs = {o["name"] for o in entities.get("organizations", []) or []}

    def known(name: str | None) -> bool:
        return bool(name) and (name in file_orgs or org_by_name(name) is not None)

    for user in entities.get("users", []) or []:
        if user.get("organization") and not known(user["organization"]):
            raise click.ClickException(
                f"user {user['username']}: unknown org {user['organization']}"
            )
    for collab in entities.get("collaborations", []) or []:
        for org_name in collab.get("participants", []) or []:
            if not known(org_name):
                raise click.ClickException(
                    f"collaboration {collab['name']}: unknown org {org_name}"
                )

    for org in entities.get("organizations", []) or []:
        row = m.Organization.first(name=org["name"])
        if row is None:
            m.Organization(
                name=org["name"],
                country=org.get("country", ""),
                domain=org.get("domain", ""),
            ).save()
            created["organizations"] += 1
    for user in entities.get("users", []) or []:
        if m.User.first(username=user["username"]) is not None:
            continue
        org = org_by_name(user.get("organization"))
        row = m.User(
            username=user["username"],
            organization_id=org.id if org else None,
            email=user.get("email", ""),
        )
        row.set_password(user["password"])
        row.save()
        for role_name in user.get("roles", []) or []:
            role = m.Role.first(name=role_name, organization_id=None)
            if role:
                row.add_role(role)
        created["users"] += 1
    for collab in entities.get("collaborations", []) or []:
        row = m.Collaboration.first(name=collab["name"])
        if row is None:
            row = m.Collaboration(
                name=collab["name"],
                encrypted=bool(collab.get("encrypted", False)),
            ).save()
            created["collaborations"] += 1
        for org_name in collab.get("participants", []) or []:
            org = org_by_name(org_name)  # pre-validated above
            row.add_organization(org)
            node = m.Node.first(
                collaboration_id=row.id, organization_id=org.id
            )
            if node is None:
                api_key = m.Node.generate_api_key()
                node = m.Node(
                    name=f"{org_name} {collab['name']} node",
                    organization_id=org.id,
                    collaboration_id=row.id,
                    status="offline",
                )
                node.set_api_key(api_key)
                node.save()
                created["nodes"].append(
                    {"organization": org_name, "api_key": api_key}
                )
    return created


@cli.command("_run-server", hidden=True)
@click.argument("name")
def _run_server_cmd(name: str) -> None:
    _run_server(name)


def _run_server(name: str) -> None:
    from vantage6_tpu.server.app import run_server

    run_server(ServerContext(name))


# -------------------------------------------------------------------- store


@cli.group()
def store() -> None:
    """Manage algorithm stores."""


@store.command("new")
@click.option("--name", prompt=True)
@click.option("--port", default=StoreContext.DEFAULT_PORT, show_default=True)
def store_new(name: str, port: int) -> None:
    ctx = StoreContext.create(name, {"port": port})
    click.echo(f"store config written to {ctx.config_path}")


@store.command("start")
@click.argument("name")
@click.option("--attach", is_flag=True)
def store_start(name: str, attach: bool) -> None:
    ctx = StoreContext(name)
    if attach:
        _run_store(name)
        return
    pid = _start_detached(ctx, "_run-store")
    click.echo(f"store {name!r} started on port {ctx.port} (pid {pid})")


@store.command("stop")
@click.argument("name")
def store_stop(name: str) -> None:
    ctx = StoreContext(name)
    click.echo(
        f"store {name!r} "
        + ("stopped" if _stop_instance(ctx) else "was not running")
    )


@cli.command("_run-store", hidden=True)
@click.argument("name")
def _run_store_cmd(name: str) -> None:
    _run_store(name)


def _run_store(name: str) -> None:
    from vantage6_tpu.store.app import StoreApp

    ctx = StoreContext(name)
    app = StoreApp(
        uri=ctx.uri,
        reviewers=ctx.config.get("reviewers", []) or [],
        trusted_servers=ctx.config.get("trusted_servers", []) or [],
        open_review=bool(ctx.config.get("open_review", False)),
    )
    app.serve(port=ctx.port)


# ---------------------------------------------------------------------- dev


@cli.group()
def dev() -> None:
    """One-machine demo networks (reference: `v6 dev`)."""


@dev.command("create-demo-network")
@click.option("--name", default="demo", show_default=True)
@click.option("-n", "--num-nodes", default=3, show_default=True)
@click.option("--directory", type=click.Path(), default=None,
              help="where demo data lands (default: server data dir)")
def dev_create(name: str, num_nodes: int, directory: str | None) -> None:
    """Generate a server config, N node configs and demo data."""
    import numpy as np
    import pandas as pd

    if ServerContext.config_exists(f"{name}_server") or (
        StoreContext.config_exists(f"{name}_store")
    ):
        raise click.ClickException(
            f"demo network {name!r} already exists (fully or partially) — "
            f"run `v6t dev remove-demo-network --name {name}` first"
        )
    # the demo gets its own algorithm store, pre-seeded with the builtin
    # algorithms' INTROSPECTED metadata (store.introspect) and linked to
    # the server — the web UI's task wizard works out of the box.
    # server_port is THE single source for every URL below (store trust,
    # node api_url, login hint).
    server_port = ServerContext.DEFAULT_PORT
    api_url = f"http://127.0.0.1:{server_port}"
    store_ctx = StoreContext.create(
        f"{name}_store",
        {
            "port": StoreContext.DEFAULT_PORT,
            "trusted_servers": [api_url],
            "open_review": True,
        },
    )
    _seed_demo_store(store_ctx)
    server_ctx = ServerContext.create(
        f"{name}_server",
        {
            "port": server_port,
            "store_url": f"http://127.0.0.1:{store_ctx.port}",
        },
    )
    data_dir = Path(directory) if directory else server_ctx.data_dir / "demo_data"
    data_dir.mkdir(parents=True, exist_ok=True)

    rng = np.random.default_rng(76)
    entities: dict = {"organizations": [], "users": [], "collaborations": []}
    node_names = []
    for i in range(num_nodes):
        org = f"{name}_org_{i}"
        csv = data_dir / f"{org}.csv"
        pd.DataFrame(
            {
                "age": rng.normal(55, 12, 200).round(1),
                "weight": rng.normal(75, 15, 200).round(1),
                "event": rng.integers(0, 2, 200),
                "time": rng.exponential(365, 200).round(0),
            }
        ).to_csv(csv, index=False)
        entities["organizations"].append({"name": org})
        node_names.append((org, csv))
    entities["users"].append(
        {
            "username": "dev_admin",
            "password": "password123",
            "organization": f"{name}_org_0",
            "roles": ["Root"],
        }
    )
    entities["collaborations"].append(
        {
            "name": name,
            "encrypted": False,
            "participants": [o["name"] for o in entities["organizations"]],
        }
    )
    from vantage6_tpu.server.app import ServerApp

    app = ServerApp(uri=server_ctx.uri)
    try:
        summary = _import_entities(app, entities)
    finally:
        app.close()
    for (org, csv), node_info in zip(node_names, summary["nodes"]):
        NodeContext.create(
            f"{name}_node_{org.removeprefix(name + '_org_')}",
            {
                "api_url": api_url,
                "api_key": node_info["api_key"],
                "databases": [
                    {"label": "default", "type": "csv", "uri": str(csv)}
                ],
                "algorithms": dict(BUILTIN_ALGORITHMS),
                "runner": {"mode": "inline"},
            },
        )
    click.echo(
        f"demo network {name!r}: 1 server + 1 store + {num_nodes} nodes "
        "configured\n"
        f"  start:  v6t dev start-demo-network --name {name}\n"
        f"  login:  dev_admin / password123 at {api_url}"
    )


# demo-store wizard set: builtin task-round algorithms whose metadata the
# web UI renders as guided forms (image -> module, from BUILTIN_ALGORITHMS)
DEMO_STORE_IMAGES = (
    "v6-average-py",
    "v6-summary-py",
    "v6-logistic-regression-py",
    "v6-kaplan-meier-py",
    "v6-glm-py",
    "v6-crosstab-py",
    "v6-preprocess-py",
    "v6-quantiles-py",
)


def _seed_demo_store(store_ctx: "StoreContext") -> None:
    """Fill a fresh demo store with the builtins' introspected metadata,
    pre-approved (demo only; real deployments approve through reviews)."""
    from vantage6_tpu.store.app import StoreApp
    from vantage6_tpu.store.introspect import build_algorithm_spec

    app = StoreApp(uri=store_ctx.uri, open_review=True)
    try:
        for image in DEMO_STORE_IMAGES:
            spec = build_algorithm_spec(
                BUILTIN_ALGORITHMS[image], name=image, image=image
            )
            app.insert_algorithm(
                spec, submitted_by="demo-seed", status="approved"
            )
    finally:
        app.close()


@dev.command("start-demo-network")
@click.option("--name", default="demo", show_default=True)
def dev_start(name: str) -> None:
    if StoreContext.config_exists(f"{name}_store"):
        pid = _start_detached(StoreContext(f"{name}_store"), "_run-store")
        click.echo(f"store up (pid {pid})")
    server_ctx = ServerContext(f"{name}_server")
    pid = _start_detached(server_ctx, "_run-server")
    click.echo(f"server up (pid {pid})")
    # wait for the port
    import requests

    url = f"http://127.0.0.1:{server_ctx.port}/api/health"
    # monotonic: wall-clock steps (NTP) must not expire the wait
    deadline = time.monotonic() + 120  # cold jax import takes a while
    while True:
        try:
            if requests.get(url, timeout=1).status_code == 200:
                break
        except requests.RequestException:
            pass
        if time.monotonic() > deadline:
            raise click.ClickException(
                "server did not come up within 120s — check "
                f"{server_ctx.log_dir / 'stdout.log'}"
            )
        time.sleep(0.25)
    for node_name in NodeContext.available_configurations():
        if node_name.startswith(f"{name}_node_"):
            pid = _start_detached(NodeContext(node_name), "_run-node")
            click.echo(f"node {node_name} up (pid {pid})")


@dev.command("stop-demo-network")
@click.option("--name", default="demo", show_default=True)
def dev_stop(name: str) -> None:
    for node_name in NodeContext.available_configurations():
        if node_name.startswith(f"{name}_node_"):
            _stop_instance(NodeContext(node_name))
            click.echo(f"node {node_name} stopped")
    if ServerContext.config_exists(f"{name}_server"):
        _stop_instance(ServerContext(f"{name}_server"))
        click.echo("server stopped")
    if StoreContext.config_exists(f"{name}_store"):
        _stop_instance(StoreContext(f"{name}_store"))
        click.echo("store stopped")


@dev.command("remove-demo-network")
@click.option("--name", default="demo", show_default=True)
def dev_remove(name: str) -> None:
    import shutil

    for node_name in list(NodeContext.available_configurations()):
        if node_name.startswith(f"{name}_node_"):
            ctx = NodeContext(node_name)
            _stop_instance(ctx)
            shutil.rmtree(ctx.data_dir, ignore_errors=True)
            ctx.config_path.unlink(missing_ok=True)
    if ServerContext.config_exists(f"{name}_server"):
        ctx = ServerContext(f"{name}_server")
        _stop_instance(ctx)
        shutil.rmtree(ctx.data_dir, ignore_errors=True)
        ctx.config_path.unlink(missing_ok=True)
    if StoreContext.config_exists(f"{name}_store"):
        ctx = StoreContext(f"{name}_store")
        _stop_instance(ctx)
        shutil.rmtree(ctx.data_dir, ignore_errors=True)
        ctx.config_path.unlink(missing_ok=True)
    click.echo(f"demo network {name!r} removed")


# ---------------------------------------------------------------- algorithm


ALGORITHM_TEMPLATE = '''"""{name} — a vantage6-tpu algorithm.

Generated by `v6t algorithm create`. The same module runs:
- on-pod via the Federation runtime (device mode optional),
- containerized via `wrap_algorithm` (the env-file ABI),
- in unit tests via MockAlgorithmClient.
"""
from vantage6_tpu.algorithm.decorators import algorithm_client, data


@data(1)
def partial_{fn}(df, column: str):
    """Runs at every station on its own data. Return aggregates, not rows."""
    col = df[column]
    return {{"sum": float(col.sum()), "count": int(col.count())}}


@algorithm_client
def central_{fn}(client, column: str, organizations=None):
    """Runs once; fans out partials and combines them."""
    orgs = organizations or [o["id"] for o in client.organization.list()]
    task = client.task.create(
        input_={{"method": "partial_{fn}", "kwargs": {{"column": column}}}},
        organizations=orgs,
    )
    results = client.wait_for_results(task_id=task["id"])
    total = sum(r["sum"] for r in results)
    count = sum(r["count"] for r in results)
    return {{"average": total / count, "count": count}}
'''

ALGORITHM_TEST_TEMPLATE = '''"""Unit test via MockAlgorithmClient (no server/node needed)."""
import pandas as pd

from vantage6_tpu.algorithm.mock_client import MockAlgorithmClient

import {module} as algo


def test_central_{fn}():
    datasets = [
        [{{"database": pd.DataFrame({{"x": [1.0, 2.0]}})}}],
        [{{"database": pd.DataFrame({{"x": [3.0, 5.0]}})}}],
    ]
    client = MockAlgorithmClient(datasets=datasets, module=algo)
    task = client.task.create(
        input_={{"method": "central_{fn}", "kwargs": {{"column": "x"}}}},
        organizations=[client.organization.list()[0]["id"]],
    )
    result = client.result.get(task["id"])[0]
    assert result["average"] == 2.75
'''


@cli.group()
def algorithm() -> None:
    """Algorithm development helpers."""


@algorithm.command("describe")
@click.argument("module")
@click.option("--name", default=None, help="algorithm display name")
@click.option("--image", default=None, help="image the nodes resolve")
def algorithm_describe(module: str, name: str | None, image: str | None) -> None:
    """Introspect a module's decorated functions into store metadata.

    Prints the JSON payload for the store's POST /api/algorithm — every
    @data/@algorithm_client function becomes a Function row with typed
    Arguments, so the web UI task wizard can render a guided form for it.
    """
    import json as _json

    from vantage6_tpu.store.introspect import build_algorithm_spec

    spec = build_algorithm_spec(
        module, name=name or module.rsplit(".", 1)[-1],
        image=image or module.rsplit(".", 1)[-1],
    )
    click.echo(_json.dumps(spec, indent=2, default=str))


@algorithm.command("create")
@click.option("--name", prompt=True, help="package name, e.g. my-average")
@click.option("--directory", type=click.Path(), default=".", show_default=True)
def algorithm_create(name: str, directory: str) -> None:
    """Generate algorithm boilerplate (reference: `v6 algorithm create`)."""
    module = name.replace("-", "_")
    root = Path(directory) / module
    if root.exists():
        raise click.ClickException(f"{root} exists")
    root.mkdir(parents=True)
    fn = module.removeprefix("v6_")
    (root / "__init__.py").write_text(
        ALGORITHM_TEMPLATE.format(name=name, fn=fn)
    )
    (root / "test_algorithm.py").write_text(
        ALGORITHM_TEST_TEMPLATE.format(module=module, fn=fn)
    )
    click.echo(
        f"algorithm package at {root}\n"
        f"  functions: central_{fn}, partial_{fn}\n"
        f"  test: python -m pytest {root / 'test_algorithm.py'}"
    )


# ---------------------------------------------------------------------- run


@cli.command("run")
@click.argument("config", type=click.Path(exists=True))
@click.option("--image", required=True, help="registered algorithm image name")
@click.option("--method", required=True)
@click.option("--kwargs", "kwargs_json", default="{}", show_default=True)
@click.option(
    "--module",
    default=None,
    help="importable module providing the image (defaults to built-ins)",
)
def run_cmd(config: str, image: str, method: str, kwargs_json: str,
            module: str | None) -> None:
    """Run one federated task on-pod from a federation YAML (the TPU fast
    path — no server/nodes; stations are mesh shards)."""
    import importlib

    from vantage6_tpu.core.compile_cache import enable_compile_cache
    from vantage6_tpu.core.config import FederationConfig
    from vantage6_tpu.runtime.federation import Federation

    enable_compile_cache()
    mod_path = module or BUILTIN_ALGORITHMS.get(image)
    if not mod_path:
        raise click.ClickException(
            f"unknown image {image!r}; pass --module for custom algorithms"
        )
    fed = Federation(
        FederationConfig.load(config),
        algorithms={image: importlib.import_module(mod_path)},
    )
    fed.load_all_data()
    task = fed.create_task(
        image, {"method": method, "kwargs": json.loads(kwargs_json)}
    )
    results = fed.wait_for_results(task.id)
    click.echo(json.dumps(results, default=str))


# --------------------------------------------------------------------- test


@cli.command("test")
def test_cmd() -> None:
    """Smoke test: in-process federation end-to-end (reference: `v6 test`)."""
    import tempfile

    import numpy as np
    import pandas as pd

    from vantage6_tpu.client import UserClient
    from vantage6_tpu.node.daemon import NodeDaemon
    from vantage6_tpu.server.app import ServerApp

    click.echo("smoke: in-process server + 2 nodes + client ...")
    srv = ServerApp()
    srv.ensure_root(password="smoke-test-pw")
    http = srv.serve(port=0, background=True)
    tmpdir = tempfile.TemporaryDirectory(prefix="v6t_smoke_")
    tmp = Path(tmpdir.name)
    client = UserClient(http.url)
    client.authenticate("root", "smoke-test-pw")
    orgs = [client.organization.create(name=f"org{i}") for i in range(2)]
    collab = client.collaboration.create(
        name="smoke", organization_ids=[o["id"] for o in orgs]
    )
    daemons = []
    rng = np.random.default_rng(0)
    for i, o in enumerate(orgs):
        csv = tmp / f"{i}.csv"
        pd.DataFrame({"age": rng.normal(50, 5, 50)}).to_csv(csv, index=False)
        info = client.node.create(
            organization_id=o["id"], collaboration_id=collab["id"]
        )
        d = NodeDaemon(
            http.url,
            info["api_key"],
            algorithms={"v6-average-py": "vantage6_tpu.workloads.average"},
            databases=[{"label": "default", "type": "csv", "uri": str(csv)}],
            mode="inline",
            poll_interval=0.05,
        )
        d.start()
        daemons.append(d)
    try:
        task = client.task.create(
            collaboration=collab["id"],
            organizations=[orgs[0]["id"]],
            image="v6-average-py",
            input_={"method": "central_average", "kwargs": {"column": "age"}},
        )
        res = client.wait_for_results(task["id"], interval=0.05, timeout=60)
        click.echo(f"smoke OK: federated average = {res[0]['average']:.3f}")
    finally:
        for d in daemons:
            d.stop()
        http.stop()
        srv.close()
        tmpdir.cleanup()


if __name__ == "__main__":
    cli()
