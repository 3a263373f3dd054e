"""CLI tests (click test runner; XDG roots redirected into tmp)."""
import json

import pytest
import yaml
from click.testing import CliRunner

from vantage6_tpu.cli.main import cli


@pytest.fixture()
def env(tmp_path, monkeypatch):
    monkeypatch.setenv("XDG_CONFIG_HOME", str(tmp_path / "cfg"))
    monkeypatch.setenv("XDG_DATA_HOME", str(tmp_path / "data"))
    monkeypatch.setenv("XDG_STATE_HOME", str(tmp_path / "state"))
    return tmp_path


@pytest.fixture()
def runner():
    return CliRunner()


class TestInstanceManagement:
    def test_node_new_list_files(self, env, runner):
        r = runner.invoke(
            cli,
            [
                "node", "new",
                "--name", "n1",
                "--api-url", "http://localhost:7601",
                "--api-key", "k",
                "--database", "default:csv:/data/x.csv",
            ],
        )
        assert r.exit_code == 0, r.output
        assert "n1.yaml" in r.output
        r = runner.invoke(cli, ["node", "list"])
        assert "n1" in r.output and "stopped" in r.output
        r = runner.invoke(cli, ["node", "files", "n1"])
        assert "config:" in r.output and "data:" in r.output

    def test_duplicate_node_rejected(self, env, runner):
        args = ["node", "new", "--name", "dup", "--api-url", "u", "--api-key", "k"]
        assert runner.invoke(cli, args).exit_code == 0
        r = runner.invoke(cli, args)
        assert r.exit_code != 0

    def test_server_new(self, env, runner):
        r = runner.invoke(cli, ["server", "new", "--name", "s1", "--port", "7777"])
        assert r.exit_code == 0, r.output
        r = runner.invoke(cli, ["server", "list"])
        assert "s1" in r.output

    def test_stop_not_running(self, env, runner):
        runner.invoke(cli, ["server", "new", "--name", "s2"])
        r = runner.invoke(cli, ["server", "stop", "s2"])
        assert "was not running" in r.output


class TestServerImport:
    def test_import_entities(self, env, runner, tmp_path):
        runner.invoke(cli, ["server", "new", "--name", "imp"])
        entities = {
            "organizations": [{"name": "a"}, {"name": "b"}],
            "users": [
                {
                    "username": "admin",
                    "password": "adminpass123",
                    "organization": "a",
                    "roles": ["Root"],
                }
            ],
            "collaborations": [
                {"name": "c1", "participants": ["a", "b"]}
            ],
        }
        f = tmp_path / "entities.yaml"
        f.write_text(yaml.safe_dump(entities))
        r = runner.invoke(cli, ["server", "import", "imp", str(f)])
        assert r.exit_code == 0, r.output
        summary = json.loads(r.stdout)
        assert summary["organizations"] == 2
        assert summary["users"] == 1
        assert len(summary["nodes"]) == 2  # one per participant, with api keys
        assert all(n["api_key"] for n in summary["nodes"])
        # idempotent re-import creates nothing new
        r = runner.invoke(cli, ["server", "import", "imp", str(f)])
        summary2 = json.loads(r.stdout)
        assert summary2["organizations"] == 0 and summary2["nodes"] == []


class TestDev:
    def test_create_demo_network_generates_everything(self, env, runner):
        r = runner.invoke(
            cli, ["dev", "create-demo-network", "--name", "d1", "-n", "2"]
        )
        assert r.exit_code == 0, r.output
        from vantage6_tpu.common.context import NodeContext, ServerContext

        assert ServerContext.config_exists("d1_server")
        nodes = [
            n
            for n in NodeContext.available_configurations()
            if n.startswith("d1_node_")
        ]
        assert len(nodes) == 2
        ctx = NodeContext(nodes[0])
        assert ctx.databases[0]["uri"].endswith(".csv")
        import pandas as pd

        df = pd.read_csv(ctx.databases[0]["uri"])
        assert {"age", "weight", "event", "time"} <= set(df.columns)
        # the demo store exists, is linked from the server config, and is
        # SEEDED with approved introspected builtin algorithms so the web
        # UI's task wizard works out of the box
        from vantage6_tpu.common.context import StoreContext

        assert StoreContext.config_exists("d1_store")
        store_ctx = StoreContext("d1_store")
        server_ctx = ServerContext("d1_server")
        assert server_ctx.config["store_url"] == (
            f"http://127.0.0.1:{store_ctx.port}"
        )
        from vantage6_tpu.store.app import StoreApp

        app = StoreApp(uri=store_ctx.uri)
        try:
            listing = app.test_client().get("/api/algorithm").json["data"]
        finally:
            app.close()
        images = {a["image"] for a in listing}
        assert "v6-average-py" in images and "v6-glm-py" in images
        avg = next(a for a in listing if a["image"] == "v6-average-py")
        assert all(a["status"] == "approved" for a in listing)
        central = next(
            f for f in avg["functions"] if f["name"] == "central_average"
        )
        assert any(
            arg["name"] == "column" and arg["type"] == "column"
            for arg in central["arguments"]
        )
        # duplicate creation refused
        r = runner.invoke(
            cli, ["dev", "create-demo-network", "--name", "d1", "-n", "2"]
        )
        assert r.exit_code != 0

    def test_remove_demo_network(self, env, runner):
        runner.invoke(cli, ["dev", "create-demo-network", "--name", "d2", "-n", "2"])
        r = runner.invoke(cli, ["dev", "remove-demo-network", "--name", "d2"])
        assert r.exit_code == 0
        from vantage6_tpu.common.context import NodeContext, ServerContext

        assert not ServerContext.config_exists("d2_server")
        assert not any(
            n.startswith("d2_node_")
            for n in NodeContext.available_configurations()
        )
        from vantage6_tpu.common.context import StoreContext

        assert not StoreContext.config_exists("d2_store")


class TestAlgorithmCreate:
    def test_boilerplate_runs_under_mock(self, env, runner, tmp_path):
        r = runner.invoke(
            cli,
            ["algorithm", "create", "--name", "my-avg", "--directory", str(tmp_path)],
        )
        assert r.exit_code == 0, r.output
        pkg = tmp_path / "my_avg"
        assert (pkg / "__init__.py").exists()
        # the generated test passes as-is
        import subprocess
        import sys

        import os

        # the child runs from tmp_path with no access to this checkout, so
        # vantage6_tpu must be made importable explicitly — the package is
        # not required to be pip-installed for the suite to pass
        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        child_env = {
            **os.environ,
            "PYTHONPATH": os.pathsep.join(
                p for p in (repo_root, os.environ.get("PYTHONPATH")) if p
            ),
            # the child only needs CPU, and a chip belongs to one process
            # at a time
            "JAX_PLATFORMS": "cpu",
        }
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", str(pkg / "test_algorithm.py"), "-q"],
            capture_output=True,
            text=True,
            cwd=tmp_path,
            timeout=300,
            env=child_env,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr


class TestRun:
    def test_run_federation_yaml(self, env, runner, tmp_path):
        import numpy as np
        import pandas as pd

        rng = np.random.default_rng(3)
        stations = []
        for i in range(2):
            csv = tmp_path / f"s{i}.csv"
            pd.DataFrame({"age": rng.normal(40, 5, 30)}).to_csv(csv, index=False)
            stations.append(
                {
                    "name": f"st{i}",
                    "databases": [
                        {"label": "default", "type": "csv", "uri": str(csv)}
                    ],
                }
            )
        cfg = tmp_path / "fed.yaml"
        cfg.write_text(
            yaml.safe_dump({"federation": {"name": "f"}, "stations": stations})
        )
        r = runner.invoke(
            cli,
            [
                "run", str(cfg),
                "--image", "v6-average-py",
                "--method", "partial_average",
                "--kwargs", '{"column": "age"}',
            ],
        )
        assert r.exit_code == 0, r.output
        results = json.loads(r.stdout)
        assert len(results) == 2 and all("sum" in x for x in results)


def test_smoke(env, runner):
    r = CliRunner().invoke(cli, ["test"])
    assert r.exit_code == 0, r.output
    assert "smoke OK" in r.output
