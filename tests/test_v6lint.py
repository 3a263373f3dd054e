"""v6lint analyzer tests (tools/analyze, docs/static_analysis.md).

Each fixture seeds EXACTLY the violation its rule exists for, in a tiny
synthetic package tree, and asserts the finding fires (and that the
well-behaved twin does not). The final tests run the analyzer over the
real repository: zero unwaived findings against the committed baseline,
inside the 10 s CI budget — the same gate `tools/check_collect.py` runs.
"""
from __future__ import annotations

import os
import sys
import textwrap
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.analyze import (  # noqa: E402
    BaselineError,
    analyze,
    audit_critical_routes,
    build_index,
    load_baseline,
    save_baseline,
)
from tools.analyze.__main__ import main as v6lint_main  # noqa: E402


def run_fixture(tmp_path: Path, files: dict[str, str], baseline=None):
    """Write a synthetic package tree and analyze it."""
    for rel, body in files.items():
        p = tmp_path / "pkg" / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(body))
        init = p.parent / "__init__.py"
        if not init.exists():
            init.write_text("")
    result, _seconds = analyze(
        str(tmp_path), subdirs=("pkg",), baseline=baseline or {}
    )
    return result


def rules(result) -> list[str]:
    return [f.rule for f in result.unwaived]


# ---------------------------------------------------------------- pass 1
class TestLockDiscipline:
    def test_blocking_sleep_under_lock(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import threading
            import time

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def bad(self):
                    with self._lock:
                        time.sleep(1.0)
            """})
        assert "lock-blocking-call" in rules(result)
        (f,) = [x for x in result.unwaived if x.rule == "lock-blocking-call"]
        assert "time.sleep" in f.message and "C._lock" in f.message

    def test_rest_request_under_lock(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._rest = object()

                def bad(self):
                    with self._lock:
                        self._rest.request("GET", "thing")

                def good(self):
                    self._rest.request("GET", "thing")
            """})
        found = [x for x in result.unwaived if x.rule == "lock-blocking-call"]
        assert len(found) == 1 and found[0].context.startswith("C.bad")

    def test_subprocess_under_lock(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import subprocess
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def bad(self):
                    with self._lock:
                        subprocess.run(["ls"])
            """})
        assert "lock-blocking-call" in rules(result)

    def test_condition_wait_on_other_lock(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._cond = threading.Condition()

                def bad(self):
                    with self._lock:
                        self._cond.wait(1.0)

                def good(self):
                    # waiting on the condition you hold RELEASES it
                    with self._cond:
                        self._cond.wait(1.0)
            """})
        found = [x for x in result.unwaived if x.rule == "lock-blocking-call"]
        assert len(found) == 1
        assert found[0].context.startswith("C.bad")

    def test_sqlite_execute_under_foreign_lock(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._db_lock = threading.Lock()
                    self.conn = None

                def bad(self):
                    with self._lock:
                        self.conn.execute("SELECT 1")

                def good(self):
                    # the db's OWN serialization lock is the exemption
                    with self._db_lock:
                        self.conn.execute("SELECT 1")
            """})
        found = [x for x in result.unwaived if x.rule == "lock-sqlite-under-lock"]
        assert len(found) == 1 and found[0].context.startswith("C.bad")

    def test_acquire_without_try_finally(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def bad(self):
                    self._lock.acquire()
                    do_work()
                    self._lock.release()

                def good(self):
                    self._lock.acquire()
                    try:
                        do_work()
                    finally:
                        self._lock.release()

            def do_work():
                pass
            """})
        found = [x for x in result.unwaived if x.rule == "lock-acquire-no-finally"]
        assert len(found) == 1 and found[0].context.startswith("C.bad")

    def test_lock_order_cycle(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        with self._b:
                            pass

                def two(self):
                    with self._b:
                        with self._a:
                            pass
            """})
        found = [x for x in result.unwaived if x.rule == "lock-order-cycle"]
        assert len(found) == 1
        assert "C._a" in found[0].message and "C._b" in found[0].message

    def test_multi_item_with_cycle_and_self_deadlock(self, tmp_path):
        # `with a, b:` acquires left-to-right while holding the earlier
        # items — the edges and the double-acquire must both register
        result = run_fixture(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a, self._b:
                        pass

                def two(self):
                    with self._b, self._a:
                        pass

                def oops(self):
                    with self._a, self._a:
                        pass
            """})
        assert "lock-order-cycle" in rules(result)
        assert "lock-self-deadlock" in rules(result)

    def test_cross_function_lock_cycle(self, tmp_path):
        # the cycle closes through a CALL: one() holds _a and calls a
        # helper that takes _b; two() nests them the other way round
        result = run_fixture(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        self.takes_b()

                def takes_b(self):
                    with self._b:
                        pass

                def two(self):
                    with self._b:
                        with self._a:
                            pass
            """})
        assert "lock-order-cycle" in rules(result)

    def test_self_deadlock_through_call(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def outer(self):
                    with self._lock:
                        self.inner()

                def inner(self):
                    with self._lock:
                        pass
            """})
        assert "lock-self-deadlock" in rules(result)

    def test_rlock_reentry_is_fine(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.RLock()

                def outer(self):
                    with self._lock:
                        self.inner()

                def inner(self):
                    with self._lock:
                        pass
            """})
        assert "lock-self-deadlock" not in rules(result)

    def test_blocking_reach_through_helper(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import threading
            import time

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def bad(self):
                    with self._lock:
                        self.helper()

                def helper(self):
                    time.sleep(0.5)
            """})
        found = [x for x in result.unwaived if x.rule == "lock-blocking-reach"]
        assert len(found) == 1
        assert "time.sleep" in found[0].message

    def test_guarded_by_escape(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = set()  # guarded-by: _lock

                def bad(self, x):
                    self._items.add(x)

                def good(self, x):
                    with self._lock:
                        self._items.add(x)

                def good_subscript_chain(self, x):
                    with self._lock:
                        self._items.discard(x)
            """})
        found = [x for x in result.unwaived if x.rule == "guarded-by-escape"]
        assert len(found) == 1
        assert found[0].context == "C.bad#_items"

    def test_guarded_by_assignment_and_subscript(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._map = {}  # guarded-by: _lock

                def bad_subscript(self, k, v):
                    self._map[k] = v

                def bad_rebind(self):
                    self._map = {}
            """})
        found = [x for x in result.unwaived if x.rule == "guarded-by-escape"]
        assert {f.context for f in found} == {
            "C.bad_subscript#_map", "C.bad_rebind#_map",
        }

    def test_guarded_by_condition_alias(self, tmp_path):
        # Condition(self._lock) IS _lock: writes under either are fine
        result = run_fixture(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.RLock()
                    self._cond = threading.Condition(self._lock)
                    self._buf = []  # guarded-by: _lock

                def good(self, x):
                    with self._cond:
                        self._buf.append(x)
            """})
        assert "guarded-by-escape" not in rules(result)

    def test_guarded_by_unknown_lock(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._items = set()  # guarded-by: _no_such_lock
            """})
        assert "guarded-by-unknown-lock" in rules(result)

    def test_locked_suffix_convention_exempt(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = set()  # guarded-by: _lock

                def _drain_locked(self):
                    # caller-holds-the-lock contract: exempt by convention
                    self._items.clear()
            """})
        assert "guarded-by-escape" not in rules(result)


# ---------------------------------------------------------------- pass 2
class TestTracerHygiene:
    def test_item_host_sync_in_jit(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import jax

            @jax.jit
            def bad(x):
                return x.item()

            def untraced(x):
                return x.item()  # host code: fine
            """})
        found = [x for x in result.unwaived if x.rule == "tracer-host-sync"]
        assert len(found) == 1 and found[0].context == "bad#item"

    def test_float_on_tracer(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import jax

            @jax.jit
            def bad(x):
                return float(x)

            @jax.jit
            def good(x):
                return float(x.shape[0])  # shapes are trace-static
            """})
        found = [x for x in result.unwaived if x.rule == "tracer-host-sync"]
        assert len(found) == 1 and found[0].context == "bad#float"

    def test_np_asarray_in_traced_helper(self, tmp_path):
        # the violation is REACHABLE from the jit root, not at it
        result = run_fixture(tmp_path, {"m.py": """
            import jax
            import numpy as np

            def helper(x):
                return np.asarray(x)

            @jax.jit
            def root(x):
                return helper(x)
            """})
        found = [x for x in result.unwaived if x.rule == "tracer-host-sync"]
        assert len(found) == 1 and "np.asarray" in found[0].message

    def test_impure_time_and_random(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import random
            import time

            import jax

            @jax.jit
            def bad(x):
                t = time.time()
                r = random.random()
                return x + t + r

            def host_side():
                return time.time()  # untraced: fine
            """})
        found = [x for x in result.unwaived if x.rule == "tracer-impure-call"]
        assert {f.context for f in found} == {"bad#time.time", "bad#random.random"}

    def test_scan_body_through_partial(self, tmp_path):
        """lax.scan(functools.partial(body, cfg), ...) — the fused-rounds
        idiom (a scan body with bound config): the closure walk must
        unwrap the partial and descend into the BODY, catching impure
        calls there; the well-behaved twin stays clean."""
        result = run_fixture(tmp_path, {"m.py": """
            import functools
            import time

            import jax
            from jax import lax

            def body_bad(cfg, carry, x):
                t = time.time()  # impure under trace: one firing per round
                return carry + x * cfg + t, None

            def body_good(cfg, carry, x):
                return carry + x * cfg, None

            @jax.jit
            def bad(xs):
                out, _ = lax.scan(functools.partial(body_bad, 2.0), 0.0, xs)
                return out

            @jax.jit
            def good(xs):
                out, _ = lax.scan(functools.partial(body_good, 2.0), 0.0, xs)
                return out
            """})
        found = [x for x in result.unwaived if x.rule == "tracer-impure-call"]
        assert {f.context for f in found} == {"body_bad#time.time"}

    def test_pure_callback_exempts_host_escape(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import jax
            import numpy as np

            @jax.jit
            def ok(x):
                return jax.pure_callback(lambda a: np.asarray(a), x, x)
            """})
        assert rules(result) == []

    def test_traced_through_shard_map_wrapper(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import time

            from jax import shard_map

            def body(x):
                time.sleep(0.1)
                return x

            def build(mesh):
                return shard_map(body, mesh=mesh, in_specs=None, out_specs=None)
            """})
        found = [x for x in result.unwaived if x.rule == "tracer-impure-call"]
        assert len(found) == 1 and found[0].context.startswith("body#")

    def test_donated_buffer_reuse(self, tmp_path):
        result = run_fixture(tmp_path, {"m.py": """
            import jax

            def run(step_fn, state, batch):
                step = jax.jit(step_fn, donate_argnums=(0,))
                new_state = step(state, batch)
                return state, new_state  # state's buffer was donated!

            def good(step_fn, state, batch):
                step = jax.jit(step_fn, donate_argnums=(0,))
                state = step(state, batch)  # rebinding: the normal pattern
                return state
            """})
        found = [x for x in result.unwaived if x.rule == "tracer-donated-reuse"]
        assert len(found) == 1 and found[0].context == "run#state"


# ---------------------------------------------------------------- pass 3
class TestContracts:
    ROUTES = """
        def register(app):
            @app.route("/api/thing", methods=("GET",))
            def thing(req):
                return {}

            @app.route("/api/thing/<int:id>", methods=("GET", "PATCH"))
            def one_thing(req, id):
                return {}
        """

    def test_route_method_mismatch(self, tmp_path):
        result = run_fixture(tmp_path, {
            "server.py": self.ROUTES,
            "client.py": """
                class C:
                    def bad(self):
                        return self.rest.request("POST", "thing")

                    def good(self):
                        return self.rest.request("GET", "thing")
                """,
        })
        found = [x for x in result.unwaived if x.rule == "route-method-mismatch"]
        assert len(found) == 1
        assert "POST" in found[0].message and "405" in found[0].message

    def test_route_unknown(self, tmp_path):
        result = run_fixture(tmp_path, {
            "server.py": self.ROUTES,
            "client.py": """
                class C:
                    def bad(self):
                        return self.rest.request("GET", "no/such/endpoint")
                """,
        })
        found = [x for x in result.unwaived if x.rule == "route-unknown"]
        assert len(found) == 1

    def test_fstring_path_matches_placeholder_route(self, tmp_path):
        result = run_fixture(tmp_path, {
            "server.py": self.ROUTES,
            "client.py": """
                class C:
                    def good(self, tid):
                        return self.rest.request("PATCH", f"thing/{tid}")

                    def bad(self, tid):
                        return self.rest.request("DELETE", f"thing/{tid}")
                """,
        })
        found = result.unwaived
        assert len(found) == 1 and found[0].rule == "route-method-mismatch"
        assert found[0].context.startswith("C.bad")

    def test_wire_magic_drift(self, tmp_path):
        result = run_fixture(tmp_path, {
            "vantage6_tpu/common/serialization.py":
                'MAGIC_V2 = b"V6X\\x03"\n',
            "vantage6_tpu/common/encryption.py":
                'ENC_MAGIC = b"V6TE\\x02"\n',
        })
        found = [x for x in result.unwaived if x.rule == "wire-magic-drift"]
        assert len(found) == 1 and "MAGIC_V2" in found[0].message

    def test_wire_magic_inline_respelling(self, tmp_path):
        result = run_fixture(tmp_path, {
            "vantage6_tpu/common/serialization.py":
                'MAGIC_V2 = b"V6T\\x02"\n',
            "vantage6_tpu/common/encryption.py":
                'ENC_MAGIC = b"V6TE\\x02"\n',
            "sneaky.py": """
                def emit(payload):
                    return b"V6T\\x02" + payload  # re-spelled frame tag
                """,
        })
        found = [x for x in result.unwaived if x.rule == "wire-magic-inline"]
        assert len(found) == 1 and found[0].path.endswith("sneaky.py")

    def test_audit_critical_routes_real_repo(self):
        index = build_index(str(REPO))
        audit = {
            "run/claim-batch": ["vantage6_tpu/node/daemon.py"],
            "event": ["vantage6_tpu/node/proxy.py"],
        }
        assert audit_critical_routes(index, audit) == []
        bad = audit_critical_routes(
            index, {"no/such/route": ["vantage6_tpu/node/daemon.py"]}
        )
        assert len(bad) == 2  # route gone AND call site missing


# ---------------------------------------------------------------- pass 4
class TestTelemetry:
    TELEMETRY = """
        KNOWN_METRICS = [
            ("v6t_good_total", "counter", "a used counter"),
            ("v6t_lonely_total", "counter", "declared but never emitted"),
        ]
        """

    def test_undeclared_and_dead_metrics(self, tmp_path):
        result = run_fixture(tmp_path, {
            "vantage6_tpu/common/telemetry.py": self.TELEMETRY,
            "app.py": """
                def handle(registry):
                    registry.counter("v6t_good_total").inc()
                    registry.counter("v6t_undeclared_total").inc()
                """,
        })
        by_rule = {}
        for f in result.unwaived:
            by_rule.setdefault(f.rule, []).append(f)
        assert [f.context for f in by_rule["metric-undeclared"]] == [
            "v6t_undeclared_total"
        ]
        assert [f.context for f in by_rule["metric-dead"]] == ["v6t_lonely_total"]

    def test_kind_mismatch(self, tmp_path):
        result = run_fixture(tmp_path, {
            "vantage6_tpu/common/telemetry.py": self.TELEMETRY,
            "app.py": """
                def handle(registry):
                    registry.gauge("v6t_good_total").set(1)
                    registry.counter("v6t_lonely_total").inc()
                """,
        })
        found = [x for x in result.unwaived if x.rule == "metric-kind-mismatch"]
        assert len(found) == 1 and found[0].context == "v6t_good_total"

    def test_collector_dict_drift(self, tmp_path):
        result = run_fixture(tmp_path, {
            "vantage6_tpu/common/telemetry.py": self.TELEMETRY,
            "app.py": """
                def collector(stats):
                    return {
                        "v6t_good_total": stats.good,
                        "v6t_lonely_total": stats.lonely,
                        "v6t_drifted_total": stats.oops,
                    }
                """,
        })
        found = [x for x in result.unwaived if x.rule == "metric-undeclared"]
        assert [f.context for f in found] == ["v6t_drifted_total"]

    def test_non_metric_v6t_strings_ignored(self, tmp_path):
        result = run_fixture(tmp_path, {
            "vantage6_tpu/common/telemetry.py": self.TELEMETRY,
            "app.py": """
                def collector(stats):
                    return {"v6t_good_total": stats.good}

                THREAD_PREFIX = "v6t_worker"  # not a metric: never flagged
                """,
        })
        assert "metric-undeclared" not in rules(result)


# --------------------------------------------------------------- baseline
class TestBaseline:
    FIXTURE = {"m.py": """
        import threading
        import time

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def bad(self):
                with self._lock:
                    time.sleep(1.0)
        """}

    def test_waiver_suppresses_and_stale_reported(self, tmp_path):
        result = run_fixture(tmp_path, self.FIXTURE)
        (finding,) = result.unwaived
        baseline = {
            finding.key: "intentional: fixture",
            "lock-blocking-call@gone.py:Nobody.nothing": "stale entry",
        }
        result2 = run_fixture(tmp_path, self.FIXTURE, baseline=baseline)
        assert result2.unwaived == []
        assert [f.key for f in result2.waived] == [finding.key]
        assert result2.stale_waivers == [
            "lock-blocking-call@gone.py:Nobody.nothing"
        ]

    def test_baseline_roundtrip_and_reason_required(self, tmp_path):
        path = tmp_path / "baseline.toml"
        save_baseline(str(path), {"rule@a.py:C.m#x": 'why "quoted" reason'})
        assert load_baseline(str(path)) == {
            "rule@a.py:C.m#x": 'why "quoted" reason'
        }
        path.write_text('[[waiver]]\nkey = "rule@a.py:C.m"\nreason = ""\n')
        with pytest.raises(BaselineError):
            load_baseline(str(path))

    def test_cli_exit_codes_and_waive(self, tmp_path, capsys, monkeypatch):
        for rel, body in self.FIXTURE.items():
            p = tmp_path / "pkg" / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(textwrap.dedent(body))
        baseline = tmp_path / "baseline.toml"
        argv = [
            "pkg", "--root", str(tmp_path), "--baseline", str(baseline),
        ]
        assert v6lint_main(argv) == 1  # unwaived finding
        assert v6lint_main(argv + ["--waive"]) == 0
        assert "TODO" in baseline.read_text()
        assert v6lint_main(argv) == 0  # waived now (reason pending review)
        capsys.readouterr()


# ------------------------------------------------------------- whole repo
class TestWholeRepo:
    def test_zero_unwaived_findings_within_budget(self):
        baseline = load_baseline(
            str(REPO / "tools" / "analyze" / "baseline.toml")
        )
        assert baseline, "committed baseline should carry the audited waivers"
        for reason in baseline.values():
            assert "TODO" not in reason, "baseline reasons must be real"
        t0 = time.perf_counter()
        result, seconds = analyze(str(REPO), baseline=baseline)
        wall = time.perf_counter() - t0
        assert [f.render() for f in result.unwaived] == []
        assert result.stale_waivers == []
        assert result.waived, "the audited daemon-sweep waivers apply"
        assert seconds < 10 and wall < 10, (
            f"analyzer over CI budget: {seconds:.1f}s"
        )

    def test_real_guarded_by_annotations_registered(self):
        index = build_index(str(REPO))
        fed = index.classes["vantage6_tpu.runtime.federation.Federation"]
        assert fed.guarded["_inflight_runs"][0] == "_inflight_lock"
        assert fed.guarded["_stacked_cache"][0] == "_stacked_lock"
        assert fed.guarded["_sessions"][0] == "_session_lock"
        daemon = index.classes["vantage6_tpu.node.daemon.NodeDaemon"]
        assert daemon.guarded["_claimed"][0] == "_claim_lock"
        assert daemon.guarded["_prefetched"][0] == "_claim_lock"
        hub = index.classes["vantage6_tpu.server.events.EventHub"]
        assert hub.guarded["_buffer"][0] == "_lock"
        execu = index.classes["vantage6_tpu.runtime.executor.StationExecutor"]
        for field in ("_queues", "_executing", "_inflight", "_rr", "_shutdown"):
            assert execu.guarded[field][0] == "_cond", field
        pool = index.classes["vantage6_tpu.common.rest._SessionPool"]
        assert pool.guarded["_idle"][0] == "_lock"

    def test_real_lock_order_graph_has_no_cycles(self):
        from tools.analyze.locks import LockPass

        lp = LockPass(build_index(str(REPO)))
        lp.run()
        # the two known benign edges exist; no finding reported a cycle
        edges = {
            (a[1], b[1]) for (a, b) in lp.edges
        }
        assert ("_sync_lock", "_claim_lock") in edges
        assert not [f for f in lp.findings if f.rule == "lock-order-cycle"]
