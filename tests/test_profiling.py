"""Device performance observatory (ISSUE 9).

What must hold:
- observed jit entry points emit `device.compile` spans (parented on the
  active trace) carrying lowering/compile wall time AND the XLA
  introspection (memory_analysis temp/arg/output bytes, cost_analysis
  flops) — with the `v6t_jit_*` telemetry moving in step;
- a retrace (same function, new abstract signature) is DETECTED and
  NAMED: the differing leaf in the span, a flight note, the watchdog
  feed;
- the two new watchdog rules (`recompile_storm`, `device_mem_growth`)
  fire on their scenario and stay quiet otherwise;
- the profile-window endpoint is user-only, registers its artifact in
  the flight recorder, and refuses concurrent windows;
- the per-device memory collector reports every local device and
  `round_timer` records the census.
"""
import json
import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vantage6_tpu.common.flight import FLIGHT
from vantage6_tpu.common.telemetry import REGISTRY
from vantage6_tpu.runtime import metrics as rtmetrics
from vantage6_tpu.runtime.profiling import (
    DEVICE_OBS,
    ProfileBusyError,
    engine_cache_event,
    observed_jit,
    profile_window,
)
from vantage6_tpu.runtime.tracing import TRACER, summarize
from vantage6_tpu.runtime.watchdog import (
    DEFAULT_RULES,
    RuleContext,
    Watchdog,
)


@pytest.fixture(autouse=True)
def observatory():
    """Tracing + observatory armed, state isolated per test."""
    TRACER.configure(enabled=True, sample=1.0, sink=None)
    TRACER.clear()
    DEVICE_OBS.configure(enabled=True, max_signatures=8)
    DEVICE_OBS.clear()
    FLIGHT.clear()
    yield
    DEVICE_OBS.configure(enabled=True, max_signatures=8)
    DEVICE_OBS.clear()


def compile_spans(trace_id=None):
    return [
        s for s in TRACER.drain(trace_id) if s["name"] == "device.compile"
    ]


def rule(name):
    return next(r for r in DEFAULT_RULES if r.name == name)


def ctx(snapshot=None, history=None, feeds=None, config=None, now=None):
    w = Watchdog(interval=60.0)
    cfg = dict(w.config)
    cfg.update(config or {})
    return RuleContext(
        snapshot or {},
        {k: deque(v) for k, v in (history or {}).items()},
        feeds or {},
        cfg,
        now if now is not None else time.time(),
    )


# ------------------------------------------------------------- observed jit
class TestObservedJit:
    def test_compile_span_carries_xla_introspection(self):
        f = observed_jit("t.intro", lambda x: jnp.sum(x * 2.0))
        with TRACER.span("root") as root:
            f(jnp.ones((16,)))
        spans = compile_spans(root.context.trace_id)
        assert len(spans) == 1
        sp = spans[0]
        # parented INSIDE the active trace, not a floating root: under the
        # device.launch that compiled, which is the root's child
        launch = next(
            s for s in TRACER.drain(root.context.trace_id)
            if s["span_id"] == sp["parent_id"]
        )
        assert launch["name"] == "device.launch"
        assert launch["parent_id"] == root.context.span_id
        a = sp["attrs"]
        assert a["function"] == "t.intro"
        assert a["retrace"] is False
        assert a["lower_ms"] > 0 and a["compile_ms"] > 0
        # memory_analysis + cost_analysis made it onto the span
        assert a["argument_bytes"] == 64 and a["output_bytes"] == 4
        assert "temp_bytes" in a and a["flops"] > 0

    def test_cache_hit_compiles_once_and_counts(self):
        before = REGISTRY.snapshot().get("v6t_jit_compiles_total", 0.0)
        f = observed_jit("t.hit", lambda x: x + 1)
        assert np.allclose(f(jnp.ones((3,))), 2.0)
        assert np.allclose(f(jnp.ones((3,))), 2.0)
        assert f.compiles == 1 and f.dispatches == 2
        snap = REGISTRY.snapshot()
        assert snap["v6t_jit_compiles_total"] == before + 1
        assert f.stats()["signatures"] == 1

    def test_retrace_named_in_span_flight_and_feed(self):
        f = observed_jit("t.storm", lambda x: jnp.sum(x))
        with TRACER.span("root") as root:
            f(jnp.ones((4,)))
            f(jnp.ones((5,)))  # the shape perturbation
        spans = compile_spans(root.context.trace_id)
        assert [s["attrs"]["retrace"] for s in spans] == [False, True]
        changed = spans[1]["attrs"]["changed"]
        assert "float32[4] -> float32[5]" in changed
        assert f.retraces == 1
        # the flight note the doctor perf digest renders
        feed = DEVICE_OBS.watchdog_feed()["retraces"]
        assert feed[-1]["function"] == "t.storm"
        assert feed[-1]["changed"] == changed

    def test_dtype_retrace_named(self):
        f = observed_jit("t.dtype", lambda x: x * 2)
        f(jnp.ones((4,), jnp.float32))
        f(jnp.ones((4,), jnp.int32))
        feed = DEVICE_OBS.watchdog_feed()["retraces"]
        assert "float32[4] -> int32[4]" in feed[-1]["changed"]

    def test_static_change_named(self):
        f = observed_jit(
            "t.static", lambda x, n=1: x * n, static_argnames=("n",)
        )
        assert np.allclose(f(jnp.ones((2,)), n=2), 2.0)
        assert np.allclose(f(jnp.ones((2,)), n=3), 3.0)
        feed = DEVICE_OBS.watchdog_feed()["retraces"]
        assert "static n: 2 -> 3" in feed[-1]["changed"]

    def test_static_positional_dropped_from_compiled_call(self):
        f = observed_jit(
            "t.staticpos", lambda s, x: x * s, static_argnums=(0,)
        )
        assert np.allclose(f(3, jnp.ones((2,))), 3.0)
        assert np.allclose(f(3, jnp.ones((2,))), 3.0)  # the cached hit
        assert f.compiles == 1

    def test_inline_under_outer_jit(self):
        inner = observed_jit("t.inner", lambda x: x + 1)
        outer = jax.jit(lambda x: inner(x) * 2)
        assert np.allclose(outer(jnp.ones((3,))), 4.0)
        # the OUTER entry owns attribution: no observed compile recorded
        assert inner.compiles == 0

    def test_disabled_is_plain_jit(self):
        DEVICE_OBS.configure(enabled=False)
        f = observed_jit("t.off", lambda x: x - 1)
        assert np.allclose(f(jnp.ones((3,))), 0.0)
        assert f.compiles == 0 and f.dispatches == 0
        assert compile_spans() == []

    def test_signature_cap_evicts_fifo(self):
        DEVICE_OBS.configure(max_signatures=2)
        f = observed_jit("t.cap", lambda x: jnp.sum(x))
        for n in (2, 3, 4):
            f(jnp.ones((n,)))
        assert f.n_signatures() == 2
        assert f.evictions == 1

    def test_evicted_recompile_is_not_a_retrace(self):
        # a workload rotating through more live shapes than the cap pays
        # the compile but must NOT feed recompile_storm — that churn is
        # the observatory's own eviction, not an unstable signature
        DEVICE_OBS.configure(max_signatures=2)
        f = observed_jit("t.evict", lambda x: jnp.sum(x))
        for n in (2, 3, 4):
            f(jnp.ones((n,)))
        retraces_before = f.retraces
        f(jnp.ones((2,)))  # shape (2,) was evicted: recompile, not retrace
        assert f.compiles == 4
        assert f.retraces == retraces_before
        spans = compile_spans()
        assert spans[-1]["attrs"].get("evicted_recompile") is True
        assert spans[-1]["attrs"]["retrace"] is False

    def test_donation_via_observed_dispatch(self):
        f = observed_jit(
            "t.donate", lambda x: x * 2, donate_argnums=(0,)
        )
        out = f(jnp.ones((4,)))
        out2 = f(out)  # chains donated buffers like run_rounds does
        assert np.allclose(out2, 4.0)
        assert f.compiles == 1

    def test_results_match_plain_jit(self):
        def g(x, y):
            return {"a": x @ y, "b": jnp.tanh(x).sum()}

        f = observed_jit("t.parity", g)
        x, y = jnp.ones((4, 3)), jnp.ones((3, 2))
        want = jax.jit(g)(x, y)
        got = f(x, y)
        assert np.allclose(got["a"], want["a"])
        assert np.allclose(got["b"], want["b"])


# ------------------------------------------------------------ engine caches
class TestEngineCacheCounters:
    def test_event_counts_hits_misses_entries(self):
        before = REGISTRY.snapshot()
        engine_cache_event("demo", hit=False, entries=1)
        engine_cache_event("demo", hit=True, entries=1)
        engine_cache_event("demo", hit=True, entries=1)
        snap = REGISTRY.snapshot()
        assert (
            snap["v6t_engine_cache_misses_total"]
            - before.get("v6t_engine_cache_misses_total", 0.0) == 1
        )
        assert (
            snap["v6t_engine_cache_hits_total"]
            - before.get("v6t_engine_cache_hits_total", 0.0) == 2
        )
        st = DEVICE_OBS.engine_cache_stats()["demo"]
        assert st == {"hits": 2, "misses": 1, "entries": 1}

    def test_quantile_runner_cache_visible(self, devices):
        from vantage6_tpu.core.mesh import FederationMesh
        from vantage6_tpu.workloads.quantiles import _quantile_runner

        mesh = FederationMesh(4)
        _quantile_runner(mesh, n_iter=7)
        _quantile_runner(FederationMesh(4), n_iter=7)  # same fingerprint
        st = DEVICE_OBS.engine_cache_stats()["quantile"]
        assert st["hits"] >= 1 and st["misses"] >= 1

    def test_glm_runner_cache_visible(self, devices):
        from vantage6_tpu.core.mesh import FederationMesh
        from vantage6_tpu.workloads.glm import _glm_runner

        mesh = FederationMesh(4)
        _glm_runner(mesh, "gaussian", 3)
        _glm_runner(mesh, "gaussian", 3)
        st = DEVICE_OBS.engine_cache_stats()["glm"]
        assert st["hits"] >= 1 and st["misses"] >= 1

    def test_disabled_layer_silences_cache_counters(self):
        # V6T_DEVICE_OBS=0 promises the WHOLE layer off — the engine
        # cache counters must not keep emitting
        before = REGISTRY.snapshot().get("v6t_engine_cache_misses_total", 0.0)
        DEVICE_OBS.configure(enabled=False)
        try:
            engine_cache_event("t.silent", hit=False, entries=1)
        finally:
            DEVICE_OBS.configure(enabled=True)
        after = REGISTRY.snapshot().get("v6t_engine_cache_misses_total", 0.0)
        assert after == before
        assert "t.silent" not in DEVICE_OBS.engine_cache_stats()

    def test_runner_cache_fifo_bound(self):
        from vantage6_tpu.runtime.profiling import RunnerCache

        cache = RunnerCache("t.rc", max_entries=2)
        made = []
        for k in range(3):
            cache.get_or_create(k, lambda k=k: made.append(k) or k)
        assert len(cache) == 2
        assert made == [0, 1, 2]
        cache.get_or_create(0, lambda: made.append("rebuild") or 0)
        assert "rebuild" in made  # 0 was FIFO-evicted, factory re-ran


# ------------------------------------------------------------ watchdog rules
class TestRecompileStorm:
    CFG = {"recompile_storm_retraces": 3, "recompile_storm_window": 4}

    def test_fires_and_names_worst_offender(self):
        now = time.time()
        hist = {"v6t_jit_retraces_total": [
            (now - 2, 0.0), (now - 1, 2.0), (now, 5.0),
        ]}
        feeds = {"device_plane": {"retraces": [
            {"function": "fedavg.round",
             "changed": "[0]['w']: float32[8,4] -> float32[8,5]"},
            {"function": "fedavg.round",
             "changed": "[0]['w']: float32[8,5] -> float32[8,6]"},
            {"function": "glm.irls.gaussian", "changed": "x"},
        ]}}
        found = rule("recompile_storm").check(
            ctx(history=hist, feeds=feeds, config=self.CFG, now=now)
        )
        assert len(found) == 1
        msg = found[0]["message"]
        assert "fedavg.round" in msg
        assert "float32[8,5] -> float32[8,6]" in msg
        assert found[0]["labels"] == {"function": "fedavg.round"}

    def test_quiet_below_threshold(self):
        now = time.time()
        hist = {"v6t_jit_retraces_total": [
            (now - 2, 10.0), (now - 1, 11.0), (now, 12.0),
        ]}
        assert rule("recompile_storm").check(
            ctx(history=hist, config=self.CFG, now=now)
        ) == []

    def test_quiet_on_flat_counter_and_short_history(self):
        now = time.time()
        flat = {"v6t_jit_retraces_total": [(now - 1, 7.0), (now, 7.0)]}
        assert rule("recompile_storm").check(
            ctx(history=flat, config=self.CFG, now=now)
        ) == []
        assert rule("recompile_storm").check(
            ctx(history={"v6t_jit_retraces_total": [(now, 50.0)]},
                config=self.CFG, now=now)
        ) == []

    def test_live_storm_raises_within_one_evaluation(self):
        """End to end on a private engine: seed a real shape-perturbed
        storm through an observed function, evaluate, and the alert
        names the function."""
        wd = Watchdog(interval=60.0)
        wd.register_feed("device_plane", DEVICE_OBS.watchdog_feed)
        wd.evaluate()  # baseline history sample
        f = observed_jit("t.live_storm", lambda x: jnp.sum(x * x))
        for n in range(4, 9):
            f(jnp.ones((n,)))
        active = wd.evaluate()
        storm = [a for a in active if a["rule"] == "recompile_storm"]
        assert storm and "t.live_storm" in storm[0]["message"]


class TestDeviceMemGrowth:
    CFG = {"device_mem_growth_evals": 3, "device_mem_growth_pct": 10.0}

    def _hist(self, values):
        now = time.time()
        return {"v6t_device_mem_bytes_in_use": [
            (now - len(values) + i, v) for i, v in enumerate(values)
        ]}

    def test_fires_on_monotonic_growth(self):
        found = rule("device_mem_growth").check(ctx(
            history=self._hist([1000.0, 1200.0, 1500.0, 2000.0]),
            config=self.CFG,
        ))
        assert len(found) == 1
        assert "100.0%" in found[0]["message"]

    def test_quiet_on_plateau_dip_or_small_growth(self):
        for values in (
            [1000.0, 1200.0, 1200.0, 1300.0],   # plateau breaks the run
            [1000.0, 1500.0, 1200.0, 1600.0],   # dip breaks the run
            [1000.0, 1010.0, 1020.0, 1030.0],   # monotonic but 3% < 10%
        ):
            assert rule("device_mem_growth").check(ctx(
                history=self._hist(values), config=self.CFG,
            )) == [], values

    def test_quiet_without_enough_history_or_zero_base(self):
        assert rule("device_mem_growth").check(ctx(
            history=self._hist([1000.0, 2000.0]), config=self.CFG,
        )) == []
        assert rule("device_mem_growth").check(ctx(
            history=self._hist([0.0, 1.0, 2.0, 3.0]), config=self.CFG,
        )) == []


# -------------------------------------------------------- per-device memory
class _FakeDev:
    def __init__(self, i, in_use, peak):
        self.id = i
        self.platform = "fake"
        self._stats = {"bytes_in_use": in_use, "peak_bytes_in_use": peak}

    def memory_stats(self):
        return self._stats


class TestPerDeviceMemory:
    def test_census_and_peak(self, monkeypatch):
        monkeypatch.setattr(
            rtmetrics.jax, "local_devices",
            lambda: [_FakeDev(0, 100, 300), _FakeDev(1, 200, 250)],
        )
        per = rtmetrics.device_memory_all()
        assert [(d["id"], d["bytes_in_use"], d["peak_bytes"])
                for d in per] == [(0, 100, 300), (1, 200, 250)]
        # worst-device peak, not first-device
        assert rtmetrics.device_peak_bytes() == 300

    def test_telemetry_gauges(self, monkeypatch):
        monkeypatch.setattr(
            rtmetrics.jax, "local_devices",
            lambda: [_FakeDev(0, 100, 300), _FakeDev(1, 200, 250)],
        )
        snap = REGISTRY.snapshot()
        assert snap["v6t_device_count"] == 2.0
        assert snap["v6t_device_mem_bytes_in_use"] == 300.0
        assert snap["v6t_device_mem_peak_bytes"] == 300.0

    def test_cpu_reports_nothing_not_zeros(self):
        # real CPU devices report no memory stats: the series must be
        # ABSENT (a fake 0 would feed the growth trend rule garbage)
        assert rtmetrics.device_memory_all() == []
        snap = REGISTRY.snapshot()
        assert "v6t_device_mem_bytes_in_use" not in snap

    def test_round_timer_records_census(self, monkeypatch, tmp_path):
        monkeypatch.setattr(
            rtmetrics.jax, "local_devices",
            lambda: [_FakeDev(0, 10, 30), _FakeDev(1, 20, 40)],
        )
        path = tmp_path / "m.jsonl"
        with rtmetrics.MetricsLogger(path) as ml:
            with ml.round_timer(0):
                pass
        rec = rtmetrics.read_jsonl(path)[0]
        assert rec["device_peak_bytes"] == 40
        assert rec["per_device_peak_bytes"] == {"0": 30, "1": 40}


# ---------------------------------------------------------- profile windows
class TestProfileWindow:
    def test_window_writes_artifact(self, tmp_path):
        out = profile_window(0.05, log_dir=str(tmp_path / "prof"))
        assert out["path"] == str(tmp_path / "prof")
        assert out["seconds"] == 0.05

    def test_flight_note_registered(self, tmp_path):
        profile_window(0.05, log_dir=str(tmp_path / "prof"))
        dump = FLIGHT.dump(path=str(tmp_path / "bundle.jsonl"))
        recs = [json.loads(line) for line in open(dump)]
        notes = [
            r for r in recs
            if r.get("type") == "note" and r.get("kind") == "profile_window"
        ]
        assert notes and notes[0]["path"] == str(tmp_path / "prof")

    def test_linked_to_requesting_trace(self, tmp_path):
        with TRACER.span("root") as root:
            out = profile_window(0.05, log_dir=str(tmp_path / "p"))
        assert out["trace_id"] == root.context.trace_id
        spans = [
            s for s in TRACER.drain(root.context.trace_id)
            if s["name"] == "device.profile"
        ]
        assert spans and spans[0]["attrs"]["log_dir"] == str(tmp_path / "p")

    def test_concurrent_window_refused(self, tmp_path):
        errs = []
        started = threading.Event()

        def long_window():
            started.set()
            profile_window(0.5, log_dir=str(tmp_path / "a"))

        t = threading.Thread(target=long_window)
        t.start()
        started.wait()
        time.sleep(0.1)  # let the window open
        try:
            profile_window(0.05, log_dir=str(tmp_path / "b"))
        except ProfileBusyError as e:
            errs.append(e)
        t.join()
        assert errs

    def test_duration_clamped(self, tmp_path):
        out = profile_window(0.0, log_dir=str(tmp_path / "p"))
        assert out["seconds"] == 0.05


class TestProfileEndpoint:
    @pytest.fixture()
    def srv(self):
        from vantage6_tpu.server.app import ServerApp

        app = ServerApp()
        yield app
        app.close()

    def _root_client(self, srv):
        c = srv.test_client()
        srv.ensure_root(password="rootpass123")
        r = c.post(
            "/api/token/user",
            {"username": "root", "password": "rootpass123"},
        )
        c.token = r.json["access_token"]
        return c

    def test_requires_auth(self, srv):
        c = srv.test_client()
        assert c.post("/api/debug/profile", {"seconds": 0.05}).status == 401

    def test_node_token_refused(self, srv):
        c = self._root_client(srv)
        org = c.post("/api/organization", {"name": "o"}).json
        collab = c.post(
            "/api/collaboration",
            {"name": "c", "organization_ids": [org["id"]]},
        ).json
        node = c.post(
            "/api/node",
            {"organization_id": org["id"],
             "collaboration_id": collab["id"]},
        ).json
        nc = srv.test_client()
        r = nc.post("/api/token/node", {"api_key": node["api_key"]})
        nc.token = r.json["access_token"]
        assert nc.post(
            "/api/debug/profile", {"seconds": 0.05}
        ).status == 403

    def test_user_window_registered_in_flight(self, srv, tmp_path,
                                              monkeypatch):
        monkeypatch.setenv("V6T_PROFILE_DIR", str(tmp_path))
        c = self._root_client(srv)
        r = c.post("/api/debug/profile", {"seconds": 0.05})
        assert r.status == 201, r
        assert r.json["path"].startswith(str(tmp_path))
        assert r.json["seconds"] == 0.05
        dump = FLIGHT.dump(path=str(tmp_path / "bundle.jsonl"))
        recs = [json.loads(line) for line in open(dump)]
        assert any(
            rec.get("kind") == "profile_window"
            and rec.get("path") == r.json["path"]
            for rec in recs
        )

    def test_bad_seconds_rejected(self, srv):
        c = self._root_client(srv)
        assert c.post(
            "/api/debug/profile", {"seconds": "fast"}
        ).status == 400


# ----------------------------------------------------- summarize + doctor
class TestToolingCallouts:
    def test_summarize_device_plane_section(self):
        f = observed_jit("t.callout", lambda x: jnp.sum(x))
        with TRACER.span("root") as root:
            f(jnp.ones((4,)))
            f(jnp.ones((6,)))
        summary = summarize(TRACER.drain(root.context.trace_id))
        dp = summary["device_plane"]
        assert dp["n_compiles"] == 2 and dp["n_retraces"] == 1
        assert dp["by_function"]["t.callout"]["compiles"] == 2
        assert "float32[4] -> float32[6]" in dp["retraces"][0]["changed"]
        assert dp["compile_total_ms"] > 0

    def test_doctor_perf_digest_names_retrace(self, tmp_path):
        import sys

        sys.path.insert(0, "/root/repo")
        from tools.doctor import perf_digest, render_perf

        f = observed_jit("t.doctor", lambda x: jnp.sum(x))
        f(jnp.ones((4,)))
        f(jnp.ones((5,)))
        FLIGHT.snapshot_metrics()
        dump = FLIGHT.dump(path=str(tmp_path / "b.jsonl"))
        from vantage6_tpu.common.flight import read_bundle

        perf = perf_digest(read_bundle(dump))
        assert perf is not None
        named = [r for r in perf["retraces"]
                 if r["function"] == "t.doctor"]
        assert named and "float32[4] -> float32[5]" in named[0]["changed"]
        text = "\n".join(render_perf(perf))
        assert "t.doctor" in text and "float32[4] -> float32[5]" in text
