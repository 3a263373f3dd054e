"""Learning-plane observatory: in-round stats, RoundHistory, the three
learning watchdog rules, /api/rounds, checkpoint continuity, and the
doctor/trace_view surfaces (docs/observability.md "learning plane")."""
import json
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vantage6_tpu.common.flight import FLIGHT, read_bundle
from vantage6_tpu.common.telemetry import REGISTRY
from vantage6_tpu.core.mesh import FederationMesh
from vantage6_tpu.fed.collectives import station_update_stats
from vantage6_tpu.fed.fedavg import FedAvg, FedAvgSpec
from vantage6_tpu.runtime.learning import (
    LEARNING,
    LearningRegistry,
    RoundHistory,
    update_stats_host,
)
from vantage6_tpu.runtime.tracing import TRACER, summarize
from vantage6_tpu.runtime.watchdog import (
    DEFAULT_RULES,
    RULE_CATALOG,
    RuleContext,
    Watchdog,
    station_window_flags,
)


@pytest.fixture()
def tracer():
    TRACER.configure(enabled=True, sample=1.0, sink=None)
    TRACER.clear()
    yield TRACER
    TRACER.configure(enabled=True, sample=1.0, sink=None)


@pytest.fixture(autouse=True)
def _clean_learning():
    LEARNING.clear()
    yield
    LEARNING.clear()


def ctx(snapshot=None, history=None, feeds=None, config=None, now=None):
    from collections import deque

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


def rule(name):
    return next(r for r in DEFAULT_RULES if r.name == name)


# ------------------------------------------------------------ the statistics
class TestStationUpdateStats:
    def test_hand_computed_values(self):
        flat = jnp.asarray([[3.0, 0.0], [0.0, 4.0]], jnp.float32)
        out = station_update_stats(flat)
        np.testing.assert_allclose(
            np.asarray(out["station_norm"]), [3.0, 4.0], rtol=1e-6
        )
        pooled = np.array([1.5, 2.0])  # unweighted mean of the rows
        np.testing.assert_allclose(
            float(out["update_norm"]), np.linalg.norm(pooled), rtol=1e-6
        )
        expect_cos = [
            (flat_row @ pooled) / (np.linalg.norm(flat_row) *
                                   np.linalg.norm(pooled))
            for flat_row in np.asarray(flat)
        ]
        np.testing.assert_allclose(
            np.asarray(out["station_cos"]), expect_cos, rtol=1e-5
        )

    def test_opposed_station_has_negative_cos(self):
        flat = jnp.asarray(
            [[1.0, 1.0], [1.0, 1.1], [-1.0, -1.0], [1.1, 1.0]], jnp.float32
        )
        cos = np.asarray(station_update_stats(flat)["station_cos"])
        assert cos[2] < 0 and all(c > 0.9 for c in cos[[0, 1, 3]])

    def test_mask_excludes_station_from_pooled_and_isolates_nan(self):
        flat = jnp.asarray(
            [[1.0, 0.0], [1.0, 0.0], [jnp.nan, jnp.inf]], jnp.float32
        )
        mask = jnp.asarray([1.0, 1.0, 0.0])
        out = station_update_stats(flat, mask=mask)
        # pooled = mean of the two live rows; the nan station is excluded
        np.testing.assert_allclose(float(out["update_norm"]), 1.0, rtol=1e-6)
        assert np.isfinite(np.asarray(out["station_cos"])[:2]).all()

    def test_weights_bias_the_pooled_delta(self):
        flat = jnp.asarray([[1.0, 0.0], [0.0, 1.0]], jnp.float32)
        out = station_update_stats(flat, weights=jnp.asarray([3.0, 1.0]))
        pooled = (3 * np.array([1.0, 0]) + np.array([0, 1.0])) / 4
        np.testing.assert_allclose(
            float(out["update_norm"]), np.linalg.norm(pooled), rtol=1e-6
        )

    def test_ef_norms_ride_along(self):
        flat = jnp.ones((2, 4), jnp.float32)
        ef = jnp.asarray([[1.0, 0, 0, 0], [0.0, 2, 0, 0]], jnp.float32)
        out = station_update_stats(flat, ef=ef)
        np.testing.assert_allclose(
            np.asarray(out["station_ef_norm"]), [1.0, 2.0], rtol=1e-6
        )

    def test_host_twin_matches_device(self):
        rng = np.random.default_rng(0)
        flat = rng.standard_normal((5, 33)).astype(np.float32)
        w = rng.uniform(1, 4, 5).astype(np.float32)
        dev = station_update_stats(jnp.asarray(flat), weights=jnp.asarray(w))
        host = update_stats_host(flat, weights=w)
        for k in ("station_norm", "station_cos"):
            np.testing.assert_allclose(
                np.asarray(dev[k]), np.asarray(host[k]), rtol=1e-5
            )
        np.testing.assert_allclose(
            float(dev["update_norm"]), host["update_norm"], rtol=1e-5
        )


# ------------------------------------------------------------------ engine
def _toy_problem(S=4, n=16, d=3, flip=None, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((S, n, d)).astype(np.float32)
    beta = np.linspace(1.0, -1.0, d).astype(np.float32)
    y = (x @ beta + 0.01 * rng.standard_normal((S, n))).astype(np.float32)
    if flip is not None:
        y[flip] = -y[flip]

    def loss_fn(p, bx, by, w):
        pred = bx @ p
        return jnp.sum(w * (pred - by) ** 2) / jnp.maximum(jnp.sum(w), 1.0)

    return loss_fn, jnp.asarray(x), jnp.asarray(y), jnp.full((S,), float(n))


class TestEngineStats:
    def test_round_returns_stats(self):
        loss_fn, x, y, counts = _toy_problem()
        eng = FedAvg(FederationMesh(4), FedAvgSpec(
            loss_fn=loss_fn, local_steps=2, batch_size=8
        ))
        p0 = jnp.zeros(3)
        _, _, loss, stats = eng.round(
            p0, eng.init(p0), x, y, counts, jax.random.key(0)
        )
        assert set(stats) == {
            "station_norm", "station_cos", "update_norm", "station_weight",
        }
        assert np.asarray(stats["station_norm"]).shape == (4,)
        assert np.isfinite(float(stats["update_norm"]))

    def test_learning_stats_off_returns_empty(self):
        loss_fn, x, y, counts = _toy_problem()
        eng = FedAvg(FederationMesh(4), FedAvgSpec(
            loss_fn=loss_fn, local_steps=1, batch_size=8,
            learning_stats=False,
        ))
        p0 = jnp.zeros(3)
        out = eng.round(p0, eng.init(p0), x, y, counts, jax.random.key(0))
        assert out[3] == {}

    def test_fp32_identical_replicated_vs_scattered(self, fresh):
        loss_fn, x, y, counts = _toy_problem(flip=1)
        kw = dict(loss_fn=loss_fn, local_steps=2, batch_size=8)
        p0 = jnp.zeros(3)
        key = jax.random.key(1)
        mesh = FederationMesh(4)
        _, _, _, s_rep = FedAvg(mesh, FedAvgSpec(**kw)).run_rounds(
            fresh(p0), x, y, counts, key, 4
        )
        _, _, _, s_sc = FedAvg(
            mesh, FedAvgSpec(**kw, shard_server_update=True)
        ).run_rounds(fresh(p0), x, y, counts, key, 4)
        for k in s_rep:
            assert np.array_equal(np.asarray(s_rep[k]), np.asarray(s_sc[k]))

    def test_compressed_round_carries_ef_norms(self):
        from vantage6_tpu.fed.compression import CompressorSpec

        loss_fn, x, y, counts = _toy_problem()
        eng = FedAvg(FederationMesh(4), FedAvgSpec(
            loss_fn=loss_fn, local_steps=1, batch_size=8,
            compressor=CompressorSpec(topk_ratio=0.5),
        ))
        p0 = jnp.zeros(3)
        _, _, _, stats = eng.run_rounds(
            p0, x, y, counts, jax.random.key(0), 3
        )
        assert "station_ef_norm" in stats
        # top-k drops mass, so EF accumulators are nonzero
        assert float(np.asarray(stats["station_ef_norm"][-1]).sum()) > 0

    def test_attach_history_autorecords(self):
        loss_fn, x, y, counts = _toy_problem()
        eng = FedAvg(FederationMesh(4), FedAvgSpec(
            loss_fn=loss_fn, local_steps=1, batch_size=8
        ))
        hist = eng.attach_history("engine-test")
        p0 = jnp.zeros(3)
        eng.run_rounds(p0, x, y, counts, jax.random.key(0), 3)
        p1 = jnp.zeros(3)
        eng.round(p1, eng.init(p1), x, y, counts, jax.random.key(1))
        assert hist.rounds_total == 4
        assert [r["round"] for r in hist.rounds()] == [0, 1, 2, 3]
        assert LEARNING.get("engine-test") is hist


# ------------------------------------------------------------- RoundHistory
class TestRoundHistory:
    def test_record_emits_telemetry(self):
        h = RoundHistory("t1")
        before = REGISTRY.counter("v6t_round_updates_total").value
        h.record(
            update_norm=2.0, station_norms=[1.0, 3.0],
            station_cos=[0.9, -0.5], loss=0.7,
        )
        snap = REGISTRY.snapshot()
        assert REGISTRY.counter(
            "v6t_round_updates_total"
        ).value == before + 1
        assert snap["v6t_round_update_norm"] == 2.0
        assert snap["v6t_round_loss"] == pytest.approx(0.7)
        assert snap["v6t_station_update_norm_max"] == 3.0
        assert snap["v6t_station_cos_min"] == -0.5

    def test_norm_decay_gauge_tracks_peak(self):
        h = RoundHistory("t2")
        h.record(update_norm=4.0, station_norms=[1], station_cos=[1])
        h.record(update_norm=1.0, station_norms=[1], station_cos=[1])
        assert REGISTRY.snapshot()["v6t_round_norm_decay"] == pytest.approx(
            0.25
        )

    def test_bounded_but_totals_survive(self):
        h = RoundHistory("t3", maxlen=8)
        for i in range(20):
            h.record(update_norm=1.0, station_norms=[1], station_cos=[1])
        assert len(h.rounds()) == 8
        assert h.rounds_total == 20
        assert h.summary()["rounds"] == 20

    def test_span_and_flight_note(self, tracer):
        FLIGHT.clear()
        h = RoundHistory("t4")
        with TRACER.span("test.root", kind="test") as root:
            trace_id = root.context.trace_id
            h.record(
                update_norm=1.0, station_norms=[1.0, 2.0],
                station_cos=[1.0, 0.1], loss=0.5, round_index=7,
            )
        spans = TRACER.drain(trace_id)
        learning = [s for s in spans if s["name"] == "learning.round"]
        assert len(learning) == 1
        assert learning[0]["attrs"]["round"] == 7
        assert learning[0]["attrs"]["min_cos_station"] == 1
        assert any(
            e["name"] == "round_recorded"
            for e in learning[0].get("events") or []
        )
        notes = [
            r for r in FLIGHT._notes if r.get("kind") == "learning_round"
        ]
        assert notes and notes[-1]["task"] == "t4"

    def test_untraced_record_mints_no_trace(self, tracer):
        h = RoundHistory("t5")
        h.record(update_norm=1.0, station_norms=[1], station_cos=[1])
        assert not [
            s for s in TRACER.drain() if s["name"] == "learning.round"
        ]

    def test_state_roundtrip_and_continuity(self):
        h = RoundHistory("t6")
        for i in range(6):
            h.record(
                update_norm=10.0 / (i + 1), station_norms=[1.0, 2.0],
                station_cos=[0.9, 0.8], loss=1.0 / (i + 1),
            )
        state = h.state_arrays()
        h2 = RoundHistory("t6").load_state(state)
        assert h2.rounds_total == 6
        assert h2.peak_norm == 10.0
        assert [r["round"] for r in h2.rounds()] == list(range(6))
        # continuing after restore keeps the trajectory continuous
        h2.record(
            update_norm=10.0 / 7, station_norms=[1.0, 2.0],
            station_cos=[0.9, 0.8],
        )
        assert h2.rounds()[-1]["round"] == 6
        norms = [r["update_norm"] for r in h2.rounds()]
        assert all(b < a for a, b in zip(norms, norms[1:]))

    def test_registry_is_bounded_fifo(self):
        reg = LearningRegistry(max_histories=8)
        for i in range(20):
            reg.history(i)
        assert len(reg.keys()) == 8
        assert reg.get(0) is None and reg.get(19) is not None


# ------------------------------------------------------- the watchdog rules
def _learning_feed(round_items, task_items):
    return {"learning": {
        "learning_rounds": round_items, "learning_tasks": task_items,
    }}


def _anomaly_rounds(n, station=2, cos=-0.8, stations=4):
    out = []
    for r in range(n):
        sts = []
        for s in range(stations):
            sts.append({
                "station": s,
                "norm": 1.0,
                "cos": cos if s == station else 0.95,
            })
        out.append({
            "task": "tk", "round": r, "ts": time.time(),
            "update_norm": 1.0, "median_norm": 1.0, "stations": sts,
        })
    return out


class TestLearningRules:
    def test_anomalous_station_fires_on_low_cos_and_names_stat(self):
        c = ctx(feeds=_learning_feed(_anomaly_rounds(5), []))
        found = rule("anomalous_station").check(c)
        assert len(found) == 1
        assert found[0]["labels"] == {"task": "tk", "station": 2}
        assert "station 2" in found[0]["message"]
        assert "cosine" in found[0]["message"]

    def test_anomalous_station_fires_on_norm_outlier(self):
        rounds = _anomaly_rounds(5, cos=0.95)  # all cosines healthy
        for r in rounds:
            r["stations"][1]["norm"] = 9.0  # 9x the median
        c = ctx(feeds=_learning_feed(rounds, []))
        found = rule("anomalous_station").check(c)
        assert len(found) == 1
        assert found[0]["labels"]["station"] == 1
        assert "norm" in found[0]["message"]
        assert "9.0x" in found[0]["message"]

    def test_anomalous_station_skips_masked_out_stations(self):
        """The runbook's remediation is 'mask the station' — once masked,
        its fictional SPMD-computed stats must stop feeding the alert,
        or the alert could never be cleared by its own runbook."""
        rounds = _anomaly_rounds(6)  # station 2 contrarian
        for r in rounds:
            r["stations"][2]["participating"] = False
        c = ctx(feeds=_learning_feed(rounds, []))
        assert rule("anomalous_station").check(c) == []

    def test_masked_station_excluded_end_to_end(self):
        """Engine round with a mask: the masked station's weight rides
        the stats, the feed marks it non-participating, and the median
        covers participants only."""
        loss_fn, x, y, counts = _toy_problem(flip=1)
        eng = FedAvg(FederationMesh(4), FedAvgSpec(
            loss_fn=loss_fn, local_steps=1, batch_size=8
        ))
        hist = eng.attach_history("masked")
        mask = jnp.asarray([1.0, 0.0, 1.0, 1.0])
        p0 = jnp.zeros(3)
        eng.round(p0, eng.init(p0), x, y, counts, jax.random.key(0),
                  mask=mask)
        item = hist.feed_items()[0][-1]
        flags = {s["station"]: s["participating"] for s in item["stations"]}
        assert flags == {0: True, 1: False, 2: True, 3: True}
        live_norms = [
            s["norm"] for s in item["stations"] if s["participating"]
        ]
        assert item["median_norm"] == pytest.approx(
            float(np.median(live_norms))
        )

    def test_anomalous_station_window_is_per_task(self):
        """Concurrent tasks must not dilute each other's evidence: task
        A's poisoned station stays detectable even when other tasks'
        rounds dominate the merged feed's tail."""
        poisoned = _anomaly_rounds(6)  # task "tk", station 2 contrarian
        noise = []
        for i in range(20):  # 20 healthy rounds from OTHER tasks, newer
            r = _anomaly_rounds(1, cos=0.9)[0]
            r["task"] = f"other-{i % 4}"
            r["ts"] = time.time() + 1 + i
            noise.append(r)
        c = ctx(feeds=_learning_feed(poisoned + noise, []))
        found = rule("anomalous_station").check(c)
        assert len(found) == 1
        assert found[0]["labels"] == {"task": "tk", "station": 2}

    def test_anomalous_station_needs_repeats(self):
        c = ctx(feeds=_learning_feed(_anomaly_rounds(2), []))
        assert rule("anomalous_station").check(c) == []

    def test_anomalous_station_quiet_on_healthy(self):
        c = ctx(feeds=_learning_feed(_anomaly_rounds(8, cos=0.9), []))
        assert rule("anomalous_station").check(c) == []

    def test_anomalous_station_ignores_zero_norm_degenerates(self):
        """A station that sent NOTHING (zero-norm row) degenerates to
        cos == 0 — absence of signal, not a contrarian update; same for
        a zero pooled update. Neither may flag."""
        rounds = _anomaly_rounds(6, cos=0.95)
        for r in rounds:
            r["stations"][2]["norm"] = 0.0
            r["stations"][2]["cos"] = 0.0
        dead_pool = _anomaly_rounds(6, cos=0.0)
        for r in dead_pool:
            r["task"] = "tk2"
            r["update_norm"] = 0.0
        c = ctx(feeds=_learning_feed(rounds + dead_pool, []))
        assert rule("anomalous_station").check(c) == []

    def test_model_divergence_fires_on_monotone_growth(self):
        task = {"task": "tk", "rounds": 10, "peak_norm": 2.0,
                "recent_norms": [1.0, 1.2, 1.5, 1.9, 2.4]}
        found = rule("model_divergence").check(
            ctx(feeds=_learning_feed([], [task]))
        )
        assert len(found) == 1
        assert "diverging" in found[0]["message"]
        assert found[0]["labels"] == {"task": "tk"}

    def test_model_divergence_quiet_on_wobble_and_tiny_growth(self):
        wobble = {"task": "a", "rounds": 10, "peak_norm": 2.0,
                  "recent_norms": [1.0, 1.4, 1.2, 1.9, 2.4]}
        tiny = {"task": "b", "rounds": 10, "peak_norm": 2.0,
                "recent_norms": [1.0, 1.001, 1.002, 1.003, 1.004]}
        c = ctx(feeds=_learning_feed([], [wobble, tiny]))
        assert rule("model_divergence").check(c) == []

    def test_non_convergence_fires_past_budget(self):
        task = {"task": "tk", "rounds": 40, "peak_norm": 1.0,
                "recent_norms": [0.8] * 16}
        found = rule("non_convergence").check(
            ctx(feeds=_learning_feed([], [task]))
        )
        assert len(found) == 1
        assert "stalled" in found[0]["message"]

    def test_non_convergence_growth_message_names_the_rise(self):
        """Non-monotonic GROWTH past the budget is non-convergence too,
        but the message must say the norm rose, not 'fell only -80%'."""
        task = {"task": "tk", "rounds": 40, "peak_norm": 2.0,
                "recent_norms": [1.0, 1.5, 1.3, 1.8]}
        found = rule("non_convergence").check(
            ctx(feeds=_learning_feed([], [task]))
        )
        assert len(found) == 1
        assert "ROSE 80.0%" in found[0]["message"]
        assert "fell only" not in found[0]["message"]
        young = {"task": "a", "rounds": 5, "peak_norm": 1.0,
                 "recent_norms": [0.8] * 5}
        decaying = {"task": "b", "rounds": 40, "peak_norm": 1.0,
                    "recent_norms": [0.8 * (0.9 ** i) for i in range(16)]}
        c = ctx(feeds=_learning_feed([], [young, decaying]))
        assert rule("non_convergence").check(c) == []

    def test_non_convergence_quiet_when_converged_at_bottom(self):
        done = {"task": "tk", "rounds": 40, "peak_norm": 1.0,
                "recent_norms": [0.001] * 16}
        c = ctx(feeds=_learning_feed([], [done]))
        assert rule("non_convergence").check(c) == []

    def test_rules_in_catalog(self):
        for name in (
            "anomalous_station", "model_divergence", "non_convergence",
        ):
            assert name in RULE_CATALOG
            assert RULE_CATALOG[name]["runbook"]

    def test_shared_helper_counts_and_worst(self):
        rounds = [
            {"v": [("a", 1.0, "one")]},
            {"v": [("a", 3.0, "three"), ("b", 1.0, "b1")]},
            {"v": []},
        ]
        counts, worst, n = station_window_flags(
            rounds, 2, lambda r: r["v"]
        )
        # window=2 drops the first round
        assert n == 2
        assert counts == {"a": 1, "b": 1}
        assert worst["a"] == (3.0, "three")

    def test_straggler_still_fires_through_helper(self):
        rounds = [
            {"straggler_station": 2, "max_exec_s": 9.0,
             "mean_exec_s": 1.0, "n": 4}
            for _ in range(4)
        ]
        found = rule("straggler_station").check(
            ctx(feeds={"f": {"rounds": rounds}})
        )
        assert len(found) == 1
        assert found[0]["labels"] == {"station": 2}
        assert "9.0x the round mean" in found[0]["message"]

    def test_end_to_end_engine_to_alert(self):
        """Label-flipped station through the REAL pipeline: engine stats →
        LEARNING feed → singleton-registered feed → rule fires naming it."""
        loss_fn, x, y, counts = _toy_problem(flip=3, seed=5)
        eng = FedAvg(FederationMesh(4), FedAvgSpec(
            loss_fn=loss_fn, local_steps=2, batch_size=8, local_lr=0.05
        ))
        hist = eng.attach_history("e2e")
        p0 = jnp.zeros(3)
        eng.run_rounds(p0, x, y, counts, jax.random.key(0), 5)
        assert hist.rounds_total == 5
        wd = Watchdog(interval=60.0)
        wd.register_feed("learning", LEARNING.feed)
        active = wd.evaluate()
        anomalies = [a for a in active if a["rule"] == "anomalous_station"]
        assert len(anomalies) == 1
        assert anomalies[0]["labels"]["station"] == 3


# --------------------------------------------------------------- server API
class TestRoundsApi:
    @pytest.fixture()
    def server(self):
        from vantage6_tpu.client import UserClient
        from vantage6_tpu.server.app import ServerApp

        srv = ServerApp()
        srv.ensure_root(password="rootpass123")
        http = srv.serve(port=0, background=True)
        client = UserClient(http.url)
        client.authenticate("root", "rootpass123")
        yield client
        http.stop()
        srv.close()

    def test_rounds_index_and_task(self, server):
        h = LEARNING.history(31)
        for i in range(3):
            h.record_stats(update_stats_host(
                np.eye(3, 5, dtype=np.float32) * (3 - i)
            ), loss=1.0 - 0.2 * i)
        idx = server.util.rounds()
        assert any(t["task"] == 31 for t in idx["tasks"])
        out = server.util.rounds(31)
        assert out["task_id"] == 31
        assert len(out["rounds"]) == 3
        assert out["summary"]["rounds"] == 3
        assert out["rounds"][-1]["loss"] == pytest.approx(0.6)

    def test_rounds_404_for_unknown_task(self, server):
        from vantage6_tpu.client.client import ClientError

        with pytest.raises(ClientError) as e:
            server.util.rounds(424242)
        assert e.value.status == 404

    def test_rounds_limit_param(self, server):
        h = LEARNING.history(32)
        for i in range(10):
            h.record(update_norm=1.0, station_norms=[1], station_cos=[1])
        out = server.parent_request_limit = server.request(
            "GET", "rounds/32", params={"limit": 4}
        )
        assert len(out["rounds"]) == 4


# ------------------------------------------------------ federation wiring
class TestFederationLearning:
    def test_device_aggregation_records_history(self):
        from vantage6_tpu.algorithm.decorators import device_step
        from vantage6_tpu.runtime.federation import federation_from_datasets

        @device_step
        def partial_sum(d):
            return {"s": jnp.sum(d), "n": jnp.asarray(4.0)}

        datasets = [jnp.arange(4.0) + i for i in range(3)]
        fed = federation_from_datasets(
            datasets, {"img": {"partial_sum": partial_sum}}
        )
        try:
            task = fed.create_task(
                image="img", input_={"method": "partial_sum"}
            )
            fed.aggregate_stacked(task.id)
            hist = fed.learning_history(task.id)
            assert hist is not None and hist.rounds_total == 1
            rec = hist.rounds()[-1]
            assert len(rec["station_norms"]) == 3
        finally:
            fed.close()

    def test_subtask_rounds_accumulate_under_parent(self):
        from vantage6_tpu.algorithm.decorators import device_step
        from vantage6_tpu.runtime.federation import federation_from_datasets

        @device_step
        def partial_sum(d):
            return {"s": jnp.sum(d)}

        datasets = [jnp.arange(4.0) + i for i in range(2)]
        fed = federation_from_datasets(
            datasets, {"img": {"partial_sum": partial_sum}}
        )
        try:
            parent = fed.create_task(
                image="img", input_={"method": "partial_sum"}
            )
            for _ in range(3):
                sub = fed.create_task(
                    image="img", input_={"method": "partial_sum"},
                    parent=parent,
                )
                fed.aggregate_stacked(sub.id)
            hist = fed.learning_history(parent.id)
            assert hist is not None and hist.rounds_total == 3
        finally:
            fed.close()


# ------------------------------------------------------------- checkpointing
class TestCheckpointContinuity:
    def test_trainstate_carries_history(self, tmp_path):
        pytest.importorskip("orbax.checkpoint")
        from vantage6_tpu.runtime.checkpoint import (
            CheckpointManager,
            TrainState,
        )

        h = RoundHistory("ckpt")
        for i in range(5):
            h.record(
                update_norm=8.0 / (i + 1), station_norms=[1.0, 2.0],
                station_cos=[0.9, 0.8], loss=0.5,
            )
        state = TrainState(
            params={"w": jnp.ones(3)}, opt_state=(),
            round_index=4, rng_key=jax.random.key(0),
            history=h.state_arrays(),
        )
        mgr = CheckpointManager(tmp_path / "ck")
        mgr.save(state, wait=True)
        restored = mgr.restore()
        mgr.close()
        assert restored.history is not None
        h2 = RoundHistory("ckpt").load_state(restored.history)
        assert h2.rounds_total == 5
        assert h2.peak_norm == pytest.approx(8.0)

    def test_old_checkpoints_restore_without_history(self, tmp_path):
        pytest.importorskip("orbax.checkpoint")
        from vantage6_tpu.runtime.checkpoint import (
            CheckpointManager,
            TrainState,
        )

        state = TrainState(
            params={"w": jnp.ones(2)}, opt_state=(),
            round_index=1, rng_key=jax.random.key(0),
        )
        mgr = CheckpointManager(tmp_path / "ck2")
        mgr.save(state, wait=True)
        restored = mgr.restore()
        mgr.close()
        assert restored.history is None

    def test_no_spurious_alerts_after_restore(self):
        """A restored trajectory continues decaying: neither
        model_divergence nor non_convergence fires on the resume."""
        h = RoundHistory("resume")
        for i in range(20):
            h.record(
                update_norm=5.0 * (0.85 ** i), station_norms=[1.0],
                station_cos=[1.0],
            )
        h2 = RoundHistory("resume").load_state(h.state_arrays())
        for i in range(20, 24):
            h2.record(
                update_norm=5.0 * (0.85 ** i), station_norms=[1.0],
                station_cos=[1.0],
            )
        reg = LearningRegistry()
        reg._histories["resume"] = h2
        wd = Watchdog(interval=60.0)
        wd.register_feed("learning", reg.feed)
        active = wd.evaluate()
        assert not [
            a for a in active
            if a["rule"] in ("model_divergence", "non_convergence")
        ]


# ------------------------------------------------------- doctor / trace_view
class TestSurfaces:
    def test_summarize_learning_plane(self, tracer):
        h = RoundHistory("sv")
        with TRACER.span("root", kind="test") as root:
            tid = root.context.trace_id
            for i in range(4):
                h.record(
                    update_norm=4.0 - i, station_norms=[1.0, 2.0],
                    station_cos=[0.9, -0.3], loss=1.0 - 0.1 * i,
                    round_index=i,
                )
        s = summarize(TRACER.drain(tid))
        lp = s["learning_plane"]
        assert lp["n_rounds"] == 4
        task = lp["tasks"][0]
        assert task["task"] == "sv"
        assert task["first_update_norm"] == 4.0
        assert task["last_update_norm"] == 1.0
        assert task["norm_decay_pct"] == pytest.approx(75.0)
        assert task["min_station_cos"] == pytest.approx(-0.3)
        assert task["min_cos_station"] == 1

    def test_summarize_learning_plane_is_per_task(self, tracer):
        """Two tasks' interleaved rounds must not fabricate one merged
        trajectory — each task gets its own first->last norm."""
        ha, hb = RoundHistory("A"), RoundHistory("B")
        with TRACER.span("root", kind="test") as root:
            tid = root.context.trace_id
            for i in range(3):
                ha.record(update_norm=3.0 - i, station_norms=[1.0],
                          station_cos=[1.0], round_index=i)
                hb.record(update_norm=10.0 + i, station_norms=[1.0],
                          station_cos=[1.0], round_index=i)
        lp = summarize(TRACER.drain(tid))["learning_plane"]
        rows = {t["task"]: t for t in lp["tasks"]}
        assert rows["A"]["norm_decay_pct"] == pytest.approx(
            100 * 2 / 3.0, abs=0.01
        )
        assert rows["B"]["norm_decay_pct"] == pytest.approx(-20.0)

    def test_trace_view_renders_learning_callout(self, tracer, tmp_path):
        h = RoundHistory("tv")
        sink = tmp_path / "spans.jsonl"
        TRACER.configure(enabled=True, sample=1.0, sink=str(sink))
        with TRACER.span("root", kind="test"):
            h.record(
                update_norm=2.0, station_norms=[1.0], station_cos=[0.5],
            )
        TRACER.configure(sink=None)
        out = subprocess.run(
            [sys.executable, "tools/trace_view.py", str(sink)],
            capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 0
        assert "learning plane" in out.stdout

    def test_doctor_learning_digest(self, tmp_path, tracer):
        FLIGHT.clear()
        h = LEARNING.history("doc-task")
        rng = np.random.default_rng(1)
        for i in range(5):
            flat = rng.standard_normal((4, 8)).astype(np.float32)
            flat[1] = -10 * flat.mean(axis=0)  # station 1 contrarian
            st = update_stats_host(flat)
            h.record_stats(st, loss=1.0 - 0.1 * i)
        path = str(tmp_path / "bundle.jsonl")
        assert FLIGHT.dump(path=path, reason="test")
        out = subprocess.run(
            [sys.executable, "tools/doctor.py", path, "--json"],
            capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 0
        digest = json.loads(out.stdout)["learning"]
        assert digest is not None
        task = next(
            t for t in digest["tasks"] if t["task"] == "doc-task"
        )
        assert task["rounds_seen"] == 5
        assert len(task["stations"]) == 4
        # text render shows the table too
        out2 = subprocess.run(
            [sys.executable, "tools/doctor.py", path],
            capture_output=True, text=True, timeout=60,
        )
        assert "learning-plane digest" in out2.stdout
        assert "doc-task" in out2.stdout

    def test_flight_dump_carries_learning_summaries(self, tmp_path):
        FLIGHT.clear()
        h = LEARNING.history("fd")
        h.record(update_norm=1.0, station_norms=[1.0], station_cos=[1.0])
        path = FLIGHT.dump(path=str(tmp_path / "b.jsonl"), reason="t")
        recs = read_bundle(path)
        learning = [r for r in recs if r.get("type") == "learning"]
        assert any(r.get("task") == "fd" for r in learning)

    def test_check_collect_learning_audit_clean(self):
        sys.path.insert(0, ".")
        from tools.check_collect import check_learning_plane

        assert check_learning_plane() == []

    def test_metrics_snapshot_helper(self):
        from vantage6_tpu.runtime.metrics import learning_snapshot

        LEARNING.history("ms").record(
            update_norm=1.0, station_norms=[1.0], station_cos=[1.0]
        )
        assert any(s["task"] == "ms" for s in learning_snapshot())
