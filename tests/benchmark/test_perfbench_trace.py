"""The reduction from a trace to busy/idle, collective and exposed time, the
operations' own times, the device time by scope path and what the host did
in the idle gaps: on a trace written by hand, where every number can be
counted, and on small ones recorded on the chip; and the reader of the
profiler's file against jax's own."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from perfbench import trace as T

DATA = Path(__file__).parent / "data"


def _ops():
    return [
        ["fusion.1", 0, 100],
        ["while.1", 150, 300],            # encloses the next three
        ["fusion.2", 160, 100],
        ["all-reduce.1", 270, 100],       # synchronous: all of it exposed
        ["fusion.3", 380, 60],
        ["all-gather-start.1", 500, 10],  # asynchronous, fusion.4 hides part
        ["fusion.4", 510, 90],
        ["all-gather-done.1", 600, 50],
        ["fusion.5", 900, 100],
    ]


def _trace(host_events, second_device=None):
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_f", 0, 1000]]},
        {"name": "XLA Ops", "events": _ops()}]}]
    if second_device is not None:
        planes.append({"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": second_device}]})
    planes.append({"name": "/host:CPU", "lines": [
        {"name": "python", "events": host_events}]})
    return {"planes": planes}


HOST = [
    ["PjitFunction(step)", 440, 70],      # covers the gap 450..500
    ["PjitFunction(step)", 640, 270],     # covers the gap 650..900 ...
    ["backend_compile", 660, 230],        # ... most of which is a compile
]


def test_busy_idle_and_own_times_by_hand():
    r = T.reduce(_trace(HOST))
    assert r.window_from == "device ops" and r.n_devices == 1
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx(650e-9)          # 100 + 300 + 150 + 100
    assert r.idle_share == pytest.approx(0.35)
    own = dict(map(tuple, r.device_ops))
    assert own["while.1"] == pytest.approx(40e-9)     # 300 - 100 - 100 - 60
    assert own["fusion.1"] == pytest.approx(100e-9)
    assert len(r.device_ops) <= 10


def test_collective_time_and_its_exposed_part_by_hand():
    r = T.reduce(_trace(HOST))
    assert r.collective_s == pytest.approx(250e-9)    # 270..370 and 500..650
    # 100 of the all-reduce, 10 before and 50 after fusion.4 of the all-gather
    assert r.collective_exposed_s == pytest.approx(160e-9)


def test_gaps_are_attributed_to_what_the_host_was_doing():
    r = T.reduce(_trace(HOST))
    gaps = [(kind, round(s * 1e9)) for kind, s in r.idle_gaps]
    assert gaps[0] == ("compile", 250)
    assert ("dispatch", 50) in gaps and ("no host event", 50) in gaps


def test_the_hosts_mark_bounds_the_window_when_the_clocks_agree():
    r = T.reduce(_trace(HOST + [[T.WINDOW_EVENT, 0, 500],
                                [T.WINDOW_EVENT, 500, 700]]))
    assert r.window_from == "host annotation"
    assert r.window_s == pytest.approx(1200e-9)
    assert r.busy_s == pytest.approx(650e-9)
    # a mark on another clock is not believed
    r = T.reduce(_trace(HOST + [[T.WINDOW_EVENT, 5000, 500]]))
    assert r.window_from == "device ops"


def test_several_devices_mean_busy_slowest_collective():
    second = [["fusion.9", 0, 200], ["all-reduce.1", 200, 400]]
    r = T.reduce(_trace(HOST, second_device=second))
    assert r.n_devices == 2
    assert r.busy_s == pytest.approx((650e-9 + 600e-9) / 2)
    assert r.collective_s == pytest.approx(400e-9)
    assert r.collective_exposed_s == pytest.approx(400e-9)


def test_a_trace_with_no_device_operation_is_refused():
    with pytest.raises(ValueError):
        T.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})


def test_interval_arithmetic():
    assert T.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]
    assert T.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


@pytest.mark.parametrize("path", sorted(DATA.glob("trace_*.json")),
                         ids=lambda p: p.stem)
def test_recorded_trace(path):
    recorded = json.loads(path.read_text())
    r = T.reduce(recorded["trace"])
    want = recorded["expected"]
    assert r.n_devices == want["n_devices"]
    assert r.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert r.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert r.collective_s == pytest.approx(want["collective_s"], rel=1e-9)
    assert [k for k, _ in r.idle_gaps][:3] == want["gap_kinds"][:3]
    assert 0.0 < r.busy_s <= r.window_s
    assert r.collective_exposed_s <= r.collective_s
    assert r.window_from == want["window_from"]
    assert r.device_ops[0][0] == want["top_op"]
    # busy time once more, by a sweep that shares no code with the reduction
    busy = []
    for plane in recorded["trace"]["planes"]:
        if not plane["name"].startswith(T.DEVICE_PLANE):
            continue
        for line in plane["lines"]:
            if line["name"] != T.OPS_LINE:
                continue
            covered, reach = 0, -1
            for _, start, dur in sorted(line["events"], key=lambda e: e[1]):
                covered += max(0, start + dur - max(start, reach))
                reach = max(reach, start + dur)
            busy.append(covered)
    assert r.busy_s == pytest.approx(sum(busy) / len(busy) / 1e9, rel=1e-9)


# ------------------------------------------------------ device time by scope
R = "jit(_round)/local_train/"


def _scoped_ops():
    """One round with the paths the v5e gives (PERF.md section 7)."""
    return [
        ["copy.1", 0, 50],                        # three elements: no path
        ["fusion.1", 50, 100, R + "vmap(jvp(embed))/gather"],
        ["while.1", 150, 300, R + "vmap(jvp(attention))/while"],  # encloses 3
        ["fusion.2", 160, 100,
         R + "vmap(jvp(attention))/while/body/closed_call/dot_general"],
        ["fusion.3", 270, 100, R + "vmap(transpose(local_train))/"
         "vmap(jvp(attention))/while/body/closed_call/dot_general"],
        ["fusion.4", 380, 60, R + "vmap(transpose(jvp(mlp)))/dot_general"],
        ["broadcast.1", 450, 30, ""],             # an empty path: no path
        ["fusion.5", 500, 80, R + "vmap(jvp())/dot_general"],
        ["all-reduce.1", 600, 100, "jit(_round)/aggregate/psum"],
        ["fusion.6", 700, 40, "jit(_round)/server_update/add"],
        ["fusion.7", 760, 40, "jit(_round)/mlp"],  # a primitive of that name
    ]


def _scoped(ops, host=(), second_device=None):
    planes = [{"name": "/device:TPU:0",
               "lines": [{"name": "XLA Ops", "events": ops}]}]
    if second_device is not None:
        planes.append({"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": second_device}]})
    planes.append({"name": "/host:CPU", "lines": [
        {"name": "python", "events": list(host)}]})
    return T.reduce({"planes": planes})


def _ns(seconds):
    return None if seconds is None else round(seconds * 1e9)


def test_a_nested_scope_is_counted_in_its_parent_and_once_among_siblings():
    r = _scoped(_scoped_ops())
    assert _ns(r.scope_s("embed")) == 100
    assert _ns(r.scope_s("attention")) == 200     # not the while around them
    assert _ns(r.scope_s("mlp")) == 60
    # the parent holds its scopes and what lies in none of them (fusion.5)
    assert _ns(r.scope_s("local_train")) == 100 + 200 + 60 + 80
    assert _ns(r.scope_s("aggregate")) == 100
    assert _ns(r.scope_s("server_update")) == 40
    # every leaf operation lies in one row: the rows come to the busy time
    # less what the enclosing while.1 took itself (300 - 100 - 100 - 60)
    assert _ns(sum(row["s"] for row in r.scopes.values())) == 740 - 40
    assert _ns(r.busy_s) == 740
    not_nested = ("local_train", "aggregate", "server_update")
    assert _ns(sum(r.scope_s(n) for n in not_nested)
               + r.scopes[T.NO_SCOPE]["s"]
               + r.scopes["jit(_round)/mlp"]["s"]) == 700


def test_a_backward_operation_falls_under_its_scope():
    r = _scoped(_scoped_ops())
    backward = R + "vmap(transpose(jvp(mlp)))/dot_general"
    assert _ns(r.scopes[backward]["s"]) == 60
    assert _ns(r.scope_s("mlp")) == 60
    # the recomputed forward pass inside the backward one is attention's too
    assert _ns(r.scope_s("attention")) == 100 + 100
    # the last component of a path is the primitive, never a scope
    assert _ns(r.scopes["jit(_round)/mlp"]["s"]) == 40
    assert r.scope_s("dot_general") is None and r.scope_s("psum") is None
    # nor is part of a name a name
    assert r.scope_s("local") is None and r.scope_s("train") is None


def test_operations_with_no_path_are_a_row_of_their_own():
    r = _scoped(_scoped_ops())
    assert _ns(r.scopes[T.NO_SCOPE]["s"]) == 50 + 30
    assert r.scope_s(T.NO_SCOPE) is None          # a row, not a scope


def test_a_trace_with_no_path_reads_nothing_under_any_scope():
    r = T.reduce(_trace(HOST))                    # events of three elements
    assert list(r.scopes) == [T.NO_SCOPE]
    assert _ns(r.scopes[T.NO_SCOPE]["s"]) == 650 - 40   # less while.1's own
    assert r.scope_s("local_train") is None
    recorded = json.loads((DATA / "trace_gpt2m_packed.json").read_text())
    assert list(T.reduce(recorded["trace"]).scopes) == [T.NO_SCOPE]


def test_scope_times_are_clipped_to_the_window_and_averaged_over_devices():
    marks = [[T.WINDOW_EVENT, 60, 730]]           # 60 .. 790
    r = _scoped(_scoped_ops(), host=marks)
    assert r.window_from == T.FROM_MARKS
    assert _ns(r.scope_s("embed")) == 90          # fusion.1 from 60 on
    assert _ns(r.scopes[T.NO_SCOPE]["s"]) == 30   # copy.1 lies before it
    assert _ns(r.scopes["jit(_round)/mlp"]["s"]) == 30    # fusion.7 to 790
    second = [["fusion.9", 0, 200, R + "vmap(jvp(mlp))/dot_general"]]
    r = _scoped(_scoped_ops(), second_device=second)
    assert _ns(r.scope_s("mlp")) == (60 + 200) / 2
    assert _ns(r.scope_s("embed")) == 100 / 2


def test_the_compilers_counts_ride_with_the_rows_they_belong_to():
    planes = [{"name": "/device:TPU:0",
               "lines": [{"name": "XLA Ops", "events": _scoped_ops()}],
               "op_costs": {"fusion.2": {"flops": 7, "bytes_accessed": 3},
                            "fusion.3": {"flops": 5, "bytes_accessed": 2},
                            "while.1": {"flops": 99, "bytes_accessed": 99}}}]
    r = T.reduce({"planes": planes})
    forward = R + "vmap(jvp(attention))/while/body/closed_call/dot_general"
    assert r.scopes[forward] == {"s": 100e-9, "flops": 7, "bytes_accessed": 3}
    assert "flops" not in r.scopes[R + "vmap(jvp(embed))/gather"]


def _run_with(window_from, host_round_s=0.5, scopes=None):
    """A `harness.Run` on four chips whose trace says 2 s for 8 rounds, and
    whose host clock says something else."""
    from perfbench import cells, harness
    from perfbench.window import Window

    cell = cells.load_cell("gpt2m.sharded-4chip")
    reduced = T.Reduced(
        n_devices=4, window_s=2.0, window_from=window_from, busy_s=1.5,
        collective_s=0.4, collective_exposed_s=0.2, device_ops=[],
        idle_gaps=[], scopes=scopes or {})
    return harness.Run(
        cell=cell, setup_s=1.0,
        window=Window(elapsed_s=16 * host_round_s, rounds=16,
                      dispatch_s=[host_round_s] * 16, rounds_per_dispatch=1),
        window_compiles=0, flops_per_round=1e12, min_bytes_per_round=1e9,
        peaks={"bf16_flops": 100e12, "hbm_bytes_per_s": 100e9},
        traced_rounds=8, trace=reduced)


def test_a_share_of_a_peak_takes_its_time_from_the_trace():
    run = _run_with(T.FROM_MARKS)
    # 1e12 operations in 2 s / 8 rounds on four chips of 100e12 a second
    assert run.traced_round_s() == pytest.approx(0.25)
    assert run.share_of_peak(run.flops_per_round, "bf16_flops") == \
        pytest.approx(100 * 1e12 / (0.25 * 400e12))
    assert run.share_of_peak(run.min_bytes_per_round, "hbm_bytes_per_s") == \
        pytest.approx(100 * 1e9 / (0.25 * 400e9))
    # the host's clock moves nothing
    assert _run_with(T.FROM_MARKS, host_round_s=9.0).share_of_peak(
        1e12, "bf16_flops") == pytest.approx(1.0)


def test_no_share_without_the_marks_a_trace_a_peak_or_a_count():
    assert _run_with(T.FROM_OPS).share_of_peak(1e12, "bf16_flops") is None
    run = _run_with(T.FROM_MARKS)
    assert run.share_of_peak(None, "bf16_flops") is None
    run.peaks = None
    assert run.share_of_peak(1e12, "bf16_flops") is None
    run.trace = None
    assert run.traced_round_s() is None


def test_a_scopes_time_per_round_and_its_share_of_a_peak():
    rows = {R + "vmap(jvp(mlp))/dot_general": {"s": 0.3},
            R + "vmap(transpose(jvp(mlp)))/dot_general": {"s": 0.5},
            R + "vmap(jvp())/add": {"s": 0.2}, T.NO_SCOPE: {"s": 0.1}}
    run = _run_with(T.FROM_MARKS, scopes=rows)
    assert run.scope_ms("mlp") == pytest.approx(1e3 * 0.8 / 8)   # 8 rounds
    assert run.scope_ms("local_train") == pytest.approx(1e3 * 1.0 / 8)
    # 1e12 operations a round in 0.1 s on four chips of 100e12 a second
    assert run.scope_share_of_peak("mlp", 1e12, "bf16_flops") == \
        pytest.approx(100 * 1e12 / (0.1 * 400e12))
    assert run.scope_share_of_peak("mlp", None, "bf16_flops") is None
    # nothing under the name, no path at all, no mark, no trace
    assert run.scope_ms("attention") is None
    assert run.scope_share_of_peak("attention", 1e12, "bf16_flops") is None
    bare = _run_with(T.FROM_MARKS, scopes={T.NO_SCOPE: {"s": 1.0}})
    assert bare.scope_ms("mlp") is None
    assert _run_with(T.FROM_OPS, scopes=rows).scope_ms("mlp") is None
    run.trace = None
    assert run.scope_ms("mlp") is None


# ------------------------------------------- the profiler's file, read here
XPLANE = DATA / "engine_v5e_cut.xplane.pb"
XPLANE_EXPECTED = json.loads((DATA / "engine_v5e_cut.json").read_text())


def test_the_wire_reader_gives_the_events_profile_data_gives():
    """Planes, lines, and every event's name, start and duration, as the
    reduction got them from `jax.profiler.ProfileData` before it read the
    file itself."""
    from jax.profiler import ProfileData

    ours = T.load_xplane(str(XPLANE))
    theirs = ProfileData.from_file(str(XPLANE))
    assert [p["name"] for p in ours["planes"]] == [
        p.name for p in theirs.planes]
    n_events = 0
    for plane, their_plane in zip(ours["planes"], theirs.planes):
        on_device = their_plane.name.startswith(T.DEVICE_PLANE)
        their_lines = list(their_plane.lines)
        assert [l["name"] for l in plane["lines"]] == [
            l.name for l in their_lines]
        for line, their_line in zip(plane["lines"], their_lines):
            want = [[T.op_name(e.name) if on_device else e.name,
                     int(e.start_ns), int(e.duration_ns)]
                    for e in their_line.events]
            assert [e[:3] for e in line["events"]] == want
            assert all(len(e) == (4 if on_device else 3)
                       for e in line["events"])
            n_events += len(want)
    assert n_events == XPLANE_EXPECTED["n_events"]


def test_the_wire_reader_finds_the_path_in_the_metadatas_stats():
    device = next(p for p in T.load_xplane(str(XPLANE))["planes"]
                  if p["name"].startswith(T.DEVICE_PLANE))
    ops = next(l for l in device["lines"] if l["name"] == T.OPS_LINE)
    paths = {e[0]: e[3] for e in ops["events"]}
    for op, path in XPLANE_EXPECTED["paths"].items():
        assert paths[op] == path
    assert device["op_costs"]["multiply_reduce_fusion.15 f32[32,32768]"] == \
        XPLANE_EXPECTED["multiply_reduce_fusion.15"]


def test_recorded_xplane_by_scope():
    r = T.reduce(T.load_xplane(str(XPLANE)))
    assert r.window_from == T.FROM_MARKS and r.n_devices == 1
    for name, seconds in XPLANE_EXPECTED["scope_s"].items():
        found = r.scope_s(name)
        assert found == (None if seconds is None else
                         pytest.approx(seconds, rel=1e-9)), name
    assert r.scopes[T.NO_SCOPE]["s"] == pytest.approx(
        XPLANE_EXPECTED["no_scope_s"], rel=1e-9)
    # gather and loss_grad lie inside local_train, beside what is in neither
    assert r.scope_s("gather") + r.scope_s("loss_grad") < \
        r.scope_s("local_train")
    # the rows once more, by a sum that shares no code with the reduction:
    # no leaf operation of this trace encloses another
    device = next(p for p in T.load_xplane(str(XPLANE))["planes"]
                  if p["name"].startswith(T.DEVICE_PLANE))
    ops = next(l for l in device["lines"] if l["name"] == T.OPS_LINE)
    leaves = sum(e[2] for e in ops["events"] if not e[0].startswith("while"))
    assert sum(row["s"] for row in r.scopes.values()) == pytest.approx(
        leaves / 1e9, rel=1e-9)
