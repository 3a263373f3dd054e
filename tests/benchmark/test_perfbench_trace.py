"""The reduction from a trace to busy/idle, collective and exposed time, the
operations' own times and what the host did in the idle gaps: on a trace
written by hand, where every number can be counted, and on a small one
recorded on the chip."""
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


def _run_with(window_from, host_round_s=0.5):
    """A `harness.Run` on four chips whose trace says 2 s for 8 rounds, and
    whose host clock says something else."""
    from perfbench import cells, harness
    from perfbench.window import Window

    cell = cells.load_cell("gpt2m.sharded-4chip")
    reduced = T.Reduced(
        n_devices=4, window_s=2.0, window_from=window_from, busy_s=1.5,
        collective_s=0.4, collective_exposed_s=0.2, device_ops=[],
        idle_gaps=[])
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
