"""The window arithmetic, on a clock the test holds."""
from __future__ import annotations

import pytest

from perfbench.window import run_window


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def _dispatch(clock, costs):
    it = iter(costs)

    def dispatch():
        clock.now += next(it)
    return dispatch


def test_round_in_flight_at_the_end_is_finished_and_counted():
    clock = FakeClock()
    w = run_window(_dispatch(clock, [0.4] * 10), 1, 1.0, clock=clock)
    # 0.4, 0.8 are inside; the third dispatch is in flight at 1.0 s
    assert w.rounds == 3
    assert w.elapsed_s == pytest.approx(1.2)
    assert w.round_s == pytest.approx(0.4)


def test_a_stall_in_the_window_moves_the_mean_of_all_rounds():
    steady, stalled = FakeClock(), FakeClock()
    a = run_window(_dispatch(steady, [0.1] * 50), 5, 2.0, clock=steady)
    costs = [0.1] * 50
    costs[7] = 0.6  # one dispatch stalls by half a second
    b = run_window(_dispatch(stalled, costs), 5, 2.0, clock=stalled)
    assert a.round_s == pytest.approx(0.02)
    assert b.rounds == 80 and b.elapsed_s == pytest.approx(2.1)
    assert b.round_s > 1.25 * a.round_s
    # a median of rounds would have hidden it
    assert sorted(b.per_round_s)[len(b.per_round_s) // 2] == pytest.approx(0.02)


def test_rounds_per_dispatch_and_time_between_dispatches_count():
    clock = FakeClock()

    def after(n):
        clock.now += 0.05  # host work between dispatches is window time

    w = run_window(_dispatch(clock, [0.2] * 20), 5, 1.0, clock=clock,
                   after_dispatch=after)
    assert w.rounds == 20 and w.elapsed_s == pytest.approx(1.0)
    assert w.dispatch_s == pytest.approx([0.2] * 4)
    assert w.round_s == pytest.approx(0.05)


def test_a_window_needs_a_length():
    with pytest.raises(ValueError):
        run_window(lambda: None, 1, 0.0)
