"""The measured window: whole dispatches, one after another, on one clock.

A cell is a closed loop of one client. The window starts after warm-up, runs
whole dispatches until ``seconds`` have elapsed, and ends when the dispatch in
flight at that moment completes. Every end-to-end number is the elapsed time
of the whole window over all the rounds completed in it: no median of rounds
decides anything, so a stall anywhere in the window shows.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable


@dataclasses.dataclass
class Window:
    elapsed_s: float            # start of the window to the end of its last dispatch
    rounds: int                 # rounds completed in it (whole dispatches only)
    dispatch_s: list[float]     # wall time of each dispatch, in order
    rounds_per_dispatch: int

    @property
    def round_s(self) -> float:
        return self.elapsed_s / self.rounds

    @property
    def per_round_s(self) -> list[float]:
        return [d / self.rounds_per_dispatch for d in self.dispatch_s]


def run_window(
    dispatch: Callable[[], None],
    rounds_per_dispatch: int,
    seconds: float,
    clock: Callable[[], float] = time.perf_counter,
    after_dispatch: Callable[[int], None] | None = None,
) -> Window:
    """Run ``dispatch`` (which returns only when its results are ready) until
    ``seconds`` have elapsed on ``clock``; the dispatch in flight then is
    finished and counted. ``after_dispatch(n)`` runs between dispatches,
    outside every dispatch's own time but inside the window's."""
    if seconds <= 0:
        raise ValueError("a window needs a positive length")
    start = clock()
    durations: list[float] = []
    last = start
    while True:
        dispatch()
        now = clock()
        durations.append(now - last)
        if after_dispatch is not None:
            after_dispatch(len(durations))
            now = clock()
        last = now
        if now - start >= seconds:
            break
    return Window(
        elapsed_s=now - start,
        rounds=len(durations) * rounds_per_dispatch,
        dispatch_s=durations,
        rounds_per_dispatch=rounds_per_dispatch,
    )
