"""One run of one cell: set-up, warm-up through the first steps, the measured
window, the memory reading, the comparison with the plain reference, and the
result line. `run.py` is the command; the tests call `run_cell` with
``require_chip=False`` on a cell cut to a tiny size."""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import statistics
import sys
import time
from typing import Any, Callable

from perfbench import cells, compare, trace as trace_mod
from perfbench.meter import CompileMeter
from perfbench.window import Window, run_window

TRACE_DIR = cells.ROOT / ".perfbench" / "trace"


class NoChip(Exception):
    """The machine does not hold the accelerator the cell asks for."""


@dataclasses.dataclass
class Run:
    """What a metric's reader may read."""
    cell: cells.Cell
    setup_s: float
    window: Window
    window_compiles: int           # backend compiles inside the window
    flops_per_round: float
    min_bytes_per_round: float | None
    peaks: dict[str, Any] | None   # None off the chip
    traced_rounds: int = 0         # rounds of the dispatches the trace holds
    trace: trace_mod.Reduced | None = None

    def traced_round_s(self) -> float | None:
        """One round's time as the trace has it: the span of the harness's
        marks around the traced dispatches, on the profiler's clock, over
        their rounds. Nothing where the trace holds no mark that encloses
        the device's operations: the extent of the operations alone leaves
        out the gaps before the first and after the last."""
        if self.trace is None or not self.traced_rounds:
            return None
        if self.trace.window_from != trace_mod.FROM_MARKS:
            return None
        return self.trace.window_s / self.traced_rounds

    def share_of_peak(self, per_round: float | None, peak: str) -> float | None:
        """``per_round`` (operations or bytes from the shapes) over the
        trace's round time and the chips' peak, in percent; nothing without
        a trace, a peak or a count."""
        round_s = self.traced_round_s()
        if round_s is None or self.peaks is None or per_round is None:
            return None
        whole = self.peaks[peak] * self.cell.chips
        return 100.0 * per_round / (round_s * whole)

    def idle_percent(self) -> float | None:
        return None if self.trace is None else 100.0 * self.trace.idle_share

    def scope_round_s(self, name: str) -> float | None:
        """Device seconds per traced round under the program's
        ``jax.named_scope(name)`` (`trace.Reduced.scope_s`), mean over the
        chips. Nothing where the trace holds no mark (its rounds are then
        not the window's), where its operations carry no path at all (an
        executable compiled without the names), or where none lies under
        ``name``."""
        if self.traced_round_s() is None:
            return None
        under = self.trace.scope_s(name)
        return None if under is None else under / self.traced_rounds

    def scope_ms(self, name: str) -> float | None:
        under = self.scope_round_s(name)
        return None if under is None else 1e3 * under

    def scope_share_of_peak(
        self, name: str, per_round: float | None, peak: str,
    ) -> float | None:
        """``per_round`` (the operations or bytes one round needs under
        ``name``, from the configuration's shapes:
        ``run.cell.reference_module()`` has the functions) over the device
        time under that scope, the chips and the peak, in percent: a
        kernel's share of its roofline."""
        under = self.scope_round_s(name)
        if under is None or self.peaks is None or per_round is None:
            return None
        return 100.0 * per_round / (under * self.peaks[peak] * self.cell.chips)


def key_from_seed(seed: int):
    """A key from any whole number up to 2**64: both halves are used."""
    import jax
    import numpy as np

    if seed < 0:
        raise ValueError("--seed is a whole number from 0")
    words = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def find_devices(chips: int, require_chip: bool) -> list:
    import jax

    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"jax found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, jax found "
                     f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache(traced: bool = False) -> None:
    """jax's persistent cache at a fixed path inside the checkout, unless
    the environment already places it. The names a program gives its
    operations (scopes, source lines) are not in the cache's key, so a cached
    executable carries the names of the tree that compiled it: a traced run,
    which reads those names, keys its programs by them too. It then loads
    only what a traced run of a tree with the same names compiled, and an
    untraced run's keys and entries stay as they are."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(cells.ROOT / ".jax_cache"))
    if traced:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)


def memory_peaks(devices: list) -> dict[str, int]:
    """The fullest chip's peak of the bytes in use, and of the bytes
    reserved, which counts a running program's temporaries too."""
    stats = [d.memory_stats() or {} for d in devices]
    return {
        "memory_peak_bytes": int(max(
            s.get("peak_bytes_in_use", 0) for s in stats)),
        "memory_reserved_bytes": int(max(
            s.get("peak_bytes_reserved", 0) for s in stats)),
    }


def run_cell(
    cell: cells.Cell, seed: int, seconds: float, trace: bool,
    require_chip: bool = True, started: float | None = None,
    clock: Callable[[], float] = time.perf_counter,
) -> dict[str, Any]:
    """Run the cell once; returns the result line as a dict."""
    started = clock() if started is None else started
    import jax

    devices = find_devices(cell.chips, require_chip)
    if require_chip:  # the tests, on the CPU, keep no cache
        enable_compile_cache(traced=trace)
    meter = CompileMeter()
    config, traffic = cell.config, cell.traffic
    reference_module = cell.reference_module()
    key = key_from_seed(seed)

    def make_inputs():
        return reference_module.make_inputs(config, traffic, key)

    found_s = clock() - started
    program = cell.entry_module().build(config, traffic, make_inputs, devices)
    built_s = clock() - started
    follow = traffic["follow_dispatches"]
    observed = program.first_steps(follow)
    setup_s = clock() - started

    # ------------------------------------------------------------- window
    n_traced = traffic["trace_dispatches"]
    traced_marks: list[int] = []  # the dispatches after which the trace ended

    def after_dispatch(n: int) -> None:
        if trace and n == n_traced:
            traced_marks.append(n)
            jax.profiler.stop_trace()

    dispatch = program.dispatch
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        plain = program.dispatch

        def dispatch() -> None:
            if traced_marks:
                return plain()
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_EVENT):
                plain()

    compiles0, from_cache = meter.compiles, meter.cache_hits
    window = run_window(dispatch, program.rounds_per_dispatch, seconds,
                        clock=clock, after_dispatch=after_dispatch)
    compiles1 = meter.compiles
    if trace and not traced_marks:  # the window closed before the nth
        traced_marks.append(len(window.dispatch_s))
        jax.profiler.stop_trace()
    memory = memory_peaks(devices)
    program.drop_state()
    del program
    gc.collect()

    # ---------------------------------------------------------- reference
    n_steps = follow * window.rounds_per_dispatch
    reference_started = clock()
    reference = getattr(reference_module, traffic["reference"])(
        config, traffic, make_inputs(), n_steps)
    correct, rows = compare.judge(
        compare.numbers(observed, reference), cell.limits)
    reference_s = clock() - reference_started
    meter.close()

    # ------------------------------------------------------------ results
    first = devices[0]
    run = Run(
        cell=cell, setup_s=setup_s, window=window,
        window_compiles=compiles1 - compiles0,
        flops_per_round=reference_module.flops_per_round(config, traffic),
        min_bytes_per_round=reference_module.min_bytes_per_round(
            config, traffic),
        peaks=cells.peaks_of(first.device_kind)
        if first.platform == "tpu" else None,
    )
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(devices), **memory}
    result: dict[str, Any] = {
        "correct": correct,
        "attempted": window.rounds + n_steps,
        "failed": 0,
    }
    if trace:
        run.traced_rounds = traced_marks[0] * window.rounds_per_dispatch
        run.trace = trace_mod.reduce(
            trace_mod.load_xplane(trace_mod.find_xplane(str(TRACE_DIR))))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["metrics"] = cells.read_metrics(cell.per_layer, run)
        result["device"] = device
        result["breakdown"] = {"device_ops": run.trace.device_ops,
                               "idle_gaps": run.trace.idle_gaps}
        # the by-scope table's size, its sum (every leaf operation lies in
        # one row, so it comes to busy_s) and its largest rows
        by_scope = sorted(((path, row["s"]) for path, row in
                           run.trace.scopes.items()), key=lambda r: -r[1])
        result["scopes"] = {"rows": len(by_scope),
                            "sum_s": sum(s for _, s in by_scope),
                            "top": [list(r) for r in by_scope[:10]]}
    else:
        result["metrics"] = cells.read_metrics(cell.end_to_end, run)
        result["device"] = device
    result["rounds"] = window.rounds
    result["reference_s"] = reference_s  # after the window; not set-up
    # set-up by its parts: to jax and the chip, the engine built and the
    # state placed, the first steps (compile or cache load, and the steps);
    # the programs it built or loaded and how many of them the cache held
    result["setup"] = {"to_the_chip_s": found_s,
                       "build_s": built_s - found_s,
                       "first_steps_s": setup_s - built_s,
                       "programs": compiles0, "from_cache": from_cache}
    # where a window's mean departs from its median, the dispatch that
    # stalled: its time and its place in the window
    slowest = max(range(len(window.dispatch_s)), key=window.dispatch_s.__getitem__)
    result["dispatches"] = {
        "n": len(window.dispatch_s),
        "median_s": statistics.median(window.dispatch_s),
        "slowest_s": window.dispatch_s[slowest],
        "slowest_at": slowest,
        "compiles_in_window": run.window_compiles,
        "each_s": [round(d, 5) for d in window.dispatch_s],
    }
    result["checks"] = rows
    return result


def print_result(result: dict[str, Any]) -> None:
    """Each number compared beside its limit as the last lines of standard
    error; the result as the last line of standard output."""
    sys.stdout.flush()
    for name, row in result["checks"].items():
        print(f"check {name}: value {row['value']} limit {row['limit']}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
