"""From a profiler trace to numbers: device busy and idle time, the operations
that took most of it, collective time and its exposed part, and what the host
was doing in the longest idle gaps.

The reduction works on a plain form of the trace, so that it can be checked on
a small recorded one (tests/benchmark/data): ``{"planes": [{"name": ...,
"lines": [{"name": ..., "events": [[name, start_ns, duration_ns], ...]}]}]}``.
``load_xplane`` brings the profiler's ``.xplane.pb`` into that form with
nothing but jax.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed operation, control-flow operations (``while``,
``conditional``, ``call``) enclosing the events of their bodies, and their
``Async XLA Ops`` line one event per asynchronous operation from its start to
its done. Host planes hold one line per thread; host and device events are on
one clock (seen on the v5e, PR 25: the host's mark around the traced
dispatches encloses the device's operations to within 4 ms in 8.6 s).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Any, Iterable

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"   # one event per asynchronous operation, whole
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
               "collective-permute", "all-to-all")
# what the host was doing, by the names jax and the harness give their events;
# the first kind that matches a name is that event's kind
HOST_KINDS = (
    ("compile", ("compile", "Compile", "lower", "Lower", "jaxpr_trace")),
    ("waiting for a result", ("block_until_ready", "BlockHostUntilReady",
                              "Await", "device_get", "ToLiteral")),
    ("dispatch", ("Execute", "PjitFunction", "pjit", "Pjit", "dispatch")),
    ("between dispatches", ("perfbench.between",)),
)
WINDOW_EVENT = "perfbench.traced"
FROM_MARKS, FROM_OPS = "host annotation", "device ops"  # Reduced.window_from

Interval = tuple[int, int]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


_HLO = re.compile(r"^%?(?P<op>[^ ]+) = \(?(?P<shape>\w+\[[^\]]*\])?")


def op_name(name: str) -> str:
    """The profiler names a device operation by its whole HLO line
    (``%fusion.1 = f32[8,128]{1,0:T(8,128)} fusion(...)``); kept are the
    operation and the shape of its (first) result: ``fusion.1 f32[8,128]``."""
    m = _HLO.match(name)
    if m is None:
        return name
    return m["op"] + (" " + m["shape"] if m["shape"] else "")


def load_xplane(path: str) -> dict[str, Any]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        on_device = plane.name.startswith(DEVICE_PLANE)
        lines = []
        for line in plane.lines:
            events = [[op_name(e.name) if on_device else e.name,
                       int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# --------------------------------------------------------------- intervals
def union(intervals: Iterable[Interval]) -> list[Interval]:
    out: list[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> list[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """The part of the union ``a`` that the union ``b`` does not cover."""
    out: list[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


def host_kind(name: str) -> str | None:
    for kind, marks in HOST_KINDS:
        if any(m in name for m in marks):
            return kind
    return None


def self_times(events: list[list[Any]]) -> dict[str, int]:
    """Time of each operation name, less what the operations it encloses
    took: a ``while`` is charged only what its body's operations leave."""
    out: dict[str, int] = {}
    stack: list[list[Any]] = []  # [name, end, children_ns, duration]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, inner, dur = stack.pop()
            out[name] = out.get(name, 0) + max(dur - inner, 0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] += dur
        stack.append([name, start + dur, 0, dur])
    close(1 << 62)
    return out


def collective_intervals(events: list[list[Any]]) -> list[Interval]:
    """Intervals in which a collective is under way: a synchronous collective
    for its own duration, an asynchronous one from the begin of its
    ``-start`` to the end of its ``-done``."""
    out: list[Interval] = []
    open_starts: dict[str, list[int]] = {}
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        if not is_collective(name):
            continue
        op = name.split(" ", 1)[0]
        if "-start" in op:
            open_starts.setdefault(op.replace("-start", ""), []).append(start)
        elif "-done" in op:
            begun = open_starts.get(op.replace("-done", ""))
            out.append((begun.pop(0) if begun else start, start + dur))
        else:
            out.append((start, start + dur))
    return out


# ------------------------------------------------------------------ reduce
@dataclasses.dataclass
class Reduced:
    n_devices: int
    window_s: float
    window_from: str                       # FROM_MARKS or FROM_OPS
    busy_s: float                          # mean over the devices
    collective_s: float                    # the slowest device
    collective_exposed_s: float            # the slowest device
    device_ops: list[list[Any]]            # [[name, seconds], ...] top 10
    idle_gaps: list[list[Any]]             # [[kind, seconds], ...] top 10

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _device_lines(trace: dict[str, Any], which: str) -> dict[str, list]:
    out = {}
    for plane in trace["planes"]:
        if not plane["name"].startswith(DEVICE_PLANE):
            continue
        for line in plane["lines"]:
            if line["name"] == which and line["events"]:
                out[plane["name"]] = line["events"]
    return out


def _host_events(trace: dict[str, Any]) -> list[list[Any]]:
    return [e for plane in trace["planes"]
            if not plane["name"].startswith("/device:")
            for line in plane["lines"] for e in line["events"]]


def reduce(trace: dict[str, Any]) -> Reduced:
    devices = _device_lines(trace, OPS_LINE)
    asynchronous = _device_lines(trace, ASYNC_LINE)
    if not devices:
        raise ValueError("the trace holds no operation that ran on a device")
    host = _host_events(trace)
    lo = min(e[1] for ev in devices.values() for e in ev)
    hi = max(e[1] + e[2] for ev in devices.values() for e in ev)
    window_from = FROM_OPS
    marks = [e for e in host if e[0] == WINDOW_EVENT]
    if marks:
        a = min(e[1] for e in marks)
        b = max(e[1] + e[2] for e in marks)
        inside = sum(total(clip([(e[1], e[1] + e[2]) for e in ev], a, b))
                     for ev in devices.values())
        whole = sum(e[2] for ev in devices.values() for e in ev)
        # the host's mark bounds the window only if the two clocks agree
        if inside >= 0.9 * whole:
            lo, hi, window_from = a, b, FROM_MARKS
    busy, coll, exposed = [], [], []
    ops: dict[str, int] = {}
    gaps: list[Interval] = []
    for plane, events in devices.items():
        spans = union(clip([(e[1], e[1] + e[2]) for e in events], lo, hi))
        busy.append(total(spans))
        in_flight = [(e[1], e[1] + e[2]) for e in asynchronous.get(plane, [])
                     if is_collective(e[0])]
        during = union(clip(collective_intervals(events) + in_flight, lo, hi))
        compute = union(clip(
            [(e[1], e[1] + e[2]) for e in events
             if not is_collective(e[0]) and _is_leaf(e[0])], lo, hi))
        coll.append(total(during))
        exposed.append(total(subtract(during, compute)))
        for name, ns in self_times(events).items():
            ops[name] = ops.get(name, 0) + ns
        if not gaps:  # the idle gaps of the first device stand for all
            gaps = subtract([(lo, hi)], spans)
    n = len(devices)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return Reduced(
        n_devices=n,
        window_s=(hi - lo) / 1e9,
        window_from=window_from,
        busy_s=sum(busy) / n / 1e9,
        collective_s=max(coll) / 1e9,
        collective_exposed_s=max(exposed) / 1e9,
        device_ops=[[name, ns / n / 1e9] for name, ns in top_ops],
        idle_gaps=[[_gap_kind(g, host), (g[1] - g[0]) / 1e9]
                   for g in top_gaps],
    )


def _is_leaf(name: str) -> bool:
    """Not an operation that only encloses others."""
    return not name.startswith(("while", "conditional", "call"))


def _gap_kind(gap: Interval, host: list[list[Any]]) -> str:
    """What the host was doing while the device idled: the kind of host
    event that covers most of the gap."""
    cover: dict[str, list[Interval]] = {}
    for name, start, dur in host:
        if start >= gap[1] or start + dur <= gap[0]:
            continue
        kind = host_kind(name)
        if kind is not None:
            cover.setdefault(kind, []).append(
                (max(start, gap[0]), min(start + dur, gap[1])))
    if not cover:
        return "no host event"
    covered = {k: total(union(v)) for k, v in cover.items()}
    # a dispatch encloses the compile it triggers: the more specific kind
    # wins where it covers half of the gap
    for kind, _ in HOST_KINDS:
        if covered.get(kind, 0) >= 0.5 * (gap[1] - gap[0]):
            return kind
    return max(covered, key=covered.get)
