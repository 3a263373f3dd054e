"""From a profiler trace to numbers: device busy and idle time, the operations
that took most of it, collective time and its exposed part, and what the host
was doing in the longest idle gaps.

The reduction works on a plain form of the trace, so that it can be checked on
a small recorded one (tests/benchmark/data): ``{"planes": [{"name": ...,
"lines": [{"name": ..., "events": [[name, start_ns, duration_ns, path],
...]}]}]}``. ``path`` is the scope path of a device operation
(``jit(_round)/local_train/vmap(transpose(jvp(mlp)))/dot_general``: the
``jax.named_scope`` names the program opened around it); an event of three
elements, or with an empty path, has none. ``load_xplane`` brings the
profiler's ``.xplane.pb`` into that form by reading the file's wire format
itself: the path lies in the stats of an event's *metadata* (``tf_op``),
which ``jax.profiler.ProfileData`` does not show.

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
import struct
from typing import Any, Callable, Iterable, Iterator

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
NO_SCOPE = "no scope"   # the row of the operations that carry no path
PATH_STAT, COST_STATS = "tf_op", ("flops", "bytes_accessed")

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


# ------------------------------------------------- the .xplane.pb, as wire
# XSpace{planes=1}; XPlane{name=2, lines=3, event_metadata=4 (map: key=1,
# value=2), stat_metadata=5 (map)}; XLine{name=2, timestamp_ns=3, events=4};
# XEvent{metadata_id=1, offset_ps=2, duration_ps=3}; XEventMetadata{id=1,
# name=2, stats=5}; XStatMetadata{id=1, name=2}; XStat{metadata_id=1,
# double=2, uint64=3, int64=4, str=5, bytes=6, ref=7 (a stat metadata's
# name)}. Fields not listed are skipped by their length.
def _varint(buf: memoryview, i: int) -> tuple[int, int]:
    x = buf[i]
    i += 1
    if x < 0x80:
        return x, i
    x &= 0x7F
    shift = 7
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf: memoryview) -> Iterator[tuple[int, Any]]:
    """(field number, value) of one message: an int for a varint, the bytes
    for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} is not one an .xplane.pb has")
        yield tag >> 3, value


def _text(value: memoryview) -> str:
    return bytes(value).decode("utf-8", "replace")


def _map_entries(plane: memoryview, field: int) -> Iterator[memoryview]:
    for number, entry in _fields(plane):
        if number == field:
            for k, value in _fields(entry):
                if k == 2:
                    yield value


def _stat(stat: memoryview, stat_names: dict[int, str]) -> tuple[str, Any]:
    name, value = "", None
    for number, v in _fields(stat):
        if number == 1:
            name = stat_names.get(v, "")
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number == 3:
            value = v
        elif number == 4:  # int64: a negative one is written as its 2**64's
            value = v - (1 << 64) if v >> 63 else v
        elif number in (5, 6):
            value = _text(v)
        elif number == 7:
            value = stat_names.get(v, "")
    return name, value


def _event_metadata(plane: memoryview) -> dict[int, tuple[str, dict[str, Any]]]:
    """id -> (name, the metadata's own stats by name)."""
    stat_names = {}
    for meta in _map_entries(plane, 5):
        found = dict(_fields(meta))
        stat_names[found.get(1, 0)] = _text(found.get(2, b""))
    out = {}
    for meta in _map_entries(plane, 4):
        ident, name, stats = 0, "", {}
        for number, v in _fields(meta):
            if number == 1:
                ident = v
            elif number == 2:
                name = _text(v)
            elif number == 5:
                key, value = _stat(v, stat_names)
                stats[key] = value
        out[ident] = (name, stats)
    return out


def load_xplane(path: str) -> dict[str, Any]:
    """The plain form of an ``.xplane.pb``. Names, starts and durations are
    those ``jax.profiler.ProfileData`` gives (nanoseconds, cut to whole
    ones); a device plane's events also get their path, and the plane an
    ``"op_costs"`` table, operation name -> the compiler's own ``flops`` and
    ``bytes_accessed`` of one execution, where the metadata holds them."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for number, plane in _fields(space):
        if number != 1:
            continue
        plane_name = next(
            (_text(v) for k, v in _fields(plane) if k == 2), "")
        on_device = plane_name.startswith(DEVICE_PLANE)
        metadata = _event_metadata(plane)
        named = {ident: (op_name(name) if on_device else name,
                         str(stats.get(PATH_STAT) or "").rstrip(":"))
                 for ident, (name, stats) in metadata.items()}
        lines = []
        for k, line in _fields(plane):
            if k != 3:
                continue
            line_name, line_start, events = "", 0, []
            for f, v in _fields(line):
                if f == 2:
                    line_name = _text(v)
                elif f == 3:
                    line_start = v
                elif f == 4:
                    found = dict(_fields(v))
                    name, scope = named.get(found.get(1, 0), ("", ""))
                    start = int(line_start + found.get(2, 0) / 1000.0)
                    event = [name, start, int(found.get(3, 0) / 1000.0)]
                    events.append(event + [scope] if on_device else event)
            lines.append({"name": line_name, "events": events})
        out = {"name": plane_name, "lines": lines}
        if on_device:
            out["op_costs"] = {
                op_name(name): {c: stats[c] for c in COST_STATS if c in stats}
                for name, stats in metadata.values()
                if any(c in stats for c in COST_STATS)}
        planes.append(out)
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


def self_times(
    events: list[list[Any]],
    key: Callable[[list[Any]], Any] = lambda event: event[0],
) -> dict[Any, int]:
    """Time of each operation name (or of each ``key(event)``), less what
    the operations it encloses took: a ``while`` is charged only what its
    body's operations leave."""
    out: dict[Any, int] = {}
    stack: list[list[Any]] = []  # [key, end, children_ns, duration]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, inner, dur = stack.pop()
            out[name] = out.get(name, 0) + max(dur - inner, 0)

    for event in sorted(events, key=lambda e: (e[1], -e[2])):
        start, dur = event[1], event[2]
        close(start)
        if stack:
            stack[-1][2] += dur
        stack.append([key(event), start + dur, 0, dur])
    close(1 << 62)
    return out


def scope_of(event: list[Any]) -> str | None:
    """The row of the by-scope table an operation's own time belongs to: its
    path, `NO_SCOPE` where it carries none, and no row for an operation that
    only encloses others."""
    if not _is_leaf(event[0]):
        return None
    return (event[3] if len(event) > 3 else "") or NO_SCOPE


def collective_intervals(events: list[list[Any]]) -> list[Interval]:
    """Intervals in which a collective is under way: a synchronous collective
    for its own duration, an asynchronous one from the begin of its
    ``-start`` to the end of its ``-done``."""
    out: list[Interval] = []
    open_starts: dict[str, list[int]] = {}
    for name, start, dur, *_ in sorted(events, key=lambda e: e[1]):
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
    # the whole by-scope table: path (or `NO_SCOPE`) -> the own time, inside
    # the window, of the leaf operations that carry it, and the compiler's
    # counts for those of them that began there; each the mean over the devices
    scopes: dict[str, dict[str, float]] = dataclasses.field(
        default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def scope_s(self, name: str) -> float | None:
        """Device seconds of every operation whose path has ``name`` as a
        component, on the way forward (``/name/``) and back
        (``transpose(jvp(name))``) alike; a scope includes the scopes opened
        inside it. A path's last component is the primitive, never a scope.
        Nothing where no operation lies under ``name``."""
        inside = re.compile("[/(]" + re.escape(name) + "[/)]")
        found = [row["s"] for path, row in self.scopes.items()
                 if inside.search(path)]
        return sum(found) if found else None


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
    scopes: dict[str, dict[str, float]] = {}
    gaps: list[Interval] = []
    n = len(devices)
    costs = {plane["name"]: plane.get("op_costs", {})
             for plane in trace["planes"]}
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
        held = [e for e in events if e[1] < hi and e[1] + e[2] > lo]
        clipped = [[e[0], max(e[1], lo),
                    min(e[1] + e[2], hi) - max(e[1], lo), *e[3:]] for e in held]
        for path, ns in self_times(clipped, key=scope_of).items():
            if path is not None:
                row = scopes.setdefault(path, {"s": 0.0})
                row["s"] += ns / n / 1e9
        for event in held:  # the counts of the operations that began inside
            row = scopes.get(scope_of(event))
            if row is not None and event[1] >= lo:
                for count, value in costs[plane].get(event[0], {}).items():
                    row[count] = row.get(count, 0.0) + value / n
        if not gaps:  # the idle gaps of the first device stand for all
            gaps = subtract([(lo, hi)], spans)
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
        scopes=scopes,
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
