"""From the profiler's trace to numbers.

``load`` turns an ``.xplane.pb`` into plain lists (it needs JAX's reader, so
it runs in the process that took the trace); everything after it is
arithmetic on those lists and is tested on a recorded trace
(``bench/testdata``). Times are seconds from the start of the trace.

A TPU's plane is ``/device:TPU:<n>``. Its line ``XLA Modules`` has one event
for each run of a compiled program, ``XLA Ops`` one for each operation
inside, nested where an operation (a loop, a fusion's caller) contains
others. Busy time is the union of the operations' intervals, so nesting
counts once; an operation's own time is its interval less its children's.
"""

from __future__ import annotations

import bisect
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS, MODULES = "XLA Ops", "XLA Modules"

Event = tuple  # (name, start_s, end_s)


def load(path: str) -> dict:
    """{plane: {line: [(name, start_s, end_s), ...]}} of the device planes
    and of the host's lines that carry dispatch spans."""
    from jax.profiler import ProfileData

    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE):
            continue
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            events = [
                (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                for e in line.events
            ]
            if events:
                lines.setdefault(line.name, []).extend(events)
    return out


def inventory(planes: dict, names: int = 12) -> dict:
    """What a trace holds, for a person to look at before trusting a pattern."""
    inv = {}
    for plane, lines in planes.items():
        for line, events in lines.items():
            count: dict = {}
            for name, a, b in events:
                c = count.setdefault(name, [0, 0.0])
                c[0] += 1
                c[1] += b - a
            top = sorted(count.items(), key=lambda kv: -kv[1][1])[:names]
            inv[f"{plane} | {line}"] = {
                "events": len(events), "names": len(count),
                "top": [[n, c[0], c[1]] for n, c in top],
            }
    return inv


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def gaps(intervals: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle intervals inside [lo, hi], longest first."""
    out, end = [], lo
    for a, b in sorted(intervals):
        if a > end:
            out.append((end, min(a, hi)))
        end = max(end, b)
    if hi > end:
        out.append((end, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def own_events(events: list[Event]) -> list[tuple]:
    """(name, start_s, end_s, own_s) of each event: its interval less its
    children's."""
    out: list[tuple] = []
    stack: list[list] = []  # [name, start, end, own]

    def close() -> None:
        out.append(tuple(stack.pop()))

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            close()
        if stack:
            stack[-1][3] -= min(b, stack[-1][2]) - a
        stack.append([name, a, b, b - a])
    while stack:
        close()
    return out


def self_times(events: list[Event]) -> dict[str, float]:
    """Own seconds by name."""
    total: dict[str, float] = {}
    for name, _, _, own in own_events(events):
        total[name] = total.get(name, 0.0) + own
    return total


def device_planes(planes: dict) -> list[str]:
    return sorted(p for p in planes if DEVICE_PLANE.match(p))


def window(planes: dict) -> tuple[float, float]:
    """First start to last end over everything the trace holds."""
    spans = [(a, b) for lines in planes.values() for ev in lines.values() for _, a, b in ev]
    if not spans:
        return (0.0, 0.0)
    return (min(a for a, _ in spans), max(b for _, b in spans))


def _whole_runs(lines: dict, module: str | None) -> tuple[list[Event], list[Event]]:
    """The first chip's operations by start, and the runs of the programs
    whose module's name matches ``module`` (every program's without it). A
    run that touches the first or the last operation of the trace was cut by
    the window's edge and is left out."""
    ops = sorted(lines.get(OPS, ()), key=lambda e: e[1])
    edge_lo, edge_hi = (ops[0][1], max(e[2] for e in ops)) if ops else (0.0, 0.0)
    wanted = re.compile(module) if module else None
    runs = [
        (name, a, b) for name, a, b in lines.get(MODULES, ())
        if (wanted is None or wanted.search(name)) and a > edge_lo and b < edge_hi
    ]
    return ops, runs


def programs(planes: dict, pattern: dict) -> list[float]:
    """Device seconds of each run of the programs a pattern picks on the
    first chip: ``module`` is a regex on the module's name; ``has_op`` and
    ``lacks_op`` are regexes of which some operation inside its interval
    must, or none may, match (two programs jitted from functions of one
    name differ only by what is inside). Cut runs are left out."""
    chips = device_planes(planes)
    if not chips:
        return []
    ops, runs = _whole_runs(planes[chips[0]], pattern["module"])
    starts = [e[1] for e in ops]
    has = re.compile(pattern["has_op"]) if pattern.get("has_op") else None
    lacks = re.compile(pattern["lacks_op"]) if pattern.get("lacks_op") else None
    out = []
    for _, a, b in runs:
        inside = ops[bisect.bisect_left(starts, a):bisect.bisect_left(starts, b)]
        if has and not any(has.search(n) for n, _, _ in inside):
            continue
        if lacks and any(lacks.search(n) for n, _, _ in inside):
            continue
        out.append(b - a)
    return out


def op_times(planes: dict, pattern: dict) -> dict:
    """One named kernel's device time: ``{"seconds", "count"}`` of the
    operations on the first chip whose name matches the regex ``op``, own
    time (an operation's interval less its children's), inside the whole
    runs of the programs ``module`` picks (of every program without it).
    Operations of a run the window's edge cut are left out, as ``programs``
    leaves the run out, so seconds over count is the time of a call."""
    chips = device_planes(planes)
    if not chips:
        return {"seconds": 0.0, "count": 0}
    ops, runs = _whole_runs(planes[chips[0]], pattern.get("module"))
    runs.sort(key=lambda r: r[1])
    starts = [a for _, a, _ in runs]
    wanted = re.compile(pattern["op"])
    seconds, count = 0.0, 0
    for name, a, b, own in own_events(ops):
        k = bisect.bisect_right(starts, a) - 1
        if k >= 0 and b <= runs[k][2] and wanted.search(name):
            seconds += own
            count += 1
    return {"seconds": seconds, "count": count}


def host_spans(planes: dict) -> list[Event]:
    return sorted(
        (e for ev in planes.get(HOST_PLANE, {}).values() for e in ev),
        key=lambda e: e[1],
    )


def summary(planes: dict) -> dict:
    """Busy seconds per chip and the window's, the ten operations with most
    own time, and the five longest idle gaps of the first chip with the
    host's spans around them."""
    lo, hi = window(planes)
    chips = device_planes(planes)
    per_chip = [
        union_seconds([(a, b) for _, a, b in planes[chip].get(OPS, [])]) for chip in chips
    ]
    out = {
        "window_s": hi - lo, "chips": len(chips), "busy_s_per_chip": per_chip,
        "device_ops": [], "idle_gaps": [],
    }
    if not chips:
        return out
    first = planes[chips[0]]
    own = self_times(first.get(OPS, []))
    out["device_ops"] = [
        [n, s] for n, s in sorted(own.items(), key=lambda kv: -kv[1])[:10]
    ]
    host = host_spans(planes)
    for a, b in gaps([(x, y) for _, x, y in first.get(OPS, [])], lo, hi)[:5]:
        during = [n for n, x, y in host if x < b and y > a]
        before = [n for n, x, y in host if y <= a][-1:]
        after = [n for n, x, y in host if x >= b][:1]
        what = " ".join(dict.fromkeys(during)) if during else (
            f"between {(before or ['start'])[0]} and {(after or ['end'])[0]}"
        )
        out["idle_gaps"].append([what[:160], b - a])
    return out
