"""From a profiler trace to numbers: busy time, kernel sums, exposed
collectives, idle gaps by what the host was doing.

The reduction works on a neutral form, so that the tests can pin it on a
small recorded trace kept as JSON::

    {plane name: {line name: [[event name, start_ns, duration_ns], ...]}}

:func:`load_xplane` makes that form from the ``.xplane.pb`` the JAX
profiler writes (``jax.profiler.ProfileData``, nothing but JAX).
"""

from __future__ import annotations

import bisect
import heapq
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: Host spans that are kept: the harness's own
#: (``jax.profiler.TraceAnnotation``) and the program's
#: (``tpu_dist.utils.profiler.span``), which lie inside them.
SPAN_PREFIX = "tpubench."
PROGRAM_PREFIX = "tpu_dist."
SPAN_PREFIXES = (SPAN_PREFIX, PROGRAM_PREFIX)
WINDOW_SPAN = "tpubench.window"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute",
    re.IGNORECASE)


def load_xplane(trace_dir) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` in the neutral form.
    Only device planes and the harness's and the program's host spans
    are kept."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    out: dict = {}
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        lines: dict = {}
        for line in plane.lines:
            if device:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events]
            else:
                events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIXES)]
            if events:
                lines.setdefault(line.name, []).extend(events)
        if lines:
            out[plane.name] = lines
    return out


def device_planes(trace: dict) -> list[str]:
    return sorted((p for p in trace if DEVICE_PLANE.match(p)),
                  key=lambda p: int(DEVICE_PLANE.match(p).group(1)))


def host_spans(trace: dict) -> list[list]:
    """Every harness or program span on any host line:
    [name, start_ns, dur_ns]."""
    spans = []
    for plane, lines in trace.items():
        if DEVICE_PLANE.match(plane):
            continue
        for events in lines.values():
            spans.extend(e for e in events
                         if e[0].startswith(SPAN_PREFIXES))
    return sorted(spans, key=lambda e: e[1])


def window_ns(trace: dict) -> tuple[int, int]:
    """[start, end) of the traced window: the harness's window span, or
    failing that the extent of the device events."""
    for name, start, dur in host_spans(trace):
        if name == WINDOW_SPAN:
            return start, start + dur
    starts, ends = [], []
    for plane in device_planes(trace):
        for _, s, d in trace[plane].get(OPS_LINE, []):
            starts.append(s)
            ends.append(s + d)
    if not starts:
        raise ValueError("trace holds no window span and no device event")
    return min(starts), max(ends)


def _clip(events, t0, t1):
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted [a, b) intervals."""
    merged: list[list[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _length(intervals) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a_iv, b_iv) -> list[tuple[int, int]]:
    """The part of merged intervals ``a_iv`` not covered by ``b_iv``."""
    out, j = [], 0
    for a, b in a_iv:
        cur = a
        while j < len(b_iv) and b_iv[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_iv) and b_iv[k][0] < b:
            if b_iv[k][0] > cur:
                out.append((cur, b_iv[k][0]))
            cur = max(cur, b_iv[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def ops_in_window(trace: dict, plane: str, line: str = OPS_LINE):
    t0, t1 = window_ns(trace)
    return list(_clip(trace[plane].get(line, []), t0, t1))


def busy_seconds(trace: dict) -> float:
    """Seconds in which an operation ran on the device, averaged over the
    device planes, inside the traced window."""
    planes = device_planes(trace)
    if not planes:
        raise ValueError("trace holds no device plane")
    total = 0
    for plane in planes:
        total += _length(union(
            (a, b) for _, a, b in ops_in_window(trace, plane)))
    return total / len(planes) / 1e9


def window_seconds(trace: dict) -> float:
    t0, t1 = window_ns(trace)
    return (t1 - t0) / 1e9


def matching_seconds(trace: dict, patterns, line: str = OPS_LINE,
                     containing=()):
    """(seconds, count) of the events on ``line`` whose name matches any
    of ``patterns``, summed over devices and divided by their number.
    With ``containing``, an event counts only if an operation matching
    one of those patterns started inside it: a program that carries no
    name of its own (``jit__unknown``) is told by what it runs."""
    regs = [re.compile(p) for p in patterns]
    inner = [re.compile(p) for p in containing]
    planes = device_planes(trace)
    total, count = 0, 0
    for plane in planes:
        marks = sorted(a for name, a, _ in ops_in_window(trace, plane)
                       if any(r.search(name) for r in inner))
        for name, a, b in ops_in_window(trace, plane, line):
            if not any(r.search(name) for r in regs):
                continue
            if inner:
                i = bisect.bisect_left(marks, a)
                if i == len(marks) or marks[i] >= b:
                    continue
            total += b - a
            count += 1
    n = max(len(planes), 1)
    return total / n / 1e9, count / n


def exposed_collective_seconds(trace: dict) -> float:
    """Collective time during which no other operation ran on that
    device, averaged over the devices."""
    planes = device_planes(trace)
    total = 0
    for plane in planes:
        coll, compute = [], []
        for name, a, b in ops_in_window(trace, plane):
            (coll if COLLECTIVE.search(name) else compute).append((a, b))
        total += _length(subtract(union(coll), union(compute)))
    return total / max(len(planes), 1) / 1e9


def op_family(name: str) -> str:
    """An event of the ops line is named by its whole HLO instruction
    (``%fusion.12 = f32[...] fusion(...)``): keep the instruction's name
    and fold away the digits that only number an instance."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.:_-]?\d+$", "", head)


def top_device_ops(trace: dict, n: int = 10) -> list[list]:
    """[name, seconds] of the device operations that took most time on
    the first device, by :func:`op_family`."""
    planes = device_planes(trace)
    if not planes:
        return []
    sums: dict = {}
    for name, a, b in ops_in_window(trace, planes[0]):
        key = op_family(name)
        sums[key] = sums.get(key, 0) + (b - a)
    top = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_gaps_by_span(trace: dict, n: int = 10) -> list[list]:
    """[host span name, idle seconds]: the first device's idle time inside
    the window, the largest first. A gap runs from the end of one program,
    across the host's phases, into the next dispatch, so every part of it
    is charged to the INNERMOST span open at that instant: the shortest
    of the harness's and the program's spans that cover it
    (``unattributed`` where none does)."""
    planes = device_planes(trace)
    if not planes:
        return []
    t0, t1 = window_ns(trace)
    busy = union((a, b) for _, a, b in ops_in_window(trace, planes[0]))
    gaps = subtract([(t0, t1)], busy)
    spans = sorted((max(s, t0), min(s + d, t1), name)
                   for name, s, d in host_spans(trace)
                   if name != WINDOW_SPAN and s < t1 and s + d > t0)
    sums: dict = {}
    open_: list = []            # heap of (length, end, name): shortest first
    k = 0
    for cur, b in gaps:
        while cur < b:
            while k < len(spans) and spans[k][0] <= cur:
                s, e, name = spans[k]
                heapq.heappush(open_, (e - s, e, name))
                k += 1
            while open_ and open_[0][1] <= cur:
                heapq.heappop(open_)
            nxt = b
            if k < len(spans):
                nxt = min(nxt, spans[k][0])
            name = "unattributed"
            if open_:
                name, nxt = open_[0][2], min(nxt, open_[0][1])
            sums[name] = sums.get(name, 0) + (nxt - cur)
            cur = nxt
    top = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def describe(trace: dict, n: int = 40) -> dict:
    """What a person looks at once: planes, lines, and the heaviest names
    on each device line."""
    out = {}
    for plane, lines in trace.items():
        out[plane] = {}
        for line, events in lines.items():
            sums: dict = {}
            for name, _, d in events:
                sums[name] = sums.get(name, 0) + d
            out[plane][line] = {
                "events": len(events),
                "top": sorted(((k, v / 1e9) for k, v in sums.items()),
                              key=lambda kv: -kv[1])[:n]}
    return out
