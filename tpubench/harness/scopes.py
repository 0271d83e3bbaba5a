"""Device time by the names the program puts on its work: a program by its
module name (``jit_serve_<kind>``), an operation by the layer scope in its
name stack (``tpu_dist.<scope>``).

A TPU trace keeps an operation's name stack in the ``tf_op`` stat of the
event's METADATA (``jit(serve_paged_decode_ragged)/tpu_dist.moe/
dot_general:``), which ``jax.profiler.ProfileData`` does not expose:
:func:`load_op_scopes` reads it from the ``.xplane.pb`` itself
(``xplane.py``) and gives one more line of the neutral form of
``trace.py`` a device plane::

    {plane: {"XLA Op Scopes": [[scope, start_ns, duration_ns], ...]}}

holding each ``XLA Ops`` event whose name stack holds a scope. The two
readers below take the same ``(ctx, spec)`` as those of ``readers.py``
and return ``None`` where they find nothing to read.
"""

from __future__ import annotations

import bisect
import pathlib
import re

from tpubench.harness import readers
from tpubench.harness import trace as trace_lib
from tpubench.harness import xplane

SCOPES_LINE = "XLA Op Scopes"
#: A layer scope in a name stack; the innermost one names the layer.
LAYER_SCOPE = re.compile(r"tpu_dist\.[A-Za-z_.]*[A-Za-z_]")


def scope_of(name_stack: str):
    """The innermost layer scope of an operation's name stack, or None.
    XLA joins the stacks of instructions it merged with ``;``: the first
    that holds a scope names the operation."""
    for stack in name_stack.split(";"):
        found = LAYER_SCOPE.findall(stack)
        if found:
            return found[-1]
    return None


def _newest(trace_dir) -> pathlib.Path:
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_op_scopes(trace_dir) -> dict:
    """The ``XLA Op Scopes`` line of every device plane of the newest
    ``.xplane.pb`` under ``trace_dir``. An event's scope is worked out
    once for each metadata it names, not once an event."""
    out: dict = {}
    for plane in xplane.read_space(_newest(trace_dir)).planes:
        if not trace_lib.DEVICE_PLANE.match(plane.name):
            continue
        scopes = {key: scope_of(stack) for key, stack
                  in xplane.metadata_stat(plane, "tf_op").items()}
        events = []
        for line in plane.lines:
            if line.name != trace_lib.OPS_LINE:
                continue
            base_ps = line.timestamp_ns * 1000
            for e in line.events:
                scope = scopes.get(e.metadata_id)
                if scope is not None:
                    events.append([scope, (base_ps + e.offset_ps) // 1000,
                                   e.duration_ps // 1000])
        if events:
            out[plane.name] = {SCOPES_LINE: events}
    return out


def with_op_scopes(trace: dict, trace_dir) -> dict:
    """``trace`` (the neutral form) with the scopes line of each device
    plane beside its other lines."""
    for plane, lines in load_op_scopes(trace_dir).items():
        trace.setdefault(plane, {}).update(lines)
    return trace


# -- readers ------------------------------------------------------------------


def trace_program_ms(ctx, spec):
    """Device milliseconds of one execution of the programs a spec names
    (``patterns`` on ``XLA Modules``, narrowed by ``containing`` as in
    ``readers._program_seconds``): their device seconds in the window over
    their count."""
    t = ctx.get("trace")
    if not t or not trace_lib.device_planes(t):
        return None
    seconds, count = readers._program_seconds(
        ctx, {"line": trace_lib.MODULES_LINE, **spec})
    if not count:
        return None
    return 1e3 * seconds / count


#: The last trace :func:`_united` read, and what it worked out.
_UNITED: list = [None, None]


def _united(trace: dict) -> dict:
    """{device plane: (busy intervals, {scope: its intervals})} inside the
    window, each united. Worked out once a trace: the metrics of one run
    read the same trace, and uniting a serve window's million operations
    is the whole cost (a trace is read as it stands when first read)."""
    if _UNITED[0] is not trace:
        out = {}
        for plane in trace_lib.device_planes(trace):
            by: dict = {}
            for name, a, b in trace_lib.ops_in_window(trace, plane,
                                                      SCOPES_LINE):
                by.setdefault(name, []).append((a, b))
            busy = trace_lib.union(
                (a, b) for _, a, b in trace_lib.ops_in_window(trace, plane))
            out[plane] = (busy, {k: trace_lib.union(v)
                                 for k, v in sorted(by.items())})
        _UNITED[:] = [trace, out]
    return _UNITED[1]


def trace_scope_share(ctx, spec):
    """Percent of the device's busy time in the window under the layer
    scopes a spec names (``patterns`` on the scope), averaged over device
    planes. The intervals are united first, so that an operation and the
    operations inside it (a ``while`` and its body) count once. With
    ``"complement": true``, the busy time under no scope at all. None
    where the trace has no scopes line: a program without scopes."""
    t = ctx.get("trace")
    planes = trace_lib.device_planes(t) if t else []
    if not planes or not any(SCOPES_LINE in t[p] for p in planes):
        return None
    regs = [re.compile(p) for p in spec.get("patterns", [])]
    shares = []
    for busy, by in _united(t).values():
        total = _length(busy)
        if not total:
            continue
        scoped = trace_lib.union(
            iv for name, ivs in by.items()
            if spec.get("complement") or any(r.search(name) for r in regs)
            for iv in ivs)
        inside = total - _length(trace_lib.subtract(busy, scoped))
        shares.append(100.0 * ((total - inside) if spec.get("complement")
                               else inside) / total)
    return sum(shares) / len(shares) if shares else None


def scope_seconds(trace: dict) -> dict:
    """{scope: device seconds} on the first device plane inside the window
    (each scope's intervals united), and ``busy`` beside them."""
    planes = trace_lib.device_planes(trace)
    if not planes:
        return {}
    busy, by = _united(trace)[planes[0]]
    return {**{k: _length(v) / 1e9 for k, v in by.items()},
            "busy": _length(busy) / 1e9}


def unscoped_ops(trace: dict, n: int = 15) -> list[list]:
    """[operation family, seconds] of the first device's operations in the
    window, each counted for the part of it that no scoped operation
    covers, the largest first: what the ``complement`` share holds. An
    operation that encloses others (a ``while``) counts with them, as in
    ``trace.top_device_ops``."""
    planes = trace_lib.device_planes(trace)
    if not planes:
        return []
    scoped = trace_lib.union(
        iv for ivs in _united(trace)[planes[0]][1].values() for iv in ivs)
    starts = [a for a, _ in scoped]
    sums: dict = {}
    for name, a, b in trace_lib.ops_in_window(trace, planes[0]):
        k = max(bisect.bisect_right(starts, a) - 1, 0)
        covered = 0
        while k < len(scoped) and scoped[k][0] < b:
            covered += max(0, min(b, scoped[k][1]) - max(a, scoped[k][0]))
            k += 1
        if b - a > covered:
            key = trace_lib.op_family(name)
            sums[key] = sums.get(key, 0) + (b - a - covered)
    top = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def _length(intervals) -> int:
    return sum(b - a for a, b in intervals)


READERS = {f.__name__: f for f in (trace_program_ms, trace_scope_share)}
