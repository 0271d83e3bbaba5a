"""Per-layer metric readers. Each metric has a file of its own under
``tpubench/layer_metrics/<name>.json`` that names one of the readers here
and gives it its patterns, counters or keys. A reader that finds nothing
to read returns ``None`` and the metric is left out of the line; it never
returns 0 for a share of a peak.

``ctx`` is what a traced run hands over:

* ``counters`` / ``distributions`` — a snapshot of ``observe.metrics``;
* ``host`` — values the harness measured on the host clock;
* ``trace`` — the profiler trace in the neutral form of ``trace.py``;
* ``work`` — operations and bytes from shapes (the cell's family
  module counts them) for the traced window, keyed by name;
* ``peaks`` — the chip's row of the table of peaks; ``chips``;
* ``sizes`` — the family's named sizes of the configuration and the
  engine's, for patterns.
"""

from __future__ import annotations

from tpubench.harness import trace as trace_lib
from tpubench.harness import peaks as peaks_lib


def _sum_counter_or_dist(ctx, name):
    if name in ctx["counters"]:
        return float(ctx["counters"][name])
    dist = ctx["distributions"].get(name)
    if dist and dist.get("count"):
        return float(dist["sum"])
    return None


def host_value(ctx, spec):
    """A value the harness measured itself: ``{"key": ...}``."""
    value = ctx["host"].get(spec["key"])
    return None if value is None else float(value) * spec.get("scale", 1.0)


def counter_ratio(ctx, spec):
    """sum(numerator) / sum(denominator) * scale. Either side is a
    counter, a distribution (its sum) or ``host:<key>``."""
    def read(name):
        if name.startswith("host:"):
            v = ctx["host"].get(name[5:])
            return None if v is None else float(v)
        return _sum_counter_or_dist(ctx, name)

    num, den = read(spec["numerator"]), read(spec["denominator"])
    if num is None or not den:
        return None
    return num / den * spec.get("scale", 1.0)


def distribution_mean(ctx, spec):
    dist = ctx["distributions"].get(spec["distribution"])
    if not dist or not dist.get("count"):
        return None
    return dist["sum"] / dist["count"] * spec.get("scale", 1.0)


def _trace(ctx):
    t = ctx.get("trace")
    return t if t and trace_lib.device_planes(t) else None


def _program_seconds(ctx, spec):
    """Device seconds and count of the program executions a spec names:
    ``patterns`` on ``line``, narrowed by ``containing`` (patterns of
    operations, in which ``{key}`` stands for one of ``ctx["sizes"]``,
    as ``{max_batch}`` or ``{n_vocab}``)."""
    containing = [p.format_map(ctx.get("sizes", {}))
                  for p in spec.get("containing", [])]
    return trace_lib.matching_seconds(
        ctx["trace"], spec["patterns"], spec.get("line", trace_lib.OPS_LINE),
        containing)


def device_idle_share(ctx, spec):
    t = _trace(ctx)
    if t is None:
        return None
    return 100.0 * (1.0 - trace_lib.busy_seconds(t)
                    / trace_lib.window_seconds(t))


def _work_over_program_seconds(ctx, spec, peak: str):
    t = _trace(ctx)
    amount = ctx["work"].get(spec["work"])
    if t is None or not amount:
        return None
    seconds, count = _program_seconds(ctx, spec)
    if not count or seconds <= 0:
        return None
    return 100.0 * amount / seconds / ctx["peaks"][peak]


def trace_flops_share(ctx, spec):
    """FLOPs named ``work`` over the device seconds of the program
    executions the spec names, over the peak: an MFU."""
    return _work_over_program_seconds(ctx, spec, "bf16_flops")


def trace_bytes_share(ctx, spec):
    """Bytes named ``work`` over the same kind of device seconds, over
    the chip's memory bandwidth."""
    return _work_over_program_seconds(ctx, spec, "hbm_bytes_per_s")


def kernel_roofline(ctx, spec):
    """Least seconds the chip could take for the kernel's work in the
    traced window (``work`` names a (flops, bytes) pair, per device) over
    the summed device seconds of its events, averaged over devices."""
    t = _trace(ctx)
    pair = ctx["work"].get(spec["work"])
    if t is None or not pair:
        return None
    seconds, count = trace_lib.matching_seconds(t, spec["patterns"])
    if not count or seconds <= 0:
        return None
    least, _ = peaks_lib.roofline_seconds(pair[0], pair[1], ctx["peaks"])
    return 100.0 * least / seconds


def exposed_collective_share(ctx, spec):
    t = _trace(ctx)
    if t is None:
        return None
    seconds = trace_lib.exposed_collective_seconds(t)
    _, count = trace_lib.matching_seconds(t, [trace_lib.COLLECTIVE.pattern])
    if not count:
        return None
    return 100.0 * seconds / trace_lib.window_seconds(t)


READERS = {f.__name__: f for f in (
    host_value, counter_ratio, distribution_mean, device_idle_share,
    trace_flops_share, trace_bytes_share, kernel_roofline,
    exposed_collective_share)}


def read_metric(ctx, spec):
    return READERS[spec["reader"]](ctx, spec)
