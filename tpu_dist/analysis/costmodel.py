"""Static communication/memory cost model over traced jaxprs.

The jaxpr rules (SC2xx) answer "can this program deadlock?"; this module
answers "how much does it communicate, and how much does it hold live?" —
the two quantities whose regressions only surface as step-time cliffs and
OOMs at pod scale. Both are computed from the same CPU ``make_jaxpr``
traces the SC2xx pass already produces; nothing compiles, nothing runs.

**Communication volume.** Every collective eqn contributes
``bytes_on_wire = formula(P, payload_bytes) * multiplier`` where

* ``payload_bytes`` is the operand aval's size — inside ``shard_map`` the
  trace already sees per-device shard shapes, i.e. the global aval divided
  by the ``in_specs``-sharded axis sizes;
* ``P`` is the participant count of the collective's mesh axes — taken
  from the enclosing ``shard_map``'s mesh, overridable per axis with a
  modeled mesh (``--mesh data=8,model=4``) so one trace prices many
  topologies. Payload shapes stay as traced; only the ring arithmetic
  rescales;
* the formula is the standard ring cost per device: all-reduce (psum/
  pmax/pmin) ``2*(P-1)/P``, all_gather ``(P-1)`` (of the per-shard
  input), reduce_scatter/all_to_all ``(P-1)/P``, ppermute ``1`` (one
  neighbor send). ``pvary``/``pcast`` are the replication-type casts
  jax's check_vma rewriter inserts — no bytes move — and cost 0;
* the ``multiplier`` folds in control flow: a collective inside a
  ``lax.scan`` of length L launches L times; ``cond``/``switch`` branches
  are all counted (a deliberate conservative over-count — branch
  probabilities are not static knowledge); a ``while`` body counts once
  (its trip count is data-dependent, which SC202 flags as a deadlock risk
  anyway).

**Peak live bytes (HBM estimate).** A linear scan over each jaxpr's eqns:
a value is born at its defining eqn and dies after its last use; the peak
of the running live-byte sum estimates per-rank HBM pressure. Sub-jaxprs
(scan/cond/while bodies, pjit calls, remat) contribute their own internal
peak minus their boundary (operands are already counted by the caller).
Rematerialization is ignored, so the estimate is an upper bound of what
XLA must schedule around.

**Argument liveness (SC303 input).** The same scan records, for every
top-level entry-point argument, how many eqns reference it — an argument
referenced exactly once is provably dead after that use, and if it is
large and never donated, ``donate_argnums`` would halve its footprint.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Iterable, Mapping, Optional

#: Replication-type casts, not communication: jax's check_vma rewriter
#: inserts these to move values between replicated and device-varying
#: types. Every device already holds the bytes; nothing crosses a link.
ZERO_COST_FRAGMENTS = ("pbroadcast", "pvary", "pcast")


def aval_bytes(aval) -> int:
    """Size of one aval in bytes (0 for tokens/opaque avals)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        itemsize = dtype.itemsize
    except AttributeError:  # pragma: no cover - exotic dtype object
        import numpy as np

        itemsize = np.dtype(dtype).itemsize
    return int(math.prod(shape)) * int(itemsize)


#: Per-topology link defaults the latency model falls back to when a
#: ``--mesh`` spec names no link parameters: ICI-order per-link bandwidth
#: and per-launch fabric latency, and one core's sustained compute rate.
#: All three are MODEL constants — the point is relative pricing of
#: schedules (launch count x latency vs bytes/bandwidth vs overlap), not
#: absolute wall-clock prophecy.
DEFAULT_LINK_BANDWIDTH_GBPS = 100.0
DEFAULT_LINK_LATENCY_US = 1.0
DEFAULT_COMPUTE_FLOPS_PER_S = 100e12


@dataclasses.dataclass(frozen=True)
class LinkSpec:
    """Link parameters for one mesh axis: sustained bandwidth (GB/s) and
    per-collective-launch latency (us)."""

    bandwidth_gbps: float = DEFAULT_LINK_BANDWIDTH_GBPS
    latency_us: float = DEFAULT_LINK_LATENCY_US

    @property
    def bytes_per_s(self) -> float:
        return self.bandwidth_gbps * 1e9

    @property
    def latency_s(self) -> float:
        return self.latency_us * 1e-6

    def to_json(self) -> dict:
        return {"bandwidth_gbps": self.bandwidth_gbps,
                "latency_us": self.latency_us}


def parse_mesh_links(spec: str) -> tuple[dict, dict]:
    """``"data=8:90:1.5,model=4"`` -> axis sizes plus per-axis link specs.

    Each axis is ``AXIS=N[:BW_GBPS[:LAT_US]]`` — the optional link suffix
    feeds the latency model (:func:`estimate_latency`); axes without one
    get the :class:`LinkSpec` defaults. Returns ``(axes, links)`` where
    ``links`` holds only explicitly-specified axes.
    """
    axes: dict[str, int] = {}
    links: dict[str, LinkSpec] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, eq, rest = part.partition("=")
        if not eq or not name.strip():
            raise ValueError(
                f"bad mesh spec {part!r}; expected axis=size (e.g. data=8)")
        fields = rest.split(":")
        if len(fields) > 3:
            raise ValueError(
                f"bad mesh spec {part!r}; expected "
                "AXIS=N[:BW_GBPS[:LAT_US]]")
        size = fields[0]
        try:
            n = int(size)
        except ValueError:
            raise ValueError(
                f"bad mesh axis size {size!r} for axis {name!r}") from None
        if n < 1:
            raise ValueError(f"mesh axis {name!r} must be >= 1, got {n}")
        name = name.strip()
        axes[name] = n
        if len(fields) > 1:
            try:
                bw = float(fields[1])
                lat = (float(fields[2]) if len(fields) > 2
                       else DEFAULT_LINK_LATENCY_US)
            except ValueError:
                raise ValueError(
                    f"bad link spec {rest!r} for axis {name!r}; expected "
                    "N[:BW_GBPS[:LAT_US]]") from None
            if bw <= 0:
                raise ValueError(
                    f"link bandwidth for axis {name!r} must be > 0, got "
                    f"{bw}")
            if lat < 0:
                raise ValueError(
                    f"link latency for axis {name!r} must be >= 0, got "
                    f"{lat}")
            links[name] = LinkSpec(bandwidth_gbps=bw, latency_us=lat)
    return axes, links


def parse_mesh(spec: str) -> dict:
    """``"data=8,model=4"`` -> ``{"data": 8, "model": 4}`` (link suffixes,
    if any, are accepted and dropped — see :func:`parse_mesh_links`)."""
    return parse_mesh_links(spec)[0]


def _axis_names(params: Mapping) -> tuple:
    """The mesh axes a collective eqn operates over (name params vary:
    psum uses ``axes``, all_gather ``axis_name`` as a tuple, all_to_all
    ``axis_name`` as a bare string)."""
    raw = params.get("axes") or params.get("axis_name")
    if raw is None:
        return ()
    if isinstance(raw, (tuple, list)):
        return tuple(str(a) for a in raw)
    return (str(raw),)


def comm_bytes(prim_name: str, payload_bytes: int, axis_size: int) -> int:
    """Per-device bytes on the wire for one launch of ``prim_name`` with a
    per-shard payload of ``payload_bytes`` over ``axis_size`` participants
    (the ring formulas from the module docstring)."""
    p = max(int(axis_size), 1)
    if any(f in prim_name for f in ZERO_COST_FRAGMENTS):
        return 0
    if p == 1:
        return 0  # a one-participant collective is a copy at worst
    if "all_gather" in prim_name or "pgather" in prim_name:
        return (p - 1) * payload_bytes
    if ("reduce_scatter" in prim_name or "psum_scatter" in prim_name
            or "all_to_all" in prim_name):
        return int(round((p - 1) / p * payload_bytes))
    if "ppermute" in prim_name or "pshuffle" in prim_name:
        return payload_bytes
    # all-reduce family (psum/pmax/pmin; pmean traces to psum + divide):
    # ring all-reduce = reduce_scatter + all_gather of 1/P shards.
    return int(round(2 * (p - 1) / p * payload_bytes))


@dataclasses.dataclass(frozen=True)
class CollectiveCost:
    """One collective launch site and its modeled wire cost."""

    op: str
    axes: tuple
    axis_size: int
    payload_bytes: int  # per-device operand bytes, as traced
    multiplier: int  # control-flow launch count (scan length product)
    bytes: int  # comm_bytes(op, payload, axis_size) * multiplier
    shape: tuple
    dtype: str

    def to_json(self) -> dict:
        return {
            "op": self.op, "axes": list(self.axes),
            "axis_size": self.axis_size,
            "payload_bytes": self.payload_bytes,
            "multiplier": self.multiplier, "bytes": self.bytes,
            "shape": list(self.shape), "dtype": self.dtype,
        }


@dataclasses.dataclass(frozen=True)
class ArgLiveness:
    """Liveness of one top-level entry-point argument."""

    index: int
    bytes: int
    shape: tuple
    dtype: str
    use_count: int  # eqns referencing it (0 = unused input)

    @property
    def dead_after_first_use(self) -> bool:
        return self.use_count == 1


@dataclasses.dataclass(frozen=True)
class CostReport:
    """The cost model's verdict for one entry point."""

    entry: str
    collectives: tuple  # of CollectiveCost
    total_comm_bytes: int
    peak_hbm_bytes: int
    args: tuple  # of ArgLiveness
    mesh: dict  # modeled axis sizes actually applied
    latency: Optional["LatencyEstimate"] = None

    def to_json(self) -> dict:
        payload = {
            "entry": self.entry,
            "total_comm_bytes": self.total_comm_bytes,
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "collectives": [c.to_json() for c in self.collectives],
            "args": [dataclasses.asdict(a) for a in self.args],
            "mesh": dict(self.mesh),
        }
        if self.latency is not None:
            payload["latency"] = self.latency.to_json()
        return payload


def _sub_jaxprs(params: Mapping):
    """(param_name, core_jaxpr) pairs for one eqn's sub-jaxprs."""
    for key, value in params.items():
        for item in (value if isinstance(value, (tuple, list)) else (value,)):
            jaxpr = getattr(item, "jaxpr", item)
            if hasattr(jaxpr, "eqns"):
                yield key, jaxpr


def _is_comm(prim_name: str, fragments) -> bool:
    return any(f in prim_name for f in fragments)


def collect_collective_costs(jaxpr, *, mesh_env: Optional[dict] = None,
                             model_mesh: Optional[Mapping] = None,
                             multiplier: int = 1) -> list:
    """Walk ``jaxpr`` depth-first, pricing every collective launch.

    ``mesh_env`` carries the axis sizes of the innermost enclosing
    shard_map; ``model_mesh`` overrides them per axis (the ``--mesh``
    contract). ``multiplier`` accumulates enclosing scan lengths.
    """
    from tpu_dist.analysis.jaxpr_checks import _COLLECTIVE_FRAGMENTS

    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    mesh_env = dict(mesh_env or {})
    model_mesh = dict(model_mesh or {})
    out: list[CollectiveCost] = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if _is_comm(name, ZERO_COST_FRAGMENTS):
            continue  # replication-type casts: no launch, no bytes
        if _is_comm(name, _COLLECTIVE_FRAGMENTS):
            axes = _axis_names(eqn.params)
            size = 1
            for a in axes:
                size *= int(model_mesh.get(
                    a, mesh_env.get(a, eqn.params.get("axis_size", 1))))
            aval = eqn.invars[0].aval if eqn.invars else None
            payload = aval_bytes(aval) if aval is not None else 0
            shape = tuple(getattr(aval, "shape", ()) or ())
            dtype = str(getattr(aval, "dtype", ""))
            per_launch = comm_bytes(name, payload, size)
            out.append(CollectiveCost(
                op=name, axes=axes, axis_size=size,
                payload_bytes=payload, multiplier=multiplier,
                bytes=per_launch * multiplier, shape=shape, dtype=dtype))
            continue
        inner_env = mesh_env
        if name == "shard_map":
            mesh = eqn.params.get("mesh")
            if mesh is not None and hasattr(mesh, "shape"):
                inner_env = dict(mesh_env)
                inner_env.update(
                    {str(k): int(v) for k, v in dict(mesh.shape).items()})
        inner_mult = multiplier
        if name == "scan":
            inner_mult = multiplier * int(eqn.params.get("length", 1))
        for _, sub in _sub_jaxprs(eqn.params):
            out.extend(collect_collective_costs(
                sub, mesh_env=inner_env, model_mesh=model_mesh,
                multiplier=inner_mult))
    return out


def _dot_general_flops(eqn) -> int:
    """2 * batch * lhs_free * rhs_free * contract for one dot_general."""
    lhs = eqn.invars[0].aval
    rhs = eqn.invars[1].aval
    try:
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    except (KeyError, ValueError, TypeError):  # pragma: no cover
        return 2 * max(aval_bytes(lhs), aval_bytes(rhs))
    del rc, rb
    lshape = tuple(getattr(lhs, "shape", ()) or ())
    rshape = tuple(getattr(rhs, "shape", ()) or ())
    contract = int(math.prod(lshape[d] for d in lc)) or 1
    batch = int(math.prod(lshape[d] for d in lb)) or 1
    lhs_free = max(int(math.prod(lshape)) // (contract * batch), 1)
    rhs_free = max(int(math.prod(rshape)) // (contract * batch), 1)
    return 2 * batch * lhs_free * rhs_free * contract


def _conv_flops(eqn) -> int:
    """2 * out_elements * (kernel_elements / out_channels) for a conv."""
    out = eqn.outvars[0].aval if eqn.outvars else None
    kernel = eqn.invars[1].aval if len(eqn.invars) > 1 else None
    if out is None or kernel is None:  # pragma: no cover
        return 0
    out_shape = tuple(getattr(out, "shape", ()) or ())
    k_shape = tuple(getattr(kernel, "shape", ()) or ())
    out_elems = int(math.prod(out_shape)) or 1
    k_elems = int(math.prod(k_shape)) or 1
    out_ch = int(out_shape[-1]) if out_shape else 1
    return 2 * out_elems * max(k_elems // max(out_ch, 1), 1)


def collect_flops(jaxpr, *, multiplier: int = 1) -> int:
    """Modeled FLOPs of one jaxpr: dot_general/conv priced exactly, every
    other eqn one flop per output element (an elementwise floor), scan
    bodies multiplied by their length, collectives excluded (the latency
    model prices those over links, not cores)."""
    from tpu_dist.analysis.jaxpr_checks import _COLLECTIVE_FRAGMENTS

    core = getattr(jaxpr, "jaxpr", jaxpr)
    total = 0
    for eqn in core.eqns:
        name = eqn.primitive.name
        if _is_comm(name, ZERO_COST_FRAGMENTS + _COLLECTIVE_FRAGMENTS):
            continue
        inner_mult = multiplier
        if name == "scan":
            inner_mult = multiplier * int(eqn.params.get("length", 1))
        subs = list(_sub_jaxprs(eqn.params))
        if subs:
            for _, sub in subs:
                total += collect_flops(sub, multiplier=inner_mult)
            continue
        if name == "dot_general":
            total += multiplier * _dot_general_flops(eqn)
        elif name == "conv_general_dilated":
            total += multiplier * _conv_flops(eqn)
        else:
            out_elems = sum(
                int(math.prod(getattr(v.aval, "shape", ()) or ())) or 1
                for v in eqn.outvars if hasattr(v, "aval"))
            total += multiplier * out_elems
    return total


@dataclasses.dataclass(frozen=True)
class LatencyEstimate:
    """Modeled per-step latency for one entry point.

    The overlap model: a collective launched mid-step can run concurrently
    with remaining compute, EXCEPT the final launch site — its result
    gates the optimizer update, so its time is a hard tail. Everything
    before it overlaps with up to ``compute_s`` of work; whatever does
    not fit (comm-bound programs) spills into the tail too.
    """

    compute_s: float  # flops / flops_per_s
    comm_s: float  # sum over launch sites of multiplier*(lat + B/bw)
    overlapped_s: float  # comm hidden under compute
    comm_tail_s: float  # non-overlappable remainder (>= last site)
    step_latency_s: float  # compute_s + comm_tail_s
    launches: int  # total collective launches (sum of multipliers)
    flops: int

    def to_json(self) -> dict:
        return {
            "compute_s": self.compute_s,
            "comm_s": self.comm_s,
            "overlapped_s": self.overlapped_s,
            "comm_tail_s": self.comm_tail_s,
            "step_latency_s": self.step_latency_s,
            "launches": self.launches,
            "flops": self.flops,
        }


def estimate_latency(flops: int, collectives: Iterable[CollectiveCost],
                     *, links: Optional[Mapping] = None,
                     flops_per_s: float = DEFAULT_COMPUTE_FLOPS_PER_S,
                     ) -> LatencyEstimate:
    """Price one step: compute from the flop count, comm from per-axis
    link specs (``links`` maps axis name -> :class:`LinkSpec`; unnamed
    axes get defaults), overlap per the :class:`LatencyEstimate` model.

    Each launch site costs ``multiplier * (link_latency + bytes/bandwidth)``
    — so a bucketed schedule pays latency once per bucket (launch-count
    accounting) while a fused schedule pays it once, and the tradeoff
    against overlap is visible in ``comm_tail_s``.
    """
    links = dict(links or {})
    default = LinkSpec()
    compute_s = float(flops) / float(flops_per_s)
    site_times = []
    launches = 0
    for c in collectives:
        link = links.get(c.axes[0], default) if c.axes else default
        mult = max(int(c.multiplier), 1)
        per_launch_bytes = c.bytes / mult
        site_times.append(
            mult * (link.latency_s + per_launch_bytes / link.bytes_per_s))
        launches += mult
    comm_s = float(sum(site_times))
    tail_site_s = float(site_times[-1]) if site_times else 0.0
    overlapped_s = min(comm_s - tail_site_s, compute_s)
    comm_tail_s = comm_s - overlapped_s
    return LatencyEstimate(
        compute_s=compute_s, comm_s=comm_s, overlapped_s=overlapped_s,
        comm_tail_s=comm_tail_s, step_latency_s=compute_s + comm_tail_s,
        launches=launches, flops=int(flops))


# -- calibration --------------------------------------------------------------
#
# The MODEL constants above make the cost model a relative-pricing tool.
# ``calibrate()`` turns it absolute for THIS host: a timed psum sweep over
# two payload sizes fits the same affine cost the latency model charges
# per launch (latency intercept + wire_bytes/bandwidth slope, wire bytes
# per the ring formulas in :func:`comm_bytes`), and one timed matmul pins
# the sustained flop rate. The result round-trips through JSON so a CI box
# can calibrate once and every later ``cost`` run prices against real
# numbers via ``--links @file.json``.


def calibrate(*, axis_names: Iterable[str] = ("data",),
              payload_bytes: Iterable[int] = (1 << 18, 1 << 21),
              matmul_dim: int = 512, repeats: int = 3) -> dict:
    """Microbench the current backend into a link/compute spec dict.

    Runs a psum over all local devices at each payload size (best of
    ``repeats``, after a warmup that also absorbs compilation) and fits
    ``t = latency + wire_bytes / bandwidth`` through the two endpoints,
    with wire bytes from the same ring model :func:`estimate_latency`
    charges — so feeding the result back reproduces the measured times.
    On a single-device host the ring moves zero bytes, so the raw payload
    stands in as the wire proxy (the copy that actually happens) and the
    numbers mean "loopback", not fabric. Every requested axis gets the
    same measured :class:`LinkSpec` — collective microbenches can't tell
    mesh axes apart without a real multi-axis topology, and on one slice
    they share the interconnect class anyway.

    Returns a plain-JSON dict: ``{"backend", "device_count", "links":
    {axis: {bandwidth_gbps, latency_us}}, "flops_per_s"}`` — the exact
    shape :func:`load_links` reads.
    """
    import time

    import jax
    import jax.numpy as jnp

    n = jax.local_device_count()

    def timed(fn, *args):
        out = fn(*args)  # warmup: compile + first run
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(max(int(repeats), 1)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    psum = jax.pmap(lambda v: jax.lax.psum(v, "data"), axis_name="data")
    points = []
    for size in payload_bytes:
        elems = max(int(size) // 4, 1)
        x = jnp.zeros((n, elems), jnp.float32)
        wire = comm_bytes("psum", elems * 4, n) or elems * 4
        points.append((float(wire), timed(psum, x)))
    (w0, t0), (w1, t1) = points[0], points[-1]
    if w1 > w0 and t1 > t0:
        bytes_per_s = (w1 - w0) / (t1 - t0)
        latency_s = max(t0 - w0 / bytes_per_s, 0.0)
    else:  # degenerate sweep: keep the model's slope, pin the intercept
        bytes_per_s = DEFAULT_LINK_BANDWIDTH_GBPS * 1e9
        latency_s = max(min(t0, t1), 0.0)
    link = LinkSpec(bandwidth_gbps=bytes_per_s / 1e9,
                    latency_us=latency_s * 1e6)

    d = int(matmul_dim)
    a = jnp.ones((d, d), jnp.float32)
    mm = jax.jit(lambda x: x @ x)
    mm_s = timed(mm, a)
    flops_per_s = (2.0 * d * d * d) / max(mm_s, 1e-12)

    return {
        "backend": jax.default_backend(),
        "device_count": n,
        "links": {str(name): link.to_json() for name in axis_names},
        "flops_per_s": flops_per_s,
    }


def load_links(path: str) -> tuple[dict, Optional[float]]:
    """Read a :func:`calibrate` JSON file -> ``(links, flops_per_s)``.

    ``links`` maps axis name -> :class:`LinkSpec`; ``flops_per_s`` is
    ``None`` when the file carries no compute rate. Unknown top-level
    keys are ignored so the file can carry provenance (backend, device
    count) without breaking older readers.
    """
    import json

    with open(path) as f:
        data = json.load(f)
    links = {
        str(name): LinkSpec(
            bandwidth_gbps=float(spec.get(
                "bandwidth_gbps", DEFAULT_LINK_BANDWIDTH_GBPS)),
            latency_us=float(spec.get(
                "latency_us", DEFAULT_LINK_LATENCY_US)))
        for name, spec in dict(data.get("links", {})).items()}
    flops = data.get("flops_per_s")
    return links, (float(flops) if flops else None)


def _boundary_bytes(jaxpr) -> int:
    core = getattr(jaxpr, "jaxpr", jaxpr)
    consts = getattr(jaxpr, "consts", ())
    total = sum(aval_bytes(v.aval) for v in core.invars)
    total += sum(aval_bytes(v.aval) for v in core.constvars)
    del consts
    return total


def peak_live_bytes(jaxpr) -> int:
    """Linear-scan liveness peak over one jaxpr (recursing into
    sub-jaxprs; see module docstring for the accounting)."""
    core = getattr(jaxpr, "jaxpr", jaxpr)
    eqns = core.eqns
    last_use: dict[int, int] = {}
    var_size: dict[int, int] = {}

    def note(v, idx):
        key = id(v)
        var_size[key] = aval_bytes(v.aval)
        last_use[key] = idx

    for v in list(core.invars) + list(core.constvars):
        note(v, -1)
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if hasattr(v, "aval") and not _is_literal(v):
                note(v, i)
    for v in core.outvars:
        if hasattr(v, "aval") and not _is_literal(v):
            note(v, len(eqns))

    live = sum(var_size[id(v)]
               for v in set(list(core.invars) + list(core.constvars)))
    peak = live
    for i, eqn in enumerate(eqns):
        inner = 0
        # A Pallas kernel's body lives in VMEM, semaphores and registers:
        # its HBM is its operands and results, which are counted here.
        subs = (() if eqn.primitive.name == "pallas_call"
                else _sub_jaxprs(eqn.params))
        for _, sub in subs:
            inner = max(inner,
                        peak_live_bytes(sub) - _boundary_bytes(sub))
        born = 0
        for v in eqn.outvars:
            if hasattr(v, "aval"):
                born += var_size.get(id(v), aval_bytes(v.aval))
        live += born
        peak = max(peak, live + max(0, inner))
        # Deaths: operands whose last use is this eqn, and outvars that
        # are never read (dropped results die immediately).
        dead = 0
        seen: set[int] = set()
        for v in list(eqn.invars) + list(eqn.outvars):
            if _is_literal(v) or not hasattr(v, "aval"):
                continue
            key = id(v)
            if key in seen:
                continue
            seen.add(key)
            if last_use.get(key, i) <= i:
                dead += var_size.get(key, aval_bytes(v.aval))
        live -= dead
    return peak


def _is_literal(v) -> bool:
    return type(v).__name__ == "Literal"


def arg_liveness(jaxpr) -> list:
    """Per-argument use counts over the TOP-LEVEL eqn list (a use inside
    a sub-jaxpr counts at the eqn that closes over it)."""
    core = getattr(jaxpr, "jaxpr", jaxpr)
    counts = {id(v): 0 for v in core.invars}
    for eqn in core.eqns:
        for v in set(id(x) for x in eqn.invars if hasattr(x, "aval")):
            if v in counts:
                counts[v] += 1
    for v in core.outvars:
        if hasattr(v, "aval") and id(v) in counts:
            counts[id(v)] += 1  # returned unchanged: alive to the end
    out = []
    for i, v in enumerate(core.invars):
        aval = v.aval
        out.append(ArgLiveness(
            index=i, bytes=aval_bytes(aval),
            shape=tuple(getattr(aval, "shape", ()) or ()),
            dtype=str(getattr(aval, "dtype", "")),
            use_count=counts[id(v)]))
    return out


def analyze_jaxpr(closed, *, entry: str,
                  model_mesh: Optional[Mapping] = None,
                  links: Optional[Mapping] = None,
                  flops_per_s: Optional[float] = None) -> CostReport:
    """The full cost-model verdict for one traced entry point.

    ``flops_per_s`` overrides the model's default compute rate (e.g. a
    :func:`calibrate` measurement); ``None`` keeps the default."""
    colls = collect_collective_costs(closed, model_mesh=model_mesh)
    latency = estimate_latency(
        collect_flops(closed), colls, links=links,
        flops_per_s=(float(flops_per_s) if flops_per_s
                     else DEFAULT_COMPUTE_FLOPS_PER_S))
    return CostReport(
        entry=entry,
        collectives=tuple(colls),
        total_comm_bytes=sum(c.bytes for c in colls),
        peak_hbm_bytes=peak_live_bytes(closed),
        args=tuple(arg_liveness(closed)),
        mesh=dict(model_mesh or {}),
        latency=latency,
    )


#: Arguments smaller than this never trip SC303 — donating a kilobyte
#: buys nothing and the rule is about the multi-MiB batches/activations.
SC303_MIN_BYTES = 1 << 20


def sc303_findings(report: CostReport, *, path: str,
                   donated: Iterable[int] = (),
                   min_bytes: int = SC303_MIN_BYTES) -> list:
    """SC303: large entry-point args provably dead after one use and
    never donated (see rules.py)."""
    from tpu_dist.analysis.rules import Finding

    donated = set(donated)
    findings = []
    for arg in report.args:
        if (arg.bytes >= min_bytes and arg.dead_after_first_use
                and arg.index not in donated):
            findings.append(Finding(
                "SC303", path, 1, 0,
                f"{report.entry}: argument {arg.index} "
                f"({arg.dtype}{list(arg.shape)}, {arg.bytes} bytes) is "
                "dead after its single use but never donated; "
                "jit(donate_argnums=...) would alias it away and cut "
                "peak HBM by its size"))
    return findings
