"""Runtime bootstrap: cluster bring-up on top of the JAX coordination service.

Reference semantics being reproduced (SURVEY.md §3.1, D3/D10/D11):

* Each process reads TF_CONFIG, then constructs the strategy, which starts a
  per-process gRPC server and blocks until every declared peer is reachable
  (README.md:65-66; tf:...collective_all_reduce_strategy.py:507-664).
* One worker (explicit chief, else worker 0) is the chief with extra duties
  (README.md:51).
* A single worker / absent TF_CONFIG degrades to local (single-process)
  training (README.md:34).

TPU-native translation: there are no user-managed servers. ``initialize()``
parses the same TF_CONFIG JSON and calls ``jax.distributed.initialize`` —
process 0 hosts the coordination service (C++ in jaxlib, gRPC underneath:
the native equivalent of the reference's GrpcServer + coordination service),
everyone else dials it, and the call blocks until all ``num_processes`` have
joined: the same "training begins when all services are ready" barrier as
README.md:66. On an actual TPU pod with no TF_CONFIG, ``jax.distributed``
autodetects the slice topology from the TPU metadata environment.
"""

from __future__ import annotations

import atexit
import logging
import os
import threading
from typing import Optional

from tpu_dist.cluster.config import ClusterConfig

logger = logging.getLogger("tpu_dist")

_STATE_LOCK = threading.Lock()
_INITIALIZED = False
_CONFIG: Optional[ClusterConfig] = None
#: The explicit (coordinator_address, num_processes, process_id) the
#: distributed client was last brought up with, recorded by ``_dist_init``.
#: This is what lets ``reinitialize`` run a REAL teardown + re-init even
#: when TF_CONFIG is absent — e.g. an explicit single-process bring-up
#: against a coordination service, where ``jax.process_count() == 1`` but a
#: live client exists. None when no explicit bring-up happened.
_DIST_PARAMS: Optional[dict] = None
#: Gang generation of this process's collective clique (see
#: ``current_generation``); None until first read (env or reinitialize).
_GENERATION: Optional[int] = None

#: Environment variable carrying the gang generation into a (re)launched
#: worker — the Supervisor stamps it on a mid-epoch replacement so the new
#: process joins the REFORMED clique, not the one that lost a member.
GENERATION_ENV = "TPU_DIST_GANG_GENERATION"


def _dist_init(*, allow_live_backend: bool = False, **kwargs):
    global _DIST_PARAMS
    import jax
    from jax._src import distributed as _dist
    from jax._src import xla_bridge

    if allow_live_backend and xla_bridge.backends_are_initialized():
        # Mid-process RE-dial: a gang-reform survivor has been computing
        # for epochs, so its backend is necessarily live, and the public
        # API refuses re-init categorically. The coordination service
        # (gRPC, C++ side) is independent of the local device backend, so
        # bring the service + client up directly; only ``reinitialize``
        # sets ``allow_live_backend`` — a FIRST bring-up after
        # computations still fails loudly, since there the backend's
        # process/device view really would be stale.
        _dist.global_state.initialize(**kwargs)
        logger.info(
            "tpu_dist: re-dialed coordination service at %s under a live "
            "backend", kwargs.get("coordinator_address"))
    else:
        jax.distributed.initialize(**kwargs)
    if kwargs.get("coordinator_address") and kwargs.get("num_processes"):
        _DIST_PARAMS = {k: kwargs.get(k) for k in
                        ("coordinator_address", "num_processes",
                         "process_id")}


def initialize(config: ClusterConfig | None = None, *,
               coordinator_address: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None) -> None:
    """Bring up the cluster runtime. Idempotent; safe to call in every process.

    Resolution order (mirrors the reference's resolver chain, SURVEY.md D1):

    1. Explicit ``config`` / explicit ``coordinator_address`` kwargs.
    2. ``TF_CONFIG`` env var (same JSON shape as the reference,
       tf_dist_example.py:6-10).
    3. TPU-pod / cloud autodetection via bare ``jax.distributed.initialize()``
       when the environment indicates a multi-process TPU job.
    4. Otherwise: single-process local mode — the README.md:34 degradation rule
       (1 worker behaves like single-host MirroredStrategy).
    """
    global _INITIALIZED, _CONFIG
    import jax

    with _STATE_LOCK:
        if _INITIALIZED:
            return

        if config is None:
            config = ClusterConfig.from_env()

        # Failure-detection latency knob (SURVEY.md D12: TF probes every 30 s
        # with 10 s timeouts; JAX's coordination service heartbeats instead).
        # Exposed mainly so fault tests can shrink detection time.
        hb = float(os.environ.get("TPU_DIST_HEARTBEAT_TIMEOUT_S", "100"))

        if coordinator_address is not None:
            # Explicit JAX-style bring-up, bypassing TF_CONFIG.
            _dist_init(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                heartbeat_timeout_seconds=max(1, round(hb)),
            )
            _log_bringup()
        elif config is not None and config.num_processes > 1:
            logger.info(
                "tpu_dist: initializing %d-process cluster from TF_CONFIG; "
                "task=(%s, %d) process_id=%d chief=%s coordinator=%s",
                config.num_processes, config.task.type, config.task.index,
                config.process_id, config.is_chief, config.coordinator_address,
            )
            # The declared addresses are ours to bind (no TF gRPC servers exist
            # in this framework); process 0's entry doubles as the coordination
            # service endpoint.
            _dist_init(
                coordinator_address=config.coordinator_address,
                num_processes=config.num_processes,
                process_id=config.process_id,
                heartbeat_timeout_seconds=max(1, round(hb)),
            )
            _log_bringup()
        elif config is None and _tpu_pod_env_present():
            logger.info("tpu_dist: no TF_CONFIG; using TPU pod autodetection")
            _dist_init(
                heartbeat_timeout_seconds=max(1, round(hb)))
            _log_bringup()
        else:
            # Single-process local mode (README.md:34): nothing to bring up.
            logger.info(
                "tpu_dist: single-process local mode (%d local device(s))",
                jax.local_device_count(),
            )

        _CONFIG = config
        _INITIALIZED = True
        atexit.register(_shutdown)


def _tpu_pod_env_present() -> bool:
    """True only for a genuinely multi-host TPU job (Cloud TPU / megascale env).

    Single-host markers must NOT trigger distributed bring-up: a lone worker
    degrades to local mode (README.md:34), and some images set
    ``TPU_WORKER_HOSTNAMES=localhost`` even for one host.
    """
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if len([h for h in hostnames.split(",") if h.strip()]) > 1:
        return True
    return bool(os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"))


def _log_bringup() -> None:
    import jax
    # The analog of the reference's bring-up log line "Enabled multi-worker
    # collective ops with available devices: [...]" (SURVEY.md §3.5) — the
    # affordance tests use to confirm the cluster really formed.
    logger.info(
        "tpu_dist: cluster up — process %d/%d, %d global device(s): %s",
        jax.process_index(), jax.process_count(), jax.device_count(),
        [str(d) for d in jax.devices()],
    )


def _shutdown() -> None:
    """Clean shutdown at exit — the README.md:68 'servers shut down when
    training ends' semantics."""
    global _INITIALIZED
    if not _INITIALIZED:
        return
    try:
        import jax
        if jax.process_count() > 1:
            jax.distributed.shutdown()
    except Exception:  # pragma: no cover - best-effort at interpreter exit
        pass
    _INITIALIZED = False


def is_initialized() -> bool:
    return _INITIALIZED


def current_generation() -> int:
    """The gang generation this process's collective clique belongs to.

    Generation 0 is the launch clique. Every mid-epoch gang reform bumps it
    (``reinitialize``); a worker relaunched INTO a reformed gang inherits it
    through ``$TPU_DIST_GANG_GENERATION`` (stamped by the Supervisor), so
    survivors and the replacement agree on the clique id without talking.
    """
    global _GENERATION
    with _STATE_LOCK:
        if _GENERATION is None:
            try:
                _GENERATION = int(os.environ.get(GENERATION_ENV, "0") or 0)
            except ValueError:
                _GENERATION = 0
        return _GENERATION


def reinitialize(generation: Optional[int] = None, *,
                 coordinator_port: Optional[int] = None) -> int:
    """Tear down and re-bring-up the collective clique under a new generation.

    The live-elasticity primitive: a survivor of a lost rank keeps its
    weights, host state, and python process — only the *clique* is reformed.
    ``jax.distributed`` is shut down (releasing membership in the dead
    clique) and re-initialized against a FRESH coordinator port, derived
    deterministically from the generation (``base_port + generation`` unless
    ``coordinator_port`` overrides it) so every survivor dials the same new
    endpoint without communicating — the old coordinator may have died with
    the lost rank, and its port may sit in TIME_WAIT.

    In single-process LOCAL mode (including the CI file-gang vehicle, where
    each supervised worker is its own jax process and the gang exists only
    in the shared-filesystem rendezvous) there is no clique to tear down:
    the call just re-stamps the generation, which re-namespaces every
    subsequent rendezvous marker. An EXPLICIT bring-up, however — even with
    ``num_processes == 1`` — started a real distributed client against a
    coordination service, so the real teardown + re-init path runs for it
    too (this is how the multi-device harness proves the collectives-capable
    leg on the CPU backend). Returns the new generation (``generation``
    when given, else current + 1).
    """
    global _INITIALIZED, _GENERATION
    import jax

    new_gen = (current_generation() + 1 if generation is None
               else int(generation))
    with _STATE_LOCK:
        was_up = _INITIALIZED
        config = _CONFIG
        if config is not None and config.num_processes > 1:
            params = {"coordinator_address": config.coordinator_address,
                      "num_processes": config.num_processes,
                      "process_id": config.process_id}
        elif _DIST_PARAMS is not None:
            params = dict(_DIST_PARAMS)
        else:
            params = None
        if was_up and params is not None:
            # A real distributed client is up (multi-process TF_CONFIG, or
            # an explicit bring-up with a coordination service): release
            # membership in the dead clique before re-dialing.
            try:
                jax.distributed.shutdown()
            except Exception as exc:  # the old clique is already broken
                logger.warning(
                    "tpu_dist: shutdown of generation %d clique failed "
                    "(%s); continuing with re-init", _GENERATION, exc)
            _INITIALIZED = False
        _GENERATION = new_gen
        # Re-exported so child processes (and a later current_generation()
        # after module reload) observe the reformed clique's id.
        os.environ[GENERATION_ENV] = str(new_gen)

    if params is not None:
        host, _, base_port = params["coordinator_address"].rpartition(":")
        try:
            port = (int(coordinator_port) if coordinator_port is not None
                    else int(base_port) + new_gen)
        except ValueError:
            port = coordinator_port or base_port
        hb = float(os.environ.get("TPU_DIST_HEARTBEAT_TIMEOUT_S", "100"))
        logger.info(
            "tpu_dist: reforming %d-process clique at generation %d "
            "(coordinator %s:%s)", params["num_processes"], new_gen, host,
            port)
        _dist_init(
            coordinator_address=f"{host}:{port}",
            num_processes=params["num_processes"],
            process_id=params["process_id"],
            heartbeat_timeout_seconds=max(1, round(hb)),
            allow_live_backend=True,
        )
        _log_bringup()
    else:
        logger.info("tpu_dist: gang generation -> %d (single-process "
                    "clique; rendezvous namespace re-stamped)", new_gen)
    with _STATE_LOCK:
        _INITIALIZED = was_up or params is not None
    return new_gen


def cluster_config() -> Optional[ClusterConfig]:
    """The parsed TF_CONFIG for this process, if any."""
    return _CONFIG


def process_index() -> int:
    import jax
    return jax.process_index()


def process_count() -> int:
    import jax
    return jax.process_count()


def is_chief() -> bool:
    """Chief duty holder: explicit TF_CONFIG chief, else global process 0.

    README.md:51: the chief saves checkpoints and writes TensorBoard; worker 0
    is the default chief.
    """
    if _CONFIG is not None:
        return _CONFIG.is_chief
    return process_index() == 0


def barrier(name: str = "tpu_dist_barrier") -> None:
    """Cluster-wide rendezvous.

    The analog of the reference's startup barrier — a dummy RING all-reduce run
    before health checking starts (tf:...collective_all_reduce_strategy.py:
    1043-1066, SURVEY.md §5.3).
    """
    import time

    import jax

    from tpu_dist.parallel.collectives import (fire_fault_hook,
                                               fire_observe_hook)

    # Chaos seam first: a single-process run has no peers to rendezvous
    # with, but an injected barrier stall must still be injectable there.
    fire_fault_hook("barrier")
    t0 = time.perf_counter()
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)
    # Barrier wait time is the cluster's skew made visible — the telemetry
    # hook records it like any other host collective (tpu_dist.observe).
    fire_observe_hook("barrier", seconds=time.perf_counter() - t0)


#: Environment variable naming the shared directory used for the elastic
#: epoch-boundary rendezvous. Setting it arms ``RejoinGate`` in every fit().
REJOIN_DIR_ENV = "TPU_DIST_REJOIN_DIR"

#: Environment variable naming the shared directory used for mid-epoch gang
#: reform (step-granular rendezvous + the reform request/ack protocol).
#: Setting it arms ``StepRejoinGate`` in every fit().
GANG_DIR_ENV = "TPU_DIST_GANG_DIR"


def _default_rendezvous_namespace() -> str:
    """Generation/attempt namespace for this process's barrier markers.

    A marker published by generation g / supervisor attempt a must never
    satisfy a barrier run by generation g' or attempt a' — a dead process's
    stale marker would let a partial gang pass. Namespacing by both ids
    makes reuse structurally impossible.
    """
    from tpu_dist.resilience.events import current_attempt

    return f"g{current_generation()}a{current_attempt()}"


def _reap_markers(d, rank: int, *, keep_namespace: str,
                  keep_min_epoch: Optional[int] = None) -> None:
    """Reap THIS rank's barrier markers that can never be waited on again.

    Removes the rank's markers from any other namespace (older generations/
    attempts, including legacy un-namespaced ``epoch-N.rank-r`` files from
    pre-generation runs), and — when ``keep_min_epoch`` is given — markers
    in the current namespace older than that epoch. Only this rank's own
    files are touched: another live rank's markers are its own to manage.
    """
    for old in d.glob(f"*rank-{rank}"):
        name = old.name
        if name.startswith("reform-"):
            # Reform-protocol acks also end in rank-{r}; they belong to the
            # supervisor handshake, not the barrier, and are not ours to GC.
            continue
        if not name.startswith(keep_namespace + "."):
            try:
                old.unlink()
            except OSError:
                pass
            continue
        if keep_min_epoch is None:
            continue
        try:
            e = int(name.split(".")[1].split("-", 1)[1])
        except (IndexError, ValueError):
            continue
        if e < keep_min_epoch:
            try:
                old.unlink()
            except OSError:
                pass


def _fs_barrier(directory, *, marker_stem: str, rank: int, world: int,
                timeout_s: float, poll_s: float, what: str,
                gc_namespace: Optional[str] = None,
                gc_min_epoch: Optional[int] = None,
                abort_check=None) -> "list[int]":
    """Shared-filesystem barrier: publish ``{marker_stem}.rank-{rank}`` and
    poll until all ``world`` ranks' markers for the same stem exist.

    On timeout this rank's own marker is reaped BEFORE raising, so a retry
    of the same barrier (or a reformed gang reusing the coordinate) cannot
    count this process as present when it has already given up.
    ``abort_check`` (if given) runs every poll round and may raise to break
    out — how a survivor parked at an epoch barrier still notices a gang
    reform whose missing rank will never publish this generation's marker.
    """
    import pathlib
    import time

    d = pathlib.Path(directory)
    d.mkdir(parents=True, exist_ok=True)
    marker = d / f"{marker_stem}.rank-{rank}"
    tmp = d / f".{marker_stem}.rank-{rank}.{os.getpid()}.tmp"
    tmp.write_text(str(os.getpid()), encoding="utf-8")
    os.replace(tmp, marker)  # atomic publish; re-publishing is idempotent
    if gc_namespace is not None:
        _reap_markers(d, rank, keep_namespace=gc_namespace,
                      keep_min_epoch=gc_min_epoch)

    deadline = time.monotonic() + timeout_s
    while True:
        if abort_check is not None:
            abort_check()
        present = set()
        for p in d.glob(f"{marker_stem}.rank-*"):
            suffix = p.name.rsplit("rank-", 1)[1]
            if suffix.isdigit():
                present.add(int(suffix))
        if len(present & set(range(world))) >= world:
            return sorted(present & set(range(world)))
        if time.monotonic() > deadline:
            missing = sorted(set(range(world)) - present)
            try:
                marker.unlink()
            except OSError:
                pass
            raise TimeoutError(
                f"{what} barrier in {d} timed out after {timeout_s:.1f}s; "
                f"missing rank(s) {missing} (present: {sorted(present)})")
        time.sleep(poll_s)


def epoch_rendezvous(directory, *, epoch: int, rank: Optional[int] = None,
                     world: Optional[int] = None, timeout_s: float = 120.0,
                     poll_s: float = 0.05,
                     namespace: Optional[str] = None) -> "list[int]":
    """Shared-filesystem epoch-boundary barrier for elastic rejoin.

    Each worker atomically publishes a ``{ns}.epoch-{E}.rank-{r}`` marker
    under ``directory`` and polls until markers from all ``world`` ranks for
    that epoch exist, then returns the sorted rank list. This is deliberately
    NOT ``sync_global_devices``: a worker relaunched after a preemption is a
    new process outside the surviving gang's collective clique, and the
    meeting protocol that lets it back in cannot itself require membership.
    A shared directory (the same assumption the v2 sharded checkpoint already
    makes) is the lowest-common-denominator rendezvous medium.

    ``namespace`` defaults to ``g{generation}a{attempt}``: markers from an
    earlier supervisor attempt or an earlier gang generation can never
    satisfy this barrier, and a rank that times out reaps its own marker, so
    a restarted gang re-running the same epoch numbers always assembles from
    scratch (previously a dead process's marker could pass a partial gang).

    Raises :class:`TimeoutError` naming the missing ranks if the gang does
    not fully assemble within ``timeout_s`` — the caller (usually
    ``RejoinGate``) surfaces that as a liveness failure rather than stepping
    with a partial gang.
    """
    if rank is None:
        rank = process_index()
    if world is None:
        world = process_count()
    ns = namespace if namespace is not None else _default_rendezvous_namespace()
    return _fs_barrier(
        directory, marker_stem=f"{ns}.epoch-{epoch}", rank=rank, world=world,
        timeout_s=timeout_s, poll_s=poll_s,
        what=f"epoch_rendezvous: epoch {epoch} ({ns})",
        gc_namespace=ns, gc_min_epoch=epoch - 1)


def generation_rendezvous(directory, *, generation: int, step: int,
                          rank: Optional[int] = None,
                          world: Optional[int] = None,
                          timeout_s: float = 120.0,
                          poll_s: float = 0.05,
                          abort_check=None) -> "list[int]":
    """Step-granular gang barrier, namespaced by gang generation.

    The mid-epoch generalization of :func:`epoch_rendezvous`: survivors of a
    lost rank drain at a step boundary and meet the relaunched rank HERE, at
    an arbitrary global-step coordinate, under the reformed generation's
    namespace — a marker from the broken generation g can never satisfy
    generation g+1's barrier. Markers from older generations (and older
    steps of this generation) published by this rank are reaped on the way
    in; this rank's marker is reaped on timeout so a retry starts clean.
    """
    if rank is None:
        rank = process_index()
    if world is None:
        world = process_count()
    ns = f"gen-{generation}"
    return _fs_barrier(
        directory, marker_stem=f"{ns}.step-{step}", rank=rank, world=world,
        timeout_s=timeout_s, poll_s=poll_s,
        what=f"generation_rendezvous: generation {generation} step {step}",
        gc_namespace=ns, gc_min_epoch=None, abort_check=abort_check)


# ---------------------------------------------------------------------------
# Gang-reform protocol (shared filesystem, torn-read tolerant)
#
# The Supervisor and the surviving workers coordinate a mid-epoch reform
# through three kinds of files under the gang directory:
#
#   reform-request.json           supervisor -> survivors: "rank R is lost;
#                                 drain, publish checkpoints, reform at
#                                 generation G"
#   reform-g{G}.drained.rank-{r}  survivor -> supervisor: "my in-flight
#                                 checkpoint is published; safe to relaunch"
#   generation                    supervisor -> everyone: the committed
#                                 current generation (a late-starting or
#                                 relaunched worker adopts max(env, file))
# ---------------------------------------------------------------------------


def _atomic_write_json(path, payload: dict) -> None:
    import json

    tmp = path.parent / f".{path.name}.{os.getpid()}.tmp"
    tmp.write_text(json.dumps(payload), encoding="utf-8")
    os.replace(tmp, path)


def _read_json(path) -> Optional[dict]:
    import json

    try:
        return json.loads(path.read_text(encoding="utf-8"))
    except (OSError, ValueError):
        return None


def request_reform(directory, *, generation: int, lost_ranks: "list[int]",
                   detect_s: Optional[float] = None) -> dict:
    """Publish a gang-reform request (supervisor side). Overwrites any older
    request — at most one reform is in flight per gang directory."""
    import pathlib
    import time

    d = pathlib.Path(directory)
    d.mkdir(parents=True, exist_ok=True)
    payload = {
        "generation": int(generation),
        "lost_ranks": sorted(int(r) for r in lost_ranks),
        "ts": time.time(),
        "detect_s": detect_s,
    }
    _atomic_write_json(d / "reform-request.json", payload)
    return payload


def read_reform_request(directory) -> Optional[dict]:
    """The pending reform request, or None (missing / torn / malformed)."""
    import pathlib

    req = _read_json(pathlib.Path(directory) / "reform-request.json")
    if not isinstance(req, dict) or "generation" not in req:
        return None
    return req


def withdraw_reform(directory) -> None:
    """Remove a pending reform request (supervisor side).

    Called when an in-flight reform is abandoned — a SECOND rank died while
    survivors were draining, or the acks timed out — and the attempt falls
    back to an ordinary gang restart. The request must not outlive the
    attempt: a relaunched worker's rejoin gate reading a stale request for a
    future generation would drain into a reform no supervisor is mediating.
    Idempotent; missing file is fine.
    """
    import pathlib

    try:
        (pathlib.Path(directory) / "reform-request.json").unlink()
    except OSError:
        pass


def ack_reform(directory, *, generation: int, rank: int,
               available_step: Optional[int] = None) -> None:
    """Survivor's drained-and-published acknowledgement for a reform.

    ``available_step`` is the newest COMPLETE checkpoint step in this rank's
    checkpoint directory after the drain published in-flight saves — the
    supervisor takes the gang-wide minimum as the consensus restore step
    (per-rank checkpoint directories can legitimately disagree by an epoch
    or two: ranks are only loosely coupled between barriers, and the dead
    rank's async save may not have published before it died).
    """
    import pathlib

    d = pathlib.Path(directory)
    d.mkdir(parents=True, exist_ok=True)
    _atomic_write_json(d / f"reform-g{int(generation)}.drained.rank-{int(rank)}",
                       {"rank": int(rank), "available_step": available_step})


def read_reform_acks(directory, *, generation: int) -> "dict[int, dict]":
    """Reform acks at ``generation``: rank -> ack payload (torn reads skip)."""
    import pathlib

    d = pathlib.Path(directory)
    acks: dict = {}
    for p in d.glob(f"reform-g{int(generation)}.drained.rank-*"):
        suffix = p.name.rsplit("rank-", 1)[1]
        if not suffix.isdigit():
            continue
        payload = _read_json(p)
        if isinstance(payload, dict):
            acks[int(suffix)] = payload
    return acks


def publish_restore_step(directory, *, generation: int,
                         step: Optional[int]) -> None:
    """Commit the consensus restore step for a reform (supervisor side).

    ``None`` means no checkpoint is common to the whole reformed gang —
    every rank re-initializes from the seed and replays from epoch 0 (the
    same exactness argument as rollback-and-replay without a checkpoint).
    """
    import pathlib

    d = pathlib.Path(directory)
    d.mkdir(parents=True, exist_ok=True)
    _atomic_write_json(d / f"reform-g{int(generation)}.restore",
                       {"step": step})


def read_restore_step(directory, *, generation: int) -> "tuple[bool, Optional[int]]":
    """``(published, step)`` for the reform's consensus restore step."""
    import pathlib

    obj = _read_json(pathlib.Path(directory)
                     / f"reform-g{int(generation)}.restore")
    if not isinstance(obj, dict) or "step" not in obj:
        return (False, None)
    step = obj["step"]
    return (True, int(step) if step is not None else None)


def publish_generation(directory, generation: int) -> None:
    """Commit ``generation`` as the gang's current generation (supervisor)."""
    import pathlib

    d = pathlib.Path(directory)
    d.mkdir(parents=True, exist_ok=True)
    tmp = d / f".generation.{os.getpid()}.tmp"
    tmp.write_text(str(int(generation)), encoding="utf-8")
    os.replace(tmp, d / "generation")


def read_generation(directory) -> int:
    """The committed gang generation for ``directory`` (0 when unset)."""
    import pathlib

    try:
        return int((pathlib.Path(directory) / "generation")
                   .read_text(encoding="utf-8").strip())
    except (OSError, ValueError):
        return 0
