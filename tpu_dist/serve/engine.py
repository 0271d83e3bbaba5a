"""ServeEngine: compiled-program inference runtime on the training mesh.

The engine owns the three compiled surfaces serving needs and nothing
else — scheduling stays host-side in ``scheduler.py``, math stays in
``kv_cache.py``:

* one **prefill** program per padded prompt length (prompts pad up to a
  power of two, so a stream of ragged prompts compiles O(log max_len)
  programs, not O(distinct lengths));
* one **decode** program per padded batch *bucket* (``scheduler.
  default_buckets``): requests come and go between steps, the active
  count maps to the smallest covering bucket, and steady-state serving
  never retraces — the same no-retrace discipline ``Trainer.predict``
  now follows. ``ragged=True`` (paged only) collapses the family to a
  single full-capacity program with a per-slot active mask;
* one **slot-swap** program (traced slot indices) mirroring the
  scheduler's compaction moves into the KV cache.

Weights come from a live model's materialized variables or a
``models/serialize.py`` saved-model directory (:meth:`ServeEngine.
from_saved`), and are placed on the active ``Strategy``'s mesh via
``strategy.replicate`` — the same placement training uses, so a model
can go fit() → save → serve without leaving the mesh.

Every step emits host-side observe metrics (never inside jit —
shardcheck SC103 guards this): ``serve.request.latency_s`` /
``serve.request.ttft_s`` / ``serve.batch.occupancy`` distributions (the
registry's reservoir quantiles give p50/p95/p99 directly),
``serve.queue.depth`` / ``serve.ready`` gauges, and
``serve.{requests.*,tokens.generated,decode.steps,prefill.chunks}``
counters. Arm ``$TPU_DIST_OBSERVE_DIR`` (or call ``metrics.enable()``) to
record; disabled is free.

A round of :meth:`ServeEngine.step` is one ``serve.step`` span
(``utils.profiler.span``, ``ident`` = the round's number) whose children
stand at the phase boundaries: ``serve.step.admit``, one
``serve.step.prefill_chunk`` a chunk (with ``serve.step.first_token_wait``
inside a last chunk where the round reads its picks round by round),
``serve.step.decode_prep``, ``.decode_dispatch``, ``.decode_wait``,
``.pick`` and ``.journal_flush`` (and, inside ``.pick``, one
``serve.step.logits_read`` in a step whose logits somebody read). A
pipelined round (greedy, paged, ragged: :meth:`ServeEngine.step`)
dispatches its decode before it reads: its ``.decode_wait`` is the read of
the previous round's decode and this round's first tokens, and it counts
``serve.decode.overlapped`` (decodes dispatched while the previous one's
picks were unread) and ``serve.decode.rows_discarded`` (picks of a request
that finished or was evicted while they were in flight).
Counters at the same boundaries: ``serve.step.rounds``,
``serve.upload.bytes`` and ``serve.logits.bytes`` (what crosses to and
from the device: the token ids a program picked, and a step's logits only
when somebody read a row of them), ``serve.pick.device`` and
``serve.pick.host_rows`` (tokens taken from a program's pick against rows
that crossed for a host pick),
``serve.programs.built``, ``serve.decode.pages_read`` and
``.pages_addressed`` (the pages a paged decode step's attention reads
against those its table rows address); and once a round
``serve.step.host_s``, the
round's duration less its two waits for the device. A model with routed
experts adds ``serve.moe.assignments``, ``.assignments_held``,
``.experts_touched``, ``.rows_multiplied``, ``.rows_sorted`` and the
distribution ``serve.moe.load_max`` (device
scalars of the decode steps that ride the logits' read-back); one with
recurrent layers adds ``serve.prefill.scan_chunks``,
``serve.state.slots_visited`` over ``.slots_addressed`` (the slot blocks a
decode step's state update reads and writes, up to its highest decoding
slot, against the batch), the gauges
``serve.state.slots_live``, ``serve.state.bytes`` and
``serve.prefix.disabled_recurrent``, and a ``serve.step.state_swap`` span
where compaction moves a slot's state on the device; one with
grouped-query layers adds ``serve.decode.keys_read`` and
``.window_keys_read`` (key positions its decode steps attended, all layers
and the window layers' part; device scalars beside the experts'),
``serve.prefill.keys_visited`` over ``.keys_addressed`` (the key blocks a
full layer's chunks walk against the table row's positions) and the gauges
``serve.cache.window_bytes`` and ``serve.cache.window_slots_live``. With whole-prompt
prefill (``prefill_chunk=0``) the prompt is one chunk and its span lies
inside ``serve.step.admit``, whose self time is then the admission alone.

Resilience (see ``serve/journal.py`` and README "Serving resilience"):
an optional durable request journal makes a supervised restart replay
queued and in-flight requests with token-identical greedy continuations;
a bounded admission queue + projected-TTFT/deadline feasibility checks
shed load the engine cannot serve (``finish_reason="shed"``); a decode-
stall watchdog converts a hung decode step into a classified fault
(:data:`~tpu_dist.resilience.faults.EXIT_SERVE_ABORT`) instead of
blocking the serving loop forever.
"""

from __future__ import annotations

import functools
import itertools
import logging
import sys
import threading
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from tpu_dist.models import hybrid
from tpu_dist.models.model import Sequential
from tpu_dist.observe import metrics
from tpu_dist.parallel import mesh as mesh_lib
from tpu_dist.parallel.strategy import get_strategy
from tpu_dist.serve import kv_cache, paging
from tpu_dist.serve import journal as journal_lib
from tpu_dist.serve.scheduler import ACTIVE, DONE, SHED, Request, Scheduler
from tpu_dist.utils import profiler

logger = logging.getLogger(__name__)

_MIN_PROMPT_PAD = 8

#: EMA smoothing for the decode-step wall-time estimate behind the
#: projected-TTFT admission check.
_EMA_ALPHA = 0.3


def _default_stall_action(info: dict) -> None:
    """What a production engine does about a hung decode step: classify it
    as a fault and die with the registered serve exit code — the
    ServeSupervisor restarts the engine and the journal replays the work.
    ``os._exit`` on purpose: the main thread is wedged inside the runtime,
    so no Python-level unwind can run."""
    import os as _os

    from tpu_dist.resilience import events
    from tpu_dist.resilience.faults import EXIT_SERVE_ABORT

    logger.error("serve: decode step stalled > %.3fs (bucket %s) — "
                 "exiting %d (serve_abort) for supervised restart",
                 info.get("timeout_s", -1.0), info.get("bucket"),
                 EXIT_SERVE_ABORT)
    events.maybe_log("serve_stall", **info)
    _os._exit(EXIT_SERVE_ABORT)


def _pad_to_pow2(n: int, *, lo: int = _MIN_PROMPT_PAD, hi: int) -> int:
    p = lo
    while p < n:
        p <<= 1
    return min(p, hi)


def _current_job():
    """The active multi-tenant job scope — probed through sys.modules so
    a solo engine that never imports :mod:`tpu_dist.jobs` pays nothing,
    not even the import (the jobs runtime's solo no-op contract)."""
    mod = sys.modules.get("tpu_dist.jobs.runtime")
    return mod.current_job() if mod is not None else None


def _picking(body):
    """Wrap one of ``kv_cache``'s program bodies, which return ``(cache,
    logits, *rest)``, so that the program also returns the greedy pick:
    ``(cache, logits, tokens, *rest)`` with ``tokens = argmax(logits, -1)``
    as int32 (``[bucket]`` for a decode program, a scalar for a prefill or
    chunk program). ``jnp.argmax`` takes the first of equal maxima, as
    ``np.argmax`` does on the same float32 values. The logits stay an
    output: they are left on the device until somebody reads them."""
    def program(*args):
        cache, logits, *rest = body(*args)
        return (cache, logits,
                jnp.argmax(logits, axis=-1).astype(jnp.int32), *rest)
    return program


@jax.jit
def _next_inputs(tokens, order, slot, token):
    """A pipelined decode's input tokens, made on the device where the
    previous decode left its picks: ``tokens[order]`` (``order[i]`` is the
    slot, as that decode was dispatched, whose pick slot ``i`` holds now),
    with ``token`` put at ``slot`` (a slot of ``len(tokens)`` puts
    nothing). One shape a capacity, traced once."""
    return tokens[order].at[slot].set(token, mode="drop")


class _Unread:
    """Picks a program made that the host has not read: ``tokens`` and
    ``logits`` on the device, ``rows`` the ``(request, index)`` each pick
    is for (a decode's slot as it was dispatched, ``()`` for a prefill's
    one token), and for a decode when it was dispatched and its device
    counters (``stats``, read only while recording)."""

    __slots__ = ("tokens", "logits", "rows", "dispatched_s", "stats")

    def __init__(self, tokens, logits, rows, dispatched_s=None, stats=None):
        self.tokens, self.logits, self.rows = tokens, logits, rows
        self.dispatched_s, self.stats = dispatched_s, stats


class _Picked:
    """One program execution's two results: the token ids it picked, on
    the host (the read the round waited for, 4 bytes a slot), and the
    logits it picked them from, left on the device. :meth:`logits`
    crosses on first call, once, however many rows ask."""

    __slots__ = ("tokens", "_engine", "_logits", "_host")

    def __init__(self, engine: "ServeEngine", logits, tokens: np.ndarray):
        self.tokens, self._engine, self._logits = tokens, engine, logits
        self._host = None

    def logits(self) -> np.ndarray:
        if self._host is None:
            self._host = self._engine._to_host(self._logits,
                                               "serve.step.logits_read")
        return self._host


class _LogitsRow:
    """What :meth:`ServeEngine._pick` is handed: the token the program
    picked, ``len()`` the vocabulary, and the float32 row itself to
    whoever asks (``np.asarray(row)``), which is when the step's logits
    cross. ``index`` is the slot's row of a decode step (the slot as the
    step was dispatched); a prefill's one row is ``[()]`` of its
    ``[vocab]`` logits and of its scalar token."""

    __slots__ = ("token", "index", "_picked")

    def __init__(self, picked: _Picked, index=()):
        self.token = int(picked.tokens[index])
        self._picked, self.index = picked, index

    def __len__(self) -> int:
        return self._picked._logits.shape[-1]

    def __array__(self, dtype=None, copy=None):
        row = self._picked.logits()[self.index]
        if dtype is not None and row.dtype != dtype:
            return row.astype(dtype)
        return row.copy() if copy else row


#: Monotonic engine generation counter — keys pool-cached decode/prefill
#: programs to one engine instance (its plan, donation mode, and KV-cache
#: shapes are baked into the traced closures).
_ENGINE_SERIALS = itertools.count()


class ServeEngine:
    """Continuous-batching decode loop over a fixed pool of KV slots.

    Args:
      model: a ``Sequential`` from the servable family (see
        ``kv_cache.build_plan``). Weights are taken from the model's live
        variables when materialized, else freshly initialized from
        ``seed`` (the demo path).
      max_batch: KV slots == maximum concurrent requests.
      max_len: per-slot cache capacity (prompt + generated tokens);
        defaults to the model's positional-table length.
      buckets / policy: forwarded to :class:`Scheduler`.
      temperature: 0 = greedy argmax, taken INSIDE the decode, prefill and
        chunk programs: a step hands the host its slots' token ids (4
        bytes each) and leaves the logits on the device. They cross only
        when somebody reads a row (``np.asarray`` of what :meth:`_pick` is
        handed), once a step however many rows are read. > 0 reads every
        row and samples from the tempered softmax with a host-side seeded
        generator (deterministic runs), at the cost of that crossing.
      clock: injectable monotonic clock (tests pin deadlines with it).
      journal: a :class:`~tpu_dist.serve.journal.RequestJournal`, or a
        directory path to open one in. When the directory already holds a
        journal, the engine RECOVERS before serving: journaled-but-
        unfinished requests are re-admitted in arrival order, formerly
        active ones re-prefilled with ``prompt + tokens_emitted_so_far``
        (token-identical greedy continuation).
      max_queue: bounded admission queue — submissions past this depth are
        shed (``finish_reason="shed"``, cause ``queue_full``).
      max_ttft_s: shed a submission whose projected time-to-first-token
        (queue + active work ahead of it, at the EMA decode-step time)
        exceeds this bound (cause ``projected_ttft``).
      retry_budget: a journal-replayed request found ACTIVE in more than
        this many crashes is shed instead of re-admitted (cause
        ``retry_budget``) — poison-pill protection.
      stall_timeout_s: decode-stall watchdog — a decode step (dispatch
        through host materialization) exceeding this wall bound triggers
        ``stall_action`` (default: exit ``EXIT_SERVE_ABORT`` for a
        supervised restart). None disables the watchdog (no per-step cost).
      stall_action: injectable watchdog action (tests record instead of
        exiting); receives an info dict.
      fault_injector: serve chaos seam — an object with ``on_decode`` /
        ``on_step_end`` hooks (see
        :class:`~tpu_dist.resilience.injector.ServeFaultInjector`).
      virtual_step_s: when > 0 and ``clock`` has an ``advance`` method,
        the engine advances the injected clock by this much per decode
        step — a deterministic stand-in for a production-sized model's
        step time, used by the request-storm chaos gate so queueing-delay
        measurements don't depend on host speed.
      paged: select the paged KV-cache subsystem (``serve/paging.py``):
        HBM is carved into fixed-size pages addressed through per-slot
        page tables, admission consults free-page headroom instead of
        slot count alone, repeated prompt prefixes resolve to shared
        read-only pages (prefill runs only over the suffix), and slot
        compaction becomes a host pointer swap. Greedy token streams are
        bit-identical to the contiguous default (tests + serve-bench pin
        it). Default False: the contiguous path and its compiled
        programs are untouched.
      page_size: positions per page (paged mode). Small pages waste less
        HBM on short requests and share prefixes at finer grain; large
        pages mean fewer gather indices per attention step.
      num_pages: pool size (paged mode). Defaults to
        ``max_batch * ceil(max_len / page_size)`` — contiguous-capacity
        parity; pass fewer (or a ``budget_bytes``) to overcommit slots
        against actual request lengths.
      budget_bytes: hard KV-memory bound. Contiguous mode: raise a loud
        sizing error (how many slots fit) instead of an XLA OOM. Paged
        mode: sizes ``num_pages`` to the budget when ``num_pages`` is
        not given, else guards the explicit pool the same way.
      prefix_caching: paged mode only — disable to keep paging without
        cross-request prefix sharing (parity baselines use this). A model
        with recurrent or window layers is served with it OFF whatever is
        passed: a slot's state or ring is not in its pages, so shared
        pages would be attached to a state that was not built over them
        (logged once; gauge ``serve.prefix.disabled_recurrent``).
      prefill_chunk: when > 0, split each admitted prompt's prefill into
        chunks of this many positions (power of two >= 8) and interleave
        them with decode steps, so one long prompt no longer stalls
        every in-flight decode stream for a whole-prompt causal pass.
        Each chunk attends over all prior cached positions — attention
        is never reordered — so greedy streams stay token-identical to
        whole-prompt prefill (tests + serve-bench pin it). A slot being
        chunk-prefilled is excluded from decode (cursor on the request)
        until its final chunk lands; the final chunk emits the first
        token. Ragged final chunks pad to a power of two, so the chunk
        program cache holds at most log2(prefill_chunk / 8) + 1
        programs. Default 0: whole-prompt prefill, compiled programs and
        scheduling byte-identical to previous behavior. Tune it to
        roughly the per-step decode token budget: smaller chunks give
        flatter inter-token latency, larger chunks finish long prompts
        in fewer (cheaper-per-token) passes.
      prefill_interleave: max prefill chunks run between consecutive
        decode steps (default 1 — the flattest-latency policy). Chunks
        drain arrival-ordered (the head request finishes before a later
        one starts), so chunked prefill cannot starve anyone.
      kv_dtype: paged-pool storage dtype — ``"fp32"``/``"bf16"``/
        ``"int8"`` (or the jnp dtypes). ``"int8"`` stores K/V pages as
        int8 with per-position fp32 scale rows: a fixed ``budget_bytes``
        buys ~2x the pages (gate: >= 1.8x concurrent slots in
        serve-bench), greedy streams stay token-identical on short
        horizons and logit drift stays bounded on long ones
        (quantization is write-order independent, so chunked prefill,
        COW, and journal replay all reproduce exact pool bytes). Paged
        mode only — the contiguous cache keeps ``cache_dtype``. Default
        None: the pool dtype is ``cache_dtype``, programs byte-unchanged.
      ragged: paged mode only — decode ALL slots in one full-capacity
        program with a per-slot active mask instead of the pow2-bucket
        program family. The page-table gather already erased contiguity,
        so bucketing is pure retrace surface: ragged engines compile
        exactly ONE decode program and stream token-identically to
        bucketed ones (tests + serve-bench pin both). Default False:
        the bucketed family remains (it is the contiguous engine's only
        mode and the bench's A/B control). A greedy ragged engine also
        runs its rounds PIPELINED (:meth:`step`): its batch layout never
        changes shape and its input tokens are the previous decode's
        picks, so round n's decode is dispatched before round n - 1's
        picks are read.
    """

    @profiler.spanned("serve.engine.build")
    def __init__(self, model: Sequential, *, max_batch: int = 8,
                 max_len: Optional[int] = None,
                 buckets: Optional[tuple[int, ...]] = None,
                 policy: str = "continuous", temperature: float = 0.0,
                 seed: int = 0, cache_dtype=jnp.float32, clock=None,
                 journal=None, max_queue: Optional[int] = None,
                 max_ttft_s: Optional[float] = None, retry_budget: int = 3,
                 stall_timeout_s: Optional[float] = None,
                 stall_action=None, fault_injector=None,
                 virtual_step_s: float = 0.0, paged: bool = False,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 budget_bytes: Optional[int] = None,
                 prefix_caching: bool = True, prefill_chunk: int = 0,
                 prefill_interleave: int = 1, kv_dtype=None,
                 ragged: bool = False):
        self.model = model
        self.plan = kv_cache.build_plan(model)
        if self.plan.paged_only and not paged:
            raise ValueError(
                "serve: latent pages, grouped K/V pages and per-slot state "
                "or window rings are kinds of the paged cache — pass "
                "paged=True")
        if max_len is None and self.plan.max_position >= 2 ** 30:
            raise ValueError(
                "serve: the model has no positional table to bound a slot "
                "— pass max_len")
        self.max_len = int(max_len or self.plan.max_position)
        if self.max_len > self.plan.max_position:
            raise ValueError(
                f"max_len {self.max_len} exceeds the model's positional "
                f"table ({self.plan.max_position})")
        self.prefill_chunk = int(prefill_chunk)
        self.prefill_interleave = int(prefill_interleave)
        if self.prefill_chunk:
            if (self.prefill_chunk < _MIN_PROMPT_PAD
                    or self.prefill_chunk & (self.prefill_chunk - 1)):
                raise ValueError(
                    f"prefill_chunk must be a power of two >= "
                    f"{_MIN_PROMPT_PAD}, got {prefill_chunk}")
            if not paged and self.max_len % self.prefill_chunk:
                raise ValueError(
                    f"prefill_chunk {self.prefill_chunk} must divide "
                    f"max_len {self.max_len} on the contiguous path — "
                    "chunk K/V writes are dynamic_update_slice windows "
                    "that must never run past the cache row")
        if self.prefill_interleave < 1:
            raise ValueError(
                f"prefill_interleave must be >= 1, got {prefill_interleave}")
        self.max_batch = int(max_batch)
        self.temperature = float(temperature)
        self.clock = clock or time.monotonic
        self._rng = np.random.default_rng(seed)
        # Mesh acquisition goes through the job runtime when a job scope
        # is active: the engine serves on its job's leased submesh slice
        # and its decode/prefill programs land in the pool-owned cache.
        self._job = _current_job()
        self._serial = next(_ENGINE_SERIALS)
        if self._job is not None:
            self.strategy = model.strategy or self._job.strategy
        else:
            self.strategy = model.strategy or get_strategy()

        variables = model.variables
        if variables is not None:
            # A trainer holds these and donates them to its next step:
            # the engine serves a snapshot of its own, through the
            # placement training uses.
            self.params = self.strategy.replicate(variables["params"])
        else:
            self.params = self._own(model.init(seed)["params"])
        self.paged = bool(paged)
        self.page_size = int(page_size)
        self.ragged = bool(ragged)
        if self.ragged and not self.paged:
            raise ValueError(
                "serve: ragged decode rides the page tables (one full-"
                "capacity program, per-slot masking) — pass paged=True")
        if kv_dtype is not None:
            if not self.paged:
                raise ValueError(
                    "serve: kv_dtype is a paged-pool knob — pass "
                    "paged=True (the contiguous cache keeps cache_dtype)")
            aliases = {"fp32": jnp.float32, "bf16": jnp.bfloat16}
            resolved = (aliases.get(kv_dtype, kv_dtype)
                        if isinstance(kv_dtype, str) else kv_dtype)
            dt = jnp.dtype(resolved)
            if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16),
                          jnp.dtype(jnp.int8)):
                raise ValueError(
                    f"serve: kv_dtype must be one of fp32/bf16/int8, "
                    f"got {kv_dtype!r}")
            cache_dtype = resolved
        self._kv_quant = (self.paged
                          and jnp.dtype(cache_dtype) == jnp.int8)
        if self.paged and self.plan.recurrent:
            # Pages hold what attention reads; a recurrent layer's state
            # is built over every token before it. Shared pages handed to
            # a slot whose state was not built over them would serve wrong
            # tokens, and this engine snapshots no state: no reuse.
            if prefix_caching:
                logger.info(
                    "serve: the model has recurrent or window layers — prefix "
                    "reuse is off (a slot's state is not in its pages)")
            prefix_caching = False
            metrics.set_gauge("serve.prefix.disabled_recurrent", 1.0)
        if self.paged:
            max_pages = -(-self.max_len // self.page_size)
            if num_pages is None and budget_bytes is not None:
                num_pages = kv_cache.pages_for_budget(
                    self.plan, page_size=self.page_size,
                    budget_bytes=budget_bytes, dtype=cache_dtype)
                if num_pages < 1:
                    raise ValueError(
                        f"serve: budget_bytes={budget_bytes} does not fit "
                        "even one page (plus scratch) at page_size="
                        f"{self.page_size}")
            if num_pages is None:
                num_pages = self.max_batch * max_pages
            self.num_pages = int(num_pages)
            self.cache = self.strategy.replicate(kv_cache.init_page_pool(
                self.plan, num_pages=self.num_pages,
                page_size=self.page_size, dtype=cache_dtype,
                budget_bytes=budget_bytes, slots=self.max_batch))
            # Decided once, here: the decode programs are built with the
            # answer and ``decode_prep`` counts pages by it.
            self._walks_pages = kv_cache.walks_pages(
                self.cache, max_pages,
                devices=self.strategy.mesh.devices.size)
            # Per-position pool bytes, derived from the page layout so
            # int8's fp32 scale rows are priced in (for float dtypes this
            # is exactly 2 * L * H * dk * itemsize).
            per_token = kv_cache.page_nbytes(
                self.plan, page_size=self.page_size,
                dtype=cache_dtype) // self.page_size
            self._paging = paging.PagedKVState(
                num_pages=self.num_pages, page_size=self.page_size,
                slots=self.max_batch, max_pages=max_pages,
                bytes_per_token=per_token, prefix_caching=prefix_caching,
                state_bytes_per_slot=kv_cache.state_nbytes_per_slot(
                    self.plan),
                window_bytes_per_slot=kv_cache.window_nbytes_per_slot(
                    self.plan, cache_dtype))
            logger.info(
                "serve: paged — %d slots, %d pages x %d positions "
                "(+scratch), pool %.1f MiB (%s), prefix caching %s, "
                "decode %s",
                self.max_batch, self.num_pages, self.page_size,
                kv_cache.page_pool_nbytes(
                    self.plan, num_pages=self.num_pages,
                    page_size=self.page_size, dtype=cache_dtype) / 2**20,
                jnp.dtype(cache_dtype).name,
                "on" if prefix_caching else "off",
                "ragged" if self.ragged else
                f"buckets {buckets or 'pow2'}")
        else:
            self._paging = None
            self.cache = self.strategy.replicate(kv_cache.init_cache(
                self.plan, max_batch=self.max_batch, max_len=self.max_len,
                dtype=cache_dtype, budget_bytes=budget_bytes))
            logger.info(
                "serve: %d slots x %d positions, KV cache %.1f MiB, "
                "buckets %s", self.max_batch, self.max_len,
                kv_cache.cache_nbytes(self.plan, max_batch=self.max_batch,
                                      max_len=self.max_len,
                                      dtype=cache_dtype) / 2**20,
                buckets or "pow2")

        self.scheduler = Scheduler(self.max_batch, buckets=buckets,
                                   policy=policy, max_queue=max_queue)
        # Host mirrors of per-slot decode state (compacted with the
        # scheduler's slot moves).
        self._tokens = np.zeros(self.max_batch, np.int32)
        self._lengths = np.zeros(self.max_batch, np.int32)
        self.finished: list[Request] = []
        #: Rounds of step() so far: the ``ident`` of a round's spans.
        self._round = 0
        #: Seconds this round has waited for the device (decode_wait and
        #: first_token_wait): what serve.step.host_s leaves out.
        self._waited_s = 0.0
        #: int8 prefill errors still on the device, read with the next
        #: read-back that happens anyway (recording runs only).
        self._pending_qerr: list = []
        #: Expert-routing counts of decode steps, likewise.
        self._pending_moe: list = []
        #: The round order follows what the engine can see: where the
        #: decode batch is the whole capacity and the pick is made on the
        #: device, the next decode's input tokens exist on the device
        #: before the host reads them, so a round dispatches its decode
        #: first and reads the previous one's picks after. A sampling
        #: engine needs its pick before the next input exists, and a
        #: bucketed or contiguous engine's batch can change between
        #: rounds: they read a step's picks in the round that made them.
        self._pipelined = self.paged and self.ragged and self.temperature <= 0
        #: Picks dispatched and not read yet, oldest first.
        self._unread: list[_Unread] = []
        #: Pipelined: this round's first tokens, still on the device, for
        #: the decode's input; the last decode's picks; the compaction
        #: swaps since it was dispatched (None: none).
        self._firsts: list = []
        self._picks = None
        self._order: Optional[np.ndarray] = None
        #: When the host last read a decode's picks.
        self._read_s: Optional[float] = None
        if self._pipelined:
            # What the input feed takes where no decode or no first token
            # is in flight, placed as a program's picks are.
            self._no_picks, self._no_pick = self.strategy.replicate(
                (np.zeros(self.max_batch, np.int32), np.zeros((), np.int32)))
            self._same_order = jnp.arange(self.max_batch, dtype=jnp.int32)

        # CPU XLA has no buffer donation — donating there only logs
        # warnings; on TPU the cache updates in place (no per-step copy).
        donate = (1,) if jax.default_backend() != "cpu" else ()
        self._decode_fns: dict[int, callable] = {}
        self._prefill_fns: dict[int, callable] = {}
        self._donate = donate
        self._swap_fn = jax.jit(kv_cache.swap_slots,
                                donate_argnums=(0,) if donate else ())
        self._paged_decode_fns: dict[int, callable] = {}
        self._paged_prefill_fns: dict[int, callable] = {}
        #: Contiguous chunked-prefill programs, one per pow2 chunk pad.
        #: (The paged chunked path reuses _paged_prefill_fns — the paged
        #: prefill kernel already takes a traced window start.)
        self._chunk_fns: dict[int, callable] = {}
        self._copy_fn = jax.jit(kv_cache.copy_page,
                                donate_argnums=(0,) if donate else ())
        #: Moves the per-slot recurrent state when compaction swaps slots
        #: (their pages move by a host pointer swap).
        self._swap_state_fn = jax.jit(kv_cache.swap_state,
                                      donate_argnums=(0,) if donate else ())

        # -- resilience state --------------------------------------------
        self.max_ttft_s = None if max_ttft_s is None else float(max_ttft_s)
        self.retry_budget = int(retry_budget)
        self.stall_timeout_s = (None if stall_timeout_s is None
                                else float(stall_timeout_s))
        self.stall_action = stall_action or _default_stall_action
        self.fault_injector = fault_injector
        self.virtual_step_s = float(virtual_step_s)
        self._step_ema_s: Optional[float] = None
        self._done_count = 0
        self._closed = False
        self.last_replay: Optional[dict] = None
        self.known_rids: set = set()
        if journal is None:
            self.journal: Optional[journal_lib.RequestJournal] = None
        elif isinstance(journal, journal_lib.RequestJournal):
            self.journal = journal
        else:
            # Directory path: the rotation threshold rides in from the
            # environment (the supervised-worker configuration channel).
            self.journal = journal_lib.RequestJournal(
                journal, max_bytes=journal_lib.journal_max_bytes_from_env())
        if self.journal is not None:
            self._recover_from_journal()
        metrics.set_gauge("serve.ready", 1.0)

    def _own(self, params):
        """Weights this engine has just initialised, which nobody else
        holds. Leaves that ``model.init`` left on the device, replicated
        over this mesh, are kept as they are, in the dtype they have: no
        trip through the host and no second copy beside weights that fill
        most of the chip. Anything else goes through
        ``strategy.replicate``."""
        want = mesh_lib.replicated(self.strategy.mesh)
        if jax.process_count() == 1 and all(
                isinstance(x, jax.Array) and x.is_fully_addressable
                and x.sharding.is_equivalent_to(want, x.ndim)
                for x in jax.tree_util.tree_leaves(params)):
            return params
        return self.strategy.replicate(params)

    # -- crash recovery -------------------------------------------------------

    def _recover_from_journal(self) -> None:
        """Replay an existing journal into the scheduler: formerly active
        requests first (arrival order, re-prefilled with their journaled
        tokens for a token-identical greedy continuation), then the queued
        ones; requests whose journaled tokens already satisfy their stop
        condition finish here; actives past the retry budget are shed."""
        t0 = time.monotonic()
        state = journal_lib.load(self.journal.path)
        self.known_rids = state.known_rids
        # Seed rid allocation from the full rid space — including rids a
        # rotation compacted away, which have no request record left to
        # bump the counter below. A fresh submit must never reuse one.
        self.scheduler._next_rid = max(self.scheduler._next_rid,
                                       state.next_rid)
        if not state.requests:
            return
        active, queued = state.pending()
        completed, replayed, shed = [], [], []
        for jr in active + queued:
            req = Request(prompt=list(jr.prompt),
                          max_new_tokens=jr.max_new_tokens,
                          eos_id=jr.eos_id, deadline_s=jr.deadline_s,
                          generated=list(jr.tokens), replays=jr.replays)
            if jr.stop_satisfied():
                # The work survived the crash; only its terminal record
                # was lost. Finish it now, never re-admit.
                req.rid = jr.rid
                req.status = DONE
                req.finish_reason = jr.implied_finish_reason()
                self.scheduler._next_rid = max(self.scheduler._next_rid,
                                               jr.rid + 1)
                self.finished.append(req)
                self.journal.record_finish(req)
                self._done_count += 1
                metrics.inc("serve.requests.completed")
                completed.append(jr.rid)
                continue
            if jr.tokens and jr.replays + 1 > self.retry_budget:
                req.rid = jr.rid
                self.scheduler._next_rid = max(self.scheduler._next_rid,
                                               jr.rid + 1)
                self._shed(req, "retry_budget", journaled=True)
                shed.append(jr.rid)
                continue
            # Deadlines re-arm relative to re-submission: the original
            # submit wall-clock is from a dead process.
            self.scheduler.submit(req, now=self.clock(), rid=jr.rid)
            replayed.append(jr.rid)
        replay_s = time.monotonic() - t0
        attempt = len(state.replay_markers) + 1
        self.last_replay = {
            "attempt": attempt,
            "active": [r.rid for r in active],
            "queued": [r.rid for r in queued],
            "replayed": replayed, "completed": completed, "shed": shed,
            "replay_s": replay_s,
        }
        self.journal.record_replay(
            attempt=attempt, queued=[r.rid for r in queued],
            active=[r.rid for r in active], completed=completed,
            replay_s=replay_s)
        metrics.observe_value("serve.journal.replay_s", replay_s)
        from tpu_dist.resilience import events
        events.maybe_log("serve_replay", attempt=attempt,
                         replayed=len(replayed), completed=len(completed),
                         shed=len(shed), replay_s=round(replay_s, 6))
        logger.info(
            "serve: journal replay #%d — %d re-admitted (%d were active), "
            "%d finished from journaled tokens, %d shed, %.3fs",
            attempt, len(replayed), len(active), len(completed),
            len(shed), replay_s)

    @classmethod
    def from_saved(cls, directory, **kwargs) -> "ServeEngine":
        """Load a ``save_model`` directory (weights restored, no training
        compile) and serve it."""
        from tpu_dist.models import serialize

        model = serialize.load_model(directory, compile=False)
        return cls(model, **kwargs)

    # -- compiled-program cache ----------------------------------------------

    def _acquire_program(self, kind: str, key, builder):
        """Build — or acquire — one compiled program. Solo engines build
        directly (the exact pre-jobs path); under an active job scope the
        program lives in the pool's MeshRuntime cache, keyed by job,
        model, and engine generation."""
        def build():
            metrics.inc("serve.programs.built")
            with profiler.span("serve.program.build", f"{kind}:{key}"):
                return builder()

        if self._job is None:
            return build()
        return self._job.runtime.cached(
            self._job.program_key(self.model.name, self._serial, kind, key),
            build)

    def _jit(self, body, **fixed):
        """The one place the engine compiles a program of ``kv_cache``
        that returns logits: its plan and ``fixed`` keywords bound, the
        greedy pick added (:func:`_picking`), the cache donated."""
        return jax.jit(_picking(functools.partial(body, self.plan, **fixed)),
                       donate_argnums=self._donate)

    def _decode_fn(self, bucket: int):
        fn = self._decode_fns.get(bucket)
        if fn is None:
            fn = self._acquire_program(
                "decode", bucket,
                lambda: self._jit(kv_cache.decode_step, bucket=bucket))
            self._decode_fns[bucket] = fn
        return fn

    def _prefill_fn(self, pad_len: int):
        fn = self._prefill_fns.get(pad_len)
        if fn is None:
            fn = self._acquire_program(
                "prefill", pad_len, lambda: self._jit(kv_cache.prefill))
            self._prefill_fns[pad_len] = fn
        return fn

    def _paged_decode_fn(self, bucket: int):
        fn = self._paged_decode_fns.get(bucket)
        if fn is None:
            if self.ragged:
                # One full-capacity program; ``bucket`` is always
                # max_batch here, kept as the cache key so
                # compiled_programs() reports the surface uniformly.
                fn = self._acquire_program(
                    "paged_decode_ragged", bucket,
                    lambda: self._jit(kv_cache.paged_decode_ragged,
                                      walk=self._walks_pages))
            else:
                fn = self._acquire_program(
                    "paged_decode", bucket,
                    lambda: self._jit(kv_cache.paged_decode_step,
                                      bucket=bucket,
                                      walk=self._walks_pages))
            self._paged_decode_fns[bucket] = fn
        return fn

    def _paged_prefill_fn(self, pad_len: int):
        fn = self._paged_prefill_fns.get(pad_len)
        if fn is None:
            fn = self._acquire_program(
                "paged_prefill", pad_len,
                lambda: self._jit(kv_cache.paged_prefill))
            self._paged_prefill_fns[pad_len] = fn
        return fn

    def _chunk_fn(self, pad_len: int):
        fn = self._chunk_fns.get(pad_len)
        if fn is None:
            fn = self._acquire_program(
                "prefill_chunk", pad_len,
                lambda: self._jit(kv_cache.prefill_chunk_step))
            self._chunk_fns[pad_len] = fn
        return fn

    def compiled_programs(self) -> dict:
        """{'decode': [buckets...], 'prefill': [pad_lens...]} — tests pin
        the no-retrace property on this. Paged engines report their
        ``paged_decode``/``paged_prefill`` surfaces too (a suffix prefill
        after a prefix hit pads to a smaller power of two, so warm and
        cold prefills land in different — but both steady — programs).
        Contiguous chunked engines add ``prefill_chunk``: one program per
        pow2 chunk pad (paged chunked engines run chunks through the
        ``paged_prefill`` surface — same traced-start programs). The
        default ``prefill_chunk=0`` leaves the dict bit-unchanged. Ragged
        paged engines report ``paged_decode == [max_batch]`` — exactly
        one full-capacity decode program, ever (tests pin it)."""
        out = {"decode": sorted(self._decode_fns),
               "prefill": sorted(self._prefill_fns)}
        if self.paged:
            out["paged_decode"] = sorted(self._paged_decode_fns)
            out["paged_prefill"] = sorted(self._paged_prefill_fns)
        if self.prefill_chunk and not self.paged:
            out["prefill_chunk"] = sorted(self._chunk_fns)
        return out

    # -- request intake -------------------------------------------------------

    def submit(self, prompt: Sequence[int], *, max_new_tokens: int = 32,
               eos_id: Optional[int] = None,
               deadline_s: Optional[float] = None) -> Request:
        prompt = [int(t) for t in prompt]
        if len(prompt) > self.max_len - 1:
            raise ValueError(
                f"prompt of {len(prompt)} tokens does not fit a "
                f"{self.max_len}-position cache slot (need >= 1 free)")
        if self.paged:
            # Reject a request that could never fit even an empty pool
            # now, loudly, instead of deadlocking admission later.
            self._paging.check_fits(
                min(len(prompt) + int(max_new_tokens), self.max_len))
        req = Request(prompt=prompt, max_new_tokens=int(max_new_tokens),
                      eos_id=eos_id, deadline_s=deadline_s)
        cause = self._shed_cause(req)
        if cause is not None:
            return self._shed(req, cause)
        self.scheduler.submit(req, now=self.clock())
        metrics.inc("serve.requests.submitted")
        if self.journal is not None:
            self.journal.record_submit(req)
        return req

    def adopt_request(self, prompt: Sequence[int], *,
                      generated: Sequence[int] = (),
                      max_new_tokens: int = 32,
                      eos_id: Optional[int] = None,
                      deadline_s: Optional[float] = None,
                      replays: int = 0) -> Request:
        """Adopt another engine's in-flight request (fleet failover).

        The caller — the fleet router, replaying a dead replica's journal
        onto a survivor — hands over the prompt plus every token the dead
        replica already emitted. The adopted request gets a FRESH rid from
        THIS engine's :meth:`Scheduler.reserve_rid` (two replicas' rid
        spaces overlap by construction, so the donor rid must never be
        pinned here), its full submit+token trail is re-journaled so a
        later crash of the survivor replays it like native work, and the
        greedy continuation re-prefills ``prompt + generated`` — token-
        identical to an uninterrupted run, same as solo journal recovery.

        Mirrors ``_recover_from_journal``'s edge handling: journaled
        tokens already satisfying the stop condition finish here without
        a slot; a request seen ACTIVE in more than ``retry_budget``
        crashes is shed (cause ``retry_budget``) instead of re-admitted.
        """
        prompt = [int(t) for t in prompt]
        if len(prompt) > self.max_len - 1:
            raise ValueError(
                f"prompt of {len(prompt)} tokens does not fit a "
                f"{self.max_len}-position cache slot (need >= 1 free)")
        if self.paged:
            self._paging.check_fits(
                min(len(prompt) + int(max_new_tokens), self.max_len))
        req = Request(prompt=prompt, max_new_tokens=int(max_new_tokens),
                      eos_id=eos_id, deadline_s=deadline_s,
                      generated=[int(t) for t in generated],
                      replays=int(replays))
        req.rid = self.scheduler.reserve_rid()
        if self.journal is not None:
            self.journal.record_submit(req)
            for t in req.generated:
                self.journal.record_token(req.rid, t)
        hit_eos = req.eos_id is not None and req.eos_id in req.generated
        if hit_eos or len(req.generated) >= req.max_new_tokens:
            # The donor's work was complete; only its terminal record
            # died with it. Finish without ever taking a slot.
            now = self.clock()
            req.status = DONE
            req.finish_reason = "eos" if hit_eos else "length"
            req.submit_s = now
            req.finish_s = now
            self.finished.append(req)
            if self.journal is not None:
                self.journal.record_finish(req)
            self._done_count += 1
            metrics.inc("serve.requests.completed")
            return req
        if req.generated and req.replays + 1 > self.retry_budget:
            return self._shed(req, "retry_budget", journaled=True)
        self.scheduler.submit(req, now=self.clock(), rid=req.rid)
        return req

    # -- overload protection --------------------------------------------------

    def _projected_ttft_s(self) -> float:
        """Conservative time-to-first-token estimate for a request joining
        the queue now: every token owed by work ahead of it (active
        remainders + whole queued requests), spread over ``max_batch``
        lanes, at the EMA decode-step time. 0.0 until the first decode
        step has been measured."""
        if self._step_ema_s is None:
            return 0.0
        owed = sum(max(r.max_new_tokens - len(r.generated), 0)
                   for r in self.scheduler.active())
        owed += sum(r.max_new_tokens for r in self.scheduler.queue)
        return (owed / self.max_batch) * self._step_ema_s

    def _shed_cause(self, req: Request) -> Optional[str]:
        """Admission control, cheapest check first: queue bound, then
        deadline feasibility (could this request meet its deadline even if
        admitted immediately?), then projected TTFT."""
        if self.scheduler.full():
            return "queue_full"
        projected = self._projected_ttft_s()
        if req.deadline_s is not None and self._step_ema_s is not None:
            need = projected + req.max_new_tokens * self._step_ema_s
            if need > req.deadline_s:
                return "deadline_unmeetable"
        if self.max_ttft_s is not None and projected > self.max_ttft_s:
            return "projected_ttft"
        return None

    def _shed(self, req: Request, cause: str, *,
              journaled: bool = False) -> Request:
        """Reject ``req`` at admission: terminal SHED state, never a slot.
        Journaled (submit + finish) so a post-crash replay does not
        resurrect it — shed is an answer, not a loss."""
        if req.rid < 0:
            req.rid = self.scheduler.reserve_rid()
        req.status = SHED
        req.finish_reason = "shed"
        req.shed_cause = cause
        now = self.clock()
        req.submit_s = req.submit_s or now
        req.finish_s = now
        self.finished.append(req)
        metrics.inc("serve.requests.shed")
        if self.journal is not None:
            if not journaled:
                self.journal.record_submit(req)
            self.journal.record_finish(req)
        logger.info("serve: shed request %d (%s)", req.rid, cause)
        return req

    # -- the pick ---------------------------------------------------------------

    def _pick(self, logits: _LogitsRow) -> int:
        """One token from one row. Greedy: the token the program picked;
        nothing crosses. ``temperature > 0``: the row is read (which
        brings the step's logits to the host, once a step) and sampled
        here, from the engine's seeded generator."""
        if self.temperature <= 0.0:
            metrics.inc("serve.pick.device")
            return logits.token
        metrics.inc("serve.pick.host_rows")
        logits = np.asarray(logits)
        z = logits.astype(np.float64) / self.temperature
        z -= z.max()
        p = np.exp(z)
        return int(self._rng.choice(logits.shape[-1], p=p / p.sum()))

    # -- the serving loop -----------------------------------------------------

    def _apply_swap(self, swap: Optional[tuple[int, int]]) -> None:
        if swap is None:
            return
        i, j = swap
        if self.paged:
            # Compaction under paging is a host page-table pointer swap;
            # a recurrent state is held by slot and moves on the device.
            self._paging.swap_slots(i, j)
            if self.plan.recurrent:
                with profiler.span("serve.step.state_swap", self._round):
                    self.cache = self._swap_state_fn(
                        self.cache, jnp.int32(i), jnp.int32(j))
        else:
            self.cache = self._swap_fn(self.cache, jnp.int32(i),
                                       jnp.int32(j))
        self._tokens[[i, j]] = self._tokens[[j, i]]
        self._lengths[[i, j]] = self._lengths[[j, i]]
        if self._picks is not None:
            # The decode in flight picked in the layout it was handed.
            if self._order is None:
                self._order = np.arange(self.max_batch, dtype=np.int32)
            self._order[[i, j]] = self._order[[j, i]]

    def _release_pages(self, req: Request) -> None:
        """Paged reclaim for a request that just left its slot: index its
        prompt's tail chunk for future prefix hits, then drop the slot's
        page references (compaction-free — freed pages go straight back
        on the free list). Must run BEFORE the mirrored slot swap, while
        the allocator row still belongs to this request."""
        if self.paged and req.released_slot is not None:
            # Bound prefix registration to positions actually written: a
            # request evicted mid-chunked-prefill holds allocated pages
            # past its cursor whose K/V are garbage.
            upto = min(req.prefill_pos, len(req.prompt))
            self._paging.finish(req.released_slot, req.prompt, upto=upto)
            req.released_slot = None

    def _retire(self, req: Request, *, now: float, status: str) -> None:
        swap = self.scheduler.finish(req, now=now, status=status)
        self._release_pages(req)
        self._apply_swap(swap)
        self.finished.append(req)
        if self.journal is not None:
            self.journal.record_finish(req)
        if status == DONE:
            self._done_count += 1
            metrics.inc("serve.requests.completed")
            if req.latency_s is not None:
                metrics.observe_value("serve.request.latency_s",
                                      req.latency_s)
            if req.ttft_s is not None:
                metrics.observe_value("serve.request.ttft_s", req.ttft_s)
        else:
            metrics.inc("serve.requests.evicted")

    def _total_tokens(self, req: Request) -> int:
        """Worst-case positions this request can occupy — the paged
        admission/reservation unit."""
        return min(len(req.prompt) + len(req.generated)
                   + max(req.max_new_tokens - len(req.generated), 0),
                   self.max_len)

    def _admission_gate(self, req: Request) -> bool:
        """Paged admission: a slot is only half the question — the pool
        must also hold this request's worst case. Reserving up front
        keeps every later incremental allocation (decode appends, COW
        clones) deadlock-free."""
        return self._paging.try_admit(self._total_tokens(req))

    def _unpack_prefill(self, out) -> tuple:
        """Unpack a prefill or chunk program's result: keep the cache,
        return the logits and the token picked from them (both still on
        the device). An int8 pool's program returns a fourth element, the
        call's max-abs dequantization error. It stays on the device unless
        the registry records, and is then read with the next read-back
        that happens anyway (:meth:`_to_host`), never with one of its
        own."""
        self.cache, logits, token, *qerr = out
        if qerr and metrics.enabled():
            self._pending_qerr.append(qerr[0])
        return logits, token

    def _state_slot(self, req: Request) -> tuple:
        """The extra argument of a paged prefill program whose plan has
        state layers: the slot whose state the chunk carries."""
        return (jnp.int32(req.slot),) if self.plan.recurrent else ()

    def _to_host(self, array, span_name: str):
        """The one place the host waits for the device: ``np.asarray`` of
        a program's result (or of a list of them, in one read-back), under
        ``span_name``: the token ids it picked, every step, or its logits,
        when somebody reads them. Prefill errors parked by
        :meth:`_unpack_prefill` ride along into ``serve.kv.quant_error``
        (host-side, after the traced program: SC103-clean)."""
        with profiler.span(span_name, self._round) as wait:
            # Blocks until the device is done.
            array = (jax.device_get(array) if isinstance(array, list)
                     else np.asarray(array))
        self._waited_s += wait.seconds
        if metrics.enabled():
            metrics.inc("serve.logits.bytes",
                        sum(a.nbytes for a in array)
                        if isinstance(array, list) else array.nbytes)
            for qerr in jax.device_get(self._pending_qerr):
                metrics.observe_value("serve.kv.quant_error", float(qerr))
            for (made, held, touched, fullest, walked, rows,
                 *keys) in jax.device_get(self._pending_moe):
                for name, n in zip(("serve.decode.keys_read",
                                    "serve.decode.window_keys_read"), keys):
                    metrics.inc(name, int(n))
                metrics.inc("serve.moe.assignments", int(made))
                metrics.inc("serve.moe.assignments_held", int(held))
                metrics.inc("serve.moe.experts_touched", int(touched))
                metrics.observe_value("serve.moe.load_max", float(fullest))
                metrics.inc("serve.moe.rows_multiplied", int(walked))
                metrics.inc("serve.moe.rows_sorted", int(rows))
        self._pending_qerr.clear()
        self._pending_moe.clear()
        return array

    def _upload(self, *arrays) -> list:
        """Host arrays to the device, counted in ``serve.upload.bytes``.
        Copies: dispatch is asynchronous, nothing waits for a mid-prompt
        chunk or a pipelined decode before the host moves on, and on the
        CPU ``jnp.asarray`` aliases a 64-byte-aligned numpy buffer, while
        the host resets, swaps and extends its table rows, lengths and
        masks in place."""
        if metrics.enabled():
            metrics.inc("serve.upload.bytes",
                        sum(a.nbytes for a in arrays))
        return [jnp.asarray(a.copy()) for a in arrays]

    def _prefill(self, req: Request) -> None:
        # A journal-recovered request re-prefills with prompt + everything
        # it had already generated: the incremental-decode ≡ full-forward
        # equivalence makes the greedy continuation token-identical to an
        # uninterrupted run (req.generated is empty on the normal path).
        # Under chunked prefill, the same holds because recovery re-admits
        # through THIS dispatch: the replayed sequence re-prefills through
        # the identical chunked path.
        if self.prefill_chunk:
            self._begin_chunked_prefill(req)
            return
        seq = list(req.prompt) + list(req.generated)
        plen = len(seq)
        if self.paged:
            setup = self._paging.begin(req.slot, seq,
                                       self._total_tokens(req))
            for src, dst in setup.copies:
                self.cache = self._copy_fn(self.cache, jnp.int32(src),
                                           jnp.int32(dst))
        # The whole prompt is one chunk: the same span as a chunk's.
        with profiler.span("serve.step.prefill_chunk", self._round):
            if self.paged:
                suffix = plen - setup.start
                pad = _pad_to_pow2(suffix, hi=self.max_len)
                tokens = np.zeros(pad, np.int32)
                tokens[:suffix] = seq[setup.start:]
                fn = self._paged_prefill_fn(pad)
                row, toks = self._upload(
                    self._paging.allocator.table[req.slot], tokens)
                last = self._unpack_prefill(
                    fn(self.params, self.cache, row, toks, jnp.int32(plen),
                       jnp.int32(setup.start), *self._state_slot(req)))
                self._paging.register_prefill(req.slot, req.prompt)
            else:
                pad = _pad_to_pow2(plen, hi=self.max_len)
                tokens = np.zeros(pad, np.int32)
                tokens[:plen] = seq
                fn = self._prefill_fn(pad)
                last = self._unpack_prefill(
                    fn(self.params, self.cache, *self._upload(tokens),
                       jnp.int32(plen), jnp.int32(req.slot)))
            req.prefill_pos = plen
            self._first_token(req, last, plen)

    def _first_token(self, req: Request, last: tuple, plen: int) -> None:
        """The end of a prefill: the token picked at the last position is
        the first generated one. Round by round it is read back here,
        stamped and emitted; a pipelined round leaves it on the device for
        the decode's input and reads it with the decode's read-back."""
        logits, token = last
        self._lengths[req.slot] = plen
        req.unread += 1
        unread = _Unread(token, logits, [(req, ())])
        if self._pipelined:
            self._unread.append(unread)
            self._firsts.append((req, token))
            return
        self._take([unread], [self._to_host(token,
                                             "serve.step.first_token_wait")])

    def _begin_chunked_prefill(self, req: Request) -> None:
        """Admission under ``prefill_chunk > 0``: set up the slot (page
        table + prefix-cache attach in paged mode — allocation is
        chunk-granular from here on) and put the request on the chunk
        queue. No forward pass runs yet; :meth:`step` drains chunks
        interleaved with decode."""
        seq = list(req.prompt) + list(req.generated)
        if self.paged:
            setup = self._paging.begin(req.slot, seq,
                                       self._total_tokens(req),
                                       chunk=self.prefill_chunk)
            for src, dst in setup.copies:
                self.cache = self._copy_fn(self.cache, jnp.int32(src),
                                           jnp.int32(dst))
            req.prefill_pos = setup.start
        else:
            req.prefill_pos = 0
        # Mirror the cursor: a mid-prefill slot rides inside the decode
        # bucket, so decode scatters one garbage K/V write at exactly
        # lengths[slot] — the next unwritten position, which the next
        # chunk (or, on the final chunk's completion, a real append)
        # overwrites before any validity mask admits it.
        self._tokens[req.slot] = 0
        self._lengths[req.slot] = req.prefill_pos
        self.scheduler.enqueue_prefill(req)

    def _prefill_chunk_one(self, req: Request) -> None:
        """Run ONE chunk of ``req``'s prefill: positions
        ``[prefill_pos, min(prefill_pos + prefill_chunk, plen))``. The
        final chunk yields the last valid position's logits — the first
        generated token — and moves the request into the decode set."""
        with profiler.span("serve.step.prefill_chunk", self._round):
            seq = list(req.prompt) + list(req.generated)
            plen = len(seq)
            startpos = req.prefill_pos
            end = min(startpos + self.prefill_chunk, plen)
            valid = end - startpos
            pad = _pad_to_pow2(valid, hi=self.prefill_chunk)
            tokens = np.zeros(pad, np.int32)
            tokens[:valid] = seq[startpos:end]
            if self.paged:
                self._paging.extend_prefill(req.slot, end)
                fn = self._paged_prefill_fn(pad)
                row, toks = self._upload(
                    self._paging.allocator.table[req.slot], tokens)
                last = self._unpack_prefill(
                    fn(self.params, self.cache, row, toks, jnp.int32(end),
                       jnp.int32(startpos), *self._state_slot(req)))
                if self.plan.state_layers:
                    metrics.inc("serve.prefill.scan_chunks",
                                self.plan.state_layers
                                * -(-pad // hybrid.SCAN_BLOCK))
                if self.plan.kv_heads:
                    # Full layers, a chunk: the key blocks walked against
                    # the positions the table row addresses.
                    metrics.inc("serve.prefill.keys_visited",
                                self.plan.num_layers
                                * kv_cache.prefill_keys_visited(
                                    len(row), self.page_size, end))
                    metrics.inc("serve.prefill.keys_addressed",
                                self.plan.num_layers * len(row)
                                * self.page_size)
            else:
                fn = self._chunk_fn(pad)
                last = self._unpack_prefill(
                    fn(self.params, self.cache, *self._upload(tokens),
                       jnp.int32(end), jnp.int32(req.slot),
                       jnp.int32(startpos)))
            req.prefill_pos = end
            self._lengths[req.slot] = end
            metrics.inc("serve.prefill.chunks")
            if end < plen:
                return  # more chunks owed; a mid-chunk's pick is unused
            self.scheduler.dequeue_prefill(req)
            if self.paged:
                self._paging.register_prefill(req.slot, req.prompt)
            self._first_token(req, last, plen)

    def step(self) -> int:
        """One scheduling round: deadline evictions → admissions → at most
        ``prefill_interleave`` prefill chunks → one decode step over the
        slots still owed a token. Returns the number of still-active
        requests.

        Round by round, a step reads each program's picks in the round
        that made it: a last chunk's first token before the decode, the
        decode's picks right after it. A PIPELINED engine (greedy, paged,
        ragged) dispatches round n's decode before it reads anything: its
        input tokens are made on the device from round n - 1's decode and
        this round's first tokens (:func:`_next_inputs`), its lengths
        advance at dispatch, and a request whose last token is in flight
        sits the round out. Then it reads round n - 1's picks and this
        round's first tokens in one read-back, records them (stamped when
        the host has them) and retires the finished requests, while the
        device runs round n. Their requests stay ``active`` until then, so
        :meth:`run_until_idle` drains the round in flight. A request that
        finished by EOS, or was evicted, with a decode in flight was
        decoded once more than needed: that pick is dropped and counted in
        ``serve.decode.rows_discarded``; its K/V write lands in pages its
        admission reserved, and the state or ring row it leaves is reset by
        the next prefill of that slot.

        Durability contract: everything journaled this round (submits,
        tokens, finishes) is flushed — one append + fsync — at the END of
        the round, after the fault-injector seams, so an injected crash
        loses the unflushed tail and recovery must regenerate it (the
        harsher ordering for the parity gate)."""
        self._round += 1
        self._waited_s = 0.0
        with profiler.span("serve.step", self._round) as whole:
            active = self._round_body()
        if metrics.enabled():
            metrics.inc("serve.step.rounds")
            # What the host did itself: the round less its waits for the
            # device. Round by round the device idles for most of it; a
            # pipelined round does it while the device runs its decode.
            metrics.observe_value("serve.step.host_s",
                                  whole.seconds - self._waited_s)
        return active

    def _flush_journal(self) -> None:
        if self.journal is not None:
            with profiler.span("serve.step.journal_flush", self._round):
                self.journal.flush()

    def _decoding(self) -> list[Request]:
        """This round's decode rows: fully prefilled requests still owed a
        token once the picks in flight are counted and whose slot has room
        for one more position."""
        return [r for r in self.scheduler.ready()
                if len(r.generated) + r.unread < r.max_new_tokens
                and self._lengths[r.slot] < self.max_len]

    def _round_body(self) -> int:
        rnd = self._round
        with profiler.span("serve.step.admit", rnd):
            now = self.clock()
            for req, swap in self.scheduler.evict_deadline(now=now):
                self._release_pages(req)
                self._apply_swap(swap)
                self.finished.append(req)
                metrics.inc("serve.requests.evicted")
                if self.journal is not None:
                    self.journal.record_finish(req)

            gate = self._admission_gate if self.paged else None
            for req in self.scheduler.admit(gate=gate, now=now):
                self._prefill(req)
            metrics.set_gauge("serve.queue.depth",
                              self.scheduler.queue_depth())

        if self.prefill_chunk:
            # Interleave policy: at most ``prefill_interleave`` prefill
            # chunks between consecutive decode steps, drained
            # arrival-ordered from the head of the chunk queue.
            for _ in range(self.prefill_interleave):
                head = self.scheduler.peek_prefill()
                if head is None:
                    break
                self._prefill_chunk_one(head)

        if self.paged:
            self._paging.note_usage()
        decoding = self._decoding()
        if not decoding and not self._unread:
            self._picks = self._order = None
            self._flush_journal()
            return self.scheduler.num_active
        if decoding:
            fn, args, bucket = self._prepare_decode(decoding)
        timer = None
        if self.stall_timeout_s is not None:
            info = {"timeout_s": self.stall_timeout_s,
                    "bucket": bucket if decoding else None,
                    "active": self.scheduler.num_active}
            timer = threading.Timer(self.stall_timeout_s,
                                    self.stall_action, args=(info,))
            timer.daemon = True
            timer.start()
        try:
            if decoding:
                self._dispatch_decode(fn, args, decoding)
                if self.fault_injector is not None:
                    # Inside the watchdog window on purpose: a decode_stall
                    # fault must look exactly like a hung runtime call.
                    self.fault_injector.on_decode()
            else:
                self._picks = self._order = None
            self._firsts.clear()
            # A pipelined round leaves the decode it just dispatched
            # running and reads everything before it.
            ahead = 1 if decoding and self._pipelined else 0
            reads = self._unread[:len(self._unread) - ahead]
            del self._unread[:len(reads)]
            picks = self._read(reads)
        finally:
            if timer is not None:
                timer.cancel()
        with profiler.span("serve.step.pick", rnd):
            self._take(reads, picks)
            if not self.scheduler.num_active and self._unread:
                # Every request finished with a decode in flight (EOS or
                # eviction): its picks are nobody's, and nothing reads them.
                for unread in self._unread:
                    for req, _ in unread.rows:
                        req.unread -= 1
                    metrics.inc("serve.decode.rows_discarded",
                                len(unread.rows))
                self._unread.clear()
                self._picks = self._order = None
        if self.fault_injector is not None:
            self.fault_injector.on_step_end(self._done_count)
        self._flush_journal()
        return self.scheduler.num_active

    def _prepare_decode(self, decoding: list[Request]):
        """Host-side page bookkeeping and the uploads of a decode step:
        returns its program, its arguments after the weights and the
        cache, and its bucket."""
        # Ragged mode decodes the whole slot capacity in one program —
        # the scheduler's pow2 bucket is never consulted, so occupancy
        # is measured against true capacity.
        bucket = (self.max_batch if self.paged and self.ragged
                  else self.scheduler.bucket())
        metrics.observe_value("serve.batch.occupancy",
                              len(decoding) / bucket)
        with profiler.span("serve.step.decode_prep", self._round):
            if self.paged:
                # Host-side page bookkeeping for this round's appends:
                # cross a page boundary -> allocate the next page (covered
                # by the admission reservation); tail page shared with the
                # prefix cache -> copy-on-write it private before the
                # scatter.
                for req in decoding:
                    for src, dst in self._paging.prepare_append(
                            req.slot, int(self._lengths[req.slot])):
                        self.cache = self._copy_fn(
                            self.cache, jnp.int32(src), jnp.int32(dst))
            host = [self._lengths]
            if self.paged:
                fn = self._paged_decode_fn(bucket)
                table = self._paging.allocator.table
                host.insert(0, table)
                if metrics.enabled():
                    # Pages this step's attention reads against those
                    # its table rows address: the kernel stops at each
                    # decoding slot's length, the XLA body gathers every
                    # row of the bucket whole.
                    addressed = bucket * table.shape[1]
                    metrics.inc("serve.decode.pages_addressed", addressed)
                    metrics.inc(
                        "serve.decode.pages_read",
                        sum(int(self._lengths[req.slot]) // self.page_size
                            + 1 for req in decoding)
                        if self._walks_pages else addressed)
            else:
                fn = self._decode_fn(bucket)
            if self.paged and self.ragged:
                # Per-slot active mask: only decoding slots write to their
                # real tail pages — empty slots, slots mid-chunked-prefill
                # (whose table rows hold real pages a stray decode write
                # must not touch) and slots whose last token is in flight
                # route their garbage write to the scratch page inside the
                # kernel.
                active = np.zeros(self.max_batch, bool)
                for req in decoding:
                    active[req.slot] = True
                host.append(active)
                if self.plan.state_layers and metrics.enabled():
                    # Slots of a state layer this step reads and writes:
                    # whole blocks up to its highest decoding slot.
                    metrics.inc("serve.state.slots_visited",
                                kv_cache.state_slots_visited(
                                    max(req.slot for req in decoding) + 1,
                                    self.max_batch))
                    metrics.inc("serve.state.slots_addressed",
                                self.max_batch)
            args = self._upload(*host)
            tokens = (self._decode_tokens() if self._pipelined
                      else self._upload(self._tokens)[0])
            args.insert(1 if self.paged else 0, tokens)
        return fn, args, bucket

    def _decode_tokens(self):
        """A pipelined decode's input tokens, on the device: the previous
        decode's picks in the slots compaction has moved them to since,
        with this round's first tokens put in at their slots."""
        tokens = self._no_picks if self._picks is None else self._picks
        if self._order is None and not self._firsts:
            return tokens
        order = (self._same_order if self._order is None
                 else self._upload(self._order)[0])
        for req, token in self._firsts or [(None, self._no_pick)]:
            slot = self.max_batch if req is None else req.slot
            tokens = _next_inputs(tokens, order, jnp.int32(slot), token)
            order = self._same_order
        return tokens

    def _dispatch_decode(self, fn, args, decoding: list[Request]) -> None:
        if any(u.dispatched_s is not None for u in self._unread):
            metrics.inc("serve.decode.overlapped")
        dispatched_s = self.clock()
        with profiler.span("serve.step.decode_dispatch", self._round):
            self.cache, logits, tokens, *stats = fn(
                self.params, self.cache, *args)
        metrics.inc("serve.decode.steps")
        for req in decoding:
            req.unread += 1
            self._lengths[req.slot] += 1
        self._unread.append(_Unread(
            tokens, logits, [(req, req.slot) for req in decoding],
            dispatched_s, stats[0] if stats and metrics.enabled() else None))
        if self._pipelined:
            self._picks, self._order = tokens, None

    def _read(self, reads: list[_Unread]) -> list:
        """One read-back of the picks in ``reads``. A decode's counters
        ride along, and the decode-step estimate behind admission takes
        the time from its dispatch, or from the read of the decode before
        it where that came later, to this read: the period between two
        read-backs when rounds are pipelined."""
        if not reads:
            return []
        self._pending_moe.extend(u.stats for u in reads
                                 if u.stats is not None)
        picks = self._to_host([u.tokens for u in reads],
                              "serve.step.decode_wait")
        for unread in reads:
            if unread.dispatched_s is None:
                continue
            if self.virtual_step_s > 0.0 and hasattr(self.clock, "advance"):
                self.clock.advance(self.virtual_step_s)
            now = self.clock()
            dt = now - max(unread.dispatched_s, self._read_s or 0.0)
            self._read_s = now
            if dt > 0.0:
                self._step_ema_s = (dt if self._step_ema_s is None else
                                    _EMA_ALPHA * dt
                                    + (1.0 - _EMA_ALPHA) * self._step_ema_s)
        return picks

    def _take(self, reads: list[_Unread], picks: list) -> None:
        """Emit the picks just read, in the order they were dispatched:
        stamp each token once the host has it (after the read and the
        pick, never at dispatch: the pre-readback stamp under-reported
        TTFT against any client's clock), journal it, and retire the
        requests it finishes, highest slot first (each swap moves the
        untouched last slot)."""
        completed, now = [], None
        for unread, tokens in zip(reads, picks):
            picked = _Picked(self, unread.logits, tokens)
            for req, index in unread.rows:
                req.unread -= 1
                if req.status != ACTIVE:
                    # Finished or evicted while this pick was in flight.
                    metrics.inc("serve.decode.rows_discarded")
                    continue
                token = self._pick(_LogitsRow(picked, index))
                now = self.clock()
                done = self.scheduler.record_token(req, token, now=now)
                metrics.inc("serve.tokens.generated")
                if self.journal is not None:
                    self.journal.record_token(req.rid, token)
                self._tokens[req.slot] = token
                # A slot of max_len positions holds the prompt and all but
                # the newest token.
                if (done or len(req.prompt) + len(req.generated)
                        > self.max_len):
                    completed.append(req)
        for req in sorted(completed, key=lambda r: r.slot, reverse=True):
            self._retire(req, now=now, status=DONE)

    def run_until_idle(self, *, max_steps: int = 100_000) -> list[Request]:
        """Drive :meth:`step` until queue and batch drain; returns all
        requests finished so far (done + evicted, completion order)."""
        steps = 0
        while not self.scheduler.idle():
            self.step()
            steps += 1
            if steps >= max_steps:
                raise RuntimeError(
                    f"serve loop still busy after {max_steps} steps "
                    f"({self.scheduler.num_active} active, "
                    f"{self.scheduler.queue_depth()} queued)")
        return self.finished

    def generate(self, prompt: Sequence[int], *, max_new_tokens: int = 32,
                 eos_id: Optional[int] = None) -> list[int]:
        """Single-request convenience: submit, drain, return the tokens."""
        req = self.submit(prompt, max_new_tokens=max_new_tokens,
                          eos_id=eos_id)
        self.run_until_idle()
        return req.generated

    # -- lifecycle ------------------------------------------------------------

    def close(self) -> None:
        """Flush + close the journal and drop readiness. Idempotent; a
        crash skips it by definition — that is what recovery is for."""
        if self._closed:
            return
        self._closed = True
        metrics.set_gauge("serve.ready", 0.0)
        if self.journal is not None:
            self.journal.close()

    def __enter__(self) -> "ServeEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
