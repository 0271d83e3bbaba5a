"""Profiling hooks: TensorBoard-compatible traces and the program's one span.

The reference's observability surface is the chief's TensorBoard duty
(README.md:51; SURVEY.md §5.1) — profiling was the era's Keras progbar timing
plus an uninvoked TF profiler. TPU-native: ``jax.profiler`` writes XLA/TPU
traces (HLO timelines, ICI collective activity) viewable in TensorBoard or
Perfetto; :func:`trace` wraps a fit/eval span, :func:`step_annotation` marks
step boundaries so the trace viewer aligns host dispatch with device work,
and :func:`span` is the only way the program records a host span: free when
nothing records, otherwise on the host line of the same trace and clock as
the device events, in the ``span.<name>.s`` distribution and in the
registry's span ring.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
import threading
import time
from typing import Iterator, Optional

from tpu_dist.observe import metrics

logger = logging.getLogger("tpu_dist.profiler")

#: True while a trace span is open in this process — lets hot loops skip
#: annotation overhead entirely when nothing is recording.
_ACTIVE = False

#: Prefix of every span's name on the profiler's host line.
TRACE_PREFIX = "tpu_dist."


def is_active() -> bool:
    return _ACTIVE


@contextlib.contextmanager
def trace(logdir: str | os.PathLike, *, chief_only: bool = True) -> Iterator[None]:
    """Capture a jax.profiler trace for the enclosed span.

    ``chief_only`` matches the reference's "chief generates TensorBoard"
    division of labor (README.md:51): non-chief processes run the body
    untraced.
    """
    import jax

    from tpu_dist.cluster import bootstrap

    if chief_only and not bootstrap.is_chief():
        yield
        return
    global _ACTIVE
    logdir = str(logdir)
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    _ACTIVE = True
    logger.info("profiler trace started -> %s", logdir)
    try:
        yield
    finally:
        _ACTIVE = False
        jax.profiler.stop_trace()
        logger.info("profiler trace written -> %s", logdir)


def step_annotation(step: int):
    """Context manager annotating one train step in the trace timeline.

    Free when no trace is active (returns a null context)."""
    if not _ACTIVE:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.StepTraceAnnotation("train", step_num=step)


class _NullSpan:
    """What :func:`span` hands out while nothing records: one shared
    object, no clock read, no allocation."""

    __slots__ = ()
    seconds = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


NULL_SPAN = _NullSpan()

_open_spans = threading.local()


def current_span() -> Optional["_Span"]:
    """The innermost span open on this thread, or None."""
    stack = getattr(_open_spans, "stack", None)
    return stack[-1] if stack else None


class _Span:
    """One recording span: a ``TraceAnnotation`` on the profiler's host
    line and, where the registry records, a ``span.<name>.s`` observation
    and a ring record naming the enclosing span of this thread."""

    __slots__ = ("name", "ident", "id", "parent", "start", "seconds",
                 "_registry", "_annotation")

    def __init__(self, name: str, ident, registry):
        import jax

        self.name, self.ident, self._registry = name, ident, registry
        self.id = registry.next_span_id()
        self.seconds = 0.0
        self._annotation = jax.profiler.TraceAnnotation(TRACE_PREFIX + name)

    def __enter__(self):
        stack = getattr(_open_spans, "stack", None)
        if stack is None:
            stack = _open_spans.stack = []
        self.parent = stack[-1].id if stack else None
        stack.append(self)
        self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        self.seconds = end - self.start
        self._annotation.__exit__(*exc)
        _open_spans.stack.pop()
        registry = self._registry
        if registry.enabled:
            registry.distribution(f"span.{self.name}.s").observe(self.seconds)
            registry.record_span(self.name, self.start, end,
                                 parent=self.parent, ident=self.ident,
                                 span_id=self.id)
        return None


def span(name: str, ident=None):
    """Context manager around one phase of the host's work.

    While the default registry is disabled and no :func:`trace` is open it
    returns :data:`NULL_SPAN`. Otherwise the phase lies on the host line of
    the profiler's trace as ``tpu_dist.<name>`` and, where the registry is
    enabled, its wall time is observed as ``span.<name>.s`` and appended to
    the registry's span ring with its parent (the enclosing span of this
    thread) and ``ident`` (the round, the step or the request the spans of
    one piece of work share). ``seconds`` holds the duration after exit
    (0.0 on the null span)."""
    if not (metrics.enabled() or _ACTIVE):
        return NULL_SPAN
    return _Span(name, ident, metrics.get_registry())


def spanned(name: str):
    """Decorator form of :func:`span`, for a set-up function that is one
    phase from its first line to its last."""
    def decorate(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return wrapper
    return decorate
