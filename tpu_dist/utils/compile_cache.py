"""Persistent XLA compile cache, placed from outside the program.

A cold process compiles every program it runs, and on the chip that is
most of a short run. JAX keeps compiled programs on disk when told where;
the directory is part of the cache key, so it must never move between
runs. The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set — whoever launched the process chose
  the place. JAX reads the variable itself; this code sets no directory.
* unset — the cache goes to ``.jax_cache`` at the root of the checkout
  (:data:`DEFAULT_DIR`, listed in ``.gitignore``): one fixed path, never
  a temporary directory, a pid or a timestamp.

Either way the minimum compile time for an entry drops to zero, so the
small programs (slot swaps, page copies, metric resets) are kept too.

:func:`configure` runs once, when ``tpu_dist`` is imported — the one seam
every entry point passes (``chip_smoke.py``, ``bench.py``, the
``python -m tpu_dist.*`` mains, the examples) and early enough that no
program has been compiled yet. It touches no backend and opens no file;
JAX creates the directory at the first write.

The same call registers the program's one ``jax.monitoring`` listener
(:class:`CompileMeter`, read through :func:`meter`): what jax itself
reports about compilation, which is where "which step recompiled" is
answered — ``jax.jit`` only wraps a function, the compile comes at the
first call.
"""

from __future__ import annotations

import os
import pathlib
import time
from typing import Optional

from tpu_dist.observe import metrics

#: The fixed in-checkout cache directory used when the environment names
#: none.
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")


class CompileMeter:
    """What jax reports about compilation, summed since :func:`configure`:
    compile requests, persistent-cache hits, and seconds in the backend
    compiler (on a hit, the time to load the entry). While the registry
    records it also counts ``compile.requests`` and ``compile.cache_hits``,
    observes ``compile.trace_s``, ``compile.lower_s`` and
    ``compile.backend_s``, and leaves one ``compile`` record in the span
    ring for each backend compile, under the span open at that moment."""

    _EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "cache_hits"}
    _DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_s"}

    def __init__(self):
        import jax.monitoring

        self.requests = self.cache_hits = 0
        self.backend_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        key = self._EVENTS.get(event)
        if key is None:
            return
        setattr(self, key, getattr(self, key) + 1)
        if metrics.enabled():
            metrics.inc(f"compile.{key}")

    def _duration(self, event, secs, **kwargs):
        key = self._DURATIONS.get(event)
        if key is None:
            return
        if key == "backend_s":
            self.backend_s += secs
        if not metrics.enabled():
            return
        metrics.observe_value(f"compile.{key}", secs)
        if key == "backend_s":
            from tpu_dist.utils import profiler

            now = time.perf_counter()
            parent = profiler.current_span()
            metrics.record_span(
                "compile", now - secs, now,
                parent=None if parent is None else parent.id,
                ident=kwargs.get("fun_name"))

    def read(self) -> tuple[int, int, float]:
        """(compile requests, cache hits, backend seconds) so far."""
        return self.requests, self.cache_hits, self.backend_s


_METER: Optional[CompileMeter] = None


def meter() -> CompileMeter:
    """The process's one listener; :func:`configure` registered it."""
    if _METER is None:
        raise RuntimeError("compile_cache.configure() has not run")
    return _METER


def configure() -> str:
    """Apply the rule above; returns the directory in effect. Idempotent."""
    import jax

    global _METER
    if _METER is None:
        _METER = CompileMeter()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
