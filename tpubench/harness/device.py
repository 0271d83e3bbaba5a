"""The chip: find it or fail, name it, read its memory, meter compiles."""

from __future__ import annotations

import os
import pathlib


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def configure_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    the machine names one (``JAX_COMPILATION_CACHE_DIR``). The same rule
    ``tpu_dist.utils.compile_cache`` applies, kept here so that the
    reference's programs are cached before the program is imported."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = str(pathlib.Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_chips(chips: int, *, rehearse: bool) -> dict:
    """The device as JAX reports it. Without ``rehearse`` anything but a
    TPU with at least ``chips`` chips raises :class:`NoChip`."""
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": chips}
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s), jax reports "
                     f"{len(devices)} {info['platform']} device(s)")
    if info["platform"] != "tpu" and not rehearse:
        raise NoChip(f"jax found no TPU (platform {info['platform']!r}); "
                     "the benchmark measures on the chip only")
    return info


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class CompileMeter:
    """What jax reports about compilation, summed since construction
    (copied from ``chip_smoke.py``): compile requests, persistent-cache
    hits, and seconds in the backend compiler (on a hit, the time to load
    the entry)."""

    def __init__(self):
        import jax.monitoring

        self.requests = self.hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def read(self) -> dict:
        return {"requests": self.requests, "hits": self.hits,
                "compile_s": self.compile_s}
