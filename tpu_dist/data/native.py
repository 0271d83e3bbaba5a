"""Native (C++) input-pipeline core with transparent numpy fallback.

Reference parity: TF's input pipeline executes in C++ tf.data kernels
(SURVEY.md D13 marks the pipeline "Python + C++"); this module is tpu-dist's
native loader core. The hot host-side path — assemble a shuffled, normalized
global batch from an in-memory array dataset — is one fused multithreaded C++
pass (``loader.cpp``): gather rows by shuffled index and convert
uint8 -> float32 * scale in the same sweep, exactly the work of the
reference's ``.map(scale) ... .shuffle(...).batch(...)`` chain
(tf_dist_example.py:20-33).

The extension compiles lazily with g++ the first time it's needed and caches
the .so next to the source; without a toolchain everything falls back to
numpy with identical results (the shuffle is seeded SplitMix64 Fisher-Yates
in both paths, so batches are bit-identical native or not).

    ds = native_pipeline("mnist", global_batch_size=128, seed=0)
    model.fit(ds, epochs=10, steps_per_epoch=20)
"""

from __future__ import annotations

import ctypes
import logging
import os
import pathlib
import subprocess
import threading
from typing import Optional

import numpy as np

logger = logging.getLogger("tpu_dist.native")

_SRC_DIR = pathlib.Path(__file__).parent / "_native"
_SO_PATH = _SRC_DIR / "libtpu_dist_loader.so"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _build() -> Optional[pathlib.Path]:
    # Compile to a per-process temp file, then os.replace() it into place:
    # several workers on one host may race the first build, and replace() is
    # atomic so no process can ever CDLL a half-written .so.
    src = _SRC_DIR / "loader.cpp"
    tmp = _SO_PATH.with_suffix(f".so.tmp.{os.getpid()}")
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-pthread", str(src),
           "-o", str(tmp)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO_PATH)
        logger.info("built native loader: %s", _SO_PATH)
        return _SO_PATH
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        logger.warning("native loader build failed (%s %s); using numpy "
                       "fallback", e, detail.decode(errors="replace")[:500])
        return None
    finally:
        tmp.unlink(missing_ok=True)


def _load() -> Optional[ctypes.CDLL]:
    """The loader library, building it on first use; None => numpy fallback."""
    global _lib, _build_failed
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        src = _SRC_DIR / "loader.cpp"
        stale = (_SO_PATH.exists() and src.exists()
                 and src.stat().st_mtime > _SO_PATH.stat().st_mtime)
        if _SO_PATH.exists() and not stale:
            path = _SO_PATH
        else:
            path = _build()
            if path is None and _SO_PATH.exists():
                # Rebuild failed (e.g. no toolchain) but a prebuilt — possibly
                # stale — library exists: keep using it rather than losing the
                # native path entirely.
                logger.warning("using existing (possibly stale) %s", _SO_PATH)
                path = _SO_PATH
        if path is None:
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(str(path))
        except OSError as e:
            # A corrupt/foreign .so must degrade to the numpy fallback, not
            # propagate out of the data pipeline.
            logger.warning("loading native loader %s failed (%s); using "
                           "numpy fallback", path, e)
            _build_failed = True
            return None
        lib.tpu_dist_gather_scale_u8_f32.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_float, ctypes.c_void_p, ctypes.c_int]
        lib.tpu_dist_gather_i64.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p]
        lib.tpu_dist_shuffled_indices.argtypes = [
            ctypes.c_int64, ctypes.c_uint64, ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


# -- primitive ops (native with numpy fallback, identical semantics) ----------


def shuffled_indices(n: int, seed: int) -> np.ndarray:
    """Seeded Fisher-Yates permutation of [0, n) — same stream native or not."""
    out = np.empty(n, dtype=np.int64)
    lib = _load()
    if lib is not None:
        lib.tpu_dist_shuffled_indices(
            n, ctypes.c_uint64(seed & (2**64 - 1)),
            out.ctypes.data_as(ctypes.c_void_p))
        return out
    # Pure-python fallback: identical SplitMix64 Fisher-Yates stream.
    out[:] = np.arange(n, dtype=np.int64)
    mask = (1 << 64) - 1
    state = seed & mask
    for i in range(n - 1, 0, -1):
        state = (state + 0x9E3779B97F4A7C15) & mask
        z = state
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & mask
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & mask
        z = z ^ (z >> 31)
        j = z % (i + 1)
        tmp = int(out[i])
        out[i] = out[j]
        out[j] = tmp
    return out


def gather_scale(images: np.ndarray, idx: np.ndarray, scale: float,
                 n_threads: int | None = None) -> np.ndarray:
    """out[i] = float32(images[idx[i]]) * scale, fused gather+normalize."""
    images = np.ascontiguousarray(images)
    idx = np.ascontiguousarray(idx, dtype=np.int64)
    row_elems = int(np.prod(images.shape[1:], dtype=np.int64))
    out = np.empty((len(idx), *images.shape[1:]), dtype=np.float32)
    lib = _load()
    if lib is not None and images.dtype == np.uint8:
        if n_threads is None:
            n_threads = min(8, os.cpu_count() or 1)
        lib.tpu_dist_gather_scale_u8_f32(
            images.ctypes.data_as(ctypes.c_void_p),
            idx.ctypes.data_as(ctypes.c_void_p),
            len(idx), row_elems, ctypes.c_float(scale),
            out.ctypes.data_as(ctypes.c_void_p), n_threads)
        return out
    # float32 multiply to match the native path's arithmetic exactly.
    np.multiply(images[idx].astype(np.float32), np.float32(scale), out=out)
    return out


def gather_labels(labels: np.ndarray, idx: np.ndarray) -> np.ndarray:
    labels = np.ascontiguousarray(labels, dtype=np.int64)
    idx = np.ascontiguousarray(idx, dtype=np.int64)
    lib = _load()
    if lib is not None and labels.ndim == 1:
        out = np.empty(len(idx), dtype=np.int64)
        lib.tpu_dist_gather_i64(
            labels.ctypes.data_as(ctypes.c_void_p),
            idx.ctypes.data_as(ctypes.c_void_p),
            len(idx), 1, out.ctypes.data_as(ctypes.c_void_p))
        return out
    return labels[idx]


# -- pipeline front-end -------------------------------------------------------


def native_pipeline(name: str, *, global_batch_size: int, seed: int = 0,
                    split: str = "train", scale: float = 1.0 / 255.0,
                    drop_remainder: bool = True,
                    synthetic_size: int | None = None,
                    transfer: str = "auto"):
    """A ``Dataset`` over a named source whose batches are assembled by the
    native core: per-epoch seeded reshuffle, fused gather+normalize.

    Semantically equals ``load(name, "train").map(scale).cache().shuffle(N).batch(B)``
    (the reference pipeline, tf_dist_example.py:20-33) with a full-dataset
    shuffle buffer; plugs into ``fit``/``experimental_distribute_dataset``
    like any other Dataset, including the shard-policy machinery.

    ``transfer``: ``"float32"`` normalizes on the host (the fused C++
    gather+scale); ``"uint8"`` ships the raw bytes and attaches the scale
    as a device transform the trainer fuses into the compiled step — 4x
    fewer bytes over the host->device link, which bounds the streaming
    path (link rate on the v5e host: not measured).
    ``"auto"`` picks uint8 on non-CPU backends when the source is uint8.
    """
    from tpu_dist.data.pipeline import Dataset
    from tpu_dist.data.sources import load_arrays

    images, labels = load_arrays(name, split, synthetic_size=synthetic_size)
    n = len(images)
    if global_batch_size > n:
        raise ValueError(f"batch {global_batch_size} exceeds dataset size {n}")
    if transfer == "auto":
        import jax

        transfer = ("uint8" if jax.default_backend() != "cpu"
                    and images.dtype == np.uint8 else "float32")
    if transfer == "uint8" and images.dtype != np.uint8:
        raise ValueError(
            f"transfer='uint8' requires a uint8 source, got {images.dtype}")
    if transfer not in ("uint8", "float32"):
        raise ValueError(f"unknown transfer mode {transfer!r}")
    epoch_counter = [0]
    steps = (n // global_batch_size if drop_remainder
             else -(-n // global_batch_size))
    device_scale = transfer == "uint8"
    if device_scale:
        images = np.ascontiguousarray(images)

    def factory():
        # Fresh permutation each pass — Dataset re-invokes the factory per
        # epoch, reproducing shuffle-per-epoch semantics deterministically.
        perm = shuffled_indices(n, seed + 0x9E37 * epoch_counter[0])
        epoch_counter[0] += 1
        for s in range(steps):
            idx = perm[s * global_batch_size:(s + 1) * global_batch_size]
            if device_scale:
                yield images[idx], gather_labels(labels, idx)
            else:
                yield (gather_scale(images, idx, scale),
                       gather_labels(labels, idx))

    ds = Dataset(factory, cardinality=steps)
    if device_scale:
        from tpu_dist.data.vectorize import _device_scale_fn

        ds._device_transform = _device_scale_fn(scale)
    return ds
