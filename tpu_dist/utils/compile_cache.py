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
"""

from __future__ import annotations

import os
import pathlib

#: The fixed in-checkout cache directory used when the environment names
#: none.
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")


def configure() -> str:
    """Apply the rule above; returns the directory in effect. Idempotent."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
