"""The benchmark's own tests run on the CPU, by hand or by a later PR:

    python -m pytest tpubench/tests -q

Four virtual devices, so that the four-chip cell's path can be driven."""

import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
