"""Test bootstrap: force an 8-device virtual CPU mesh before JAX initializes.

This is the JAX analog of TF's in-process multi-worker fakes (SURVEY.md §4):
``--xla_force_host_platform_device_count=8`` gives every test a deterministic
8-device mesh on CPU, so single-host "MirroredStrategy-equivalent" and sharding
behavior is exercised without TPU hardware. Multi-process behavior is covered
separately by the loopback-process harness (tests/test_multiprocess.py, added
with the trainer layer).

``JAX_PLATFORMS`` and ``XLA_FLAGS`` are exported here, before jax is
imported, so the tests — and every subprocess they spawn — run on the CPU
whatever the shell exported. ``jax.config.update`` repeats the platform pin
for the case where a pytest plugin imported jax before this file ran (the
backend itself initializes lazily, at the first device query).
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # for any subprocesses tests spawn
# No persistent compile cache under test, here or in spawned workers: the
# suite must not write into the checkout, and a TPU program compiled for a
# described topology (test_tpu_compile.py) cannot be read back without a chip.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multiprocess: spawns loopback multi-worker processes (slower)")
    config.addinivalue_line(
        "markers",
        "realdata: needs real datasets under $TPU_DIST_DATA_DIR "
        "(populate with scripts/fetch_data.py; skipped otherwise)")
    config.addinivalue_line(
        "markers",
        "slow: long builds/runs (e.g. sanitizer rebuilds); excluded from "
        "the tier-1 gate, run explicitly with -m slow")


@pytest.fixture(scope="session")
def eight_devices():
    devices = jax.devices()
    assert len(devices) == 8, (
        "expected 8 virtual CPU devices; platform override failed "
        f"(got {len(devices)}: {devices})"
    )
    return devices
