"""Deterministic in-process multi-DEVICE test harness.

The sibling of ``tests/multiprocess_harness.py`` for the other axis of
scale: instead of N cooperating processes with one device each, ONE process
with a chosen number of virtual devices. The device count is baked into XLA
at backend initialization (``--xla_force_host_platform_device_count``), so a
test that needs a count different from the suite's (conftest pins 8) — or
that needs DIFFERENT counts in sequence, e.g. reshape-on-restore saving on 8
devices and restoring on 4 — must re-execute in a fresh subprocess. This
module owns that re-execution.

Workers run a source snippet under ``JAX_PLATFORMS=cpu`` with the forced
device count and report one JSON line prefixed ``HARNESS_RESULT:`` via the
prelude's ``emit``; :func:`run_with_devices` returns the parsed dict.
Snippets share state across invocations the same way real elastic attempts
do: through files (checkpoints) in a caller-provided directory.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

REPO_ROOT = str(pathlib.Path(__file__).resolve().parent.parent)

_RESULT_PREFIX = "HARNESS_RESULT:"


class HarnessFailure(AssertionError):
    """A NAMED harness-child failure.

    ``mode`` says WHICH way the child failed — ``"timeout"``,
    ``"nonzero_exit"``, ``"torn_result"`` (a HARNESS_RESULT line that is not
    valid JSON, e.g. the child died mid-print), or ``"no_result"`` — so a
    debugging human (or a test of the harness itself) doesn't have to parse
    the message text. Subclasses AssertionError so existing callers that
    catch/expect assertion failures keep working.
    """

    def __init__(self, mode: str, message: str):
        self.mode = mode
        super().__init__(message)

#: Prepended to every snippet: pin the platform BEFORE jax initializes and
#: give the body ``emit`` + the forced device-count sanity check.
PRELUDE = """\
import json, os, sys

import jax

jax.config.update("jax_platforms", "cpu")


def emit(obj):
    print("HARNESS_RESULT:" + json.dumps(obj), flush=True)


_want = int(os.environ["TPU_DIST_HARNESS_DEVICES"])
assert jax.device_count() == _want, (
    f"harness asked for {_want} devices, backend gave "
    f"{jax.device_count()} — XLA_FLAGS not honored?")

"""

#: The prelude for bodies that must run ``jax.distributed.initialize``
#: themselves: touching ``jax.device_count()`` here would initialize the
#: backend and make a later distributed bring-up illegal, so the device
#: count is only handed over via ``_want`` and the body owns the check.
DEFERRED_PRELUDE = """\
import json, os, sys

import jax

jax.config.update("jax_platforms", "cpu")


def emit(obj):
    print("HARNESS_RESULT:" + json.dumps(obj), flush=True)


_want = int(os.environ["TPU_DIST_HARNESS_DEVICES"])

"""


def run_with_devices(body: str, n_devices: int, *, timeout: float = 300.0,
                     extra_env: dict | None = None,
                     init_backend: bool = True) -> dict:
    """Run ``PRELUDE + body`` in a subprocess with ``n_devices`` virtual CPU
    devices; returns the dict the body passed to ``emit``.

    Raises :class:`HarnessFailure` (an AssertionError carrying a named
    ``mode`` plus the captured output) if the subprocess times out, exits
    nonzero, emits a torn ``HARNESS_RESULT`` line, or emits none — a
    harness problem must read as a test failure, never a silent pass.

    ``init_backend=False`` swaps in :data:`DEFERRED_PRELUDE` for bodies
    that must bring up ``jax.distributed`` before the first computation.
    """
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_devices}",
        "TPU_DIST_HARNESS_DEVICES": str(n_devices),
        "PYTHONPATH": REPO_ROOT + os.pathsep + env.get("PYTHONPATH", ""),
    })
    env.update(extra_env or {})
    prelude = PRELUDE if init_backend else DEFERRED_PRELUDE
    proc = subprocess.Popen(
        [sys.executable, "-c", prelude + body],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, err = proc.communicate()
        raise HarnessFailure(
            "timeout",
            f"{n_devices}-device harness run timed out after {timeout}s\n"
            f"--- stdout ---\n{out}\n--- stderr ---\n{err}")
    if proc.returncode != 0:
        raise HarnessFailure(
            "nonzero_exit",
            f"{n_devices}-device harness run exited {proc.returncode}\n"
            f"--- stdout ---\n{out}\n--- stderr ---\n{err}")
    result = None
    for line in out.splitlines():
        if line.startswith(_RESULT_PREFIX):
            try:
                result = json.loads(line[len(_RESULT_PREFIX):])
            except ValueError:
                raise HarnessFailure(
                    "torn_result",
                    f"{n_devices}-device harness run emitted a torn "
                    f"{_RESULT_PREFIX} line (not valid JSON): {line!r}\n"
                    f"--- stdout ---\n{out}\n--- stderr ---\n{err}")
    if result is None:
        raise HarnessFailure(
            "no_result",
            f"{n_devices}-device harness run emitted no {_RESULT_PREFIX} "
            f"line\n--- stdout ---\n{out}\n--- stderr ---\n{err}")
    return result
