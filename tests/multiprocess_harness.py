"""Loopback multi-process test harness — the ``multi_process_runner`` analog.

TF tests multi-worker strategies without a real cluster by forking local
processes with synthesized TF_CONFIG (tf:python/distribute/
multi_process_runner.py + multi_worker_test_base.py; SURVEY.md §4). This is
the JAX version: spawn N python subprocesses, each with

* a fabricated loopback TF_CONFIG (``make_local_cluster``) — worker 0's port
  doubles as the JAX coordination-service endpoint,
* ``JAX_PLATFORMS=cpu`` and one virtual CPU device per process.

Workers run a source snippet that prints one JSON line to stdout prefixed with
``RESULT:``; :func:`run_workers` collects them.
"""

from __future__ import annotations

import json
import os
import pathlib
import socket
import subprocess
import sys
import dataclasses

REPO_ROOT = str(pathlib.Path(__file__).resolve().parent.parent)

#: Boilerplate prepended to every worker snippet: parse TF_CONFIG before
#: touching JAX (the load-bearing program order, README.md:82 semantics).
PRELUDE = """\
import json, os, sys
import numpy as np


def emit(obj):
    print("RESULT:" + json.dumps(obj), flush=True)

"""


def free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


#: jaxlib's CPU collective backend gap (raised from sync_global_devices /
#: cross-process collectives on some jax builds). A worker dying with this
#: is an environment limitation, not a regression in the code under test.
BACKEND_LIMIT_MARKER = (
    "Multiprocess computations aren't implemented on the CPU backend")


@dataclasses.dataclass
class WorkerResult:
    index: int
    returncode: int
    result: dict | None
    stdout: str
    stderr: str


def run_workers(body: str, num_workers: int = 2, *, timeout: float = 300.0,
                extra_env: dict | None = None) -> list[WorkerResult]:
    """Run ``PRELUDE + body`` in ``num_workers`` loopback processes.

    The body sees ``TF_CONFIG`` already exported (per-worker task index) and
    must call ``emit({...})`` with its JSON-serializable result.
    """
    from tpu_dist.cluster.config import make_local_cluster

    # Only worker 0's address is ever bound (it hosts the coordination
    # service); make_local_cluster's sequential ports for the rest are names,
    # not listeners.
    port = free_ports(1)[0]
    configs = make_local_cluster(num_workers, base_port=port)
    procs = []
    for i, cfg in enumerate(configs):
        env = dict(os.environ)
        env.update({
            "TF_CONFIG": json.dumps(cfg),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "PYTHONPATH": REPO_ROOT + os.pathsep + env.get("PYTHONPATH", ""),
        })
        env.update(extra_env or {})
        procs.append(subprocess.Popen(
            [sys.executable, "-c", PRELUDE + body],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True))

    results = []
    for i, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            out, err = p.communicate()
            raise AssertionError(
                f"worker {i} timed out after {timeout}s\n"
                f"--- stdout ---\n{out}\n--- stderr ---\n{err}")
        result = None
        for line in out.splitlines():
            if line.startswith("RESULT:"):
                result = json.loads(line[len("RESULT:"):])
        results.append(WorkerResult(i, p.returncode, result, out, err))

    failed = [r for r in results if r.returncode != 0]
    if failed and any(BACKEND_LIMIT_MARKER in r.stderr for r in failed):
        import pytest

        pytest.skip(
            "this jax build cannot run cross-process collectives on the "
            f"CPU backend ({BACKEND_LIMIT_MARKER!r}); multiprocess "
            "semantics need a TPU/GPU backend or a collectives-capable "
            "CPU jaxlib")
    return results


def assert_all_succeeded(results: list[WorkerResult]) -> None:
    for r in results:
        assert r.returncode == 0, (
            f"worker {r.index} exited {r.returncode}\n--- stdout ---\n"
            f"{r.stdout}\n--- stderr ---\n{r.stderr}")
        assert r.result is not None, (
            f"worker {r.index} emitted no RESULT line\n--- stdout ---\n"
            f"{r.stdout}\n--- stderr ---\n{r.stderr}")
