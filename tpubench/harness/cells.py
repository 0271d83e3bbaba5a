"""Find a cell's files by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a mix, a metric or a model
family by name: a later PR adds files and entries, and edits no file that
is there.

**A model family is one module, named by its configurations.** A
configuration file's ``reference`` key is the path, from the root of the
checkout, of a Python module under the benchmark's directory. It is loaded
by that path and is everything the benchmark knows of the family: the
only place where the family's key names, parameter names and layer kinds
live. ``cfg`` below is the configuration file as a dict (at rehearsal
sizes where a test asks for them). The module gives:

* the plain reference, which imports nothing of the program:
  ``make_params(key, cfg)`` (a flat dict of float32 weights from one PRNG
  key, jit it whole; a leaf that holds every layer on a leading axis is
  named ``h.<leaf>``), ``forward(params, tokens, cfg, quant=None)``
  (logits ``[B, L, vocabulary]``; ``quant="fp8"`` is the control) and,
  where a training cell uses the family, ``loss_sum(params, x, y, cfg,
  quant=None)``. The key from a seed, Adam, the steps in blocks of rows,
  leaf norms and the served-token comparison are no family's:
  ``harness/reference.py``;
* the program at these sizes: ``build_program(cfg, seed)``, the repo's
  model through its normal constructor, whose ``init`` hands out
  ``make_params(seed_key(seed), cfg)``; for training also
  ``program_grad_norms(cfg)`` (jit: Adam's first moment after one step ->
  leaf norms of the gradient) and ``program_delta_norms(cfg)`` (jit:
  parameters now, key -> leaf norms of their change), both under the
  reference's leaf names, ``h<i>.<leaf>`` for layer ``i``;
* ``sizes(cfg)``: the sizes the harness and the metric patterns ask for
  under fixed names: ``n_vocab`` (the traffic draws its ids below it),
  ``n_ctx`` (the longest sequence: a training row, the reference's pad),
  and whatever a ``containing`` pattern of ``layer_metrics/`` formats;
* work from shapes, which the harness only sums: for serving
  ``decode_step_flops(cfg, contexts)`` and ``decode_step_bytes(cfg,
  live_tokens, kv_dtype)`` for one decode step (``kv_bytes_per_token(cfg,
  kv_dtype)`` beside them); for training ``train_flops_per_token(cfg,
  seq)`` and ``train_kernels(cfg, rows, seq)``, kernel name -> (FLOPs,
  bytes) of one step on a device, the ``work`` names of
  ``kernel_roofline`` metrics. A family whose layers differ counts each
  kind itself.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH_DIR = ROOT / "tpubench"


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def _load_module(path: str):
    spec = importlib.util.spec_from_file_location(
        "tpubench_family_" + pathlib.Path(path).stem, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_family(path):
    """The family module at ``path``, loaded once for each file: two
    checkouts' modules of one name never meet."""
    return _load_module(str(pathlib.Path(path).resolve()))


class Cell:
    """One entry of ``workloads`` with everything its run needs."""

    def __init__(self, name: str, root: pathlib.Path = ROOT):
        self.root = pathlib.Path(root)
        bench = load_json(self.root / "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"there are {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = load_json(self.root / self.config_entry["file"])
        self.family = load_family(self.root / self.config["reference"])
        bench_dir = self.root / bench["paths"][0]
        self.bench_dir = bench_dir
        self.mix = load_json(
            bench_dir / "traffic" / f"{self.entry['traffic']}.json")
        self.kind = self.mix["kind"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    @property
    def sizes(self) -> dict:
        """The family's named sizes of the configuration as it runs."""
        return self.family.sizes(self.config)

    def at_rehearsal_sizes(self) -> "Cell":
        """The same cell at its files' ``rehearsal`` sizes: for the CPU
        tests and rehearsals, never for a result."""
        self.config = {**self.config, **self.config["rehearsal"]}
        self.mix = {**self.mix, **self.mix.get("rehearsal", {})}
        return self

    def metric_spec(self, metric_name: str) -> dict:
        """The reader's own file for one per-layer metric."""
        return load_json(
            self.bench_dir / "layer_metrics" / f"{metric_name}.json")
