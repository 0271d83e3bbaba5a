"""Find a cell's files by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a mix or a metric by name: a
later PR adds files and entries, and edits no file that is there.
"""

from __future__ import annotations

import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH_DIR = ROOT / "tpubench"


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


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
        bench_dir = self.root / bench["paths"][0]
        self.bench_dir = bench_dir
        self.mix = load_json(
            bench_dir / "traffic" / f"{self.entry['traffic']}.json")
        self.kind = self.mix["kind"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

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
