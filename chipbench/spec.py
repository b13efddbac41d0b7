"""Finds what ``BENCHMARK.json`` names, by name, in files of their own.

  configs/<config>.json    a configuration: the program's graph builder and
                           engine, the server settings, the architecture the
                           reference builds, its calibration and its checks
  traffic/<mix>.json       a traffic mix: loop kind, clients or rate, pool
                           size, warm-up and drain seconds
  metrics/<metric>.py      a metric's reader: ``read(rec)`` returns the
                           number, or None where the run has nothing to read

A later change adds a configuration, a mix or a metric as a new file and an
entry in ``BENCHMARK.json``; nothing here names any of them.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib


class Benchmark:
    """``BENCHMARK.json`` under ``root`` (the checkout), and its files."""

    def __init__(self, root: pathlib.Path):
        self.root = pathlib.Path(root)
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / "chipbench"

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.doc["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")

    def config(self, name: str) -> dict:
        entry = next(c for c in self.doc["configs"] if c["name"] == name)
        return json.loads((self.root / entry["file"]).read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def metrics(self, cell: str, per_layer: bool) -> list:
        """The metric entries a run of ``cell`` reports: its end-to-end
        metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``.
        An entry without ``workloads`` belongs to every cell."""
        group = self.doc["per_layer" if per_layer else "end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        """``read(rec)`` of ``metrics/<metric>.py``."""
        path = self.dir / "metrics" / f"{metric}.py"
        mod_spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read
