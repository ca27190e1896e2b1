"""Cells, configurations, traffic and metric readers, found by name.

``BENCHMARK.json`` at the checkout's root names every cell; a cell names a
configuration (its file is in the configuration's entry) and a traffic mix
(``benchmark/traffic/<name>.json``); a metric's reader is
``benchmark/metrics/<name>.py``.  A later cell, configuration or metric is
therefore new files and new entries, never an edit of this module.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _flags(params: dict) -> list[str]:
    return [a for k, v in params.items() for a in (f"--{k}", str(v))]


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple  # BENCHMARK.json entries this cell reports
    per_layer: tuple
    root: Path

    def program_argv(self) -> list[str]:
        """The ``tts`` command line of one search of this cell."""
        return ([self.config["problem"]] + _flags(self.config["args"])
                + _flags(self.traffic["search"]))

    def reference_argv(self) -> list[str]:
        """Arguments of the plain reference (``reference/<problem>.c``)."""
        return _flags(self.config["instance"]) + _flags(self.traffic["search"])


def load(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reported_in(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: Path = ROOT) -> Cell:
    spec = load(root)
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json "
                       f"(cells: {', '.join(sorted(by_name))})")
    w = by_name[name]
    entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / "benchmark" / "traffic" / f"{w['traffic']}.json").read_text())
    if (traffic["loop"], traffic["clients"]) != ("closed", 1):
        raise ValueError(f"traffic {w['traffic']!r}: the harness drives one "
                         "closed-loop client")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(m for m in spec["end_to_end"] if _reported_in(m, name)),
        per_layer=tuple(m for m in spec["per_layer"] if _reported_in(m, name)),
        root=root,
    )


def reader(metric: str, root: Path = ROOT):
    """The ``read(run)`` function of ``benchmark/metrics/<metric>.py``: it
    returns the metric's value, or None where the run holds nothing to read
    it from (the harness then leaves the metric out)."""
    path = root / "benchmark" / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read
