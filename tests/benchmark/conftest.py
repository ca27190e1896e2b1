"""Fixtures of the benchmark's tests: a checkout root to which a test adds
cells by adding files, and a run of the harness on the CPU."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def add_cell(root: Path, cell: str, config: dict, traffic: dict | None = None,
             metric: tuple[dict, str] | None = None) -> None:
    """Adds a cell to ``root`` by new files and new entries only: the
    configuration's file, a traffic file where given, a metric's reader
    where given, and their entries in BENCHMARK.json."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfile = f"benchmark/configs/{config['name']}.json"
    assert not (root / cfile).exists()
    (root / cfile).write_text(json.dumps(config))
    if not any(c["name"] == config["name"] for c in spec["configs"]):
        spec["configs"].append({"name": config["name"], "source": "test",
                                "file": cfile, "reduced": [], "why": "test"})
    tname = cell.split(".", 1)[1]
    if traffic is not None:
        tfile = root / "benchmark" / "traffic" / f"{tname}.json"
        assert not tfile.exists()
        tfile.write_text(json.dumps(traffic))
    spec["workloads"].append({"name": cell, "config": config["name"],
                              "traffic": tname, "chips": 1, "why": "test"})
    if metric is not None:
        entry, source = metric
        mfile = root / "benchmark" / "metrics" / f"{entry['name']}.py"
        assert not mfile.exists()
        mfile.write_text(source)
        spec["end_to_end"].append(entry)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def nqueens(n: int) -> dict:
    return {"name": f"nqueens-{n}", "problem": "nqueens",
            "args": {"N": n, "g": 1, "tier": "device"}, "instance": {"N": n},
            "reduced": []}


@pytest.fixture
def bench_root(tmp_path) -> Path:
    """A copy of the benchmark's files, with the tiny cell
    ``nqueens-8.count`` added (the N=8 tree: 2056 nodes)."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("_build", "__pycache__"))
    add_cell(tmp_path, "nqueens-8.count", nqueens(8))
    return tmp_path


@pytest.fixture
def run_cell(bench_root, capsys, monkeypatch):
    """Runs the harness in this process on the CPU, with its look for a
    chip skipped; returns (exit code, the result object)."""
    # No persistent compile cache: enable() then leaves JAX's config alone.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(bench_root / "cache"))
    from benchmark import run

    def go(cell: str, seconds: float = 0.5, trace: int = 0,
           grace_s: float = 20.0):
        import jax

        rc = run.main(["--workload", cell, "--seed", str(2**31 + 7),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      find_chips=lambda chips: jax.devices(),
                      grace_s=grace_s, root=bench_root)
        out = capsys.readouterr().out.strip().splitlines()
        return rc, (json.loads(out[-1]) if out else None)

    return go
