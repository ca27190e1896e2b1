"""Cells, configurations, traffic and metrics are found by name, and a new
cell is new files and entries only."""

from __future__ import annotations

import json

import pytest
from conftest import REPO, add_cell, nqueens

from benchmark import spec


def test_every_cell_of_the_benchmark_resolves():
    bench = spec.load()
    assert {m["name"] for m in bench["end_to_end"]} == {
        "nodes_per_s", "solve_s", "setup_s"}
    for w in bench["workloads"]:
        cell = spec.cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["name"] == w["traffic"]
        assert cell.program_argv()[0] == cell.config["problem"]
        assert (REPO / "benchmark" / "reference"
                / f"{cell.config['problem']}.c").exists()
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.reader(m["name"]))


def test_cell_argv():
    cell = spec.cell("pfsp-ta014.lb2")
    assert cell.program_argv() == ["pfsp", "--inst", "14", "--tier",
                                   "device", "--lb", "lb2", "--ub", "1"]
    assert cell.reference_argv() == [
        "--jobs", "20", "--machines", "10", "--time_seed", "268827376",
        "--optimum", "1377", "--lb", "lb2", "--ub", "1"]


def test_an_unknown_cell_names_the_known_ones():
    with pytest.raises(KeyError, match="pfsp-ta014.lb1"):
        spec.cell("no-such.cell")


def test_a_new_cell_is_only_added_files(bench_root, run_cell):
    """A configuration, a traffic mix and an end-to-end metric, each a new
    file with a new entry in BENCHMARK.json, run through the harness
    unchanged; no file that was there is edited."""
    before = {p: p.read_bytes() for p in (bench_root / "benchmark").rglob("*")
              if p.is_file()}
    add_cell(bench_root, "nqueens-9.twice", nqueens(9),
             traffic={"name": "twice", "loop": "closed", "clients": 1,
                      "search": {}},
             metric=({"name": "searches_per_s", "unit": "1/s",
                      "better": "higher", "bound": 0.05,
                      "source": "host_clock", "workloads": ["nqueens-9.twice"]},
                     "def read(run):\n"
                     "    return len(run.searches) / run.window_s\n"))
    assert all(p.read_bytes() == b for p, b in before.items())
    rc, result = run_cell("nqueens-9.twice")
    assert rc == 0 and result["correct"] is True
    assert set(result["metrics"]) == {"nodes_per_s", "solve_s", "setup_s",
                                      "searches_per_s"}
    assert result["metrics"]["searches_per_s"]["unit"] == "1/s"
    # the metric is reported only in the cell it names
    assert "searches_per_s" not in [
        m["name"] for m in spec.cell("nqueens-8.count", bench_root).end_to_end]
    assert json.loads((bench_root / "BENCHMARK.json").read_text())[
        "workloads"][-1]["name"] == "nqueens-9.twice"
