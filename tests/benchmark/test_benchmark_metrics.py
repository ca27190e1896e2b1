"""The metric readers' arithmetic."""

from __future__ import annotations

import pytest

from benchmark import spec, xplane
from benchmark.run import Run, compare, passes


def search(tree, seconds, host=(0.01, 0.02), phase2_tree=None):
    return {"tree": tree, "sol": 0, "best": 1, "complete": True,
            "seconds": seconds,
            "phases": [[host[0], 10, 0],
                       [seconds - sum(host), phase2_tree or tree - 10, 0],
                       [host[1], 0, 0]]}


def read(name, run):
    return spec.reader(name)(run)


def test_a_search_that_runs_past_the_seconds_counts_whole():
    # --seconds 10: the third search starts at 8 s and ends at 12 s; the
    # window is 12 s and holds all three searches' nodes.
    run = Run(setup_s=20.0, window_s=12.0,
              searches=[search(1000, 4.0)] * 3,
              compile_setup={"compile_s": 1.5}, compile_window={})
    assert read("nodes_per_s", run) == pytest.approx(3000 / 12.0)
    assert read("solve_s", run) == pytest.approx(4.0)
    assert read("setup_s", run) == 20.0
    assert read("compile_s", run) == 1.5
    assert read("host_phase_pct", run) == pytest.approx(100 * 0.09 / 12.0)


def test_device_metrics_need_the_trace():
    run = Run(setup_s=1.0, window_s=2.0, searches=[search(100, 2.0,
                                                          phase2_tree=80)],
              compile_setup={}, compile_window={})
    assert read("step_ns_per_node", run) is None
    assert read("device_idle_pct", run) is None
    run.trace = xplane.Reduction(busy_s=1.5, window_s=2.0, step_s=0.8e-6)
    assert read("step_ns_per_node", run) == pytest.approx(10.0)
    assert read("device_idle_pct", run) == pytest.approx(25.0)


def test_compare_holds_every_search_to_the_reference():
    want = {"tree": 100, "sol": 5}
    ok = [{"tree": 100, "sol": 5, "complete": True}] * 3
    assert passes(compare(ok, want, started=3))
    off = ok[:2] + [{"tree": 101, "sol": 5, "complete": True}]
    checks = compare(off, want, started=3)
    assert checks["tree_gap"] == {"value": 1, "limit": 0}
    assert not passes(checks)
    # a search started and never ended
    assert compare(ok, want, started=4)["searches_lost"]["value"] == 1
    assert list(compare(ok, want, 3)) == ["tree_gap", "sol_gap",
                                          "searches_lost"]
