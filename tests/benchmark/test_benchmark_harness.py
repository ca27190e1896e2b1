"""The harness end to end on the CPU: no TPU, no program, a sound run, the
control, and the faults the check must catch."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
import threading

import pytest
from conftest import REPO

from benchmark import control, spec

CPU = {**os.environ, "JAX_PLATFORMS": "cpu"}
ARGS = ["--workload", "pfsp-ta014.lb1", "--seed", "4294967311",
        "--seconds", "1", "--trace", "0"]


def run_script(cwd, timeout=120):
    return subprocess.run([sys.executable, "benchmark/run.py", *ARGS],
                          cwd=cwd, env=CPU, capture_output=True, text=True,
                          timeout=timeout)


def test_no_tpu_no_result():
    p = run_script(REPO)
    assert p.returncode == 2
    assert "no TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_no_result_beside_the_benchmark_alone(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    files, the program is missing: no result, whatever the device."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("_build", "__pycache__"))
    p = run_script(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    code = ("import sys, jax; sys.path.insert(0, '.'); "
            "from benchmark import run; "
            f"sys.exit(run.main({ARGS!r}, find_chips=lambda c: jax.devices()))")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=CPU,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_a_sound_run_is_correct(run_cell):
    rc, r = run_cell("nqueens-8.count")
    assert rc == 0 and r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert r["window_compiles"] == 0
    assert list(r)[-1] == "checks"
    assert r["checks"]["tree_gap"] == {"value": 0, "limit": 0}
    assert set(r["metrics"]) == {"nodes_per_s", "solve_s", "setup_s"}
    assert r["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", ["pfsp-ta014.lb1", "nqueens-10.count"])
def test_the_control_is_not_correct(bench_root, cell):
    from conftest import add_cell, nqueens

    add_cell(bench_root, "nqueens-10.count", nqueens(10))
    out = control.control(spec.cell(cell, bench_root))
    assert out["correct"] is False
    assert out["checks"]["tree_gap"]["value"] > 0


def _plant(monkeypatch, fault):
    """Breaks the timed path underneath the harness."""
    from tpu_tree_search.engine import resident

    if fault == "answer":  # an answer altered where it is produced
        real = resident.resident_search

        def search(*a, **kw):
            res = real(*a, **kw)
            res.explored_tree += 1
            return res

        monkeypatch.setattr(resident, "resident_search", search)
    elif fault == "half_batch":  # half of each popped chunk left out
        import jax.numpy as jnp

        real = resident._NQueensResident._make_eval

        def make_eval(self):
            evaluate = real(self)

            def half(board, depth, valid, best):
                keep = jnp.arange(valid.shape[0]) % 2 == 0
                return evaluate(board, depth, valid & keep, best)

            return half

        monkeypatch.setattr(resident._NQueensResident, "_make_eval", make_eval)
    elif fault == "state_unchanged":  # the step returns its state unchanged
        stop = threading.Event()
        real = resident._ResidentProgram.step

        def step(self, state):
            if stop.is_set():
                raise RuntimeError("test over")
            out = real(self, state)
            return (*state, *out[len(state):])

        monkeypatch.setattr(resident._ResidentProgram, "step", step)
        return stop
    return None


@pytest.mark.parametrize("fault", ["answer", "half_batch", "state_unchanged"])
def test_a_broken_timed_path_is_not_correct(run_cell, monkeypatch, fault):
    stop = _plant(monkeypatch, fault)
    try:
        rc, r = run_cell("nqueens-8.count", grace_s=3.0)
    finally:
        if stop is not None:
            stop.set()
    assert r is not None and r["correct"] is False
    assert r["failed"] >= 1
