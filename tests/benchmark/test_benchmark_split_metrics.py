"""The readers that split the step into kernel and glue, and device idle
into the program's own host code and the runtime calls it issues."""

from __future__ import annotations

import gzip
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from benchmark import spec, xplane
from benchmark.run import Run

DATA = Path(__file__).parent / "data"
SPLIT = ("kernel_ns_per_node", "glue_ns_per_node", "host_idle_ms",
         "runtime_idle_ms")


def search(phase2_tree: int) -> dict:
    return {"tree": phase2_tree + 20, "sol": 0, "best": 1, "complete": True,
            "seconds": 1.0,
            "phases": [[0.01, 10, 0], [0.98, phase2_tree, 0], [0.01, 10, 0]]}


def run_of(trace, searches=2, phase2_tree=1000) -> Run:
    return Run(setup_s=1.0, window_s=2.0,
               searches=[search(phase2_tree)] * searches,
               compile_setup={}, compile_window={}, trace=trace)


def read(name, run):
    return spec.reader(name)(run)


def test_every_split_metric_is_reported_by_every_cell():
    for w in spec.load()["workloads"]:
        names = {m["name"] for m in spec.cell(w["name"]).per_layer}
        assert set(SPLIT) <= names, w["name"]


def test_kernel_and_glue_add_up_to_the_step():
    # 2 searches x 1000 phase-2 nodes; the step ran 5 us, of which the
    # named cycle kernel 4 us; %while holds the kernel and is not one.
    trace = xplane.Reduction(busy_s=6e-6, window_s=7e-6, step_s=5e-6, ops=[
        ("%while while", 5e-6),
        ("%tts_cycle_lb1.3 custom-call tpu_custom_call", 3e-6),
        ("%tts_eval_lb2.1 custom-call tpu_custom_call", 1e-6),
        ("%dynamic_update_slice.33 dynamic-update-slice", 0.5e-6),
    ])
    run = run_of(trace)
    assert read("kernel_ns_per_node", run) == pytest.approx(2.0)
    assert read("glue_ns_per_node", run) == pytest.approx(0.5)
    assert (read("kernel_ns_per_node", run) + read("glue_ns_per_node", run)
            == pytest.approx(read("step_ns_per_node", run), rel=1e-12))


def test_idle_splits_by_the_innermost_span():
    trace = xplane.Reduction(busy_s=1.0, window_s=1.2, step_s=0.9, gaps=[
        ("np.asarray(jax.Array)", 0.040),  # a scalar or residual read
        ("tts.warmup", 0.006),
        ("DevicePut", 0.010),
        ("tts.search", 0.004),  # the loop's own bookkeeping
        ("bench.search", 0.002),  # the harness, outside the search
        ("bench.between", 0.001),
        ("PjitFunction(broadcast_in_dim)", 0.002),
        ("(no host span)", 0.003),
    ])
    run = run_of(trace, searches=4)
    assert read("host_idle_ms", run) == pytest.approx(1e3 * 0.010 / 4)
    assert read("runtime_idle_ms", run) == pytest.approx(1e3 * 0.052 / 4)


@pytest.mark.parametrize("name", SPLIT)
def test_split_metrics_need_the_trace(name):
    assert read(name, run_of(None)) is None


def test_a_program_without_the_names_gives_nothing_to_split():
    """A trace of a program whose kernels and spans carry no ``tts`` name
    reads no kernel, glue or host idle; the runtime's own spans still
    read."""
    trace = xplane.Reduction(
        busy_s=1.0, window_s=1.1, step_s=0.9,
        ops=[("%while while", 0.9),
             ("%body.3 custom-call tpu_custom_call", 0.8)],
        gaps=[("bench.search", 0.05), ("DevicePut", 0.02)])
    run = run_of(trace)
    for name in ("kernel_ns_per_node", "glue_ns_per_node", "host_idle_ms"):
        assert read(name, run) is None
    assert read("runtime_idle_ms", run) == pytest.approx(10.0)


def test_recorded_chip_trace_of_the_unnamed_program():
    """The recorded ta014 lb2 search (chip run, PR 22) predates the names:
    its cycle kernel is ``%body.3``, and its only program-side idle is the
    harness's ``bench.search``."""
    raw = json.loads(gzip.decompress(
        (DATA / "ta014_lb2_search.xplane.json.gz").read_bytes()))
    trace = xplane.reduce_profile(NS(planes=[
        NS(name=p["name"], lines=[
            NS(name=ln["name"], events=[NS(name=n, start_ns=s, duration_ns=d)
                                        for n, s, d in ln["events"]])
            for ln in p["lines"]])
        for p in raw["planes"]]), chips=1)
    run = run_of(trace, searches=1, phase2_tree=144000)
    assert read("kernel_ns_per_node", run) is None
    assert read("host_idle_ms", run) is None
    runtime = sum(s for n, s in trace.gaps
                  if not n.startswith(("bench.", "(no host span)")))
    assert read("runtime_idle_ms", run) == pytest.approx(1e3 * runtime)
