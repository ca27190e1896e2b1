"""The reduction from a profiler trace to the device numbers."""

from __future__ import annotations

import gzip
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from benchmark import xplane

DATA = Path(__file__).parent / "data"


def profile(planes: dict) -> NS:
    """A trace shaped as jax.profiler.ProfileData's: {plane: {line: [(name,
    start_ns, duration_ns)]}}."""
    return NS(planes=[
        NS(name=p, lines=[NS(name=l, events=[NS(name=n, start_ns=s,
                                                duration_ns=d)
                                             for n, s, d in evs])
                          for l, evs in lines.items()])
        for p, lines in planes.items()])


def test_busy_is_the_union_of_ops_inside_the_window():
    trace = profile({
        "/host:CPU": {"bench-window": [("bench.search", 100, 900),
                                       ("PjitFunction(step)", 150, 50),
                                       ("D2H", 600, 200)]},
        "/device:TPU:0": {
            "XLA Modules": [("jit_step(1)", 0, 400), ("jit_other(2)", 450, 50)],
            # overlapping, nested and straddling the window's start
            "XLA Ops": [("%a = fusion(x)", 0, 300), ("%b = copy(x)", 200, 200),
                        ("%c = custom-call(x), custom_call_target=\"k\"",
                         250, 50), ("%d = fusion(x)", 450, 50)],
        },
    })
    r = xplane.reduce_profile(trace, chips=1)
    assert r.window_s == pytest.approx(900e-9)
    # union of [100, 400) and [450, 500)
    assert r.busy_s == pytest.approx(350e-9)
    assert r.step_s == pytest.approx(300e-9)  # jit_step clipped to the window
    ops = dict(r.ops)
    assert ops["%a fusion"] == pytest.approx(200e-9)
    assert ops["%c custom-call k"] == pytest.approx(50e-9)
    gaps = dict(r.gaps)
    # idle [400, 450) under bench.search alone; [500, 1000) mid 750 in D2H
    assert gaps == {"bench.search": pytest.approx(50e-9),
                    "D2H": pytest.approx(500e-9)}
    assert r.breakdown()["device_ops"][0] == ["%a fusion", ops["%a fusion"]]


def test_busy_averages_over_the_chips_used():
    dev = {"XLA Ops": [("%a = fusion(x)", 0, 100)]}
    half = {"XLA Ops": [("%a = fusion(x)", 0, 50)]}
    trace = profile({"/host:CPU": {"t": [("bench.search", 0, 100)]},
                     "/device:TPU:0": dev, "/device:TPU:1": half,
                     "/device:TPU:2": dev})
    assert xplane.reduce_profile(trace, 2).busy_s == pytest.approx(75e-9)


def test_a_trace_without_the_window_span_or_a_chip_is_refused():
    with pytest.raises(ValueError, match="bench.search"):
        xplane.reduce_profile(profile({"/host:CPU": {"t": []}}), 1)
    with pytest.raises(ValueError, match="TPU"):
        xplane.reduce_profile(
            profile({"/host:CPU": {"t": [("bench.search", 0, 1)]}}), 1)


@pytest.mark.parametrize("hlo, name", [
    ("%while = (s8[4]{0}, s32[]) while((s8[4]{0}) %t), condition=%c", "%while while"),
    ("%body.3 = (s32[2,20]{1,0:T(8,128)S(1)}) custom-call(s32[1]{0} %x), "
     "custom_call_target=\"tpu_custom_call\"", "%body.3 custom-call tpu_custom_call"),
    ("%copy.7 = s8[2,20]{1,0:T(8,128)(4,1)} copy(s8[2,20]{0,1} %p)", "%copy.7 copy"),
    ("no instruction text", "no instruction text"),
])
def test_op_names(hlo, name):
    assert xplane.op_name(hlo) == name


def test_recorded_chip_trace():
    """One whole ta014 lb2 search on a TPU v5 lite (chip run, PR 22), cut
    to the lines the reduction reads; the numbers are those of the full
    trace."""
    raw = json.loads(gzip.decompress(
        (DATA / "ta014_lb2_search.xplane.json.gz").read_bytes()))
    r = xplane.reduce_profile(profile({
        p["name"]: {l["name"]: l["events"] for l in p["lines"]}
        for p in raw["planes"]}), chips=1)
    assert r.window_s == pytest.approx(0.714371221)
    assert r.busy_s == pytest.approx(0.695300193)
    assert r.step_s == pytest.approx(0.694986838)
    assert r.ops[0] == ("%while while", pytest.approx(0.692496323))
    assert r.ops[1] == ("%body.3 custom-call tpu_custom_call",
                        pytest.approx(0.684657866))
    assert sum(s for _, s in r.gaps) == pytest.approx(r.window_s - r.busy_s)
    assert len(r.breakdown()["idle_gaps"]) <= xplane.TOP
