"""The compiled-program contract registry + `tts check` auditor (ISSUE 8).

Three layers:

* registry mechanics (declaration, collision rejection, the >= 12 bar);
* **tamper tests** — mutate each contract class's subject (inject a sort
  into dense compaction, drop the donation, fork / collapse a cache key,
  leak telemetry into the off path, serialize the pair axis, drift an op
  fingerprint, build a lock cycle) and assert `tts check` fails with the
  MATCHING named contract — the checker itself is what these tests test;
* CLI surfaces (`tts check --list`, a narrowed end-to-end run).

The full-matrix green run is CI's dedicated `tts check` job; tests here
stay on single cells so the tier-1 budget is untouched.
"""

from __future__ import annotations

import os
from pathlib import Path

import pytest

from tpu_tree_search.analysis import contracts, program_audit
from tpu_tree_search.ops import compaction

FIXTURES = Path(__file__).parent / "data" / "lint"

program_audit.load_contracts()


# -- registry mechanics ----------------------------------------------------


def test_registry_meets_the_contract_bar():
    reg = program_audit.load_contracts()
    assert len(reg) >= 12
    assert {
        "dense-step-no-sort-scatter", "dense-ids-shift-only",
        "fused-push-single-gather", "pool-donation",
        "step-callback-armed-only", "program-cache-key-sound",
        "lb2-pairblock-loop-free", "obs-off-identity", "obs-counter-block",
        "phaseprof-off-identity", "pipeline-knob-inert", "guard-knob-inert",
        "lock-order-acyclic", "op-fingerprint",
    } <= set(reg)
    # Declared next to the code they pin, not centrally.
    assert reg["dense-step-no-sort-scatter"].declared_in.endswith(
        "ops.compaction")
    assert reg["pool-donation"].declared_in.endswith("engine.resident")
    assert reg["obs-off-identity"].declared_in.endswith("obs.counters")


def test_contract_name_collision_rejected():
    with pytest.raises(ValueError, match="already declared"):
        contracts.contract(
            "pool-donation", claim="imposter", artifact="resident-step"
        )(lambda a, c: [])


def test_unknown_contract_name_raises():
    with pytest.raises(KeyError, match="unknown contract"):
        contracts.get("no-such-contract")


# -- tamper tests: each contract class must catch its injected violation ---


def test_tamper_sort_injected_into_dense_compaction(monkeypatch):
    """Re-route the dense rank inversion through the sort implementation:
    the dense-path contract must name the smuggled sort."""
    real = compaction.compact_ids

    def tampered(keep, S, mode):
        return real(keep, S, "sort" if mode == "dense" else mode)

    monkeypatch.setattr(compaction, "compact_ids", tampered)
    cell = program_audit.Cell("nqueens", compact="dense")
    art = program_audit.trace_cell(cell)
    msgs = contracts.run_one("dense-step-no-sort-scatter", art, cell)
    assert msgs and "sort" in msgs[0], msgs


def test_tamper_broken_donation(monkeypatch):
    """Rebuild the step without donate_argnums: the donation contract must
    notice the aliasing is gone from the lowered program."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from tpu_tree_search.engine import resident
    from tpu_tree_search.obs import counters as obs_counters
    from tpu_tree_search.obs import phases as obs_phases

    def build_nodonate(self):
        cond, body = self.loop_fns()
        obs, phaseprof = self.obs, self.phaseprof

        def step(pool_vals, pool_aux, size, best):
            zero = jnp.int32(0)
            init = (pool_vals, pool_aux, size, best, zero, zero, zero)
            if obs:
                init = init + (obs_counters.init_block(),)
            if phaseprof:
                init = init + (
                    obs_phases.seed_block(size.astype(jnp.uint32)),
                )
            return lax.while_loop(cond, body, init)

        return jax.jit(step)  # the tamper: donation dropped

    monkeypatch.setattr(resident._ResidentProgram, "_build", build_nodonate)
    cell = program_audit.Cell("nqueens")
    art = program_audit.trace_cell(cell)
    msgs = contracts.run_one("pool-donation", art, cell)
    assert msgs and "donation" in msgs[0], msgs


def test_tamper_cache_key_collapsed(monkeypatch):
    """Make the program cache blind to TTS_OBS: the cache-key contract
    must report the flip reusing a stale program."""
    from tpu_tree_search.obs import counters as obs_counters

    monkeypatch.setattr(obs_counters, "device_counters_enabled",
                        lambda: False)
    art = program_audit.cache_key_artifact("nqueens")
    msgs = contracts.run_one("program-cache-key-sound", art)
    assert any("TTS_OBS" in m and "reused" in m for m in msgs), msgs


def test_tamper_cache_key_forked_by_host_knob(monkeypatch):
    """Leak the host-only TTS_PIPELINE knob into the routing token: the
    cache-key contract must report the forked compilation."""
    from tpu_tree_search.ops import pfsp_device as P

    real = P.routing_cache_token
    monkeypatch.setattr(
        P, "routing_cache_token",
        lambda problem, device=None: real(problem, device)
        + (os.environ.get("TTS_PIPELINE"),),
    )
    art = program_audit.cache_key_artifact("nqueens")
    msgs = contracts.run_one("program-cache-key-sound", art)
    assert any("TTS_PIPELINE" in m and "rebuilt" in m for m in msgs), msgs


def test_tamper_counters_leak_into_off_path(monkeypatch):
    """Force the counter block on unconditionally: the off-identity
    contract must notice the off build is no longer the 7-leaf carry."""
    from tpu_tree_search.obs import counters as obs_counters

    monkeypatch.setattr(obs_counters, "device_counters_enabled",
                        lambda: True)
    art = program_audit.variant_artifact(
        "nqueens", labels=["off", "obs0", "obs-host", "obs1"]
    )
    msgs = contracts.run_one("obs-off-identity", art)
    assert msgs and "7" in " ".join(msgs), msgs


def test_tamper_phase_clock_in_unarmed_step(monkeypatch):
    """Force the phase profiler on unconditionally: the callback contract
    must flag the clock callback inside an unarmed steady-state cell."""
    from tpu_tree_search.obs import phases as obs_phases

    monkeypatch.setattr(obs_phases, "phase_profiling_enabled", lambda: True)
    cell = program_audit.Cell("nqueens", phaseprof="0")
    art = program_audit.trace_cell(cell)
    msgs = contracts.run_one("step-callback-armed-only", art, cell)
    assert msgs and "callback" in msgs[0], msgs


def test_tamper_pair_axis_serialized(monkeypatch):
    """Collapse the auto pair-block policy to the serial loop: the
    pair-axis contract must fail at the published blocked shape."""
    from tpu_tree_search.ops import pfsp_device as P

    monkeypatch.setattr(P, "lb2_pairblock", lambda Pn, n: 1)
    findings = program_audit.audit_lb2_eval(pairblocks=(None,))
    assert findings, "serialized pair axis not caught"
    assert all(f.rule == "contract:lb2-pairblock-loop-free"
               for f in findings)


def test_tamper_engine_stitch_behind_the_pushing_cycle(monkeypatch):
    """Write a pool from XLA again behind the armed cycle: the push
    contract must name the dynamic_update_slice, and the untampered cell
    stays green."""
    import jax

    from tpu_tree_search.ops import megakernel as MK

    real = MK.make_cycle

    def tampered(*a, **kw):
        cycle = real(*a, **kw)

        def stitched(pool_vals, pool_aux, size, *rest):
            out = cycle(pool_vals, pool_aux, size, *rest)
            pv = jax.lax.dynamic_update_slice(out[0], out[0][:8], (size, 0))
            return (pv,) + tuple(out[1:])

        return stitched

    monkeypatch.setattr(MK, "make_cycle", tampered)
    cell = program_audit.Cell("nqueens", megakernel="force", mt="16")
    art = program_audit.trace_cell(cell)
    msgs = contracts.run_one("megakernel-push-in-kernel", art, cell)
    assert msgs and "dynamic_update_slice" in msgs[0], msgs
    monkeypatch.setattr(MK, "make_cycle", real)
    art = program_audit.trace_cell(cell)
    assert contracts.run_one("megakernel-push-in-kernel", art, cell) == []


def test_tamper_fingerprint_drift():
    """An op histogram differing from the committed baseline must fail
    with the named cell and a per-op diff."""
    import jax

    baseline = {
        "jax": jax.__version__,
        "cells": {"cellA": {"ops": {"gather": 1, "while": 1}, "outvars": 7}},
    }
    current = {"cellA": {"ops": {"gather": 2, "while": 1}, "outvars": 7}}
    msgs = contracts.run_one(
        "op-fingerprint",
        {"current": current, "baseline": baseline, "path": "x.json"},
    )
    assert msgs == ["cellA: op drift — gather: 1 -> 2"], msgs
    # outvar drift and missing/stale cells are also named
    current2 = {"cellA": {"ops": {"gather": 1, "while": 1}, "outvars": 8},
                "cellB": {"ops": {}}}
    msgs2 = contracts.run_one(
        "op-fingerprint",
        {"current": current2, "baseline": baseline, "path": "x.json"},
    )
    assert any("outvar count 7 -> 8" in m for m in msgs2)
    assert any("cellB" in m and "missing" in m for m in msgs2)
    # no baseline at all: actionable, not a crash
    msgs3 = contracts.run_one(
        "op-fingerprint",
        {"current": current, "baseline": None, "path": "x.json"},
    )
    assert msgs3 and "--update" in msgs3[0]


def test_tamper_lock_cycle_detected():
    """A deliberate A->B / B->A blocking cycle must fail the lock-order
    contract (and the same fixture drives the lint-rule test in
    tests/test_lint.py)."""
    findings = program_audit.audit_locks(
        paths=[str(FIXTURES / "bad_lock_order.py")]
    )
    assert findings, "lock cycle not caught"
    assert all(f.rule == "contract:lock-order-acyclic" for f in findings)
    text = " ".join(f.message for f in findings)
    assert "A.lock -> B.lock -> A.lock" in text
    assert "same-class" in text


def test_repo_lock_graph_is_clean():
    """The acceptance bar: zero acquisition cycles across the
    lock-bearing host runtime — pool/, parallel/, and the KV/event/
    recorder layers.  (The whole-package run is test_lint's single full
    scan; scoping here keeps the contract test's parse cost down.)"""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(
        program_audit.__file__)))
    findings = program_audit.audit_locks(paths=[
        os.path.join(pkg, "pool"),
        os.path.join(pkg, "parallel"),
        os.path.join(pkg, "obs"),
        os.path.join(pkg, "engine"),
    ])
    assert findings == [], [f.render() for f in findings]


# -- audit mechanics -------------------------------------------------------


def test_matrix_cells_cover_every_axis():
    cells = program_audit.matrix_cells()
    keys = {c.key for c in cells}
    assert len(keys) == len(cells)  # no duplicate cells
    fams = {c.family for c in cells}
    assert fams == set(program_audit.FAMILIES)
    # every lb2 cell carries the pair-block axis, nobody else does
    for c in cells:
        assert (c.pairblock is not None) == (c.family == "pfsp-lb2")
    compacts = {c.compact for c in cells}
    assert compacts == set(program_audit.COMPACT_AXIS)


def test_pin_is_hermetic(monkeypatch):
    """The audit's env pin must isolate from CI matrix pins (TTS_OBS=1 /
    TTS_COMPACT=sort jobs run this suite too) and restore afterwards."""
    monkeypatch.setenv("TTS_COMPACT", "sort")
    monkeypatch.setenv("TTS_OBS", "1")
    with program_audit._pin({"TTS_PHASEPROF": "1"}):
        assert os.environ.get("TTS_COMPACT") is None
        assert os.environ.get("TTS_OBS") is None
        assert os.environ.get("TTS_PHASEPROF") == "1"
    assert os.environ.get("TTS_COMPACT") == "sort"
    assert os.environ.get("TTS_OBS") == "1"


def test_committed_baseline_is_loadable_and_hashed():
    doc = program_audit.load_baseline(
        str(Path(program_audit.__file__).parents[2] / ".tts-contracts.json")
    )
    assert doc is not None, "commit .tts-contracts.json (tts check --update)"
    assert doc["fingerprint"] == program_audit._hash_cells(doc["cells"])
    assert len(doc["cells"]) >= 100  # the full matrix, not a stub
    fp = program_audit.committed_fingerprint(
        str(Path(program_audit.__file__).parents[2] / ".tts-contracts.json")
    )
    assert fp == doc["fingerprint"]


# -- CLI surfaces ----------------------------------------------------------


def test_cli_check_list(capsys):
    from tpu_tree_search import cli

    assert cli.main(["check", "--list"]) == 0
    out = capsys.readouterr().out
    assert "dense-step-no-sort-scatter" in out
    assert "lock-order-acyclic" in out


@pytest.mark.slow  # ~20 s of tracing; CI's `tts check` job runs the FULL matrix
def test_cli_check_family_end_to_end(tmp_path, capsys):
    """A narrowed end-to-end run: one family, contracts only (the
    whole-matrix fingerprint gate is CI's dedicated job)."""
    from tpu_tree_search import cli

    rc = cli.main(["check", "--family", "nqueens", "--no-locks"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "0 finding(s)" in out


def test_cli_check_rejects_update_with_family(capsys):
    from tpu_tree_search import cli

    assert cli.main(["check", "--update", "--family", "nqueens"]) == 2


def test_cli_check_update_roundtrip(tmp_path, monkeypatch, capsys):
    """--update writes a loadable baseline whose hash matches its cells
    (family-scoped into a temp file — never the committed one)."""
    bl = tmp_path / "contracts.json"
    res = program_audit.run_check(
        families=["nqueens"], update=True, baseline_path=str(bl),
        with_locks=False,
    )
    assert res.findings == [], [f.render() for f in res.findings]
    doc = program_audit.load_baseline(str(bl))
    assert doc is not None
    assert doc["fingerprint"] == program_audit._hash_cells(doc["cells"])
    assert res.updated == str(bl)
