"""Per-phase cycle-clock profiler (`tpu_tree_search/obs/phases.py`,
docs/OBSERVABILITY.md leg 7): byte-identical jaxprs when off, the exact
phase-sum == total telescoping identity, bit-identical search results
armed vs not, cross-tier harvest parity, guard interaction, and the
`tts report` / `tts profile` decomposition table."""

from __future__ import annotations

import json

import pytest

from tpu_tree_search import cli
from tpu_tree_search.obs import phases, report
from tpu_tree_search.problems import NQueensProblem


def _cycle_sum(pp: dict) -> int:
    return sum(pp[s] for s in phases.CYCLE_SLOTS)


# -- zero-cost disabled path (routed through the contract registry) --------
# The byte-identity, block-leaf, and cache-key claims are Contracts
# (obs/phases.py, engine/resident.py) checked over the whole knob matrix
# by `tts check`; these tests pin the same registry entries on the
# historical cell.


def test_disabled_jaxpr_identical_and_clock_free():
    from tpu_tree_search.analysis import contracts, program_audit

    program_audit.load_contracts()
    art = program_audit.variant_artifact(
        "nqueens", labels=["off", "phase0", "phase1", "phase1-obs1"]
    )
    # Off builds are byte-identical: the phase block is compiled out, not
    # branched — exactly the counter-block contract (tests/test_obs.py).
    # The armed build carries exactly one extra output leaf (the phase
    # block); with device counters too, one more (order: ..., ctr, ph).
    assert contracts.run_one("phaseprof-off-identity", art) == []
    assert contracts.run_one("phaseprof-block-leaf", art) == []
    assert art.outvars("off") == 7


def test_program_cache_keys_on_phaseprof():
    from tpu_tree_search.analysis import contracts, program_audit

    program_audit.load_contracts()
    art = program_audit.cache_key_artifact("nqueens")
    a, b = art.distinct["TTS_PHASEPROF"]
    assert b.phaseprof and not a.phaseprof
    assert contracts.run_one("program-cache-key-sound", art) == []


# -- armed semantics: bit-identity + the telescoping identity --------------


def test_resident_bit_identity_and_phase_sum(monkeypatch):
    from tpu_tree_search.engine.resident import resident_search

    monkeypatch.delenv("TTS_PHASEPROF", raising=False)
    res_off = resident_search(NQueensProblem(N=9), m=5, M=128)
    monkeypatch.setenv("TTS_PHASEPROF", "1")
    res_on = resident_search(NQueensProblem(N=9), m=5, M=128)
    # Clock reads feed only the phase block: search results stay
    # bit-identical armed vs not.
    assert (res_on.explored_tree, res_on.explored_sol, res_on.best) == \
        (res_off.explored_tree, res_off.explored_sol, res_off.best)
    assert res_off.phase_profile is None
    pp = res_on.phase_profile
    assert pp is not None and pp["total"] > 0
    # The stated consistency bound: within a cycle the same clock readings
    # bound adjacent phases, so the in-cycle slots telescope to `total`
    # EXACTLY (uint32 wrap arithmetic is exact; host merge uses int64+).
    assert _cycle_sum(pp) == pp["total"]
    # Sanity: measured on-device cycle time fits inside the run's wall
    # clock (single device — no aggregation slack needed).
    assert pp["total"] < res_on.elapsed * 1e9
    # The armed result also rides the obs payload for stats lines.
    assert res_on.obs["device_phases"] == pp


def test_mesh_phase_parity(monkeypatch):
    import jax

    from tpu_tree_search.parallel.resident_mesh import mesh_resident_search

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    monkeypatch.setenv("TTS_PHASEPROF", "1")
    res = mesh_resident_search(NQueensProblem(N=8), m=5, M=64, D=4)
    # Counting invariance is untouched by the clocks.
    assert (res.explored_tree, res.explored_sol) == (2056, 92)
    pp = res.phase_profile
    assert pp is not None
    # Telescoping holds summed across shards too (it holds per shard and
    # the merge is a plain sum).
    assert _cycle_sum(pp) == pp["total"] > 0
    # The mesh tiers charge the pmin fold + ppermute diffusion to
    # `balance` — present (>= 0; N=8 on 4 shards always runs rounds).
    assert pp["balance"] >= 0 and pp["loop"] > 0


def test_dist_mesh_phase_parity(monkeypatch):
    import jax

    from tpu_tree_search.parallel.dist_mesh import dist_mesh_search

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    monkeypatch.setenv("TTS_PHASEPROF", "1")
    res = dist_mesh_search(NQueensProblem(N=8), m=5, M=64, D=2, num_hosts=2)
    assert (res.explored_tree, res.explored_sol) == (2056, 92)
    pp = res.phase_profile
    assert pp is not None
    assert _cycle_sum(pp) == pp["total"] > 0


def test_guard_green_while_armed(monkeypatch):
    from tpu_tree_search.engine.resident import resident_search

    monkeypatch.setenv("TTS_PHASEPROF", "1")
    monkeypatch.setenv("TTS_GUARD", "1")
    # The armed variant harvests at the same dispatch boundaries: zero new
    # transfers, zero steady-state recompiles — GuardViolation would raise.
    res = resident_search(NQueensProblem(N=9), m=5, M=128)
    assert (res.explored_tree, res.explored_sol) == (8393, 352)


# -- merge/share helpers ---------------------------------------------------


def test_merge_host_and_shares():
    import numpy as np

    blk = np.zeros((2, phases.NSLOTS + 1), np.uint32)
    blk[0, phases.IDX["eval"]] = 100
    blk[0, phases.IDX["total"]] = 150
    blk[1, phases.IDX["eval"]] = 50
    blk[1, phases.IDX["total"]] = 150
    blk[:, phases.TPREV] = 12345  # carried clock reading: never merged
    tot = phases.merge_host(None, blk)
    assert tot["eval"] == 150 and tot["total"] == 300
    assert "tprev" not in tot and len(tot) == phases.NSLOTS
    tot = phases.merge_host(tot, blk[:1])
    assert tot["eval"] == 250
    sh = phases.shares(tot)
    assert sh["eval"] == pytest.approx(250 / 450)
    name, share = phases.dominant_phase(tot)
    assert name == "eval"
    assert phases.dominant_phase({}) is None
    assert phases.dominant_phase(None) is None


# -- report/CLI surfaces ---------------------------------------------------


def _phase_counter_event(ns: dict) -> dict:
    return {"name": "device_phases", "cat": "metrics", "ph": "C",
            "ts": 1.0, "pid": 0, "tid": 0, "args": ns}


def test_report_phase_table_golden(capsys):
    evts = [
        _phase_counter_event({"pop": 100, "eval": 200, "compact": 410,
                              "push": 250, "overflow": 40, "balance": 5,
                              "loop": 30, "total": 1000}),
        _phase_counter_event({"pop": 0, "eval": 0, "compact": 0,
                              "push": 0, "overflow": 0, "balance": 0,
                              "loop": 0, "total": 0}),
    ]
    summary = report.summarize(evts)
    pd = summary["phase_decomp"]
    assert pd["ns"]["compact"] == 410 and pd["ns"]["total"] == 1000
    assert pd["dominant"] == "compact"
    assert pd["dominant_share"] == pytest.approx(0.41)
    text = report.render(summary)
    # Golden lines of the decomposition table.
    assert "phase decomposition (on-device cycle clocks, ns):" in text
    assert "next structural cost: compaction, 41% of cycle" in text
    assert "bound evaluation" in text and "fused prune+push" in text
    # No device_phases events -> no table, no crash.
    empty = report.summarize([])
    assert empty["phase_decomp"] is None
    assert "next structural cost" not in report.render(empty)


def test_cli_profile_subcommand(monkeypatch, capsys):
    monkeypatch.delenv("TTS_PHASEPROF", raising=False)
    rc = cli.main(["profile", "nqueens", "--N", "8", "--tier", "device",
                   "--M", "64", "--m", "5", "--json"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "phase decomposition (on-device cycle clocks, ns):" in out
    assert "next structural cost:" in out
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["obs"]["device_phases"]["total"] > 0
    # The pin is restored: a later build in this process is unarmed.
    import os

    assert os.environ.get("TTS_PHASEPROF") is None


def test_cli_profile_requires_run_command(capsys):
    with pytest.raises(SystemExit):
        cli.main(["profile"])
    with pytest.raises(SystemExit):
        cli.main(["profile", "report", "x.json"])


def test_cli_phase_profile_flag_rejected_off_resident():
    with pytest.raises(SystemExit):
        cli.main(["nqueens", "--tier", "seq", "--phase-profile"])
    with pytest.raises(SystemExit):
        cli.main(["nqueens", "--tier", "device", "--engine", "offload",
                  "--phase-profile"])


def test_cli_profile_trace_nests_the_search_spans(tmp_path):
    """``--profile DIR`` is the one in-program profiler entry point: on
    one host line of its trace, the search's host spans nest under
    ``tts.search`` in the order the search runs them, every one carries
    the search's number, and each dispatch has one ``tts.dispatch`` and
    one later ``tts.wait`` of the same number."""
    import glob

    from jax.profiler import ProfileData

    out_dir = tmp_path / "xprof"
    rc = cli.main(["nqueens", "--N", "9", "--tier", "device", "--M", "128",
                   "--m", "5", "--K", "4", "--profile", str(out_dir)])
    assert rc == 0
    (path,) = glob.glob(str(out_dir / "**" / "*.xplane.pb"), recursive=True)
    prof = ProfileData.from_file(path)
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
              for e in line.events if e.name.startswith("tts.")]
             for plane in prof.planes if plane.name == "/host:CPU"
             for line in plane.lines]
    (spans,) = [ln for ln in lines if ln]
    spans.sort(key=lambda s: (s[1], -s[2]))
    (name, t0, t1, stats), rest = spans[0], spans[1:]
    assert name == "tts.search"
    assert all(t0 <= a and b <= t1 for _, a, b, _ in rest)
    assert {s["search"] for *_, s in spans} == {stats["search"]}
    names = [n for n, *_ in rest]
    assert names[:3] == ["tts.warmup", "tts.program", "tts.upload"]
    assert names[-2:] == ["tts.download", "tts.drain"]
    loop = rest[3:-2]
    assert {n for n, *_ in loop} == {"tts.dispatch", "tts.wait"}
    sent = {s["dispatch"]: b for n, _, b, s in loop if n == "tts.dispatch"}
    waited = {s["dispatch"]: a for n, a, _, s in loop if n == "tts.wait"}
    assert len(sent) == len([n for n, *_ in loop if n == "tts.dispatch"])
    assert sorted(sent) == sorted(waited) == list(range(1, len(sent) + 1))
    assert all(sent[d] <= waited[d] for d in sent)


def test_flightrec_snapshot_names_dominant_phase(monkeypatch):
    from tpu_tree_search.obs import flightrec
    from tpu_tree_search.obs.live import format_snapshot

    monkeypatch.setenv("TTS_FLIGHTREC", "1")
    rec = flightrec.FlightRecorder(snapshot_period_us=0.0)
    rec.heartbeat("resident", seq=1, cycles=4, size=10, best=3, tree=100,
                  sol=1, phases={"pop": 10, "eval": 20, "compact": 50,
                                 "push": 15, "overflow": 5, "balance": 0,
                                 "loop": 2, "total": 100})
    snap = rec.latest()
    assert snap["dominant_phase"] == "compact"
    assert snap["dominant_phase_share"] == pytest.approx(0.5)
    assert snap["phases"]["compact"] == 50
    # /state (the post-mortem payload) carries the split per worker.
    st = rec.state()
    assert st["last_dispatch"]["h0/w0"]["phases"]["compact"] == 50
    # The watch line names it.
    assert "phase=compact:50%" in format_snapshot(snap)
