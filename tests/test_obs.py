"""Telemetry subsystem (`tpu_tree_search/obs/`, docs/OBSERVABILITY.md):
counter parity against engine counts, trace-file schema, the zero-cost
disabled path (byte-identical jaxprs), and guard interaction."""

from __future__ import annotations

import json

import pytest

from tpu_tree_search import cli
from tpu_tree_search.obs import capture, counters, events, export, report
from tpu_tree_search.problems import NQueensProblem, PFSPProblem


# -- counter parity: obs totals must equal the engine's counts exactly ----


def test_seq_counter_parity():
    from tpu_tree_search.engine import sequential_search

    with capture() as cap:
        res = sequential_search(NQueensProblem(N=8))
    assert cap.explored_totals() == (res.explored_tree, res.explored_sol)
    assert (res.explored_tree, res.explored_sol) == (2056, 92)


def test_device_counter_parity_nqueens():
    from tpu_tree_search.engine.resident import resident_search

    with capture() as cap:
        res = resident_search(NQueensProblem(N=9), m=5, M=128)
    assert cap.explored_totals() == (res.explored_tree, res.explored_sol)
    # The device-phase totals come from the HARVESTED counter block, not
    # the engine's own sums (engine/resident._emit_device_explored), so
    # this equality exercises the on-device accumulation path itself.
    c = res.obs["device_counters"]
    assert c["pushed"] + res.phases[0].tree + res.phases[2].tree \
        == res.explored_tree
    assert c["leaves"] + res.phases[0].sol + res.phases[2].sol \
        == res.explored_sol
    # Structural invariants of the slot semantics.
    assert c["popped"] >= c["pushed"] // NQueensProblem(N=9).child_slots
    assert c["pool_hwm"] > 0
    assert c["surv_hwm"] > 0
    assert c["overflow"] >= 0


def test_device_counter_parity_pfsp_lb1():
    # Budgeted run (full Taillard searches take minutes on CPU): the
    # max_steps cutoff path emits the same explored samples, so parity
    # holds for partial counts too.
    from tpu_tree_search.engine.resident import resident_search

    with capture() as cap:
        res = resident_search(PFSPProblem(inst=1, lb="lb1", ub=1),
                              m=5, M=256, K=4, max_steps=3)
    assert res.explored_tree > 0
    assert cap.explored_totals() == (res.explored_tree, res.explored_sol)


def test_mesh_counter_parity():
    import jax

    from tpu_tree_search.parallel.resident_mesh import mesh_resident_search

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    with capture() as cap:
        res = mesh_resident_search(NQueensProblem(N=8), m=5, M=64, D=4)
    assert cap.explored_totals() == (res.explored_tree, res.explored_sol)
    assert (res.explored_tree, res.explored_sol) == (2056, 92)


def test_multi_counter_parity():
    import jax

    from tpu_tree_search.parallel.multidevice import multidevice_search

    D = min(4, len(jax.devices()))
    with capture() as cap:
        res = multidevice_search(NQueensProblem(N=8), m=5, M=64, D=D)
    assert cap.explored_totals() == (res.explored_tree, res.explored_sol)
    assert (res.explored_tree, res.explored_sol) == (2056, 92)


@pytest.mark.parametrize("mt", [None, "128"])
def test_push_rows_counts_the_kernel_push(mt, monkeypatch):
    """With the TPU cycle armed, ``push_rows`` is what the kernel's push
    wrote: whole chunks of nodes covering each tile's live prefix from the
    8-row pool tile it starts in — at least the nodes pushed, at most a
    chunk and a row tile more per tile — and not the constant M*n
    reservation per cycle it charged while XLA stitched."""
    import jax
    import numpy as np

    from tpu_tree_search.engine.resident import _make_program, resolve_capacity
    from tpu_tree_search.ops import megakernel as MK

    monkeypatch.setenv("TTS_OBS", "1")
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    if mt:
        monkeypatch.setenv("TTS_MEGAKERNEL_MT", mt)
    prob = NQueensProblem(N=8)
    capacity, M = resolve_capacity(prob, 512, 8192)
    prog = _make_program(prob, 1, M, 4, capacity, jax.devices()[0])
    assert prog.obs and prog.megakernel.enabled
    G, n = prog.megakernel.grid, prob.child_slots
    chunk = MK._chunk_nodes(prog.row_pack)
    tile = 8 * prog.row_pack  # nodes of an 8-row pool tile
    state = prog.init_state({k: np.repeat(v, 9, axis=0)
                             for k, v in prob.root().items()}, 0)
    total = cycles_total = 0
    for _ in range(6):
        out = prog.step(state)
        tree, _sol, cycles, _size, _best, ctr = prog.read_scalars(out)
        state = prog.carry(out)
        rows = int(ctr[counters.IDX["push_rows"]])
        pushed = int(ctr[counters.IDX["pushed"]])
        assert pushed == tree
        assert rows % chunk == 0
        assert pushed <= rows <= pushed + cycles * G * (chunk + tile - 1)
        total += rows
        cycles_total += cycles
        if not cycles:
            break
    assert 0 < total < cycles_total * M * n


# -- zero-cost disabled path (routed through the contract registry) --------
# The byte-identity and cache-key claims are Contracts (obs/counters.py,
# engine/resident.py) checked over the whole knob matrix by `tts check`;
# these tests pin the same registry entries on the historical cell.


def test_disabled_mode_jaxpr_identical_and_counter_free():
    from tpu_tree_search.analysis import contracts, program_audit

    program_audit.load_contracts()
    art = program_audit.variant_artifact(
        "nqueens", labels=["off", "obs0", "obs-host", "obs1"]
    )
    # Disabled (and host-only) builds are byte-identical: counters are
    # compiled OUT, not branched — the 7-leaf carry of the original step;
    # the enabled build carries exactly one extra leaf (the counter block).
    assert contracts.run_one("obs-off-identity", art) == []
    assert contracts.run_one("obs-counter-block", art) == []


def test_program_cache_keys_on_obs():
    from tpu_tree_search.analysis import contracts, program_audit

    program_audit.load_contracts()
    art = program_audit.cache_key_artifact("nqueens")
    a, b = art.distinct["TTS_OBS"]
    assert b.obs and not a.obs
    assert contracts.run_one("program-cache-key-sound", art) == []


# -- trace file schema -----------------------------------------------------


def test_cli_trace_schema_and_report(tmp_path, capsys):
    trace = tmp_path / "t.json"
    metrics = tmp_path / "m.jsonl"
    assert cli.main([
        "nqueens", "--N", "8", "--tier", "device", "--m", "5", "--M", "64",
        "--trace", str(trace), "--metrics-file", str(metrics), "--json",
    ]) == 0
    out = capsys.readouterr().out
    assert "Trace written" in out
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["obs"]["device_counters"]["leaves"] == 92

    obj = json.loads(trace.read_text())
    evts = obj["traceEvents"]
    assert isinstance(evts, list) and evts
    # Metadata names every (pid, tid) track.
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evts)
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in evts)
    for e in evts:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] != "M":
            assert isinstance(e["ts"], (int, float))
        if e["ph"] == "X":
            assert e["dur"] >= 0
    names = {e["name"] for e in evts}
    assert {"dispatch", "explored", "device_counters"} <= names

    # Metrics JSONL: one flat object per counter sample.
    lines = [json.loads(ln) for ln in metrics.read_text().splitlines()]
    assert lines and all("ts_us" in r and "name" in r for r in lines)
    assert any(r["name"] == "device_counters" for r in lines)

    # tts report over the written trace prints all three summaries.
    assert cli.main(["report", str(trace)]) == 0
    rep = capsys.readouterr().out
    assert "steal efficiency" in rep
    assert "idle fraction per worker" in rep
    assert "cycle-rate timeline" in rep


def test_report_json_and_missing_file(tmp_path, capsys):
    assert cli.main(["report", str(tmp_path / "nope.json")]) == 2
    capsys.readouterr()
    trace = tmp_path / "t.json"
    with capture(trace_path=str(trace)):
        from tpu_tree_search.engine import sequential_search

        sequential_search(NQueensProblem(N=6))
    assert cli.main(["report", str(trace), "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert {"steal", "idle", "cycle_rate", "events"} <= set(summary)


def test_report_truncated_trace_salvages_events(tmp_path, capsys):
    """Robustness contract: a killed writer's truncated trace is
    summarized as far as it parses — exit 0 with a warning."""
    trace = tmp_path / "t.json"
    with capture(trace_path=str(trace)):
        from tpu_tree_search.engine.resident import resident_search

        resident_search(NQueensProblem(N=9), m=8, M=128, K=4)
    full = trace.read_text()
    (tmp_path / "cut.json").write_text(full[: int(len(full) * 0.6)])
    assert cli.main(["report", str(tmp_path / "cut.json")]) == 0
    captured = capsys.readouterr()
    assert "salvaged" in captured.err
    assert "cycle-rate timeline" in captured.out


def test_report_empty_and_garbage_files_exit_zero(tmp_path, capsys):
    (tmp_path / "empty.json").write_text("")
    (tmp_path / "junk.json").write_text("not a trace at all")
    assert cli.main(["report", str(tmp_path / "empty.json"),
                     str(tmp_path / "junk.json")]) == 0
    captured = capsys.readouterr()
    assert "Warning" in captured.err
    assert "steal efficiency" in captured.out  # full report shape, zeros


def test_report_merges_multiple_metrics_files(tmp_path, capsys):
    """Multi-worker sessions write one metrics file per host; the report
    merges any mix of traces and metrics JSONL into one summary."""
    m1 = tmp_path / "h0.jsonl"
    m2 = tmp_path / "h1.jsonl"
    m1.write_text(json.dumps(
        {"ts_us": 10.0, "name": "explored", "host": 0, "worker": 0,
         "tree": 100, "sol": 2, "phase": 2}) + "\n")
    m2.write_text(json.dumps(
        {"ts_us": 12.0, "name": "explored", "host": 1, "worker": 0,
         "tree": 50, "sol": 1, "phase": 2}) + "\n"
        + "{torn line")  # mid-write kill tail: skipped, not fatal
    assert cli.main(["report", str(m1), str(m2), "--json"]) == 0
    captured = capsys.readouterr()
    summary = json.loads(captured.out)
    assert summary["events"] == 2
    assert summary["hosts"] == 2


def test_multi_trace_records_steals_and_idle(tmp_path):
    import jax

    from tpu_tree_search.parallel.multidevice import multidevice_search

    D = min(4, len(jax.devices()))
    with capture(mode="host") as cap:
        multidevice_search(NQueensProblem(N=8), m=5, M=64, D=D)
    s = cap.summary()
    # Worker tracks exist and the steal/idle sections are populated (the
    # termination scan guarantees at least one miss per worker).
    assert len(s["idle"]) == D
    assert s["steal"]["attempts"] >= 1


# -- guard interaction -----------------------------------------------------


def test_guard_green_with_obs(monkeypatch):
    """TTS_GUARD=1 + TTS_OBS=1 together: the counter block rides the
    existing dispatch result, so steady state must stay transfer- and
    recompile-free (the ISSUE 2 acceptance criterion)."""
    from tpu_tree_search.engine.resident import resident_search

    monkeypatch.setenv("TTS_GUARD", "1")
    with capture() as cap:
        res = resident_search(NQueensProblem(N=8), m=5, M=64)
    assert res.explored_sol == 92
    assert cap.explored_totals() == (res.explored_tree, res.explored_sol)


# -- events/export units ---------------------------------------------------


def test_recorder_thread_merge_and_disabled_noop(monkeypatch):
    import threading

    monkeypatch.delenv("TTS_OBS", raising=False)
    events.reset()
    events.emit("never")  # disabled: must not record
    assert events.drain() == []
    monkeypatch.setenv("TTS_OBS", "host")
    events.reset()

    def worker(wid):
        for _ in range(5):
            events.emit("tick", wid=wid)

    threads = [threading.Thread(target=worker, args=(w,)) for w in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    events.emit("tick", wid=99)
    evts = events.drain()
    assert len(evts) == 16
    assert [e["ts"] for e in evts] == sorted(e["ts"] for e in evts)
    assert {e["tid"] for e in evts} == {0, 1, 2, 99}


class _FakeProfiler:
    """Stands in for ``jax.profiler``: records every annotation entered
    and left, with its stats."""

    def __init__(self):
        self.log = []
        log = self.log

        class TraceAnnotation:
            def __init__(self, name, **stats):
                self.name, self.stats = name, stats

            def __enter__(self):
                log.append(("enter", self.name, self.stats))

            def __exit__(self, *exc):
                log.append(("exit", self.name))

        self.TraceAnnotation = TraceAnnotation


@pytest.mark.parametrize("obs", ["0", "host"])
def test_span_enters_the_profiler_annotation_either_way(obs, monkeypatch):
    """``events.span`` always puts its block into the profiler's trace
    (its args as the annotation's stats); only with TTS_OBS on does it
    also record a Chrome event."""
    import sys

    fake = _FakeProfiler()
    monkeypatch.setitem(sys.modules, "jax.profiler", fake)
    monkeypatch.setenv("TTS_OBS", obs)
    events.reset()
    with events.span("tts.wait", args={"search": 4, "dispatch": 2}):
        fake.log.append(("body",))
    assert fake.log == [("enter", "tts.wait", {"search": 4, "dispatch": 2}),
                        ("body",), ("exit", "tts.wait")]
    evts = events.drain()
    if obs == "0":
        assert evts == []
    else:
        (ev,) = evts
        assert (ev["name"], ev["ph"]) == ("tts.wait", "X")
        assert ev["args"] == {"search": 4, "dispatch": 2}


def test_span_without_jax_loaded_annotates_nothing(monkeypatch):
    """Where JAX was never imported (the host-only fleet router), a span
    neither imports it nor annotates."""
    import sys

    monkeypatch.delitem(sys.modules, "jax.profiler", raising=False)
    monkeypatch.setenv("TTS_OBS", "host")
    events.reset()
    with events.span("tts.program") as sp:
        assert sp._ann is None
    assert "jax.profiler" not in sys.modules
    assert [e["name"] for e in events.drain()] == ["tts.program"]


def test_search_span_numbers_each_search_once(monkeypatch):
    """A search span binds one number for the spans inside it (an inner
    search span keeps it) and the next search gets another."""
    import sys

    fake = _FakeProfiler()
    monkeypatch.setitem(sys.modules, "jax.profiler", fake)
    with events.search_span("tts.search"):
        seq = events.search_seq()
        assert events.search_seq() == seq
        with events.search_span("tts.search"):
            assert events.search_seq() == seq
    assert events.search_seq() > seq
    with events.search_span("tts.search"):
        assert events.search_seq() > seq + 1
    stats = [s for kind, _, *s in fake.log if kind == "enter"]
    assert stats[0] == stats[1] == [{"search": seq}]


def test_counter_block_merge_semantics():
    import numpy as np

    a = np.zeros((counters.NSLOTS,), np.int64)
    b = np.zeros((counters.NSLOTS,), np.int64)
    a[counters.IDX["pushed"]] = 10
    a[counters.IDX["pool_hwm"]] = 100
    b[counters.IDX["pushed"]] = 5
    b[counters.IDX["pool_hwm"]] = 70
    total = counters.merge_host(counters.merge_host(None, a), b)
    assert total["pushed"] == 15  # additive
    assert total["pool_hwm"] == 100  # high-water mark
    stacked = counters.as_args(np.stack([a, b]))
    assert stacked["pushed"] == 15 and stacked["pool_hwm"] == 100


def test_export_roundtrip(tmp_path):
    evts = [
        {"name": "dispatch", "cat": "tts", "ph": "X", "ts": 10.0,
         "dur": 5.0, "pid": 0, "tid": 0, "args": {"cycles": 3, "tree": 7}},
        {"name": "explored", "cat": "metrics", "ph": "C", "ts": 16.0,
         "pid": 0, "tid": 0, "args": {"tree": 7, "sol": 1, "phase": 2}},
    ]
    path = tmp_path / "t.json"
    assert export.write_chrome_trace(evts, str(path)) == 2
    back = export.load_trace(str(path))
    assert back == evts  # metadata stripped, payload preserved
    s = report.summarize(back)
    assert s["events"] == 2
    assert s["cycle_rate"] and s["cycle_rate"][0]["dispatches"] == 1
