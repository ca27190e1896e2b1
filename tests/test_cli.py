"""CLI surface tests: flag parsing/validation, banner + JSON + stats-file
output, golden counts through ``main()`` — the reference's per-main
`check_parameters`/`print_results` behavior (`pfsp_chpl.chpl:42-77`)
centralized in one program."""

from __future__ import annotations

import json

import pytest

from tpu_tree_search import cli


def _last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def test_seq_json_golden(capsys):
    assert cli.main(["nqueens", "--N", "8", "--json"]) == 0
    rec = _last_json(capsys.readouterr().out)
    assert (rec["explored_tree"], rec["explored_sol"]) == (2056, 92)
    assert rec["tier"] == "seq"


def test_device_tier_banner_and_stats(tmp_path, capsys):
    stats = tmp_path / "stats.dat"
    assert cli.main([
        "nqueens", "--N", "8", "--tier", "device", "--m", "5", "--M", "64",
        "--stats-file", str(stats),
    ]) == 0
    out = capsys.readouterr().out
    assert "Single-device TPU tree search" in out
    assert "Size of the explored tree: 2056" in out
    rec = json.loads(stats.read_text().strip())
    assert rec["explored_sol"] == 92


def test_pfsp_banner_reports_makespan(capsys):
    # Full Taillard searches take minutes on CPU; a --max-steps cutoff still
    # exercises the whole banner path (settings, interruption notice, and
    # the ub=1 makespan line).
    assert cli.main([
        "pfsp", "--inst", "1", "--lb", "lb1", "--tier", "device",
        "--m", "5", "--M", "512", "--K", "2", "--max-steps", "2",
    ]) == 0
    out = capsys.readouterr().out
    assert "Taillard's instance: ta001" in out
    assert "Exploration interrupted" in out
    assert "Optimal makespan: 1278 (not improved)" in out


@pytest.mark.parametrize("argv,msg", [
    (["nqueens", "--tier", "mesh", "--engine", "offload"], "resident-only"),
    (["nqueens", "--tier", "seq", "--perc", "0.3"], "--perc only applies"),
    (["nqueens", "--tier", "seq", "--hosts", "2"], "only apply to --tier dist"),
    (["nqueens", "--tier", "dist", "--hosts", "0"], "--hosts must be >= 1"),
    (["nqueens", "--tier", "seq", "--mp", "2"], "--mp only applies"),
    (["pfsp", "--tier", "mesh", "--lb", "lb1", "--mp", "2"], "lb2 Johnson"),
    (["nqueens", "--tier", "dist", "--distributed", "--hosts", "2"],
     "mutually exclusive"),
    (["nqueens", "--tier", "multi", "--perc", "1.5"], "in (0, 1]"),
    (["nqueens", "--tier", "multi", "--perc", "0"], "in (0, 1]"),
    (["nqueens", "--tier", "multi", "--perc", "-0.25"], "in (0, 1]"),
    (["nqueens", "--tier", "dist", "--coordinator", "localhost:1"],
     "require --distributed"),
    (["nqueens", "--tier", "dist", "--host-id", "0"], "require --distributed"),
    (["nqueens", "--tier", "seq", "--steal-interval", "0.1"],
     "only applies to --tier dist"),
    (["nqueens", "--tier", "dist", "--steal-interval", "-1"], "must be > 0"),
])
def test_flag_validation(argv, msg, capsys):
    with pytest.raises(SystemExit) as e:
        cli.main(argv)
    assert e.value.code == 2
    assert msg in capsys.readouterr().err


@pytest.mark.parametrize("M,name,tier,engine,backend,expect", [
    (7777, "pfsp", "device", "resident", "tpu", 7777),  # explicit wins
    (None, "pfsp", "device", "resident", "tpu", 1024),  # measured default
    (None, "pfsp", "device", "resident", "cpu", 50000),  # unmeasured backend
    (None, "pfsp", "device", "offload", "tpu", 50000),  # per-chunk round trip
    (None, "pfsp", "mesh", "resident", "tpu", 1024),   # per shard, same cycle
    (None, "pfsp", "dist_mesh", "offload", "tpu", 1024),  # always resident
    (None, "pfsp", "mesh", "resident", "cpu", 50000),
    (None, "nqueens", "device", "resident", "tpu", 50000),  # wide frontier
])
def test_resolve_chunk_size(M, name, tier, engine, backend, expect):
    """--M defaults come from the round-5 on-chip tuning
    (docs/HW_VALIDATION.md); explicit values, the offload engine, and
    unmeasured combinations keep the reference's 50000 (`util.chpl`)."""
    assert cli.resolve_chunk_size(M, name, tier, engine, backend) == expect


def test_resolve_chunk_size_backend_default_tracks_kernel_knob(monkeypatch):
    """With no explicit backend the candidate row follows the host
    platform (`pallas_kernels.platform`): this CPU host takes the 50000
    row, and asking for the kernels (TTS_PALLAS=force) does not turn it
    into the TPU's 1024 row."""
    import jax

    from tpu_tree_search.ops import pallas_kernels as PK

    assert PK.platform() == jax.default_backend() == "cpu"
    monkeypatch.delenv("TTS_PALLAS", raising=False)
    assert cli.resolve_chunk_size(None, "pfsp", "device", "resident") == 50000
    monkeypatch.setenv("TTS_PALLAS", "force")
    assert cli.resolve_chunk_size(None, "pfsp", "device", "resident") == 50000


def test_resolve_chunk_size_non_candidates_skip_backend_probe():
    """--tier seq (and every non-candidate) must not import/initialize jax
    just to compute a chunk size it discards."""
    import builtins
    from unittest import mock

    real_import = builtins.__import__

    def guarded(name, *a, **kw):
        assert name != "jax", "non-candidate resolved the backend"
        return real_import(name, *a, **kw)

    with mock.patch.object(builtins, "__import__", side_effect=guarded):
        assert cli.resolve_chunk_size(None, "nqueens", "seq", "resident") == 50000
        assert cli.resolve_chunk_size(None, "pfsp", "device", "offload") == 50000


def test_compact_flag_pins_env_and_is_recorded(capsys, monkeypatch):
    """--compact must pin TTS_COMPACT for the run (restoring afterwards —
    two main() calls in one process must not leak the pin) and the JSON
    record must name the active mode (so a stats line proves which
    compaction ran); tiers whose engine never compacts carry no key and
    reject the flag."""
    import os

    monkeypatch.delenv("TTS_COMPACT", raising=False)
    cli.main(["nqueens", "--N", "8", "--tier", "device", "--M", "64",
              "--compact", "sort", "--json"])
    rec = _last_json(capsys.readouterr().out)
    assert rec["compact"] == "sort"
    assert rec["explored_sol"] == 92  # N=8 golden
    assert "TTS_COMPACT" not in os.environ  # pin restored, not leaked

    cli.main(["nqueens", "--N", "8", "--tier", "device", "--M", "64",
              "--json"])
    rec2 = _last_json(capsys.readouterr().out)
    # Default knob is auto; the record carries the RESOLVED path (dense for
    # N-Queens — ops/compaction.py policy), not the prior run's pin.
    assert rec2["compact"] == "dense" and rec2["compact_auto"] is True

    # Offload/seq runs never compact: no flag, no key.
    with pytest.raises(SystemExit) as e:
        cli.main(["nqueens", "--N", "8", "--tier", "device",
                  "--engine", "offload", "--compact", "sort"])
    assert e.value.code == 2
    cli.main(["nqueens", "--N", "8", "--tier", "device",
              "--engine", "offload", "--M", "64", "--json"])
    assert "compact" not in _last_json(capsys.readouterr().out)


# -- compile cache placement (utils/compile_cache.py) ------------------------

_CACHE_PROBE = """
import sys, jax, jax.numpy as jnp
sys.path.insert(0, {repo!r})
from tpu_tree_search.utils import compile_cache
print(compile_cache.enable())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: x * {k!r} + 1.0)(jnp.ones(4)).block_until_ready()
"""


def _cache_probe(tmp_path, env_dir):
    import os
    import random
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    # a fresh constant: a program no earlier run has cached
    code = _CACHE_PROBE.format(repo=repo, k=random.random() + 2.0)
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120,
                         cwd=str(tmp_path))
    assert res.returncode == 0, res.stderr
    return res.stdout.split()[:2]


def test_compile_cache_honors_env_dir(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: entries land there and the code sets
    no other directory."""
    from tpu_tree_search.utils import compile_cache

    where = tmp_path / "placed"
    said, used = _cache_probe(tmp_path, where)
    assert said == used == str(where)
    assert any(where.iterdir())
    assert str(compile_cache.DEFAULT_DIR) not in (said, used)


def test_compile_cache_defaults_inside_checkout(tmp_path):
    """Unset: a fixed directory inside the checkout (listed in
    .gitignore), whatever the working directory or host."""
    from tpu_tree_search.utils import compile_cache

    d = compile_cache.DEFAULT_DIR
    repo = d.parent
    assert d.name == ".jax_cache" and (repo / "tpu_tree_search").is_dir()
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
    before = set(d.iterdir()) if d.is_dir() else set()
    said, used = _cache_probe(tmp_path, None)
    assert said == used == str(d)
    assert set(d.iterdir()) - before


def test_json_record_names_the_device_and_the_flavor_that_ran(capsys):
    """A CPU device-tier run says where it ran, and that no Pallas kernel
    was on its path (the jnp oracles ran, not the TPU flavor)."""
    import jax

    assert cli.main(["nqueens", "--N", "8", "--tier", "device",
                     "--json"]) == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    dev = jax.devices()
    assert rec["platform"] == dev[0].platform == "cpu"
    assert rec["device_kind"] == dev[0].device_kind
    assert rec["device_count"] == len(dev)
    assert rec["kernel_backend"] == "jnp" and rec["pallas"] is False
    assert rec["kernel_interpret"] is False


def test_json_record_flags_interpreted_kernels(capsys, monkeypatch):
    monkeypatch.setenv("TTS_PALLAS_INTERPRET", "1")
    assert cli.main(["nqueens", "--N", "6", "--tier", "device",
                     "--M", "64", "--json"]) == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["kernel_backend"] == "tpu" and rec["pallas"] is True
    assert rec["kernel_interpret"] is True


_RESIDENT_N6 = ["nqueens", "--N", "6", "--tier", "device", "--engine",
                "resident", "--m", "4", "--M", "64"]


def test_cli_json_records_the_armed_cycle_route(capsys, monkeypatch):
    """The armed cycle, interpreted on this CPU host, is the Pallas route:
    the record says ``tpu`` and interpreted, and has no
    ``kernel_backend_mode`` (there is no flavor knob to report)."""
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    assert cli.main(_RESIDENT_N6 + ["--json"]) == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["megakernel"] == "on"
    assert rec["kernel_backend"] == "tpu" and rec["kernel_interpret"] is True
    assert "kernel_backend_mode" not in rec


def test_cli_banner_names_the_kernel_route(capsys, monkeypatch):
    """The banner prints the route as ``Kernel backend: <route>``, with
    no forced-flavor tag."""
    monkeypatch.setenv("TTS_PALLAS_INTERPRET", "1")
    assert cli.main(_RESIDENT_N6) == 0
    out = capsys.readouterr().out
    assert "Kernel backend: tpu\n" in out
    assert "forced" not in out


def test_cli_json_default_backend_unforced(capsys, monkeypatch):
    """A default CPU run records the jnp route and no
    ``kernel_backend_mode``; asking for the kernels (TTS_PALLAS=force)
    changes neither on a host that is no TPU."""
    for env in ({}, {"TTS_PALLAS": "force"}):
        for k in ("TTS_PALLAS", "TTS_PALLAS_INTERPRET", "TTS_MEGAKERNEL"):
            monkeypatch.delenv(k, raising=False)
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        assert cli.main(_RESIDENT_N6 + ["--json"]) == 0
        rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rec["kernel_backend"] == "jnp", env
        assert "kernel_backend_mode" not in rec
