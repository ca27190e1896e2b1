"""``tts check`` — the compiled-program contract auditor.

Enumerates the **knob matrix** (problem family x bound x ``TTS_COMPACT`` x
``TTS_LB2_PAIRBLOCK`` x ``TTS_OBS`` x ``TTS_PHASEPROF``, with
``TTS_PIPELINE``/``TTS_GUARD`` covered by inertness variants), traces every
cell's resident program with ``jax.make_jaxpr`` / lowered StableHLO on
whatever backend is present (CPU is enough — **no execution happens**),
and evaluates every registered :class:`~.contracts.Contract` against the
artifacts.  Three kinds of output:

* **Contract violations** — a named claim (see ``docs/ANALYSIS.md``
  catalogue) failing on a named cell.  Always fatal: contracts carry no
  accepted-debt baseline.
* **Fingerprint drift** — each cell's recursive primitive histogram is
  compared against the committed ``.tts-contracts.json``
  (``tts check --update`` regenerates it).  Drift fails with the named
  cell and a per-op diff; this is the same commit-the-expected-state
  ratchet discipline as ``tts lint``'s baseline, at program granularity.
  The baseline records the jax version it was traced under — under a
  different jax the op-level comparison is skipped with a warning (XLA's
  lowering is not stable across releases; the structural contracts above
  still run and still gate).
* **Lock-order audit** — the static lock-acquisition graph
  (``analysis/lockorder.py``) evaluated as a contract over the package.

The knob pins are process-local and restored: the audit clears every
knob it does not set, so ``tts check`` is deterministic under CI's
``TTS_OBS=1`` / ``TTS_COMPACT=<mode>`` matrix jobs too.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os

from .contracts import (
    CONTRACTS,
    CacheKeyArtifact,
    StepArtifact,
    VariantArtifact,
    contract,
    prim_counts,
)
from .core import Finding, Project, parse_modules

DEFAULT_BASELINE = ".tts-contracts.json"

#: Every knob a cell may pin; ``_pin`` clears the rest so the audit is
#: deterministic under CI's env-matrix jobs.
KNOBS = (
    "TTS_COMPACT", "TTS_OBS", "TTS_PHASEPROF", "TTS_LB2_PAIRBLOCK",
    "TTS_PIPELINE", "TTS_K", "TTS_GUARD", "TTS_PALLAS", "TTS_PALLAS_LB2",
    "TTS_LB2_STAGED", "TTS_FLIGHTREC", "TTS_COSTMODEL",
    "TTS_QUALITY", "TTS_MEGAKERNEL", "TTS_MEGAKERNEL_MT", "TTS_STEAL",
    "TTS_PODS", "TTS_SIM_LAT_ICI", "TTS_SIM_LAT_DCN", "TTS_NARROW",
    "TTS_HBM_GBPS",
)

#: Matrix axes (the lb2 families add the pair-block axis).
COMPACT_AXIS = ("auto", "scatter", "sort", "search", "dense")
OBS_AXIS = ("0", "1")
PHASEPROF_AXIS = ("0", "1")
PAIRBLOCK_AXIS = ("1", "4", "auto")

FAMILIES = ("nqueens", "pfsp-lb1", "pfsp-lb1d", "pfsp-lb2")


def load_contracts() -> dict:
    """Import every contract-declaring module (registration side effects)
    and return the registry."""
    from ..engine import batched, pipeline, resident  # noqa: F401
    from ..obs import counters, phases, quality  # noqa: F401
    from ..ops import compaction, megakernel, pfsp_device  # noqa: F401
    from ..parallel import topology  # noqa: F401
    from . import guard, lockorder  # noqa: F401

    return CONTRACTS


@contextlib.contextmanager
def _pin(env: dict[str, str]):
    """Pin exactly ``env`` over the audit knobs (everything else unset);
    restore on exit."""
    prev = {k: os.environ.get(k) for k in KNOBS}
    for k in KNOBS:
        os.environ.pop(k, None)
    os.environ.update({k: v for k, v in env.items() if v is not None})
    try:
        yield
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# -- the matrix ------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Cell:
    """One knob-matrix cell of one problem family."""

    family: str
    compact: str = "auto"
    obs: str = "0"
    phaseprof: str = "0"
    pairblock: str | None = None
    # None = knob unset (the historical matrix — keys stay byte-stable);
    # "force" pins the one-kernel cycle (ops/megakernel.py) armed, or the
    # refusal fallback where the family cannot arm (pfsp-lb1d).
    megakernel: str | None = None
    # None = TTS_MEGAKERNEL_MT unset (keys stay byte-stable); a width
    # pins the streamed pool-tile axis so the grid form (grid = M/Mt > 1)
    # gets its own audited cell.
    mt: str | None = None

    @property
    def key(self) -> str:
        s = f"{self.family}|compact={self.compact}|obs={self.obs}|ph={self.phaseprof}"
        if self.pairblock is not None:
            s += f"|pb={self.pairblock}"
        if self.megakernel is not None:
            s += f"|mk={self.megakernel}"
        if self.mt is not None:
            s += f"|mt={self.mt}"
        return s

    def env(self) -> dict[str, str]:
        e = {
            "TTS_COMPACT": self.compact,
            "TTS_OBS": self.obs,
            "TTS_PHASEPROF": self.phaseprof,
        }
        if self.pairblock is not None:
            e["TTS_LB2_PAIRBLOCK"] = self.pairblock
        if self.megakernel is not None:
            e["TTS_MEGAKERNEL"] = self.megakernel
        if self.mt is not None:
            e["TTS_MEGAKERNEL_MT"] = self.mt
        return e


def _family_factory(family: str):
    """(problem factory, build params) for one family.  Shapes are the
    smallest ones that still exercise every structural path (tracing cost,
    not runtime, is what matters — nothing here executes)."""
    from ..problems import NQueensProblem, PFSPProblem
    from ..problems.pfsp import taillard

    if family == "nqueens":
        return (lambda: NQueensProblem(N=8)), dict(m=5, M=64, K=4)
    if family == "pfsp-lb1":
        return (lambda: PFSPProblem(
            lb="lb1", ub=0, p_times=taillard.reduced_instance(14, 10, 5)
        )), dict(m=5, M=128, K=4)
    if family == "pfsp-lb1d":
        return (lambda: PFSPProblem(
            lb="lb1_d", ub=0, p_times=taillard.reduced_instance(14, 10, 5)
        )), dict(m=5, M=128, K=4)
    if family == "pfsp-lb2":
        return (lambda: PFSPProblem(
            lb="lb2", ub=0, p_times=taillard.reduced_instance(14, 8, 5)
        )), dict(m=5, M=64, K=4)
    raise ValueError(f"unknown family {family!r} (know {FAMILIES})")


def matrix_cells(families=None, compact=None, obs=None, phaseprof=None,
                 pairblock=None) -> list[Cell]:
    """The full (or axis-filtered) knob matrix."""
    out: list[Cell] = []
    for fam in families or FAMILIES:
        pbs = (pairblock or PAIRBLOCK_AXIS) if fam == "pfsp-lb2" else (None,)
        for c in compact or COMPACT_AXIS:
            for o in obs or OBS_AXIS:
                for ph in phaseprof or PHASEPROF_AXIS:
                    for pb in pbs:
                        out.append(Cell(fam, c, o, ph, pb))
        # One-kernel cycle axis (TTS_MEGAKERNEL=force): compact stays
        # auto (the fused cycle subsumes the survivor path), pairblock
        # stays auto on lb2; pfsp-lb1d pins the REFUSAL fallback — the
        # megakernel-single-call contract asserts a recorded reason and
        # zero pallas_calls there.
        pb = "auto" if fam == "pfsp-lb2" else None
        for o in obs or OBS_AXIS:
            for ph in phaseprof or PHASEPROF_AXIS:
                out.append(Cell(fam, "auto", o, ph, pb, megakernel="force"))
        # Streamed-grid axis (TTS_MEGAKERNEL_MT, ops/megakernel.py): one
        # tiled force cell per armable family — Mt=16 divides every matrix
        # M (64/128) so the pool axis genuinely tiles (grid > 1). pfsp-lb1d
        # is the refusal family; the tile width is inert there and the
        # force cells above already audit the fallback.
        if fam != "pfsp-lb1d":
            out.append(Cell(fam, "auto", (obs or OBS_AXIS)[0],
                            (phaseprof or PHASEPROF_AXIS)[0], pb,
                            megakernel="force", mt="16"))
    return out


def trace_cell(cell: Cell, problem=None, params=None) -> StepArtifact:
    """Build + trace one cell's resident program (no execution).  A shared
    ``problem`` instance exercises the program cache across cells; None
    builds a fresh one."""
    import jax

    factory, p = _family_factory(cell.family)
    if problem is None:
        problem = factory()
    if params is None:
        params = p
    from ..engine.resident import _make_program, resolve_capacity

    with _pin(cell.env()):
        capacity, M = resolve_capacity(problem, params["M"], None)
        prog = _make_program(problem, params["m"], M, params["K"], capacity,
                             jax.devices()[0])
        state = prog.init_state({}, getattr(problem, "initial_ub", 0))
        jaxpr = jax.make_jaxpr(prog._step)(*state)
        eval_counts = _eval_counts(prog, M)
    return StepArtifact(
        prog, jaxpr, lower_fn=lambda: prog._step.lower(*state).as_text(),
        eval_counts=eval_counts,
    )


def _eval_counts(prog, M: int) -> dict[str, int]:
    """Primitive histogram of the cell's BARE bound evaluator — the
    op budget the survivor-path contracts charge against (an lb2
    evaluator's one-hot free-flag scatter is the evaluator's business;
    the dense survivor path may add nothing on top)."""
    import jax
    import jax.numpy as jnp

    ev = prog._make_eval()
    n = prog.problem.child_slots
    args = (
        jnp.zeros((M, n), jnp.int32),
        jnp.zeros((M,), jnp.int32),
        jnp.zeros((M,), bool),
        jnp.int32(0),
    )
    return prim_counts(jax.make_jaxpr(ev)(*args))


def _contracts_for(artifact_kind: str):
    return [c for c in CONTRACTS.values() if c.artifact == artifact_kind]


def _violations(name: str, cell_key: str, msgs) -> list[Finding]:
    return [
        Finding(f"contract:{name}", cell_key, 0, 0, m) for m in msgs
    ]


def audit_matrix(cells, fingerprints: dict | None = None) -> list[Finding]:
    """Trace every cell and run the resident-step contracts.  When
    ``fingerprints`` is given, each cell's op histogram + outvar count is
    recorded into it under the cell key."""
    findings: list[Finding] = []
    by_family: dict[str, list[Cell]] = {}
    for c in cells:
        by_family.setdefault(c.family, []).append(c)
    step_contracts = _contracts_for("resident-step")
    for fam, fam_cells in by_family.items():
        factory, params = _family_factory(fam)
        problem = factory()  # shared per family: exercises the cache keys
        for cell in fam_cells:
            art = trace_cell(cell, problem=problem, params=params)
            for c in step_contracts:
                findings.extend(_violations(c.name, cell.key, c.run(art, cell)))
            if fingerprints is not None:
                fingerprints[cell.key] = {
                    "ops": art.counts,
                    "outvars": len(art.jaxpr.jaxpr.outvars),
                }
    return findings


def audit_compact_ids(fingerprints: dict | None = None) -> list[Finding]:
    """The bare rank-inversion contracts (`ops/compaction.compact_ids`),
    traced per mode on the (64, 20)-grid shape the tests pinned."""
    import jax
    import numpy as np

    from ..ops.compaction import MODES, compact_ids

    findings: list[Finding] = []
    ids_contracts = _contracts_for("compact-ids")
    with _pin({}):
        for mode in MODES:
            jaxpr = jax.make_jaxpr(
                lambda k, m=mode: compact_ids(k, 640, m)
            )(np.zeros((64, 20), bool))
            art = {"mode": mode, "jaxpr": jaxpr}
            key = f"compact-ids|mode={mode}"
            for c in ids_contracts:
                findings.extend(_violations(c.name, key, c.run(art, None)))
            if fingerprints is not None:
                fingerprints[key] = {"ops": prim_counts(jaxpr)}
    return findings


def audit_lb2_eval(fingerprints: dict | None = None,
                   pairblocks=(1, 8, None)) -> list[Finding]:
    """The lb2 pair-axis contracts on the published blocked shape (ta021:
    P=190 pairs — where the auto policy genuinely blocks, so the loop-free
    pin is not vacuous).  ``None`` in ``pairblocks`` = the auto
    resolution."""
    import jax
    import jax.numpy as jnp

    from ..ops import pfsp_device as P
    from ..problems import PFSPProblem

    findings: list[Finding] = []
    eval_contracts = _contracts_for("lb2-eval")
    with _pin({}):
        prob = PFSPProblem(inst=21, lb="lb2", ub=1)
        t = prob.device_tables()
        n = prob.jobs
        args = (jnp.zeros((8, n), jnp.int32), jnp.zeros((8,), jnp.int32),
                t.ptm_t, t.min_heads, t.min_tails, t.pairs, t.lags,
                t.johnson_schedules)
        for pb in pairblocks:
            pb_resolved = P.lb2_pairblock(t.pairs.shape[0], n) if pb is None \
                else pb
            child = jax.make_jaxpr(
                lambda *a: P._lb2_chunk(*a, pairblock=pb_resolved))(*args)
            self_ = jax.make_jaxpr(
                lambda *a: P._lb2_self_chunk(*a, pairblock=pb_resolved))(*args)
            art = {"pairblock": pb_resolved, "auto": pb is None,
                   "child": child, "self": self_}
            key = f"lb2-eval|pb={'auto:' if pb is None else ''}{pb_resolved}"
            for c in eval_contracts:
                findings.extend(_violations(c.name, key, c.run(art, None)))
            if fingerprints is not None:
                fingerprints[key] = {
                    "ops": prim_counts(child),
                    "ops_self": prim_counts(self_),
                }
    return findings


def audit_batched(fingerprints: dict | None = None,
                  widths=(1, 2)) -> list[Finding]:
    """The instance-batch contracts (``engine/batched.py``): B=1 jaxpr
    byte-identity against the solo resident step, and splice-aval
    equality (``make_slot`` leaves == the compiled step's per-slot input
    avals) for each audited width.  Tracing only — nothing executes."""
    import jax

    from ..engine.batched import make_batched_program
    from ..engine.resident import _make_program, resolve_capacity

    factory, params = _family_factory("nqueens")
    findings: list[Finding] = []
    step_contracts = _contracts_for("batched-step")
    with _pin({}):
        problem = factory()
        capacity, M = resolve_capacity(problem, params["M"], None)
        dev = jax.devices()[0]
        inner = _make_program(problem, params["m"], M, params["K"],
                              capacity, dev)
        state = inner.init_state({}, getattr(problem, "initial_ub", 0))
        resident_text = str(jax.make_jaxpr(inner._step)(*state))
        for B in widths:
            prog = make_batched_program(problem, B, params["m"], M,
                                        params["K"], capacity, dev)
            args = [leaf for _ in range(B) for leaf in state]
            jaxpr = jax.make_jaxpr(prog._step)(*args)
            art = {
                "B": B,
                "b1_text": str(jaxpr) if B == 1 else None,
                "resident_text": resident_text,
                "slot_avals": [(tuple(s.shape), str(s.dtype))
                               for s in prog.slot_avals()],
                "carry_avals": [(tuple(a.shape), str(a.dtype))
                                for a in jaxpr.in_avals],
            }
            key = f"batched|nqueens|B{B}"
            for c in step_contracts:
                findings.extend(_violations(c.name, key, c.run(art, None)))
            if fingerprints is not None:
                fingerprints[key] = {
                    "ops": prim_counts(jaxpr),
                    "outvars": len(jaxpr.jaxpr.outvars),
                }
    return findings


# -- variant (byte-identity / knob-inertness) artifacts --------------------

#: label -> env pins.  "off" is the all-unset baseline every identity
#: contract compares against.
VARIANT_ENVS = {
    "off": {},
    "obs0": {"TTS_OBS": "0"},
    "obs-host": {"TTS_OBS": "host"},
    "obs1": {"TTS_OBS": "1"},
    "phase0": {"TTS_PHASEPROF": "0"},
    "phase1": {"TTS_PHASEPROF": "1"},
    "phase1-obs1": {"TTS_PHASEPROF": "1", "TTS_OBS": "1"},
    "pipe0": {"TTS_PIPELINE": "0"},
    "pipe2": {"TTS_PIPELINE": "2"},
    "guard1": {"TTS_GUARD": "1"},
    "quality1": {"TTS_QUALITY": "1"},
    "mk0": {"TTS_MEGAKERNEL": "0"},
    # Streamed-grid axis: off must stay byte-identical under a pinned tile
    # width (the knob only matters once the kernel arms), and the tiled
    # force build must keep the off step's outvar signature
    # (megakernel-tiled-identity, ops/megakernel.py).
    "mk0-mt": {"TTS_MEGAKERNEL": "0", "TTS_MEGAKERNEL_MT": "16"},
    "mk-tiled": {"TTS_MEGAKERNEL": "force", "TTS_MEGAKERNEL_MT": "16"},
    "steal-flat": {"TTS_STEAL": "flat"},
    "steal-hier": {"TTS_STEAL": "hier", "TTS_PODS": "2"},
    "narrow0": {"TTS_NARROW": "0"},
}


def variant_artifact(family: str, labels=None) -> VariantArtifact:
    """Trace one family's step under each variant env — every label on a
    FRESH problem instance, so identity is a fact about the build, never a
    cache hit."""
    import jax

    from ..engine.resident import _make_program, resolve_capacity
    from ..ops.compaction import resolve_compact_mode

    factory, params = _family_factory(family)
    variants: dict[str, tuple[str, int]] = {}

    def trace(env) -> tuple[str, int]:
        problem = factory()
        with _pin(env):
            capacity, M = resolve_capacity(problem, params["M"], None)
            prog = _make_program(problem, params["m"], M, params["K"],
                                 capacity, jax.devices()[0])
            state = prog.init_state({}, getattr(problem, "initial_ub", 0))
            jaxpr = jax.make_jaxpr(prog._step)(*state)
        return str(jaxpr), len(jaxpr.jaxpr.outvars)

    for label, env in VARIANT_ENVS.items():
        if labels is not None and label not in labels:
            continue
        variants[label] = trace(env)
    if labels is None or any(lb.startswith("compact-") for lb in labels):
        # auto-vs-explicit identity: trace auto and the mode it resolves to.
        with _pin({"TTS_COMPACT": "auto"}):
            _, M0 = resolve_capacity(factory(), params["M"], None)
            resolved = resolve_compact_mode(
                factory(), M0, factory().child_slots
            )
        variants["compact-auto"] = trace({"TTS_COMPACT": "auto"})
        variants[f"compact-{resolved}"] = trace({"TTS_COMPACT": resolved})
    return VariantArtifact(variants)


def audit_variants(families=None) -> list[Finding]:
    findings: list[Finding] = []
    var_contracts = _contracts_for("variants")
    for fam in families or FAMILIES:
        art = variant_artifact(fam)
        for c in var_contracts:
            findings.extend(_violations(c.name, f"{fam}|variants",
                                        c.run(art, None)))
    return findings


def cache_key_artifact(family: str) -> CacheKeyArtifact:
    """Observed ``_make_program`` cache behavior on one instance: knobs
    that are baked into the compiled program must rebuild on a flip; the
    host-only knobs must hit the same cached program."""
    import jax

    from ..engine.resident import _make_program, resolve_capacity

    factory, params = _family_factory(family)
    problem = factory()

    def build(env):
        with _pin(env):
            capacity, M = resolve_capacity(problem, params["M"], None)
            return _make_program(problem, params["m"], M, params["K"],
                                 capacity, jax.devices()[0])

    base = {"TTS_COMPACT": "sort"}
    p0 = build(base)
    distinct = {
        "TTS_COMPACT": (p0, build({**base, "TTS_COMPACT": "search"})),
        "TTS_OBS": (p0, build({**base, "TTS_OBS": "1"})),
        "TTS_PHASEPROF": (p0, build({**base, "TTS_PHASEPROF": "1"})),
        # The one-kernel cycle is baked into the step (and into the
        # routing token even when the resolver refuses), so a knob flip
        # must rebuild — a stale cached off-program under force (or vice
        # versa) would silently run the wrong cycle body.
        "TTS_MEGAKERNEL": (
            build({**base, "TTS_MEGAKERNEL": "0"}),
            build({**base, "TTS_MEGAKERNEL": "force"}),
        ),
        # The streamed pool-tile width changes the armed cycle's grid
        # (single-tile resident vs tiled streaming), so a pinned Mt under
        # force must build a distinct program from plain force.
        "TTS_MEGAKERNEL_MT": (
            build({**base, "TTS_MEGAKERNEL": "force"}),
            build({**base, "TTS_MEGAKERNEL": "force",
                   "TTS_MEGAKERNEL_MT": "16"}),
        ),
        # Narrow host storage: the device step jaxpr is knob-inert
        # (`narrow-knob-inert`), but the HOST staging avals the program
        # was built against are not — a flip must rebuild so a stale
        # program never receives the other layout's arrays.
        "TTS_NARROW": (
            build({**base, "TTS_NARROW": "auto"}),
            build({**base, "TTS_NARROW": "0"}),
        ),
    }
    if family == "pfsp-lb2":
        distinct["TTS_LB2_PAIRBLOCK"] = (
            build({**base, "TTS_LB2_PAIRBLOCK": "1"}),
            build({**base, "TTS_LB2_PAIRBLOCK": "4"}),
        )
    shared = {
        "TTS_PIPELINE": (p0, build({**base, "TTS_PIPELINE": "2"})),
        "TTS_GUARD": (p0, build({**base, "TTS_GUARD": "1"})),
        "TTS_STEAL": (p0, build({**base, "TTS_STEAL": "hier"})),
        "rebuild": (p0, build(base)),
    }
    return CacheKeyArtifact(distinct=distinct, shared=shared)


def audit_cache_keys(families=None) -> list[Finding]:
    findings: list[Finding] = []
    key_contracts = _contracts_for("cache-key")
    for fam in families or FAMILIES:
        art = cache_key_artifact(fam)
        for c in key_contracts:
            findings.extend(_violations(c.name, f"{fam}|cache-key",
                                        c.run(art, None)))
    return findings


def audit_locks(paths=None) -> list[Finding]:
    """The lock-order contract over the package sources (or ``paths``)."""
    from . import lockorder

    if paths is None:
        pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = ["tpu_tree_search" if os.path.isdir("tpu_tree_search") else pkg]
    modules, parse_errors = parse_modules(paths)
    findings = list(parse_errors)
    graph = lockorder.build_graph(Project(modules))
    for c in _contracts_for("lock-graph"):
        findings.extend(_violations(c.name, "lock-graph", c.run(graph, None)))
    return findings


# -- the op-fingerprint baseline -------------------------------------------


def _hash_cells(cells: dict) -> str:
    blob = json.dumps(cells, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def load_baseline(path: str) -> dict | None:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (FileNotFoundError, ValueError):
        return None


def save_baseline(path: str, cells: dict) -> dict:
    import jax

    doc = {
        "comment": "tts check op-fingerprint baseline: per-cell primitive "
                   "histogram of every compiled program in the knob "
                   "matrix; regenerate with `tts check --update` (drift "
                   "must be intentional and reviewed)",
        "jax": jax.__version__,
        "fingerprint": _hash_cells(cells),
        "cells": cells,
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    return doc


def committed_fingerprint(path: str | None = None) -> str | None:
    """The committed baseline's overall fingerprint hash — bench rows
    record it so a banked perf number is tied to the exact program
    structure it measured (ISSUE 8 satellite)."""
    if path is None:
        path = DEFAULT_BASELINE if os.path.exists(DEFAULT_BASELINE) else \
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))),
                DEFAULT_BASELINE,
            )
    doc = load_baseline(path)
    return doc.get("fingerprint") if doc else None


def _diff_ops(old: dict, new: dict) -> str:
    """Readable per-op delta: the jaxpr-level diff a drift report needs."""
    deltas = []
    for op in sorted(set(old) | set(new)):
        a, b = old.get(op, 0), new.get(op, 0)
        if a != b:
            deltas.append(f"{op}: {a} -> {b}")
    return "; ".join(deltas) or "(identical op counts)"


@contract(
    "op-fingerprint",
    claim="every matrix cell's recursive primitive histogram matches the "
          "committed .tts-contracts.json baseline — compiled-program "
          "structure cannot drift silently (`tts check --update` accepts "
          "reviewed drift; a baseline traced under a different jax "
          "version is reported as a warning, not compared op-by-op)",
    artifact="fingerprint",
)
def _check_fingerprint(art, cell=None):
    current, doc = art["current"], art["baseline"]
    out = []
    if doc is None:
        return [f"no committed baseline at {art['path']} — run "
                "`tts check --update` and commit it"]
    base_cells = doc.get("cells", {})
    for key in sorted(current):
        if key not in base_cells:
            out.append(f"{key}: cell missing from baseline (new matrix "
                       "cell? run --update)")
            continue
        old, new = base_cells[key], current[key]
        if old.get("ops") != new.get("ops"):
            out.append(f"{key}: op drift — {_diff_ops(old.get('ops', {}), new.get('ops', {}))}")
        elif old.get("outvars") != new.get("outvars"):
            out.append(f"{key}: outvar count {old.get('outvars')} -> "
                       f"{new.get('outvars')}")
    for key in sorted(set(base_cells) - set(current)):
        out.append(f"{key}: baseline cell no longer produced (stale "
                   "baseline? run --update)")
    return out


# -- orchestration ---------------------------------------------------------


@dataclasses.dataclass
class CheckResult:
    findings: list[Finding]
    fingerprints: dict
    cells: int
    contracts: int
    warnings: list[str]
    updated: str | None = None

    @property
    def fingerprint(self) -> str:
        return _hash_cells(self.fingerprints)


def run_check(families=None, update: bool = False,
              baseline_path: str | None = None,
              lock_paths=None, with_locks: bool = True,
              with_fingerprint: bool = True) -> CheckResult:
    """The full audit (the ``tts check`` entry point)."""
    load_contracts()
    baseline_path = baseline_path or DEFAULT_BASELINE
    findings: list[Finding] = []
    fingerprints: dict = {}
    warnings: list[str] = []
    cells = matrix_cells(families=families)
    findings += audit_matrix(cells, fingerprints)
    findings += audit_variants(families)
    findings += audit_cache_keys(families)
    if families is None:
        findings += audit_compact_ids(fingerprints)
        findings += audit_lb2_eval(fingerprints)
        findings += audit_batched(fingerprints)
    if with_locks:
        findings += audit_locks(lock_paths)
    updated = None
    if update:
        save_baseline(baseline_path, fingerprints)
        updated = baseline_path
    elif with_fingerprint and families is None:
        doc = load_baseline(baseline_path)
        if doc is not None:
            import jax

            if doc.get("jax") != jax.__version__:
                warnings.append(
                    f"baseline {baseline_path} traced under jax "
                    f"{doc.get('jax')}, running {jax.__version__}: op-level "
                    "comparison skipped (re-run --update under this jax to "
                    "re-arm the fingerprint gate)"
                )
                doc = False  # sentinel: skip comparison, not "missing"
        if doc is not False:
            art = {"current": fingerprints, "baseline": doc,
                   "path": baseline_path}
            findings += _violations(
                "op-fingerprint", "fingerprint",
                CONTRACTS["op-fingerprint"].run(art, None),
            )
    n_contracts = len(load_contracts())
    findings.sort(key=lambda f: (f.path, f.rule, f.message))
    return CheckResult(findings, fingerprints, len(cells), n_contracts,
                       warnings, updated)


# -- CLI -------------------------------------------------------------------


def add_check_args(p) -> None:
    p.add_argument("--update", action="store_true",
                   help="regenerate the op-fingerprint baseline "
                        f"(./{DEFAULT_BASELINE}) from the current programs")
    p.add_argument("--baseline", default=None,
                   help=f"fingerprint baseline path (default ./{DEFAULT_BASELINE})")
    p.add_argument("--family", action="append", default=None, dest="families",
                   metavar="NAME", choices=FAMILIES,
                   help="audit only this problem family (repeatable; "
                        "skips the fingerprint gate, which is whole-matrix)")
    p.add_argument("--no-locks", action="store_true",
                   help="skip the lock-order audit")
    p.add_argument("--list", action="store_true", dest="list_contracts",
                   help="print the contract catalogue and exit")
    p.add_argument("--json", action="store_true", dest="check_json",
                   help="emit one JSON object instead of text")


def run_check_cli(args) -> int:
    if args.list_contracts:
        for name, c in sorted(load_contracts().items()):
            print(f"{name}  [{c.artifact}]  ({c.declared_in})")
            print(f"    {c.claim}")
        return 0
    if args.update and args.families:
        print("tts check: --update regenerates the WHOLE-matrix baseline; "
              "it cannot be combined with --family")
        return 2
    res = run_check(
        families=args.families, update=args.update,
        baseline_path=args.baseline,
        with_locks=not args.no_locks,
    )
    if args.check_json:
        print(json.dumps({
            "findings": [vars(f) for f in res.findings],
            "cells": res.cells,
            "contracts": res.contracts,
            "fingerprint": res.fingerprint,
            "warnings": res.warnings,
            "updated": res.updated,
        }))
        return 1 if res.findings else 0
    for w in res.warnings:
        print(f"warning: {w}")
    for f in res.findings:
        print(f.render())
    if res.updated:
        print(f"fingerprint baseline written: {res.updated} "
              f"({len(res.fingerprints)} cells, hash {res.fingerprint})")
    print(
        f"tts check: {len(res.findings)} finding(s) over {res.cells} matrix "
        f"cells, {res.contracts} contracts (fingerprint {res.fingerprint})"
    )
    return 1 if res.findings else 0
