"""Shape-class keyed program pool — the daemon's admission control.

The resident engines already cache compiled programs per problem instance
(``problem._resident_programs`` / ``problem._mesh_programs``, keyed by
(m, M, K, capacity, device, routing token, ...)). What a one-shot CLI
cannot do is reuse them ACROSS runs: every process rebuilds its problem
and pays the while-loop compile again. The pool closes that gap by making
the problem instance itself the shared resource: requests are mapped to a
**shape class** — (problem family, shape/identity, bound variant,
knob-resolved routing token, tier, m/M/K/D/mp) — and every job of a class
runs against the same problem object, so the second same-class job finds
its program already compiled (zero recompiles, TTS_GUARD green).

Two layers of sharing fall out of the identity/class split:

  * same identity, different class (e.g. two M values) -> same problem
    instance, distinct program-cache entries — the engine's own cache key
    keeps them apart;
  * same class -> same program entry, a pure cache hit.

The class key is computed WITHOUT mutating process env: per-job knobs
(compact, lb2 pair block) are resolved through the same policy functions
the engines call at trace time (``_auto_compact``, ``_auto_pairblock``),
and server-wide routing env (pallas, staging, guard, obs) is captured once
at daemon start — the daemon's env never changes mid-flight, jobs only pin
their declared knobs through the scheduler's ``EnvLease``.
"""

from __future__ import annotations

import threading
import time


def identity_key(spec: dict) -> tuple:
    """The problem-instance identity: two specs with equal identity share
    one problem object (and therefore one program cache)."""
    if spec["problem"] == "nqueens":
        return ("nqueens", spec["N"], spec["g"])
    return ("pfsp", spec["inst"], spec["lb"], spec["ub"],
            spec.get("lb2_variant", "full"))


def server_env_token() -> tuple:
    """Server-wide routing env baked into every compiled program
    (``ops.pfsp_device.routing_cache_token`` reads these at trace time).
    Captured once per daemon: flipping them requires a restart, so they
    are part of every class key only for honesty in ``/classes`` output."""
    import os

    return tuple(
        (k, os.environ.get(k))
        for k in ("TTS_PALLAS", "TTS_PALLAS_LB2", "TTS_PALLAS_INTERPRET",
                  "TTS_LB2_STAGED", "TTS_GUARD", "TTS_OBS", "TTS_PHASEPROF",
                  "TTS_PIPELINE", "TTS_K")
    )


def _problem_shape(spec: dict) -> tuple:
    """(n, machines) without constructing the problem (host-only data)."""
    if spec["problem"] == "nqueens":
        return spec["N"], None
    from ..problems.pfsp import taillard

    return taillard.nb_jobs(spec["inst"]), taillard.nb_machines(spec["inst"])


def resolved_knobs(spec: dict, platform: str) -> dict:
    """Resolve the per-job routing knobs exactly as the engines will at
    trace time on ``platform`` (the daemon's ``jax.default_backend()``),
    without env mutation — the knob-resolved part of the class token.
    Returns ``{"compact": mode, "lb2_pairblock": int | None}``."""
    import os

    n, machines = _problem_shape(spec)
    knob = spec.get("compact") or os.environ.get("TTS_COMPACT", "auto")
    if knob == "auto":
        from ..ops.compaction import _auto_compact

        # _auto_compact only reads problem.name/initial_ub; a shim spares
        # constructing the real problem in the admission path.
        shim = type("S", (), {
            "name": spec["problem"],
            "initial_ub": 0 if spec.get("ub", 1) else (1 << 30),
        })()
        compact = _auto_compact(shim, spec["M"], n, platform)
    else:
        compact = knob
    pairblock = None
    if spec["problem"] == "pfsp" and spec["lb"] == "lb2":
        from ..ops import pfsp_device as P
        from ..problems.pfsp import bounds as PB

        Pn = len(PB.machine_pairs(machines, spec.get("lb2_variant", "full")))
        pb = spec.get("lb2_pairblock") or os.environ.get(
            "TTS_LB2_PAIRBLOCK", "auto"
        )
        if pb == "auto":
            pairblock = P._auto_pairblock(Pn, n)
        else:
            pairblock = min(int(pb), Pn)
    return {"compact": compact, "lb2_pairblock": pairblock}


def class_key(spec: dict, platform: str) -> str:
    """The human-readable shape-class token on ``platform``. Everything
    that selects a distinct compiled program is in here; two jobs with
    equal keys hit the same program-cache entry."""
    ident = identity_key(spec)
    knobs = resolved_knobs(spec, platform)
    parts = ["-".join(str(p) for p in ident), spec["tier"],
             f"m{spec['m']}", f"M{spec['M']}"]
    if spec.get("K") is not None:
        parts.append(f"K{spec['K']}")
    if spec["tier"] == "mesh":
        parts.append(f"D{spec.get('D', 'all')}")
        if spec.get("mp", 1) != 1:
            parts.append(f"mp{spec['mp']}")
    parts.append(f"compact={knobs['compact']}")
    if knobs["lb2_pairblock"] is not None:
        parts.append(f"pb{knobs['lb2_pairblock']}")
    return "-".join(parts)


def compile_stats(problem) -> tuple[int, int]:
    """(program entries, jit step-cache entries) currently compiled on a
    problem instance — the pool's recompile accounting unit. Measured
    around each job slice: a warm-class admission must leave both deltas
    at zero (the serve analogue of the TTS_GUARD steady-state assertion,
    and the number `tts warmup` reports as hit/miss)."""
    from ..analysis.guard import _cache_size

    progs = 0
    steps = 0
    for attr in ("_resident_programs", "_mesh_programs",
                 "_batched_programs"):
        # Snapshot: a scheduler worker may be inserting a program while a
        # stats request iterates (len+list are atomic under the GIL).
        cache = list((getattr(problem, attr, None) or {}).values())
        progs += len(cache)
        for prog in cache:
            size = _cache_size(getattr(prog, "_step", None))
            if size is not None:
                steps += size
    return progs, steps


def resident_pool_bytes(problem) -> int:
    """Device-resident pool bytes across every program cached on a
    problem instance — the pool arrays as the device holds them
    (`pool_shapes`: capacity x per-node bytes, plus the slot padding of
    the lane-dense value rows and the slack nodes where the TPU cycle
    pushes), times the slot (B) / shard (D)
    count for the batched and mesh programs. Read at
    scrape time for the `tts_serve_pool_bytes{cls}` gauge: the number
    that shrinks when narrow node storage (TTS_NARROW) lands, and the
    per-class HBM footprint an operator sizes co-tenancy against."""
    import numpy as np

    total = 0
    for attr in ("_resident_programs", "_mesh_programs",
                 "_batched_programs"):
        cache = list((getattr(problem, attr, None) or {}).values())
        for prog in cache:
            inner = getattr(prog, "inner", prog)
            fields = getattr(inner, "pool_fields", None)
            if fields is None:
                continue
            copies = int(getattr(prog, "B", 0) or getattr(prog, "D", 0) or 1)
            total += copies * sum(
                int(np.prod(full, dtype=np.int64)) * np.dtype(dt).itemsize
                for (_name, dt, _shape), full in zip(fields,
                                                     inner.pool_shapes())
            )
    return total


class ClassEntry:
    """One shape class: the shared problem instance plus admission
    bookkeeping. ``warm`` flips after the first job of the class has
    compiled-and-run — later admissions are promised zero recompiles."""

    def __init__(self, key: str, spec: dict, problem):
        self.key = key
        self.spec = dict(spec)  # the first admitting spec (class exemplar)
        self.problem = problem
        self.created = time.time()
        self.jobs_admitted = 0
        self.warm = False

    def stats(self) -> dict:
        progs, steps = compile_stats(self.problem)
        return {
            "class": self.key,
            "jobs_admitted": self.jobs_admitted,
            "warm": self.warm,
            "programs": progs,
            "step_cache_entries": steps,
            "pool_bytes": resident_pool_bytes(self.problem),
        }


class ProgramPool:
    """class key -> ClassEntry, with identity-level problem sharing."""

    def __init__(self, platform: str):
        self.platform = platform
        self._lock = threading.Lock()
        self._classes = {}  # guarded-by: _lock
        self._problems = {}  # guarded-by: _lock  (identity -> problem)
        self.server_token = server_env_token()

    def admit(self, spec: dict) -> ClassEntry:
        """Map a validated spec to its class entry, constructing the
        shared problem on first contact. Called by the scheduler (jax
        side); the constructor runs under the lock — problem construction
        is host-only table building, never a device compile."""
        key = class_key(spec, self.platform)
        with self._lock:
            entry = self._classes.get(key)
            if entry is None:
                ident = identity_key(spec)
                problem = self._problems.get(ident)
                if problem is None:
                    from .jobs import build_problem

                    problem = build_problem(spec)
                    self._problems[ident] = problem
                entry = ClassEntry(key, spec, problem)
                self._classes[key] = entry
            entry.jobs_admitted += 1
            return entry

    def peek(self, spec: dict) -> dict:
        """Admission-time class info for the submit response (HTTP thread;
        must not build problems): the key plus whether it is already warm."""
        key = class_key(spec, self.platform)
        with self._lock:
            entry = self._classes.get(key)
            return {"class": key, "warm": entry.warm if entry else False}

    def mark_warm(self, entry: ClassEntry) -> None:
        with self._lock:
            entry.warm = True

    def stats(self) -> list[dict]:
        with self._lock:
            entries = list(self._classes.values())
        return [e.stats() for e in entries]
