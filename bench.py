"""Benchmark harness — prints ONE JSON line for the driver.

Headline metric (BASELINE.json): nodes/sec/chip on PFSP ta014 (lb1, ub=1,
single device) = exploredTree / device-phase seconds, with strict makespan
parity (1377) and tree/sol parity against the reference C implementation
(tree 2573652, sol 2648 — recorded goldens, see tests/test_sequential.py).
Extra records (same JSON line): PFSP ta014 lb2 (tree 144639, sol 0) and
N-Queens N=15 (sol 2279184) — BASELINE.md configs 2/4 anchors.

``vs_baseline`` is measured against REFERENCE_NODES_PER_SEC below: the first
*recorded* value of this benchmark on this hardware — 1,414,503 nodes/s,
verified on the real v5e chip in the round-2 review (`TTS_PALLAS=0
python bench.py`). The reference repo publishes no in-repo numbers
(`published: {}` in BASELINE.json), so this self-anchor shows relative
progress across rounds; the *external* anchors are ``vs_ref_c_seq`` /
``vs_ref_c_lb1d`` — the reference's own C sequential programs measured on
this host (REF_C_SEQ below, BASELINE.md).

A kernel that does not compile, or a device error, fails the run: nothing
is probed and routed around. Runs on whatever platform jax picks; set
JAX_PLATFORMS=cpu to smoke-test on CPU (no number from such a run is a
device measurement).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

# Self-anchored baseline: first recorded nodes/sec of the headline benchmark
# on the v5e chip (round-2 review, jnp path — see module docstring).
REFERENCE_NODES_PER_SEC = 1_414_503.0

# External, non-circular anchors: the reference's own C sequential programs
# (`baselines/pfsp/pfsp_c.c`, `baselines/nqueens/nqueens_c.c`) built with
# gcc -O3 and measured on this host's Xeon @2.10GHz, single core, best of 3
# with full tree/sol/makespan parity (see BASELINE.md "Measured reference C
# sequential baselines"). The headline ratio ``vs_ref_c_seq`` divides by the
# same-bound-variant anchor; ``vs_ref_c_lb1d`` uses the reference's fastest
# CPU formulation of the same tree (lb1_d) as a second honesty anchor.
REF_C_SEQ = {
    "pfsp_ta014_lb1": 927_909.0,
    "pfsp_ta014_lb1_d": 3_899_473.0,
    "pfsp_ta014_lb2": 65_391.0,
    "nqueens_n14": 10_471_617.0,
    "nqueens_n15": 9_942_907.0,
}

GOLDEN_LB1 = {"tree": 2_573_652, "sol": 2648, "makespan": 1377}
GOLDEN_LB2 = {"tree": 144_639, "sol": 0, "makespan": 1377}
# Classical N-Queens solution counts (BASELINE.md correctness anchors).
NQ_SOL = {12: 14_200, 14: 365_596, 15: 2_279_184}
GOLDEN_NQ14 = {"tree": 27_358_552, "sol": NQ_SOL[14]}

HERE = os.path.dirname(os.path.abspath(__file__))

# Headline chunk size, measured on the real v5e (scripts/headline_tune.py,
# round 5): per-cycle cost is ~linear in M (dense padded compute) while the
# ta014 frontier rarely fills large chunks, so small-but-full chunks win —
# M=1024 ran 1.87M nodes/s vs 1.46M at the old 65536 (28% on the same tree;
# 512 underutilizes, the 1024-8192 plateau is flat within ~3%).
HEADLINE_M = 1024


def flops_per_parent_model(n: int, m: int, P: int | None, lb: str) -> float:
    """Hand-counted FLOPs per explored parent of the jnp evaluators — the
    fallback when XLA cost analysis is unavailable, cross-checked against it
    by ``tests/test_bench.py``. lb1 = two (n, n) x (n, m) one-hot gathers
    (2 * 2n^2m) plus the O(nm) scan and the m-chain over n children (~6nm);
    lb2 adds, per machine pair, one (n, n) one-hot reorder contraction
    (2n^2) and the O(n) closed-form Johnson scan (~8n) — NOT per-pair
    (n, n) x (n, n) matmuls; the implementation is O(P n^2), which the
    round-5 cost-analysis cross-check confirmed (the earlier 6n^3-per-pair
    model overstated lb2 work ~67x)."""
    if lb == "lb2":
        return (P or 0) * (2.0 * n**2 + 8.0 * n) + 4.0 * n**2 * m
    return 4.0 * n**2 * m + 6.0 * n * m


def flops_per_parent_xla(problem, lb: str, batch: int = 64) -> float | None:
    """Compiler-measured FLOPs per parent: lower + compile the jnp chunk
    evaluator for the current backend and read XLA's cost analysis. This is
    the authoritative roofline numerator — it counts what the compiled
    program executes, not what a hand model assumes. Returns None when cost
    analysis is unavailable (some backends); callers then use
    ``flops_per_parent_model``. A compile failure raises. The Pallas kernels do the same
    semantic work with the same asymptotics (XLA cannot see inside a custom
    call), so the jnp figure stands in for both paths."""
    cache = getattr(problem, "_flops_per_parent_xla", None)
    if cache is None:
        cache = problem._flops_per_parent_xla = {}
    if lb in cache:
        return cache[lb]
    import jax.numpy as jnp
    import numpy as np

    from tpu_tree_search.ops import pfsp_device as P

    t = problem.device_tables()
    n = problem.jobs
    prmu = jnp.asarray(
        np.tile(np.arange(n, dtype=np.int32), (batch, 1))
    )
    limit1 = jnp.zeros((batch,), dtype=jnp.int32)
    # Lower the module-level jits with the tables as RUNTIME arguments —
    # exactly how production calls them. A wrapper closure would bake
    # the tables in as HLO constants and cost-analyse a
    # differently-folded program.
    if lb == "lb2":
        lowered = P._lb2_chunk.lower(
            prmu, limit1, t.ptm_t, t.min_heads, t.min_tails, t.pairs,
            t.lags, t.johnson_schedules, bf16=t.exact_bf16,
        )
    else:
        lowered = P._lb1_chunk.lower(
            prmu, limit1, t.ptm_t, t.min_heads, t.min_tails,
            bf16=t.exact_bf16,
        )
    ca = lowered.compile().cost_analysis()
    flops = float(ca.get("flops", 0.0)) if ca else 0.0
    cache[lb] = flops / batch if flops > 0 else None
    return cache[lb]


def roofline(nps: float, n: int, m: int, P: int | None, lb: str,
             problem=None) -> dict:
    """Achieved-work roofline for the headline run. ``nps`` counts explored
    parents/sec; every explored parent evaluates all n children in one
    evaluator pass, so bound-evals/sec = nps * n. FLOPs/parent comes from
    XLA cost analysis of the compiled jnp evaluator when ``problem`` is
    given (``flop_source: xla_cost_analysis``), else the hand model.
    ``mfu_pct`` is achieved-FLOPs / the bf16 MXU peak of the device the
    run is on (`obs/roofline.PEAKS`, keyed by ``device_kind``) — "not
    measured" on a device with no published peak."""
    import jax

    from tpu_tree_search.obs.roofline import NOT_MEASURED, PEAKS

    measured = flops_per_parent_xla(problem, lb) if problem is not None else None
    flops_per_parent = (
        measured if measured is not None
        else flops_per_parent_model(n, m, P, lb)
    )
    gflops = nps * flops_per_parent / 1e9
    out = {
        "bound_evals_per_sec": round(nps * n, 1),
        "flops_per_parent": int(flops_per_parent),
        "flop_source": "xla_cost_analysis" if measured is not None else "model",
        "achieved_gflops": round(gflops, 2),
    }
    peak = PEAKS.get(jax.devices()[0].device_kind)
    if peak is None:
        out["peak_bf16_tflops"] = out["mfu_pct"] = NOT_MEASURED
    else:
        out["peak_bf16_tflops"] = peak["bf16_tflops"]
        out["mfu_pct"] = round(
            100.0 * gflops / (peak["bf16_tflops"] * 1e3), 4)
    return out


def contracts_fingerprint() -> str | None:
    """The committed compiled-program contract fingerprint
    (`.tts-contracts.json`, ISSUE 8): recorded in every bench artifact so
    a banked perf number is tied to the exact program STRUCTURE it
    measured — a later `tts check --update` (reviewed drift) makes old
    rows distinguishable from new ones at a glance."""
    try:
        from tpu_tree_search.analysis.program_audit import (
            committed_fingerprint,
        )

        return committed_fingerprint(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), ".tts-contracts.json"
        ))
    except Exception:  # noqa: BLE001 — provenance must never break a row
        return None


def _git_head() -> str:
    try:
        out = subprocess.run(
            ["git", "-C", os.path.dirname(os.path.abspath(__file__)),
             "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
        )
        return out.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


# Failure-provenance artifact (flush-as-you-go): the one JSON line only
# prints at the very end, so a run that dies leaves nothing. This file is
# rewritten
# (atomic replace + fsync) after every stage, so whatever already ran is
# on disk when the process dies — rc, per-stage/per-row status, and the
# failure reason included. TTS_BENCH_PARTIAL overrides the path; =0
# disables.
PARTIAL_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_PARTIAL.json")


class BenchPartial:
    """Crash-durable per-stage bench status (see PARTIAL_PATH note)."""

    def __init__(self, path: str | None = None):
        raw = os.environ.get("TTS_BENCH_PARTIAL", "")
        if raw == "0":
            default = None
        elif os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
            # CPU smoke (JAX_PLATFORMS=cpu pins) must not dirty the
            # working tree — same policy as BENCH_TRACE.json; hardware
            # runs keep the committed path.
            import tempfile

            default = os.path.join(tempfile.gettempdir(),
                                   "BENCH_PARTIAL.json")
        else:
            default = PARTIAL_PATH
        self.path = None if raw == "0" else (path or raw or default)
        self.doc = {
            "status": "running",
            "rc": None,
            "started": time.strftime("%Y-%m-%d %H:%M:%S UTC", time.gmtime()),
            "commit": _git_head(),
            # Program-structure provenance: the committed contract
            # fingerprint every row in this document was measured under.
            "contracts": contracts_fingerprint(),
            "rows": [],
        }
        self._index: dict[str, int] = {}
        self._prev_sigterm = None
        self.write()

    def stage(self, name: str, status: str = "ok", **info) -> None:
        row = {"stage": name, "status": status, **info}
        i = self._index.get(name)
        if i is None:
            self._index[name] = len(self.doc["rows"])
            self.doc["rows"].append(row)
        else:
            self.doc["rows"][i] = row
        self.write()

    def rows_from_extras(self, extras: list[dict]) -> None:
        for rec in extras:
            name = rec.get("metric", "extra")
            self.stage(
                name,
                "error" if "error" in rec else "ok",
                **({"error": rec["error"]} if "error" in rec
                   else {"value": rec.get("value")}),
            )

    def finish(self, rc: int, status: str = "complete") -> None:
        self.doc["status"] = status
        self.doc["rc"] = rc
        self.write()

    def write(self) -> None:
        if self.path is None:
            return
        try:
            self.doc["updated"] = time.strftime(
                "%Y-%m-%d %H:%M:%S UTC", time.gmtime()
            )
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(self.doc, f, indent=1)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)
        except OSError:
            pass  # provenance must never break the bench itself

    def install_sigterm(self) -> None:
        """SIGTERM (the driver's timeout kill) marks the partial before
        the process dies with the honest signal status."""
        import signal
        import threading

        if threading.current_thread() is not threading.main_thread():
            return

        def _on_term(signum, frame):
            self.finish(128 + signum, "killed: SIGTERM")
            signal.signal(signum, self._prev_sigterm or signal.SIG_DFL)
            os.kill(os.getpid(), signum)

        try:
            self._prev_sigterm = signal.signal(signal.SIGTERM, _on_term)
        except (ValueError, OSError):
            pass

# Host-runtime rows. Goldens are substituted from GOLDEN_LB1/GOLDEN_LB2/NQ_SOL below (one
# source of truth; a count correction must not silently fail parity here).
# Each workload streams its own flushed HOST_SEQ_ROW line so measurements
# that finished before a timeout/crash still get banked.
_HOST_SEQ = r"""
import json, os, time
# Unconditional CPU pin: the host-seq measurement is a host number, and
# this child must not take the chip from the bench process that holds it.
os.environ["JAX_PLATFORMS"] = "cpu"
from tpu_tree_search.engine import sequential_search
from tpu_tree_search.problems import NQueensProblem, PFSPProblem
for tag, mk, tree, sol, best in (
    ("pfsp_ta014_lb1", lambda: PFSPProblem(inst=14, lb="lb1", ub=1),
     @LB1_TREE@, @LB1_SOL@, @LB1_MS@),
    ("pfsp_ta014_lb2", lambda: PFSPProblem(inst=14, lb="lb2", ub=1),
     @LB2_TREE@, @LB2_SOL@, @LB2_MS@),
    ("nqueens_n14", lambda: NQueensProblem(N=14), @NQ14_TREE@, @NQ14_SOL@,
     None),
):
    bnps = None
    parity = True
    for _ in range(2):
        t0 = time.time()
        r = sequential_search(mk())
        dt = time.time() - t0
        parity &= (r.explored_tree, r.explored_sol) == (tree, sol)
        if best is not None:
            parity &= r.best == best
        nps = r.explored_tree / max(dt, 1e-9)
        bnps = nps if bnps is None else max(bnps, nps)
    print("HOST_SEQ_ROW " + json.dumps(
        {"tag": tag, "nodes_per_sec": round(bnps, 1), "parity": parity}
    ), flush=True)
""".replace("@LB1_TREE@", str(GOLDEN_LB1["tree"])) \
   .replace("@LB1_SOL@", str(GOLDEN_LB1["sol"])) \
   .replace("@LB1_MS@", str(GOLDEN_LB1["makespan"])) \
   .replace("@LB2_TREE@", str(GOLDEN_LB2["tree"])) \
   .replace("@LB2_SOL@", str(GOLDEN_LB2["sol"])) \
   .replace("@LB2_MS@", str(GOLDEN_LB2["makespan"])) \
   .replace("@NQ14_TREE@", str(GOLDEN_NQ14["tree"])) \
   .replace("@NQ14_SOL@", str(GOLDEN_NQ14["sol"]))


def host_seq_extras(timeout_s: float = 180.0) -> list[dict]:
    """Measured host-runtime (C++ sequential tier) records with ratios
    against the reference C programs (BASELINE.md) — these need no TPU, so
    even an outage round's artifact carries real numbers. Subprocess +
    timeout; NEVER raises: a native-runtime crash, a timeout, or garbled
    output must cost only this block, not the bench's JSON line (rows
    already streamed before the failure are kept)."""
    try:
        err = None
        try:
            res = subprocess.run(
                [sys.executable, "-c", _HOST_SEQ],
                timeout=timeout_s, capture_output=True, text=True,
            )
            out = res.stdout or ""
            if res.returncode != 0:
                tail = (res.stderr or out).strip().splitlines()[-2:]
                err = "host_seq child rc={}: {}".format(
                    res.returncode, " | ".join(tail))
        except subprocess.TimeoutExpired as e:
            raw = e.stdout
            out = (raw.decode(errors="replace")
                   if isinstance(raw, bytes) else raw) or ""
            err = f"timed out after {timeout_s:.0f}s"
        extras = []
        for ln in out.splitlines():
            if not ln.startswith("HOST_SEQ_ROW "):
                continue
            try:
                r = json.loads(ln[len("HOST_SEQ_ROW "):])
                extras.append({
                    "metric": f"host_seq_{r['tag']}_nodes_per_sec",
                    "value": r["nodes_per_sec"],
                    "vs_ref_c_seq": round(
                        r["nodes_per_sec"] / REF_C_SEQ[r["tag"]], 3
                    ) if r["tag"] in REF_C_SEQ else None,
                    "parity": r["parity"],
                })
            except (ValueError, KeyError):
                continue  # torn line from a mid-write kill
        if err is not None:
            extras.append({"metric": "host_seq", "error": err})
        return extras
    except Exception as e:  # noqa: BLE001 — the bench line must survive
        return [{"metric": "host_seq",
                 "error": f"{type(e).__name__}: {e}"}]


from contextlib import contextmanager, nullcontext


@contextmanager
def _env_override(key: str, value: str):
    """Temporarily set an env knob; on exit restore the previous value or
    pop the key (never clobber a user's explicit setting)."""
    prev = os.environ.get(key)
    os.environ[key] = value
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = prev


def eval_microbench(problem, on_tpu: bool, iters: int | None = None) -> dict:
    """Pure-evaluator throughput on the search's exact chunk shape — the
    measured cross-check for the model-derived roofline (VERDICT r4 weak
    #5): if the search-loop MFU sits far below this, the gap is
    orchestration (pool ops, compaction, dispatch), not the kernel; if they
    match, the kernel is the ceiling. B matches HEADLINE_M so (a) the
    jnp-vs-Pallas headline-path pick is measured at the production chunk
    shape, not a 64x bigger one, and (b) the compiles warm exactly the
    evaluator the chosen path dispatches."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_tree_search.ops import pfsp_device as P

    t = problem.device_tables()
    n, m = problem.jobs, problem.machines
    B = HEADLINE_M if on_tpu else 4096
    if iters is None:
        # Keep the timed section comparable to the old B=65536 runs so
        # small chunks don't measure noise: scale repetitions inversely
        # with the batch, per backend (CPU's B is unchanged -> 20).
        base = 65536 if on_tpu else 4096
        iters = max(20, (base // B) * 20)
    rng = np.random.default_rng(5)
    prmu = rng.permuted(
        np.tile(np.arange(n, dtype=np.int32), (B, 1)), axis=1
    )
    limit1 = rng.integers(-1, n - 1, B).astype(np.int32)
    pd, ld = jnp.asarray(prmu), jnp.asarray(limit1)

    fn = jax.jit(lambda a, b: P.lb1_bounds(a, b, t))
    fn(pd, ld).block_until_ready()  # compile + warm
    t0 = time.time()
    for _ in range(iters):
        out = fn(pd, ld)
    out.block_until_ready()
    dt = time.time() - t0
    parents_per_sec = B * iters / dt
    # Same FLOP model + MFU formula as the search-loop roofline — the two
    # numbers must stay comparable (this microbench exists to cross-check
    # that roofline).
    rl = roofline(parents_per_sec, n, m, None, "lb1", problem=problem)
    return {
        "kernel": "lb1",
        "batch": B,
        "iters": iters,
        "bound_evals_per_sec": rl["bound_evals_per_sec"],
        "achieved_gflops": rl["achieved_gflops"],
        "mfu_pct": rl["mfu_pct"],
    }


COMPACT_MODES = ("scatter", "sort", "search", "dense")


def _phaseprof_armed() -> bool:
    """Session-level TTS_PHASEPROF=1: hardware sessions arm it for the
    decomposition stages; the default bench never pays the armed
    variant's compiles or callback clocks."""
    from tpu_tree_search.obs import phases as obs_phases

    return obs_phases.phase_profiling_enabled()


def phase_split_probe(problem, m: int, M: int, K: int = 64,
                      max_steps: int = 2) -> dict | None:
    """Measured per-phase cycle split from a short ARMED resident run
    (TTS_PHASEPROF=1, obs/phases.py): the real engine with its phase
    clocks on, bounded to ``max_steps`` dispatches.  The armed program is
    a separate cache-keyed variant, so the headline program is untouched.
    Returns ``{"ns", "shares", "cycles", "dominant"}`` or None (the probe
    is best-effort and must never cost the bench line)."""
    from tpu_tree_search.engine.resident import resident_search
    from tpu_tree_search.obs import phases as obs_phases

    with _env_override("TTS_PHASEPROF", "1"):
        res = resident_search(problem, m=m, M=M, K=K,
                              max_steps=max_steps)
    pp = res.phase_profile
    if not pp or not pp.get("total"):
        return None
    dom = obs_phases.dominant_phase(pp)
    return {
        "ns": {k: int(v) for k, v in pp.items()},
        "shares": {k: round(v, 4)
                   for k, v in obs_phases.shares(pp).items()},
        "cycles": int(res.diagnostics.kernel_launches),
        "dominant": dom[0] if dom else None,
    }


def eval_cycle_ms(problem, m: int, M: int, cycles: int = 64) -> float | None:
    """Measured evaluator-in-loop cost per cycle at the production chunk
    shape.

    When the phase profiler is armed for the session (``TTS_PHASEPROF=1``
    — hardware sessions arm it for the decomposition stages), the number
    comes from the profiler itself: the ``eval`` phase clock of a short
    armed resident run (``phase_split_probe``) — ONE decomposition
    mechanism, measured inside the real loop.  Otherwise (the CPU/default
    fallback) it is the original stripped while_loop whose body runs ONLY
    the evaluator — no pop, no compaction, no push
    (scripts/cycle_profile.py's c-loop, inlined so pick_compact can price
    the survivor path per mode).  A mode's maintenance share is then its
    measured cycle_ms minus this; the on-device ``push_rows`` counter
    carries the matching WORK series (docs/OBSERVABILITY.md).  Returns
    None on any failure — the decomposition is best-effort and must never
    cost the bench line."""
    from tpu_tree_search.obs import phases as obs_phases

    if obs_phases.phase_profiling_enabled():
        split = phase_split_probe(problem, m, M, K=cycles)
        if split and split["cycles"]:
            return round(split["ns"]["eval"] / 1e6 / split["cycles"], 3)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from tpu_tree_search.engine.resident import (
        _make_program,
        resolve_capacity,
    )

    capacity, M = resolve_capacity(problem, M, None)
    prog = _make_program(problem, m, M, cycles, capacity,
                         jax.devices()[0])
    evaluate = prog._make_eval()
    n = problem.child_slots
    vals = jnp.asarray(np.tile(np.arange(n, dtype=np.int32), (M, 1)))
    aux = jnp.zeros((M,), jnp.int32)
    valid = jnp.ones((M,), bool)
    ub = jnp.int32(min(getattr(problem, "initial_ub", 2**30), 2**30))

    def body(carry):
        best, tree, cyc = carry
        keep, sol_inc, best = evaluate(vals, aux, valid, best)
        # Fold keep into the carry so nothing is dead-code-eliminated.
        tree = tree + jnp.sum(keep, dtype=jnp.int32) + sol_inc * 0
        return best, tree, cyc + 1

    fn = jax.jit(lambda: lax.while_loop(
        lambda c: c[2] < cycles, body, (ub, jnp.int32(0), jnp.int32(0))
    ))

    def block(out):
        for x in out:
            if hasattr(x, "block_until_ready"):
                x.block_until_ready()
        return out

    block(fn())  # compile + warm
    t0 = time.time()
    block(fn())
    return round(1e3 * (time.time() - t0) / cycles, 3)


@contextmanager
def _mode_timeout(seconds: float | None):
    """Best-effort hard wall-clock bound for one in-process measurement:
    ``SIGALRM`` + ``setitimer`` raise ``TimeoutError`` inside the running
    mode instead of merely gating the next one. Limitations: signals
    deliver only in the MAIN
    thread — elsewhere this is a no-op — and a native call that never
    returns to the interpreter (a truly hung Mosaic compile) postpones
    delivery until it does; long-but-finite compiles and runs ARE
    interrupted, which is the case the budget exists for."""
    import signal
    import threading

    if (
        seconds is None
        or threading.current_thread() is not threading.main_thread()
        or not hasattr(signal, "setitimer")
    ):
        yield
        return

    def _raise(signum, frame):
        raise TimeoutError(f"mode run exceeded its {seconds:.0f}s slice")

    old = signal.signal(signal.SIGALRM, _raise)
    signal.setitimer(signal.ITIMER_REAL, max(seconds, 1e-3))
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, old)


def pick_compact(run_fn, parity_fn, budget_s: float | None = None,
                 eval_ms: float | None = None, auto_mode: str | None = None,
                 phase_probe=None):
    """Measure ``run_fn()`` under each compaction mode (TTS_COMPACT) and
    pick the fastest PARITY-PASSING one (fallback: fastest overall — a
    fast-but-wrong mode must never displace a clean measurement, but if
    none is clean the caller's own parity gate reports it). A mode that
    overruns its slice of the budget is recorded and skipped; any other
    failure raises.

    The stats blob records WHY a mode won, not just that it did: per mode,
    the measured device ms/cycle and — when the caller supplies the
    evaluator-only calibration ``eval_ms`` (``eval_cycle_ms``) — the
    implied maintenance (pop+compact+push) ms/cycle; ``auto_mode`` records
    what ``TTS_COMPACT=auto`` would have resolved for this config, so the
    artifact shows whether the policy table agrees with the measurement.

    ``phase_probe`` (armed sessions: a zero-arg callable wrapping
    ``phase_split_probe``) runs once per surviving mode UNDER that mode's
    ``TTS_COMPACT`` pin, so the row records the measured per-phase cycle
    split of each compaction mode — the phase-profiler counterpart of the
    ``eval_ms`` subtraction (one decomposition mechanism when armed).

    ``budget_s`` is a HARD bound on the whole A/B, not just a start gate:
    each mode runs inside its remaining slice of the budget under
    ``_mode_timeout`` (SIGALRM), so a mode that begins just under the
    budget is interrupted rather than overrunning arbitrarily (ADVICE r5).
    The first mode gets the full budget (the old single-mode behavior is
    the floor — if IT times out, the caller's fallback plain run still
    produces the record); later modes get what is left and are skipped
    outright once nothing is. Residual overshoot is limited to native
    calls that never re-enter the interpreter (see ``_mode_timeout``).
    Returns ``(stats, best_run)``; ``(None, None)`` if every mode failed
    to run. Shared by the headline A/B and the N-Queens probe so the mode
    list and selection rule cannot drift apart."""
    runs, nps, par, errors = {}, {}, {}, {}
    phase_splits: dict = {}
    t0 = time.monotonic()
    skipped = []
    for i, mode in enumerate(COMPACT_MODES):
        remaining = (
            None if budget_s is None
            else budget_s - (time.monotonic() - t0)
        )
        # Only the FIRST mode is exempt from the skip (it still runs under
        # the full-budget timeout): a mode that burns the budget and then
        # fails must still stop the A/B (the guarantee is a bound on total
        # wall time, success or not).
        if i > 0 and remaining is not None and remaining <= 0:
            skipped.append(mode)
            continue
        try:
            with _env_override("TTS_COMPACT", mode), \
                    _mode_timeout(budget_s if i == 0 else remaining):
                r = run_fn()
                if phase_probe is not None:
                    # Short armed run under the same mode pin: the row's
                    # measured phase split (still inside the timeout).
                    phase_splits[mode] = phase_probe()
        except TimeoutError as e:
            errors[mode] = f"TimeoutError: {e}"
            continue
        runs[mode] = r
        nps[mode] = round(r[1], 1)
        par[mode] = bool(parity_fn(r))
    decomp = {}
    for mode, r in runs.items():
        # r = (result, nps, elapsed, device_phase): per-mode cycle cost
        # from the run's own diagnostics (guarded — unit tests pass stubs).
        cyc = getattr(getattr(r[0], "diagnostics", None),
                      "kernel_launches", 0)
        if cyc and r[3]:
            row = {"cycle_ms": round(1e3 * r[3] / cyc, 3)}
            if eval_ms is not None:
                row["eval_ms"] = eval_ms
                row["maint_ms"] = round(row["cycle_ms"] - eval_ms, 3)
            if phase_splits.get(mode):
                row["phases"] = phase_splits[mode]
            decomp[mode] = row
    if not runs:
        # Preserve the per-mode diagnostics even when every mode failed —
        # the caller falls back to a plain run, but the record must show
        # that three measured modes crashed and why.
        return ({"picked": None, "errors": errors} if errors else None), None
    clean = {k: v for k, v in runs.items() if par[k]}
    pool = clean or runs
    pick = max(pool, key=lambda k: pool[k][1])
    stats = {
        "picked": pick,
        "nodes_per_sec": nps,
        "parity": par,
        **({"decomp": decomp} if decomp else {}),
        **({"auto": auto_mode} if auto_mode is not None else {}),
        **({"errors": errors} if errors else {}),
        **({"skipped_budget": skipped} if skipped else {}),
    }
    return stats, runs[pick]


def _compact_ctx(stats):
    """Context manager pinning TTS_COMPACT to a pick_compact result's
    winner; a no-op when there is no usable pick."""
    if stats and stats.get("picked"):
        return _env_override("TTS_COMPACT", stats["picked"])
    return nullcontext()


def _drive_dispatch_loop(problem, m: int, M: int, K: int, depth: int,
                         half_lat_s: float) -> tuple[int, float]:
    """Drive the resident program's dispatch loop by hand at a given
    pipeline depth with an injected host round-trip latency: sleep
    ``half_lat_s`` before each enqueue (command travel) and after each
    scalar read (response travel) — a slow host link. Returns
    ``(dispatches, wall_seconds)`` of the device phase. Deterministic for
    a fixed (problem, m, M, K): both depths run the identical dispatch
    sequence, so the wall delta is pure overlap."""
    from collections import deque

    import jax

    from tpu_tree_search.engine.device import warmup
    from tpu_tree_search.engine.resident import (
        _make_program,
        resolve_capacity,
    )
    from tpu_tree_search.pool import SoAPool
    from tpu_tree_search.problems.base import INF_BOUND, index_batch

    capacity, M = resolve_capacity(problem, M, None)
    prog = _make_program(problem, m, M, K, capacity, jax.devices()[0])
    pool = SoAPool(problem.node_fields())
    pool.push_back(index_batch(problem.root(), 0))
    best = getattr(problem, "initial_ub", INF_BOUND)
    _, _, best = warmup(problem, pool, best, m)
    state = prog.init_state(pool.as_batch(), best)
    q: deque = deque()
    dispatches = 0
    done = None
    t0 = time.perf_counter()
    while True:
        while len(q) < depth:
            if half_lat_s:
                time.sleep(half_lat_s)  # command latency (host -> device)
            out = prog.step(state)
            state = prog.carry(out)
            q.append(out)
        # Keep each consumed output bound one iteration longer (`done`):
        # on the CPU backend, dropping an output tuple whose pool buffers
        # were donated into a still-in-flight dispatch blocks in the
        # destructor until that dispatch finishes — which would silently
        # serialize the pipeline this harness exists to measure.
        done = q.popleft()
        size = prog.read_scalars(done)[3]
        if half_lat_s:
            time.sleep(half_lat_s)  # response latency (device -> host)
        dispatches += 1
        if size < m:
            while q:  # speculative no-ops
                done = q.popleft()
                prog.read_scalars(done)
            break
    return dispatches, time.perf_counter() - t0


def simulated_latency_ab(problem=None, m: int = 25, M: int = 512,
                         K: int = 8, half_lat_s: float | None = None) -> dict:
    """Pipeline A/B on the simulated-latency CPU harness: the same full
    search driven at depth 1 (synchronous — every dispatch pays the
    injected round trip with the device idle) vs depth 2 (speculative —
    the round trip overlaps device compute). The expected per-dispatch
    drop is ``min(T_dev, round_trip)``; the default latency is calibrated
    to ~60%% of the measured per-dispatch device time so the full
    round-trip drop is achievable (round trips comparable to
    multi-K-cycle dispatch blocks)."""
    if problem is None:
        from tpu_tree_search.problems import NQueensProblem

        problem = NQueensProblem(N=11)
    # Calibrate: latency-free depth-1 passes measure T_dev per dispatch —
    # the first warms the compile, the second is the measurement.
    _drive_dispatch_loop(problem, m, M, K, depth=1, half_lat_s=0.0)
    n0, t_cal = _drive_dispatch_loop(problem, m, M, K, depth=1,
                                     half_lat_s=0.0)
    t_dev = t_cal / max(n0, 1)
    if half_lat_s is None:
        # round_trip = 0.6 * T_dev keeps T_dev > round_trip, the regime
        # where depth 2 hides the FULL round trip (drop = min(T_dev, L)).
        half_lat_s = max(0.002, 0.3 * t_dev)
    n1, t1 = _drive_dispatch_loop(problem, m, M, K, 1, half_lat_s)
    n2, t2 = _drive_dispatch_loop(problem, m, M, K, 2, half_lat_s)
    per1 = t1 / max(n1, 1)
    per2 = t2 / max(n2, 1)
    return {
        "dispatches": n1,
        "t_dev_ms": round(1e3 * t_dev, 3),
        "round_trip_ms": round(1e3 * 2 * half_lat_s, 3),
        "depth1_ms_per_dispatch": round(1e3 * per1, 3),
        "depth2_ms_per_dispatch": round(1e3 * per2, 3),
        "drop_ms_per_dispatch": round(1e3 * (per1 - per2), 3),
    }


def _dispatch_latency_rows(extras: list, on_tpu: bool) -> None:
    """Dispatch-latency microbench rows (never fail the bench):

    * ``dispatch_pipeline_sim_ab`` — the simulated-latency CPU harness
      above, on every backend (the no-TPU-window proof that depth 2 hides
      the scalar-read round trip).
    * on TPU: per-dispatch host wall at K=1 vs K=max, depth 1 vs 2, on the
      headline config — bounded by max_steps so each cell costs a few
      dispatches; these are the numbers that show the per-dispatch round
      trip amortized (K) and overlapped (depth).
    """
    extras.append({
        "metric": "dispatch_pipeline_sim_ab",
        **simulated_latency_ab(),
    })
    if not on_tpu:
        return
    from tpu_tree_search.engine.resident import resident_search
    from tpu_tree_search.problems import PFSPProblem

    for K, steps in ((1, 32), (4096, 4)):
        for depth in (1, 2):
            metric = f"dispatch_wall_K{K}_depth{depth}_ms"
            with _env_override("TTS_PIPELINE", str(depth)):
                prob = PFSPProblem(inst=14, lb="lb1", ub=1)
                resident_search(prob, m=25, M=HEADLINE_M, K=K,
                                max_steps=1)  # warm
                res = resident_search(prob, m=25, M=HEADLINE_M, K=K,
                                      max_steps=steps)
            dev_s = (res.phases[1].seconds if len(res.phases) > 1
                     else res.elapsed)
            # Pipelining drains up to depth-1 extra dispatches at cut.
            n_disp = steps + depth - 1
            extras.append({
                "metric": metric,
                "value": round(1e3 * dev_s / max(n_disp, 1), 3),
                "unit": "ms/dispatch",
                "dispatches": n_disp,
                "cycles": res.diagnostics.kernel_launches,
            })
    # Headline-config pipeline on/off A/B (bounded): same K, same steps,
    # only TTS_PIPELINE flips — the wall delta is the hidden round trip.
    from tpu_tree_search.engine.resident import resident_search

    prob = PFSPProblem(inst=14, lb="lb1", ub=1)
    walls = {}
    for depth in (1, 2):
        with _env_override("TTS_PIPELINE", str(depth)):
            resident_search(prob, m=25, M=HEADLINE_M, max_steps=1)
            res = resident_search(prob, m=25, M=HEADLINE_M, max_steps=8)
        walls[depth] = (res.phases[1].seconds if len(res.phases) > 1
                        else res.elapsed)
    extras.append({
        "metric": "pipeline_ab_headline",
        "depth1_s": round(walls[1], 3),
        "depth2_s": round(walls[2], 3),
        "speedup": round(walls[1] / max(walls[2], 1e-9), 3),
    })


def _batch_ab_rows(extras: list) -> None:
    """Instance-batching A/B on the CPU-sim harness (never fails the
    bench): the same N same-shape-class jobs run serially through
    ``resident_search`` vs through ``engine/batched.batched_search`` at
    B in {1, 4, 8}. Reported per width: batch wall, aggregate nodes/s,
    mean per-job latency, speedup over serial, and bit-identity of every
    job against its solo run (the batching contract — a throughput win
    that perturbed a single count would be a bug, not a result). B=1 is
    the degenerate case and should run at ~serial speed. Wider batches
    amortize per-dispatch host overhead across tenants — a device-side
    effect: on the CPU sim the unrolled slots multiply per-cycle compute
    (the off-chip bottleneck), so expect b4/b8 to LOSE here; the row's
    job is structure + parity evidence, and the hardware session banks
    the real speedup (scripts/hw_session.sh)."""
    from tpu_tree_search.engine.batched import batched_search
    from tpu_tree_search.engine.resident import resident_search
    from tpu_tree_search.problems import NQueensProblem

    n_jobs, m, M, K = 8, 5, 64, 8

    def _mk():
        return NQueensProblem(N=9)

    problem = _mk()
    resident_search(problem, m=m, M=M, K=K)  # warm the solo program
    t0 = time.perf_counter()
    serial = [resident_search(problem, m=m, M=M, K=K)
              for _ in range(n_jobs)]
    serial_s = time.perf_counter() - t0
    golden = [(r.explored_tree, r.explored_sol, r.best) for r in serial]
    nodes = sum(r.explored_tree for r in serial)
    row = {
        "metric": "batch_ab_sim",
        "jobs": n_jobs,
        "serial_s": round(serial_s, 3),
        "serial_nodes_per_sec": round(nodes / max(serial_s, 1e-9), 1),
        "serial_job_latency_ms": round(1e3 * serial_s / n_jobs, 2),
    }
    for B in (1, 4, 8):
        batched_search(problem, n_jobs=B, B=B, m=m, M=M, K=K)  # warm
        t0 = time.perf_counter()
        results = batched_search(problem, n_jobs=n_jobs, B=B,
                                 m=m, M=M, K=K)
        wall = time.perf_counter() - t0
        parity = (
            [(r.explored_tree, r.explored_sol, r.best) for r in results]
            == golden
        )
        row[f"b{B}_s"] = round(wall, 3)
        row[f"b{B}_nodes_per_sec"] = round(nodes / max(wall, 1e-9), 1)
        row[f"b{B}_job_latency_ms"] = round(1e3 * wall / n_jobs, 2)
        row[f"b{B}_speedup"] = round(serial_s / max(wall, 1e-9), 3)
        row[f"b{B}_parity"] = parity
    row["parity"] = all(row[f"b{B}_parity"] for B in (1, 4, 8))
    extras.append(row)


def steal_ab(problem=None, m: int = 5, M: int = 64, D: int = 1,
             hosts: int = 6, pods: int = 2,
             ici_lat_s: float = 0.002, dcn_lat_s: float = 0.25,
             interval_s: float = 0.005) -> dict:
    """Hierarchical-stealing A/B on the CPU-sim virtual-host harness
    (ISSUE 14 acceptance row): the same dist-tier search, flat vs hier
    (``TTS_STEAL``), over 6 virtual hosts in 2 pods (``TTS_PODS``) with
    injected asymmetric link latencies (cheap ICI, expensive DCN) and
    adversarial initial imbalance — one rich host per pod (hosts 0 and
    ``hosts//2``), every other host starts empty. Flat's matching is
    topology-blind: its size-ordered donor->needy zip systematically
    pairs rich hosts with needy hosts ACROSS pods, paying the injected
    DCN latency while a same-pod donor sits unused, and its tail ships
    end-of-run scraps over the same expensive link. Hier feeds every
    starved host from its own pod over ICI and takes the far link only
    for bulk quanta that amortize the latency (parallel/topology.py).
    Reported per mode: wall time, mean worker idle fraction (from the
    drained host trace, obs/report.summarize), donation totals, and the
    resolved policy — parity-gated on bit-identical node counts vs
    sequential (N-Queens never prunes, so ANY steal schedule must
    reproduce them)."""
    from tpu_tree_search.engine import sequential_search
    from tpu_tree_search.obs import events as obs_events
    from tpu_tree_search.obs import report as obs_report
    from tpu_tree_search.parallel.dist import dist_search
    from tpu_tree_search.problems import NQueensProblem

    if problem is None:
        problem = NQueensProblem(N=10)
    seq = sequential_search(problem)
    golden = (seq.explored_tree, seq.explored_sol)
    rich = (0, hosts // 2)  # one donor per pod

    def skew(warm, host_id, num_hosts):
        n = len(next(iter(warm.values())))
        if host_id == rich[0]:
            return {k: v[: n // 2] for k, v in warm.items()}
        if host_id == rich[1]:
            return {k: v[n // 2:] for k, v in warm.items()}
        return {k: v[:0] for k, v in warm.items()}

    # Warm the compile cache outside the measured pair: the first dist run
    # traces the chunk program, and that cost must not land in one arm's
    # busy spans (no latency injection, default balanced partition).
    dist_search(problem, m=m, M=M, D=D, num_hosts=hosts,
                steal_interval_s=interval_s)

    out: dict = {
        "metric": "steal_ab_sim",
        "hosts": hosts,
        "pods": pods,
        "workers_per_host": D,
        "ici_lat_ms": round(1e3 * ici_lat_s, 1),
        "dcn_lat_ms": round(1e3 * dcn_lat_s, 1),
        "golden_tree": golden[0],
    }
    for mode in ("flat", "hier"):
        with _env_override("TTS_STEAL", mode), \
                _env_override("TTS_PODS", str(pods)), \
                _env_override("TTS_SIM_LAT_ICI", str(ici_lat_s)), \
                _env_override("TTS_SIM_LAT_DCN", str(dcn_lat_s)), \
                _env_override("TTS_OBS", "host"):
            obs_events.reset()
            t0 = time.perf_counter()
            res = dist_search(problem, m=m, M=M, D=D, num_hosts=hosts,
                              steal_interval_s=interval_s,
                              partition_fn=skew)
            wall = time.perf_counter() - t0
            summ = obs_report.summarize(obs_events.drain())
        idle = [w["idle_fraction"] for w in summ["idle"].values()]
        links = {
            k: {"attempts": v["attempts"], "hits": v["hits"]}
            for k, v in summ["steal_links"].items()
        }
        out[f"{mode}_s"] = round(wall, 3)
        out[f"{mode}_idle_frac"] = round(
            sum(idle) / len(idle), 4) if idle else None
        out[f"{mode}_blocks"] = (res.comm or {}).get("blocks_received")
        out[f"{mode}_nodes"] = (res.comm or {}).get("nodes_received")
        out[f"{mode}_links"] = links
        out[f"{mode}_parity"] = (
            (res.explored_tree, res.explored_sol) == golden
        )
        if mode == "hier":
            out["policy"] = res.steal_policy
    out["parity"] = out["flat_parity"] and out["hier_parity"]
    out["speedup"] = round(out["flat_s"] / max(out["hier_s"], 1e-9), 3)
    if (out["flat_idle_frac"] is not None
            and out["hier_idle_frac"] is not None):
        out["idle_drop"] = round(
            out["flat_idle_frac"] - out["hier_idle_frac"], 4)
    return out


def _steal_ab_rows(extras: list) -> None:
    """Hierarchical-stealing A/B row (never fails the bench)."""
    extras.append(steal_ab())


def _bytes_ab_rows(extras: list) -> None:
    """Narrow-node-storage A/B (problems/base.py TTS_NARROW — never fails
    the bench): per arm (auto vs 0) on real ta014 shapes, the host bytes
    per node and per prmu row from ``node_fields`` (the 80B -> 20B
    headline), plus measured artifacts from a budgeted resident run —
    checkpoint file size and the snapshot's host-transfer payload bytes —
    and a complete CPU-sim search on a reduced instance whose counts gate
    the row (``parity``): the encoding at rest must never change what the
    search explores. On the CPU sim the wall delta is noise; the byte
    columns are the evidence, the hardware session banks the bandwidth
    effect (scripts/hw_session.sh NARROW_AB)."""
    import tempfile

    import numpy as np

    from tpu_tree_search.engine import checkpoint as _ckpt
    from tpu_tree_search.engine.resident import resident_search
    from tpu_tree_search.problems import PFSPProblem
    from tpu_tree_search.problems.pfsp import taillard

    row = {"metric": "bytes_ab", "inst": "ta014"}
    ptm = taillard.reduced_instance(14, jobs=10, machines=5)
    counts = {}
    for arm, mode in (("narrow", "auto"), ("wide", "0")):
        with _env_override("TTS_NARROW", mode):
            prob = PFSPProblem(inst=14)
            fields = prob.node_fields()
            per_node = sum(
                int(np.prod(shape, dtype=np.int64))
                * np.dtype(dt).itemsize
                for shape, dt in fields.values()
            )
            row[f"{arm}_bytes_per_node"] = per_node
            row[f"{arm}_prmu_bytes"] = (
                int(np.prod(fields["prmu"][0], dtype=np.int64))
                * fields["prmu"][1].itemsize
            )
            with tempfile.TemporaryDirectory() as td:
                path = os.path.join(td, "ab.ckpt")
                resident_search(prob, m=8, M=256, K=2, max_steps=2,
                                checkpoint_path=path)
                row[f"{arm}_ckpt_bytes"] = os.path.getsize(path)
                snap = _ckpt.load(path, prob)
                row[f"{arm}_snapshot_host_bytes"] = sum(
                    np.asarray(v).nbytes for v in snap.batch.values()
                )
            small = PFSPProblem(lb="lb1", ub=0, p_times=ptm)
            resident_search(small, m=8, M=64, K=8)  # warm
            t0 = time.perf_counter()
            res = resident_search(small, m=8, M=64, K=8)
            row[f"{arm}_sim_wall_s"] = round(time.perf_counter() - t0, 3)
            counts[arm] = (res.explored_tree, res.explored_sol, res.best)
    row["prmu_shrink"] = round(
        row["wide_prmu_bytes"] / max(row["narrow_prmu_bytes"], 1), 2)
    row["node_shrink"] = round(
        row["wide_bytes_per_node"] / max(row["narrow_bytes_per_node"], 1),
        2)
    row["ckpt_shrink"] = round(
        row["wide_ckpt_bytes"] / max(row["narrow_ckpt_bytes"], 1), 2)
    row["parity"] = counts["narrow"] == counts["wide"]
    extras.append(row)


def _megakernel_ab_rows(extras: list, on_tpu: bool) -> None:
    """One-kernel-cycle A/B (ops/megakernel.py — the keep/retire evidence
    row, docs/HW_VALIDATION.md). Off-chip the row is a PARITY GATE only:
    ``TTS_MEGAKERNEL=force`` arms the fused Pallas cycle in interpret mode
    (same program structure, reference semantics) and every count must be
    bit-identical to the off build — no timing claim, interpret wall time
    means nothing. A third arm forces the STREAMED grid form
    (``TTS_MEGAKERNEL_MT``) under the same gate, and an M-ladder records
    the auto resolver's decision per pool-size rung (the past-2^16 rung
    must arm tiled). On TPU the row adds the timed A/B/tiled triple on
    ta014 lb1 at M=1024 — off vs force vs tiled nodes/s, speedups, golden
    parity for all arms, and a phase-profiled roofline audit per arm
    (``*_roofline_mem``, obs/roofline.py) — the numbers the round-6
    keep/retire bars judge."""
    from tpu_tree_search.engine.resident import resident_search
    from tpu_tree_search.problems import NQueensProblem, PFSPProblem

    row = {"metric": "megakernel_ab"}
    import numpy as np

    rng = np.random.default_rng(13)
    ptm = np.ascontiguousarray(
        rng.integers(1, 100, size=(5, 8)).astype(np.int32))
    cases = [
        ("nqueens", lambda: NQueensProblem(N=10)),
        ("lb1", lambda: PFSPProblem(lb="lb1", ub=0, p_times=ptm)),
        ("lb2", lambda: PFSPProblem(lb="lb2", ub=0, p_times=ptm)),
    ]
    parity = True
    for name, mk in cases:
        with _env_override("TTS_MEGAKERNEL", "0"):
            off = resident_search(mk(), m=5, M=64, K=8)
        with _env_override("TTS_MEGAKERNEL", "force"):
            on = resident_search(mk(), m=5, M=64, K=8)
        # Streamed-grid arm: a forced Mt=16 at M=64 tiles the pool
        # axis 4-wide — the double-buffered HBM->VMEM form must stay
        # bit-identical to both the off build and the single-tile arm.
        with _env_override("TTS_MEGAKERNEL", "force"), \
                _env_override("TTS_MEGAKERNEL_MT", "16"):
            tiled = resident_search(mk(), m=5, M=64, K=8)
        ok = (
            on.megakernel == "on"
            and (on.explored_tree, on.explored_sol, on.best)
            == (off.explored_tree, off.explored_sol, off.best)
        )
        tok = (
            tiled.megakernel == "on" and tiled.megakernel_tiled
            and tiled.megakernel_mt == 16
            and (tiled.explored_tree, tiled.explored_sol, tiled.best)
            == (off.explored_tree, off.explored_sol, off.best)
        )
        row[f"{name}_parity"] = ok
        row[f"{name}_tiled_parity"] = tok
        if not ok:
            row[f"{name}_reason"] = on.megakernel_reason
        if not tok:
            row[f"{name}_tiled_reason"] = tiled.megakernel_reason
        parity = parity and ok and tok
    row["parity"] = parity

    # -- pool-size ladder (the streamed/tiled axis evidence) ----------
    # Decision rows at every rung: what the AUTO resolver does at this
    # M (patching the backend gate on so the rows mean the same thing
    # on- and off-chip) — the past-2^16 rung must arm TILED with a
    # recorded Mt, the refusal the streaming rewrite removed. The
    # smallest rung also EXECUTES the off/tiled pair off-chip as a
    # parity fact (interpret wall time means nothing; the timed
    # evidence stays on the TPU rows below).
    from tpu_tree_search.ops import megakernel as MK

    ladder = []
    orig_on_tpu = MK._native_kind
    MK._native_kind = ((lambda device=None: True) if not on_tpu
                       else orig_on_tpu)
    try:
        for Mr in (4096, 16384, 65536):
            entry = {"M": Mr}
            dec = MK.resolve(NQueensProblem(N=10), Mr)
            entry["auto_enabled"] = dec.enabled
            entry["auto_mt"] = dec.mt
            entry["auto_grid"] = dec.grid
            if dec.reason:
                entry["auto_reason"] = dec.reason
            ladder.append(entry)
    finally:
        MK._native_kind = orig_on_tpu
    if parity:
        Mr = 4096
        with _env_override("TTS_MEGAKERNEL", "0"):
            off = resident_search(NQueensProblem(N=10), m=5, M=Mr, K=2)
        with _env_override("TTS_MEGAKERNEL", "force"), \
                _env_override("TTS_MEGAKERNEL_MT", str(Mr // 4)):
            tiled = resident_search(
                NQueensProblem(N=10), m=5, M=Mr, K=2)
        ladder[0]["exec_tiled_parity"] = (
            tiled.megakernel == "on" and tiled.megakernel_tiled
            and (tiled.explored_tree, tiled.explored_sol)
            == (off.explored_tree, off.explored_sol)
        )
    row["m_ladder"] = ladder
    if on_tpu and parity:
        import contextlib

        timed = {}
        # Third arm: forced Mt=256 at M=1024 streams the pool 4-wide —
        # the grid form's timed number next to the single-tile one.
        for label, env, mt in (("off", "0", None),
                               ("force", "force", None),
                               ("tiled", "force", "256")):
            with contextlib.ExitStack() as stack:
                stack.enter_context(
                    _env_override("TTS_MEGAKERNEL", env))
                if mt is not None:
                    stack.enter_context(
                        _env_override("TTS_MEGAKERNEL_MT", mt))
                resident_search(PFSPProblem(inst=14, lb="lb1", ub=1),
                                m=25, M=1024)  # warm/compile
                t0 = time.perf_counter()
                res = resident_search(
                    PFSPProblem(inst=14, lb="lb1", ub=1), m=25, M=1024)
                wall = time.perf_counter() - t0
                # Separate phase-profiled pass: the roofline audit
                # needs the phase clocks, whose instrumented build
                # must never time the A/B arms themselves.
                stack.enter_context(
                    _env_override("TTS_PHASEPROF", "1"))
                prof = resident_search(
                    PFSPProblem(inst=14, lb="lb1", ub=1), m=25, M=1024)
            timed[label] = (res, wall)
            row[f"{label}_s"] = round(wall, 3)
            row[f"{label}_nodes_per_sec"] = round(
                res.explored_tree / max(wall, 1e-9), 1)
            row[f"{label}_megakernel"] = res.megakernel
            if res.megakernel_mt:
                row[f"{label}_mt"] = res.megakernel_mt
            if res.megakernel_reason:
                row[f"{label}_reason"] = res.megakernel_reason
            if prof.roofline is not None:
                row[f"{label}_roofline_mem"] = prof.roofline
        row["speedup"] = round(
            timed["off"][1] / max(timed["force"][1], 1e-9), 3)
        row["speedup_tiled"] = round(
            timed["off"][1] / max(timed["tiled"][1], 1e-9), 3)
        row["tpu_parity"] = (
            (timed["off"][0].explored_tree, timed["off"][0].explored_sol,
             timed["off"][0].best)
            == (timed["force"][0].explored_tree,
                timed["force"][0].explored_sol, timed["force"][0].best)
            == (timed["tiled"][0].explored_tree,
                timed["tiled"][0].explored_sol, timed["tiled"][0].best)
        )
    extras.append(row)


def run_config(problem, m: int, M: int):
    """Warm-up run (compiles) + measured run; returns
    (result, nodes/s, elapsed, device_phase_s)."""
    from tpu_tree_search.engine.resident import resident_search

    resident_search(problem, m=m, M=M)
    t0 = time.time()
    res = resident_search(problem, m=m, M=M)
    elapsed = time.time() - t0
    device_phase = res.phases[1].seconds if len(res.phases) > 1 else res.elapsed
    return res, res.explored_tree / max(device_phase, 1e-9), elapsed, device_phase


def main() -> int:
    partial = BenchPartial()
    partial.install_sigterm()
    try:
        return _main(partial)
    except BaseException as e:  # noqa: BLE001 — provenance, then re-raise
        partial.finish(1, f"crashed: {type(e).__name__}: {e}")
        raise


# -- fleet saturation (`python bench.py fleet_sat`) --------------------------

FLEET_SAT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "FLEET_SAT.json")


def fleet_sat_main() -> int:
    """``python bench.py fleet_sat``: drive an in-process fleet (router +
    N daemons) through the seeded loadgen rate ladder and bank the
    saturation curve — offered jobs/s vs achieved jobs/s and p50/p99
    queue-wait ms, overall and per shape class — as FLEET_SAT.json.

    The curve is banked flush-as-you-go (one atomic rewrite per rate
    point), so a wall-clock kill still leaves a usable prefix — the same
    lesson BENCH_PARTIAL.json encodes. Knobs: TTS_FLEET_SAT_RATES
    (comma list of offered jobs/s), TTS_FLEET_SAT_JOBS (jobs per rate),
    TTS_FLEET_SAT_DAEMONS, TTS_FLEET_SAT_SEED, TTS_FLEET_SAT_OUT.
    CPU-sim runs (JAX_PLATFORMS=cpu — the CI smoke) write to tempdir to
    keep the working tree clean; hardware sessions keep the committed
    path (scripts/hw_session.sh stage 9b)."""
    partial = BenchPartial()
    partial.install_sigterm()
    import tempfile as _tempfile

    from tpu_tree_search.fleet.loadgen import make_plan, saturation_curve
    from tpu_tree_search.fleet.router import FleetRouter
    from tpu_tree_search.serve.server import ServeDaemon
    from tpu_tree_search.utils import compile_cache

    compile_cache.enable()
    cpu = os.environ.get("JAX_PLATFORMS", "").startswith("cpu")
    out = os.environ.get("TTS_FLEET_SAT_OUT") or (
        os.path.join(_tempfile.gettempdir(), "FLEET_SAT.json") if cpu
        else FLEET_SAT_PATH)
    rates = [float(x) for x in os.environ.get(
        "TTS_FLEET_SAT_RATES", "0.5,1,2").split(",") if x.strip()]
    jobs_per_rate = int(os.environ.get("TTS_FLEET_SAT_JOBS", "6"))
    n_daemons = int(os.environ.get("TTS_FLEET_SAT_DAEMONS", "2"))
    seed = int(os.environ.get("TTS_FLEET_SAT_SEED", "0"))
    doc = {
        "metric": "fleet_saturation_curve",
        "daemons": n_daemons,
        "jobs_per_rate": jobs_per_rate,
        "seed": seed,
        "commit": _git_head(),
        "contracts": contracts_fingerprint(),
        "platform": "cpu-sim" if cpu else "accelerator",
        "status": "running",
        "points": [],
    }

    def bank() -> None:
        doc["updated"] = time.strftime("%Y-%m-%d %H:%M:%S UTC",
                                       time.gmtime())
        tmp = out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, out)

    bank()
    state_root = _tempfile.mkdtemp(prefix="fleet_sat_")
    partial.stage("fleet_up", "running", daemons=n_daemons)
    daemons = [ServeDaemon(port=0,
                           state_dir=os.path.join(state_root, f"d{i}"))
               for i in range(n_daemons)]
    for d in daemons:
        d.start()
    router = FleetRouter(port=0,
                         state_dir=os.path.join(state_root, "fleet"),
                         daemons=[d.url for d in daemons],
                         scrape_interval_s=0.3, pull_interval_s=1.0)
    router.start()
    partial.stage("fleet_up", "ok", router=router.url,
                  daemons=[d.url for d in daemons])
    try:
        # Pre-warm every class in the mix once (make_plan's own class
        # set), so the curve measures queueing, not first-compile — the
        # same reason the main bench warms before timing.
        partial.stage("fleet_warm", "running")
        warm_specs = {}
        for row in make_plan(seed, 24, 100.0):
            spec = {k: v for k, v in row["spec"].items()
                    if k not in ("max_steps", "label")}
            warm_specs.setdefault(json.dumps(spec, sort_keys=True), spec)
        import urllib.request as _rq

        for spec in warm_specs.values():
            spec = dict(spec)
            spec["max_steps"] = 8
            req = _rq.Request(router.url + "/submit",
                              data=json.dumps(spec).encode(),
                              headers={"Content-Type": "application/json"})
            with _rq.urlopen(req, timeout=600) as r:
                json.loads(r.read().decode())
        deadline = time.time() + 600
        while time.time() < deadline:
            if all(j.brief()["state"] in ("done", "failed", "cancelled")
                   for j in router.jobs.all()):
                break
            time.sleep(0.5)
        partial.stage("fleet_warm", "ok", classes=len(warm_specs))

        def on_point(row: dict) -> None:
            doc["points"].append(row)
            bank()
            partial.stage(f"rate_{row['offered_jobs_per_s']:g}", "ok",
                          achieved=row["achieved_jobs_per_s"],
                          p99_ms=row["queue_wait_ms_p99"],
                          done=row["done"])

        saturation_curve(router.url, rates, seed=seed,
                         jobs_per_rate=jobs_per_rate,
                         steps_scale=12, steps_cap=80,
                         timeout_s=600.0, on_point=on_point)
        doc["status"] = "complete"
        bank()
        print(json.dumps({"metric": "fleet_saturation_curve",
                          "points": len(doc["points"]),
                          "artifact": out}))
        partial.finish(0)
        return 0
    finally:
        router.close()
        for d in daemons:
            d.scheduler.drain(timeout_s=30.0)
            d.close()


def _main(partial: BenchPartial) -> int:
    from tpu_tree_search.utils import compile_cache

    compile_cache.enable()

    import jax

    from tpu_tree_search.problems import PFSPProblem

    from tpu_tree_search.ops import pallas_kernels as PK

    on_tpu = jax.default_backend() == "tpu"
    # Whether the kernels are on the path: a kernel that does not compile
    # fails the run, it is never routed around.
    pallas_ok = PK.use_pallas()
    record: dict = {}

    class _FlushingExtras(list):
        # Every extra row lands in the partial the moment it is measured
        # (flush-as-you-go): a timeout mid-extras keeps the finished rows.
        def append(self, rec):
            super().append(rec)
            partial.rows_from_extras([rec])

        def extend(self, recs):
            for rec in recs:
                self.append(rec)

    extras: list[dict] = _FlushingExtras()
    prob_hl = PFSPProblem(inst=14, lb="lb1", ub=1)
    # Empirical headline-path selection: both evaluators are exact — if the
    # jnp/XLA path outruns the Pallas lb1 kernel on this chip, the headline
    # uses the faster one. The kernel microbench on the search's chunk
    # shape decides; its compiles warm the cache the chosen path reuses.
    micro: dict = {}
    headline_path = "jnp" if not pallas_ok else "pallas"
    if on_tpu and pallas_ok:
        # The lb1 family is demoted to jnp by default (TTS_PALLAS=force
        # re-arms it — docs/HW_VALIDATION.md decision record), so the
        # kernel arm of the A/B must force the route explicitly.
        with _env_override("TTS_PALLAS", "force"):
            mb_pallas = eval_microbench(prob_hl, on_tpu)
        with _env_override("TTS_PALLAS", "0"):
            mb_jnp = eval_microbench(prob_hl, on_tpu)
        micro = {"pallas": mb_pallas, "jnp": mb_jnp}
        if (mb_jnp["bound_evals_per_sec"]
                > mb_pallas["bound_evals_per_sec"]):
            headline_path = "jnp"
        else:
            headline_path = "pallas"
    else:
        micro = {"jnp" if not pallas_ok else "pallas":
                 eval_microbench(prob_hl, on_tpu)}
    # Host-event trace of the headline run (TTS_OBS=host: host tracing
    # only, device programs stay byte-identical — the measurement is NOT
    # perturbed; docs/OBSERVABILITY.md). An explicit TTS_OBS is respected.
    from tpu_tree_search.obs import events as obs_events

    _obs_prev = os.environ.get("TTS_OBS")
    if _obs_prev is None:
        os.environ["TTS_OBS"] = "host"
    obs_events.reset()
    # -- headline: PFSP ta014 lb1 --------------------------------------
    # A jnp demotion is scoped to THIS run: the lb2/nqueens extras have
    # their own kernels, which the lb1 microbench says nothing about.
    def _headline_run():
        if headline_path == "jnp" and pallas_ok:
            with _env_override("TTS_PALLAS", "0"):
                return run_config(prob_hl, m=25, M=HEADLINE_M)
        if headline_path == "pallas":
            # Demoted-by-default lb1 kernels need the force spelling.
            with _env_override("TTS_PALLAS", "force"):
                return run_config(prob_hl, m=25, M=HEADLINE_M)
        return run_config(prob_hl, m=25, M=HEADLINE_M)

    compact_stats = None
    best_run = None
    if on_tpu:
        # Empirical compaction pick (cf. the jnp-vs-Pallas pick above):
        # scatter serializes on TPU, sort loses on CPU, dense is the
        # shift-based fast path — measure each on the production
        # config, bank the winner, record all plus the per-mode cycle
        # decomposition (evaluator vs maintenance). One problem
        # instance is fine: the program cache keys on the routing
        # token, which includes TTS_COMPACT.
        from tpu_tree_search.ops.compaction import resolve_compact_mode

        compact_stats, best_run = pick_compact(
            _headline_run,
            lambda r: (r[0].explored_tree == GOLDEN_LB1["tree"]
                       and r[0].explored_sol == GOLDEN_LB1["sol"]
                       and r[0].best == GOLDEN_LB1["makespan"]),
            budget_s=600.0,
            eval_ms=eval_cycle_ms(prob_hl, 25, HEADLINE_M),
            auto_mode=resolve_compact_mode(
                prob_hl, HEADLINE_M, prob_hl.jobs, jax.devices()[0]
            ),
            phase_probe=(
                (lambda: phase_split_probe(prob_hl, 25, HEADLINE_M))
                if _phaseprof_armed() else None
            ),
        )
    if best_run is not None:
        res, nps, elapsed, device_phase = best_run
    else:
        res, nps, elapsed, device_phase = _headline_run()
    parity = (
        res.explored_tree == GOLDEN_LB1["tree"]
        and res.explored_sol == GOLDEN_LB1["sol"]
        and res.best == GOLDEN_LB1["makespan"]
    )
    record = {
        "metric": "pfsp_ta014_lb1_nodes_per_sec_per_chip",
        "value": round(nps, 1),
        "unit": "nodes/sec",
        "vs_baseline": round(nps / REFERENCE_NODES_PER_SEC, 3),
        "vs_ref_c_seq": round(nps / REF_C_SEQ["pfsp_ta014_lb1"], 3),
        "vs_ref_c_lb1d": round(nps / REF_C_SEQ["pfsp_ta014_lb1_d"], 3),
        "parity": parity,
        "explored_tree": res.explored_tree,
        "explored_sol": res.explored_sol,
        "makespan": res.best,
        "device_phase_s": round(device_phase, 3),
        "total_s": round(elapsed, 3),
        "kernel_launches": res.diagnostics.kernel_launches,
        # One-kernel cycle provenance: the resolved TTS_MEGAKERNEL
        # state the headline number ran under (and, when the resolver
        # declined/refused, why) — a banked rate is meaningless
        # without knowing which cycle body produced it.
        "megakernel": res.megakernel,
        "roofline": roofline(nps, prob_hl.jobs, prob_hl.machines, None,
                             "lb1", problem=prob_hl),
    }
    if res.megakernel_reason:
        record["megakernel_reason"] = res.megakernel_reason
    if res.megakernel_mt:
        record["megakernel_mt"] = res.megakernel_mt
        record["megakernel_tiled"] = res.megakernel_tiled
    if res.roofline is not None:
        # Memory-roofline audit (obs/roofline.py) — distinct from the
        # FLOP-MFU "roofline" key above: per-phase %-of-memory-bound
        # peak when the headline ran phase-profiled.
        record["roofline_mem"] = res.roofline
    if compact_stats is not None:
        record["compact"] = compact_stats
    # Measured kernel-only throughput on the same chunk shape: the
    # roofline's empirical cross-check (search MFU << kernel MFU means
    # the gap is orchestration, not the kernel) — and the basis of the
    # headline-path selection above.
    record["kernel_microbench"] = micro
    record["headline_eval_path"] = headline_path
    partial.stage(
        "headline",
        "ok" if record.get("parity") else "error",
        value=record.get("value"),
        **({"error": record["error"]} if record.get("error") else {}),
    )
    # Attach the headline trace artifact (never fatal): Perfetto-loadable
    # file next to the bench, summary riding the JSON line.
    hl_events = obs_events.drain()
    if _obs_prev is None:
        os.environ.pop("TTS_OBS", None)
    try:
        import tempfile

        from tpu_tree_search.obs import export as obs_export
        from tpu_tree_search.obs import report as obs_report

        # Artifact next to the bench only for real on-chip runs; CPU
        # smoke runs must not dirty the working tree.
        trace_dir = HERE if on_tpu else tempfile.gettempdir()
        trace_path = os.path.join(trace_dir, "BENCH_TRACE.json")
        n_ev = obs_export.write_chrome_trace(
            hl_events, trace_path, label="bench-headline"
        )
        record["trace"] = {
            "path": os.path.basename(trace_path) if on_tpu else trace_path,
            "events": n_ev,
            "span_s": obs_report.summarize(hl_events)["span_s"],
        }
    except Exception:  # noqa: BLE001 — bookkeeping must not cost the line
        pass
    # Cost-model capture from the same headline events (on-chip only — a
    # CPU smoke fit would pace real controllers with nonsense): measured
    # dispatch latency+bandwidth lands in COSTMODEL.json next to the
    # bench, where TTS_COSTMODEL can arm it (docs/OBSERVABILITY.md).
    if on_tpu:
        try:
            from tpu_tree_search.obs import costmodel as obs_costmodel

            prob_cm = PFSPProblem(inst=14, lb="lb1", ub=1)
            profile = obs_costmodel.build_profile(
                hl_events, "tpu", "device-D1",
                obs_costmodel.shape_class(prob_cm),
            )
            cm_path = os.path.join(HERE, "COSTMODEL.json")
            obs_costmodel.save(cm_path, profile)
            record["costmodel"] = {
                "path": os.path.basename(cm_path),
                "links": sorted(next(iter(profile.values()))["links"]),
            }
        except Exception:  # noqa: BLE001 — capture must not cost the line
            pass

    # -- extras: ta014 lb2 + N-Queens N=15 -------------------------------
    _collect_extras(extras, on_tpu)
    # Dispatch-latency microbench: K=1 vs K=max × depth 1 vs 2 rows +
    # the headline pipeline on/off A/B (TPU) and the simulated-latency
    # CPU harness row (every backend).
    _dispatch_latency_rows(extras, on_tpu)
    # Instance-batching A/B: serial vs batched_search at B in
    # {1, 4, 8}, bit-identity checked per job (CPU-sim, every
    # backend — the --batch-slots evidence row).
    _batch_ab_rows(extras)
    # One-kernel-cycle A/B: interpret parity gate on every backend,
    # timed off-vs-force ta014 lb1 rows on TPU (the keep/retire
    # evidence, docs/HW_VALIDATION.md).
    _megakernel_ab_rows(extras, on_tpu)
    # Hierarchical-stealing A/B: flat vs hier on the virtual-host
    # simulated-latency harness, parity-gated on node counts
    # (CPU-sim, every backend — the TTS_STEAL evidence row).
    _steal_ab_rows(extras)
    # Narrow-node-storage A/B: bytes/node, prmu row, checkpoint and
    # snapshot payload sizes narrow-vs-wide on ta014, parity-gated on
    # a reduced-instance search (the TTS_NARROW evidence row).
    _bytes_ab_rows(extras)
    # Published-config rate rows (bounded — a few dispatches each).
    _published_rate_rows(extras, on_tpu)
    record["backend"] = jax.default_backend()
    # Provenance: the contract fingerprint this number was measured under
    # (ties the row to the exact compiled-program structure — ISSUE 8).
    record["contracts"] = contracts_fingerprint()
    record["pallas"] = pallas_ok
    record["extra"] = extras
    rc = 0 if record.get("parity") else 1
    partial.rows_from_extras(extras)
    partial.finish(rc)
    print(json.dumps(record))
    return rc


def _published_rate_rows(extras: list, on_tpu: bool) -> None:
    """First measured numbers for the published BASELINE configs 2 and 4
    (N-Queens N=16/17 and ta021 lb2 — VERDICT r5 #5): their full searches
    are minutes-to-hours at current rates, so these are BOUNDED-dispatch
    rate rows — ``max_steps`` cuts after a few K-cycle dispatches and the
    metric is device-phase nodes/s (golden-count parity is not computable
    on a cutoff; ``complete`` records whether the run happened to finish).
    On-TPU only: CPU smoke must not pay minutes for rate rows that mean
    nothing off-chip. One warm dispatch per config compiles via the
    persistent cache (scripts/warm_cache.py banks the same shapes)."""
    if not on_tpu:
        return
    from tpu_tree_search.engine.resident import resident_search
    from tpu_tree_search.problems import NQueensProblem, PFSPProblem

    configs = [
        ("pfsp_ta021_lb2_nodes_per_sec_per_chip_bounded",
         lambda: PFSPProblem(inst=21, lb="lb2", ub=1), 1024, 4),
        ("nqueens_n16_nodes_per_sec_per_chip_bounded",
         lambda: NQueensProblem(N=16), 65536, 4),
        ("nqueens_n17_nodes_per_sec_per_chip_bounded",
         lambda: NQueensProblem(N=17), 65536, 4),
    ]
    for metric, mk, M, steps in configs:
        resident_search(mk(), m=25, M=M, max_steps=1)  # compile + warm
        res = resident_search(mk(), m=25, M=M, max_steps=steps)
        device_phase = (res.phases[1].seconds if len(res.phases) > 1
                        else res.elapsed)
        extras.append({
            "metric": metric,
            "value": round(res.explored_tree / max(device_phase, 1e-9), 1),
            "unit": "nodes/sec",
            "bounded_steps": steps,
            "explored_tree": res.explored_tree,
            "complete": res.complete,
        })


def _collect_extras(extras: list, on_tpu: bool) -> None:
    """The bench's extra records (ta014 lb2 + staged comparison,
    N-Queens, host-seq)."""
    from tpu_tree_search.ops import pfsp_device as P
    from tpu_tree_search.problems import NQueensProblem, PFSPProblem

    staged_ok = P.lb2_staged_enabled(None, 20)
    # Chunk size measured on the real v5e (scripts/lb2_tune.py, round
    # 5): like the headline, per-cycle cost scales with M while the
    # heavily-pruned lb2 frontier rarely fills big chunks — staged
    # M=1024 ran 158.8k nodes/s (2.43x ref C) vs 50.7k at the old
    # 65536. CPU smoke keeps moderate chunks (jnp lb2's per-pair
    # intermediates dominate there).
    lb2_m, lb2_M = 25, (1024 if on_tpu else 4096)

    def _lb2_run():
        return run_config(
            PFSPProblem(inst=14, lb="lb2", ub=1), m=lb2_m, M=lb2_M
        )

    lb2_compact, lb2_best = None, None
    if on_tpu:
        # Same empirical compaction pick as the headline — lb2 runs are
        # ~1s each at the tuned chunk size, so the A/B is nearly free.
        from tpu_tree_search.ops.compaction import resolve_compact_mode

        _p2 = PFSPProblem(inst=14, lb="lb2", ub=1)
        lb2_compact, lb2_best = pick_compact(
            _lb2_run,
            lambda r: (r[0].explored_tree == GOLDEN_LB2["tree"]
                       and r[0].explored_sol == GOLDEN_LB2["sol"]
                       and r[0].best == GOLDEN_LB2["makespan"]),
            budget_s=300.0,
            eval_ms=eval_cycle_ms(_p2, lb2_m, lb2_M),
            auto_mode=resolve_compact_mode(_p2, lb2_M, _p2.jobs),
            phase_probe=(
                (lambda: phase_split_probe(_p2, lb2_m, lb2_M))
                if _phaseprof_armed() else None
            ),
        )
    if lb2_best is not None:
        res2, nps2, _, _ = lb2_best
    else:
        res2, nps2, _, _ = _lb2_run()
    staged_speedup = None
    if staged_ok and os.environ.get("TTS_LB2_STAGED", "auto") != "0":
        # Measure the incumbent-staging win directly (VERDICT r3 #4):
        # the same config with staging forced off, on a fresh problem
        # (resident programs cache per instance + env knob), in the
        # same compaction mode — the speedup isolates staging. The env
        # override is restored, never popped (bench must not eat a
        # user's explicit TTS_LB2_STAGED).
        with _env_override("TTS_LB2_STAGED", "0"), \
                _compact_ctx(lb2_compact):
            _, nps2_off, _, _ = _lb2_run()
        staged_speedup = round(nps2 / max(nps2_off, 1e-9), 3)
    extras.append({
        "metric": "pfsp_ta014_lb2_nodes_per_sec_per_chip",
        "value": round(nps2, 1),
        "vs_ref_c_seq": round(nps2 / REF_C_SEQ["pfsp_ta014_lb2"], 3),
        "parity": (
            res2.explored_tree == GOLDEN_LB2["tree"]
            and res2.explored_sol == GOLDEN_LB2["sol"]
            and res2.best == GOLDEN_LB2["makespan"]
        ),
        "explored_tree": res2.explored_tree,
        "makespan": res2.best,
        "staged": os.environ.get("TTS_LB2_STAGED", "auto") == "1"
        or (staged_ok
            and os.environ.get("TTS_LB2_STAGED", "auto") != "0"),
        **({"staged_speedup": staged_speedup}
           if staged_speedup is not None else {}),
        **({"compact": lb2_compact} if lb2_compact else {}),
    })
    N = 15 if on_tpu else 12  # CPU smoke stays fast
    # N-Queens cycles are compaction-bound (no pruning: every cycle
    # compacts a full M*n grid, and XLA:TPU serializes the scatter), so
    # the compaction mode matters MOST here. The N=15 tree costs ~60s a
    # run — too dear to A/B directly — so probe the modes on N=14
    # (~27M nodes) and run N=15 once with the winner.
    nq_compact = None
    if on_tpu:
        from tpu_tree_search.ops.compaction import resolve_compact_mode

        _pq = NQueensProblem(N=14)
        nq_compact, _ = pick_compact(
            lambda: run_config(NQueensProblem(N=14), m=25, M=65536),
            lambda r: r[0].explored_sol == NQ_SOL[14],
            budget_s=420.0,
            eval_ms=eval_cycle_ms(_pq, 25, 65536, cycles=16),
            auto_mode=resolve_compact_mode(_pq, 65536, _pq.N),
            phase_probe=(
                (lambda: phase_split_probe(_pq, 25, 65536, K=16))
                if _phaseprof_armed() else None
            ),
        )
        if nq_compact is not None:
            # The stats were measured on the PROBE config, not N=15 —
            # make the artifact self-describing.
            nq_compact["probe"] = "nqueens_n14"
    with _compact_ctx(nq_compact):
        resq, npsq, _, _ = run_config(NQueensProblem(N=N), m=25, M=65536)
    extras.append({
        "metric": f"nqueens_n{N}_nodes_per_sec_per_chip",
        "value": round(npsq, 1),
        **({"vs_ref_c_seq": round(npsq / REF_C_SEQ[f"nqueens_n{N}"], 3)}
           if f"nqueens_n{N}" in REF_C_SEQ else {}),
        "parity": resq.explored_sol == NQ_SOL[N],
        "explored_tree": resq.explored_tree,
        "explored_sol": resq.explored_sol,
        **({"compact": nq_compact} if nq_compact else {}),
    })

    extras.extend(host_seq_extras())


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "fleet_sat":
        sys.exit(fleet_sat_main())
    sys.exit(main())
