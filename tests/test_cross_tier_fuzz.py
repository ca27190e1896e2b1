"""Randomized cross-tier equivalence: every tier must reproduce the
sequential tier's exploredTree/exploredSol EXACTLY under a fixed incumbent,
on randomly generated instances — chunking, work stealing, diffusion
balancing, and mp-sharding may only permute visit order (SURVEY.md §4.2's
determinism invariant, fuzzed instead of fixed-instance)."""

from __future__ import annotations

import numpy as np
import pytest

from tpu_tree_search.engine.batched import batched_search
from tpu_tree_search.engine.device import device_search
from tpu_tree_search.engine.resident import resident_search
from tpu_tree_search.engine.sequential import sequential_search
from tpu_tree_search.parallel.dist import dist_search
from tpu_tree_search.parallel.dist_mesh import dist_mesh_search
from tpu_tree_search.parallel.multidevice import multidevice_search
from tpu_tree_search.parallel.resident_mesh import mesh_resident_search
from tpu_tree_search.problems import PFSPProblem


def _fuzz_all_tiers(seed: int, lb: str):
    rng = np.random.default_rng(seed)
    jobs = int(rng.integers(6, 9))
    machines = int(rng.integers(3, 6))
    ptm = np.ascontiguousarray(
        rng.integers(1, 100, size=(machines, jobs)).astype(np.int32)
    )

    def mk():
        return PFSPProblem(lb=lb, ub=0, p_times=ptm)

    # Fixed incumbent: solve once with ub=0, then pin every tier to the
    # optimum (the ub=1 regime of the reference's validity check).
    opt = sequential_search(mk()).best
    seq = sequential_search(mk(), initial_best=opt)
    golden = (seq.explored_tree, seq.explored_sol)

    results = {
        "device": device_search(mk(), m=4, M=64, initial_best=opt),
        "resident": resident_search(mk(), m=4, M=64, K=8, initial_best=opt),
        "mesh": mesh_resident_search(
            mk(), m=4, M=64, K=4, rounds=2, D=4, initial_best=opt
        ),
        "multi": multidevice_search(mk(), m=4, M=64, D=3, initial_best=opt),
        "dist": dist_search(
            mk(), m=4, M=64, D=2, num_hosts=2, initial_best=opt,
            steal_interval_s=0.005,
        ),
    }
    results["dist_mesh"] = dist_mesh_search(
        mk(), m=4, M=64, K=4, rounds=2, D=2, num_hosts=2, initial_best=opt
    )
    if lb == "lb2":
        results["mesh_mp"] = mesh_resident_search(
            mk(), m=4, M=64, K=4, rounds=2, D=4, mp=2, initial_best=opt
        )
        # The full composition: staged (when forced) + mp pair sharding
        # inside each host, host exchange between steps.
        results["dist_mesh_mp"] = dist_mesh_search(
            mk(), m=4, M=64, K=4, rounds=2, D=2, mp=2, num_hosts=2,
            initial_best=opt,
        )
    for tier, res in results.items():
        assert (res.explored_tree, res.explored_sol) == golden, (
            f"{tier} diverged on seed={seed} jobs={jobs} machines={machines} "
            f"lb={lb}: {(res.explored_tree, res.explored_sol)} != {golden}"
        )
        assert res.best == opt

@pytest.mark.parametrize(
    "seed,lb", [(11, "lb1"), (23, "lb1_d"), (47, "lb2")]
)
def test_all_tiers_match_sequential_on_random_instance(seed, lb):
    _fuzz_all_tiers(seed, lb)


@pytest.mark.parametrize("seed,lb", [(11, "lb1"), (47, "lb2")])
def test_batched_axis_matches_sequential(seed, lb):
    """The instance-batch axis (engine/batched.py, serve --batch-slots):
    3 identical tenants through a 2-slot batched program — slot refill
    included — must EACH land the sequential counts on a random
    instance; frozen-slot masking may never leak one tenant's updates
    into another. A dedicated test (not part of _fuzz_all_tiers) so the
    B=2 while-loop compiles once per bound family, not once per fuzz
    parametrization."""
    rng = np.random.default_rng(seed)
    jobs = int(rng.integers(6, 9))
    machines = int(rng.integers(3, 6))
    ptm = np.ascontiguousarray(
        rng.integers(1, 100, size=(machines, jobs)).astype(np.int32)
    )

    def mk():
        return PFSPProblem(lb=lb, ub=0, p_times=ptm)

    opt = sequential_search(mk()).best
    seq = sequential_search(mk(), initial_best=opt)
    golden = (seq.explored_tree, seq.explored_sol)
    for i, res in enumerate(
        batched_search(mk(), n_jobs=3, B=2, m=4, M=64, K=8,
                       initial_best=opt)
    ):
        assert (res.explored_tree, res.explored_sol) == golden, (
            f"batched job {i} diverged on seed={seed} jobs={jobs} "
            f"machines={machines} lb={lb}: "
            f"{(res.explored_tree, res.explored_sol)} != {golden}"
        )
        assert res.best == opt


@pytest.mark.parametrize("seed", [59, 83])
def test_all_tiers_match_sequential_staged_lb2(seed, monkeypatch):
    """The staged lb2 evaluator (forced via TTS_LB2_STAGED=1; the jnp self
    path stands in for the kernel on CPU) through every tier at once —
    the same determinism invariant, same shared body. Includes the
    dp x mp mesh: staging now composes with the sharded pair loop
    (`lb2_self_bounds_mp`), closing the silent-fallback hole."""
    monkeypatch.setenv("TTS_LB2_STAGED", "1")
    _fuzz_all_tiers(seed, "lb2")


@pytest.mark.parametrize("seed,pairblock,staged", [
    (101, "1", "0"),   # serial pair loop (degenerate old behavior)
    (101, "4", "0"),   # multi-block at these P (machines 3-5 -> P 3-10)
    (101, "4", "1"),   # blocked self bound through the staged evaluator
    (131, "auto", "1"),  # the default policy end to end
])
def test_all_tiers_match_sequential_pairblocked_lb2(seed, pairblock, staged,
                                                    monkeypatch):
    """Fuzz axis over the lb2 pair-block size: every tier — including the
    dp x mp mesh, where each shard blocks its own P/mp pair subset — must
    land the sequential counts under every block size, serial through
    auto, staged and unstaged."""
    monkeypatch.setenv("TTS_LB2_PAIRBLOCK", pairblock)
    monkeypatch.setenv("TTS_LB2_STAGED", staged)
    _fuzz_all_tiers(seed, "lb2")


@pytest.mark.parametrize("pipeline,kmode", [("0", None), ("2", "auto")])
def test_all_tiers_match_sequential_pipeline_axis(pipeline, kmode,
                                                  monkeypatch):
    """Dispatch-pipeline axis (engine/pipeline.py): speculative pipelined
    dispatch is EXACT — every tier must land the sequential counts with
    pipelining off (TTS_PIPELINE=0, the synchronous pre-pipeline loops)
    and with one speculative dispatch in flight plus the adaptive
    geometric-ladder K controller (TTS_PIPELINE=2 + TTS_K=auto, the
    defaults-and-then-some).  Bit-parity across this axis is the ISSUE 5
    acceptance criterion."""
    monkeypatch.setenv("TTS_PIPELINE", pipeline)
    if kmode is not None:
        monkeypatch.setenv("TTS_K", kmode)
    _fuzz_all_tiers(211, "lb1")


@pytest.mark.slow  # every tier recompiles under force; CI tests-megakernel runs it unfiltered
@pytest.mark.parametrize("seed,lb", [(173, "lb1"), (179, "lb2")])
def test_all_tiers_match_sequential_megakernel_axis(seed, lb, monkeypatch):
    """One-kernel cycle axis (ops/megakernel.py): with the fused Pallas
    cycle forced (interpret mode on CPU — same program, reference
    semantics), every tier that can arm it must land the sequential
    counts, and the tiers that refuse (mp pair sharding, lb1_d) must
    fall back bit-correct.  The megakernel changes WHERE the cycle runs,
    never what it counts."""
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    _fuzz_all_tiers(seed, lb)


@pytest.mark.slow  # every tier recompiles under force+Mt; CI tests-megakernel runs it unfiltered
@pytest.mark.parametrize("seed,lb", [(173, "lb1"), (179, "lb2")])
def test_all_tiers_match_sequential_megakernel_tiled_axis(seed, lb,
                                                          monkeypatch):
    """Streamed-grid axis (ops/megakernel.py TTS_MEGAKERNEL_MT): a forced
    Mt=16 tiles every tier's M=64 pool 4-wide through the double-buffered
    grid — per-tile compaction plus the SMEM-carried cross-tile offset
    must land the sequential counts on every tier, armed or refused.
    Streaming changes how the cycle's bytes move, never what it counts."""
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    monkeypatch.setenv("TTS_MEGAKERNEL_MT", "16")
    _fuzz_all_tiers(seed, lb)


@pytest.mark.slow  # every tier recompiles per TTS_NARROW token; CI tests-narrow runs it unfiltered
@pytest.mark.parametrize("mode", ["0", "auto"])
def test_all_tiers_match_sequential_narrow_axis(mode, monkeypatch):
    """Narrow-node-storage axis (problems/base.py TTS_NARROW): with host
    pools/staging at int8/int16 storage dtypes (auto) and with everything
    forced wide int32 (0), every tier must land the sequential counts —
    widening happens only inside evaluator arithmetic, so the dtype of
    the bytes at rest can never change what the search explores."""
    monkeypatch.setenv("TTS_NARROW", mode)
    _fuzz_all_tiers(193, "lb1")


@pytest.mark.parametrize("kb", ["jnp", "tpu"])
def test_all_tiers_match_sequential_kernel_backend_inert_axis(kb,
                                                              monkeypatch):
    """Kernels-or-jnp axis (`pallas_kernels.use_pallas`, decided by the
    device's platform): the kernels switched off (TTS_PALLAS=0) and
    asked for on a host that is no TPU (TTS_PALLAS=force, which keeps the
    jnp evaluators here) must land the sequential counts on every tier."""
    monkeypatch.setenv("TTS_PALLAS", "0" if kb == "jnp" else "force")
    _fuzz_all_tiers(227, "lb1")


@pytest.mark.parametrize("mode", ["dense", "auto"])
def test_all_tiers_match_sequential_compact_axis(mode, monkeypatch):
    """Compaction-path axis (survivor-path overhaul): every tier — the
    fused prune+push runs shard-local inside mesh/dist_mesh via the shared
    loop body — must land the sequential counts under the dense shift path
    and under the auto policy.  The sort/search modes ride CI's dedicated
    per-mode tier-1 jobs (.github/workflows/ci.yml tests-compact)."""
    monkeypatch.setenv("TTS_COMPACT", mode)
    _fuzz_all_tiers(167, "lb1")


def _random_instance(seed: int, jobs: int, machines: int):
    rng = np.random.default_rng(seed)
    return np.ascontiguousarray(
        rng.integers(1, 100, size=(machines, jobs)).astype(np.int32)
    )


@pytest.mark.parametrize(
    "jobs,machines,lb,M",
    [
        (50, 10, "lb1", 256),   # ta031-class shapes through every size-
        (50, 10, "lb2", 64),    # dependent path (VERDICT r4 #6)
        (200, 10, "lb1", 128),  # int16 pool dtype (n > 127) engages
    ],
)
def test_large_instance_budgeted_resident_and_mesh(jobs, machines, lb, M,
                                                   tmp_path):
    """Large random instances end to end under a ``max_steps`` budget: the
    full search is intractable, but the size-dependent machinery — int8/
    int16 pool dtypes, `_auto_tile` shapes, the survivor-budget overflow
    fallback (a ub=0 infinite incumbent keeps nearly every child, far
    exceeding the survivor budget S = max(64n, Mn/4)) — must run, count,
    checkpoint, and resume at realistic widths. The reference cannot
    represent these nodes at all without a rebuild (MAX_JOBS=20,
    `Taillard.chpl:29-52`)."""
    from tpu_tree_search.engine.resident import _pool_int_dtype

    ptm = _random_instance(97 + jobs, jobs, machines)

    def mk():
        return PFSPProblem(lb=lb, ub=0, p_times=ptm)

    # The dtype claim the test name makes must actually hold.
    import jax.numpy as jnp

    assert _pool_int_dtype(jobs) == (jnp.int8 if jobs <= 127 else jnp.int16)

    path = str(tmp_path / "big.ckpt")
    r1 = resident_search(mk(), m=25, M=M, K=2, max_steps=1,
                         checkpoint_path=path)
    assert not r1.complete and r1.explored_tree > 0
    r2 = resident_search(mk(), m=25, M=M, K=2, max_steps=1,
                         resume_from=path)
    assert r2.explored_tree > r1.explored_tree  # resumed and progressed

    mres = mesh_resident_search(mk(), m=25, M=M, K=2, rounds=1, D=4,
                                max_steps=1)
    assert not mres.complete and mres.explored_tree > 0
    # Same frontier prefix, same fixed incumbent: the first budgeted step
    # explores nodes, never solutions (depth << jobs at step 1).
    assert mres.explored_sol == 0 and r1.explored_sol == 0


def test_large_instance_dist_runs_at_width_50():
    """The dist tier at 50-job width: a root-bound incumbent prunes every
    child immediately (lb1 of any deeper node >= the root bound), so the
    run terminates fast while still exercising 50-wide warm-up, per-host
    partitioning, the termination rounds, and the final reductions."""
    from tpu_tree_search.problems.pfsp import bounds as B

    ptm = _random_instance(147, 50, 10)
    prob = PFSPProblem(lb="lb1", ub=0, p_times=ptm)
    root_lb = B.lb1_bound(prob.lb1_data, np.arange(50, dtype=np.int32),
                          -1, 50)
    seq = sequential_search(
        PFSPProblem(lb="lb1", ub=0, p_times=ptm), initial_best=int(root_lb)
    )
    ds = dist_search(
        PFSPProblem(lb="lb1", ub=0, p_times=ptm), m=5, M=64, D=2,
        num_hosts=2, initial_best=int(root_lb), steal_interval_s=0.005,
    )
    assert (ds.explored_tree, ds.explored_sol) == (
        seq.explored_tree, seq.explored_sol
    )
    assert ds.best == root_lb  # no leaf can beat a lower bound


def test_survivor_budget_overflow_fallback_matches_goldens():
    """Force the resident engine's full-scatter fallback (`big` branch):
    N-Queens keeps every safe child, so a 512-parent chunk at shallow depth
    keeps ~512*(N-d) children >> S = max(64N, MN/2) — and the counts must
    still land exactly on the sequential goldens."""
    from tpu_tree_search.problems import NQueensProblem

    prob = NQueensProblem(N=11)
    seq = sequential_search(prob)
    res = resident_search(NQueensProblem(N=11), m=8, M=512, K=8)
    assert (res.explored_tree, res.explored_sol) == (
        seq.explored_tree, seq.explored_sol
    )
