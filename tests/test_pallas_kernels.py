"""Pallas kernels vs jnp/XLA oracles, bit-for-bit (interpret mode on CPU).

Mirrors the reference's cross-implementation validation strategy: the CUDA
device bounds are checked against the C host bounds by numeric agreement
(SURVEY.md §4.3); here the Pallas kernels are checked against the jnp
evaluators, which are themselves oracle-tested against the NumPy ports of
`c_bound_simple.c` (tests/test_bounds_oracle.py).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from tpu_tree_search.ops import nqueens_device, pallas_kernels, pfsp_device
from tpu_tree_search.problems import PFSPProblem
from tpu_tree_search.problems.pfsp import taillard


@pytest.mark.parametrize("g", [1, 3])
@pytest.mark.parametrize("N", [9, 11, 12])
def test_nqueens_labels_match_oracle(N, g):
    rng = np.random.default_rng(7)
    B = 700  # not a tile multiple: exercises padding
    boards = np.stack([rng.permutation(N).astype(np.uint8) for _ in range(B)])
    depth = rng.integers(0, N + 1, B).astype(np.int32)
    oracle = nqueens_device.make_core(N, g)(jnp.asarray(boards), jnp.asarray(depth))
    got = pallas_kernels.nqueens_labels(
        jnp.asarray(boards), jnp.asarray(depth), N, g, interpret=True
    )
    assert np.array_equal(np.asarray(oracle), np.asarray(got))


@pytest.mark.parametrize("bf16", [False, True])
@pytest.mark.parametrize(
    "inst,jobs,machines",
    [(14, 20, 10), (21, 20, 20), (1, 12, 5)],
)
def test_lb1_bounds_match_oracle(inst, jobs, machines, bf16):
    rng = np.random.default_rng(3)
    if jobs == 20:
        prob = PFSPProblem(inst=inst, lb="lb1", ub=1)
    else:
        ptm = taillard.reduced_instance(inst, jobs=jobs, machines=machines)
        prob = PFSPProblem(lb="lb1", ub=0, p_times=ptm)
    t = pfsp_device.PFSPDeviceTables(prob.lb1_data, prob.lb2_data)
    B = 300
    prmu = np.stack([rng.permutation(jobs).astype(np.int32) for _ in range(B)])
    limit1 = rng.integers(-1, jobs - 1, B).astype(np.int32)
    oracle = pfsp_device._lb1_chunk(
        jnp.asarray(prmu), jnp.asarray(limit1), t.ptm_t, t.min_heads,
        t.min_tails, bf16=bf16,
    )
    got = pallas_kernels.pfsp_lb1_bounds(
        jnp.asarray(prmu), jnp.asarray(limit1), t.ptm_t, t.min_heads, t.min_tails,
        interpret=True,
    )
    assert np.array_equal(np.asarray(oracle), np.asarray(got))


@pytest.mark.parametrize("bf16", [False, True])
@pytest.mark.parametrize(
    "inst,jobs,machines",
    [(14, 20, 10), (1, 12, 5)],
)
def test_lb2_bounds_match_oracle(inst, jobs, machines, bf16):
    rng = np.random.default_rng(11)
    if jobs == 20:
        prob = PFSPProblem(inst=inst, lb="lb2", ub=1)
    else:
        ptm = taillard.reduced_instance(inst, jobs=jobs, machines=machines)
        prob = PFSPProblem(lb="lb2", ub=0, p_times=ptm)
    t = pfsp_device.PFSPDeviceTables(prob.lb1_data, prob.lb2_data)
    B = 200
    prmu = np.stack([rng.permutation(jobs).astype(np.int32) for _ in range(B)])
    limit1 = rng.integers(-1, jobs - 1, B).astype(np.int32)
    oracle = pfsp_device._lb2_chunk(
        jnp.asarray(prmu), jnp.asarray(limit1), t.ptm_t, t.min_heads,
        t.min_tails, t.pairs, t.lags, t.johnson_schedules, bf16=bf16,
    )
    got = pallas_kernels.pfsp_lb2_bounds(
        jnp.asarray(prmu), jnp.asarray(limit1), t, interpret=True
    )
    # Compare only open child slots (k > limit1): closed slots are garbage
    # by contract (never read by the host/engine).
    k = np.arange(jobs)[None, :]
    open_ = k >= limit1[:, None] + 1
    assert np.array_equal(
        np.asarray(oracle)[open_], np.asarray(got)[open_]
    )


@pytest.mark.parametrize("pair_group", [1, 4, None])
@pytest.mark.parametrize("inst", [14, 21])
def test_lb2_bounds_across_pair_groups(inst, pair_group):
    """The lb2 lane body over the pair-group axis — one pair a group, four
    interleaved, and the group the TTS_LB2_PAIRBLOCK policy resolves — on
    ta014 (P=45) and ta021 (20x20, P=190, where the policy blocks),
    bit-equal to `_lb2_chunk` on open child slots (closed slots are
    garbage by contract)."""
    rng = np.random.default_rng(7 + inst)
    prob = PFSPProblem(inst=inst, lb="lb2", ub=1)
    jobs = prob.jobs
    t = pfsp_device.PFSPDeviceTables(prob.lb1_data, prob.lb2_data)
    B = 200
    prmu = np.stack([rng.permutation(jobs).astype(np.int32) for _ in range(B)])
    limit1 = rng.integers(-1, jobs - 1, B).astype(np.int32)
    pd, ld = jnp.asarray(prmu), jnp.asarray(limit1)
    oracle = pfsp_device._lb2_chunk(
        pd, ld, t.ptm_t, t.min_heads, t.min_tails,
        t.pairs, t.lags, t.johnson_schedules,
    )
    got = pallas_kernels.pfsp_lb2_bounds(pd, ld, t, interpret=True,
                                         pair_group=pair_group)
    open_ = np.arange(jobs)[None, :] >= limit1[:, None] + 1
    assert np.array_equal(np.asarray(oracle)[open_], np.asarray(got)[open_])


def _lb2_lane_problem(shape: str):
    if shape == "2m":  # two machines: one Johnson pair
        rng = np.random.default_rng(29)
        ptm = rng.integers(1, 100, size=(2, 9)).astype(np.int32)
        return PFSPProblem(lb="lb2", ub=0, p_times=np.ascontiguousarray(ptm))
    return PFSPProblem(inst=int(shape[2:]), lb="lb2", ub=1)


@pytest.mark.parametrize("shape,entry,tile", [
    ("ta014", "eval", 128),     # 20x10, P=45 padded to 48
    ("ta014", "mega", 64),
    ("ta021", "eval", 64),      # 20x20, P=190 padded to 192
    ("ta021", "mega", 128),
    ("ta031", "eval", 128),     # 50x5, P=10 padded to 16
    ("ta031", "stream", 64),
    ("2m", "eval", 64),         # P=1
    ("2m", "stream", 128),
])
def test_lb2_lane_body_matches_oracles(shape, entry, tile, monkeypatch):
    """The TPU lb2 body (`pallas_kernels._lb2_tile_lb`: children as (n, T)
    slabs with the parents on the lanes, the Johnson order read from SMEM)
    is bit-equal on every open child slot to the pair-blocked jnp oracle
    `_lb2_chunk` and to the host bound of the child (`lb2_bound`), through
    the standalone kernel (``eval``), `megakernel_lb2_bounds` (``mega``)
    and the Megacore pass (``stream``), at tiles of 64 and 128, with the
    tables staged through SMEM one pair group at a time.  limit1 runs from
    -1 to n-2, the pair tables are padded to the pair group, and the tiles
    hold rows that are not permutations (and pad rows) whose bounds nobody
    reads."""
    from tpu_tree_search.ops import megakernel
    from tpu_tree_search.problems.pfsp import bounds as PB

    monkeypatch.setenv("TTS_TILE_LB2", str(tile))
    prob = _lb2_lane_problem(shape)
    t = prob.device_tables()
    n = prob.jobs
    P = t.pairs.shape[0]
    rng = np.random.default_rng(len(shape) * 100 + tile)
    B = 2 * tile if entry == "stream" else 2 * tile - 8  # a padded tile
    prmu = np.stack([rng.permutation(n) for _ in range(B)]).astype(np.int32)
    limit1 = (np.arange(B) % n - 1).astype(np.int32)  # -1 .. n-2
    bad = rng.choice(B, 6, replace=False)  # rows that are no permutation
    prmu[bad] = rng.integers(0, n, size=(6, n))
    pd, ld = jnp.asarray(prmu), jnp.asarray(limit1)
    if entry == "eval":
        got = pallas_kernels.pfsp_lb2_bounds(pd, ld, t, interpret=True)
    elif entry == "mega":
        got = megakernel.megakernel_lb2_bounds(pd, ld, t, interpret=True)
    else:
        got = megakernel.streamed_eval_bounds(prob, pd, ld, mt=tile,
                                              interpret=True)
    got = np.asarray(got)
    want = np.asarray(pfsp_device._lb2_chunk(
        pd, ld, t.ptm_t, t.min_heads, t.min_tails,
        t.pairs, t.lags, t.johnson_schedules,
    ))
    valid = np.ones(B, bool)
    valid[bad] = False
    open_ = (np.arange(n)[None, :] > limit1[:, None]) & valid[:, None]
    assert np.array_equal(got[open_], want[open_])
    for i in np.flatnonzero(valid)[[0, 1, n // 2, n - 2]]:
        li = int(limit1[i])
        for k in range(li + 1, n):
            child = prmu[i].copy()
            child[li + 1], child[k] = child[k], child[li + 1]
            assert got[i, k] == PB.lb2_bound(
                prob.lb1_data, prob.lb2_data, child, li + 1, n, 10**9
            ), (i, k)


#: (instance, tile) of the prologue parity cases: ta014 (20x10) and ta001
#: (20x5) at the cycle's 128-wide tile, ta051 (50x20) at a 64-wide one.
_PROLOGUE_SHAPES = {"ta014-T128": (14, 128), "ta001-T128": (1, 128),
                    "ta051-T64": (51, 64)}
#: bound -> (the kernel's `_KERNEL_MODEL` name, its tile knob)
_PROLOGUE_KERNELS = {"lb1": ("lb1", "TTS_TILE_LB1"),
                     "lb1_d": ("lb1d", "TTS_TILE_LB1D"),
                     "lb2": ("lb2", "TTS_TILE_LB2"),
                     "lb2_self": ("lb2self", "TTS_TILE_LB2SELF")}


@pytest.mark.parametrize("shape", sorted(_PROLOGUE_SHAPES))
@pytest.mark.parametrize("bound", sorted(_PROLOGUE_KERNELS))
def test_bound_prologue_matches_oracles(bound, shape, monkeypatch):
    """The lane-dense tile prologue (`pallas_kernels._tile_parent_state`:
    the tile's parents on the lanes, the times gathered by selects, the
    fronts scanned on (1, T) rows) under each PFSP bound kernel, at the
    tile the case pins, bit-equal to the jnp oracles `_lb1_chunk`,
    `_lb1_d_chunk`, `_lb2_chunk` and `_lb2_self_chunk`.  Every tile holds
    rows at the root (limit1 = -1; the self bound's rows are children, so
    0 there), at mid-depth and at the leaf level (n - 2), and rows that
    are no permutation; the last tile is padded.  lb1 and lb1_d agree on
    every slot of every row; the lb2 bounds on the open slots of the real
    nodes (the others are garbage by contract)."""
    inst, tile = _PROLOGUE_SHAPES[shape]
    kernel, knob = _PROLOGUE_KERNELS[bound]
    monkeypatch.setenv(knob, str(tile))
    prob = PFSPProblem(inst=inst, lb="lb2", ub=1)
    t = prob.device_tables()
    n, m, P = prob.jobs, prob.machines, t.pairs.shape[0]
    pg = 1 if bound == "lb2_self" else None  # its matmul body fits at T
    assert pallas_kernels.effective_tile(kernel, n, m, P,
                                         pair_group=pg) == tile
    rng = np.random.default_rng(inst * 1000 + tile)
    B = 2 * tile - 8
    prmu = np.stack([rng.permutation(n) for _ in range(B)]).astype(np.int32)
    lo = 0 if bound == "lb2_self" else -1
    limit1 = (lo + np.arange(B) % (n - 1 - lo)).astype(np.int32)
    bad = rng.choice(B, 6, replace=False)
    prmu[bad] = rng.integers(0, n, size=(6, n))
    pd, ld = jnp.asarray(prmu), jnp.asarray(limit1)
    inst_tables = (t.ptm_t, t.min_heads, t.min_tails)
    johnson = (t.pairs, t.lags, t.johnson_schedules)
    if bound == "lb1":
        got = pallas_kernels.pfsp_lb1_bounds(pd, ld, *inst_tables,
                                             interpret=True)
        want = pfsp_device._lb1_chunk(pd, ld, *inst_tables)
    elif bound == "lb1_d":
        got = pallas_kernels.pfsp_lb1_d_bounds(pd, ld, *inst_tables,
                                               interpret=True)
        want = pfsp_device._lb1_d_chunk(pd, ld, *inst_tables)
    elif bound == "lb2":
        got = pallas_kernels.pfsp_lb2_bounds(pd, ld, t, interpret=True)
        want = pfsp_device._lb2_chunk(pd, ld, *inst_tables, *johnson)
    else:
        got = pallas_kernels.pfsp_lb2_self_bounds(pd, ld, B, t,
                                                  interpret=True,
                                                  pair_group=pg)
        want = pfsp_device._lb2_self_chunk(pd, ld, *inst_tables, *johnson)
    got, want = np.asarray(got), np.asarray(want)
    if bound in ("lb1", "lb1_d"):
        assert np.array_equal(got, want)
        return
    real = np.ones(B, bool)
    real[bad] = False
    if bound == "lb2_self":
        assert np.array_equal(got[real], want[real])
        return
    open_ = (np.arange(n)[None, :] > limit1[:, None]) & real[:, None]
    assert np.array_equal(got[open_], want[open_])


def test_use_pallas_is_off_on_cpu(monkeypatch):
    monkeypatch.delenv("TTS_PALLAS", raising=False)
    assert pallas_kernels.use_pallas() is False  # tests run on the CPU backend


def test_use_pallas_routes_per_device():
    """A CPU target device must never route to Pallas, whatever the default
    backend is (the round-2 dryrun failure mode)."""
    import jax

    cpus = jax.devices("cpu")
    assert pallas_kernels.use_pallas(cpus[0]) is False


@pytest.mark.parametrize(
    "lb,inst,jobs,machines",
    [
        ("lb1", 31, 50, 5),     # ta031 class
        ("lb1", 61, 100, 5),    # ta061 class
        ("lb1", 91, 200, 10),   # ta091 class
        ("lb1", 111, 500, 20),  # ta111 class — the reference's largest
        ("lb1_d", 31, 50, 5),
        ("lb2", 31, 50, 5),
        ("lb2", 61, 100, 5),
    ],
)
def test_large_instance_kernels_match_oracle(lb, inst, jobs, machines):
    """Large Taillard sizes (50-100 jobs) must stay on the Pallas path:
    _auto_tile shrinks the batch tile so the VMEM-resident pass still fits
    (the reference covers these by rebuilding with bigger params,
    `Taillard.chpl:29-52`). Full-size n with a small batch keeps interpret
    mode tractable on CPU."""
    rng = np.random.default_rng(5)
    prob = PFSPProblem(inst=inst, lb=lb, ub=1)
    assert prob.jobs == jobs and prob.machines == machines
    t = pfsp_device.PFSPDeviceTables(prob.lb1_data, prob.lb2_data)
    B = 24 if jobs <= 100 else 8  # interpret mode: keep 200/500-job cheap
    prmu = np.stack([rng.permutation(jobs).astype(np.int32) for _ in range(B)])
    limit1 = rng.integers(-1, jobs - 1, B).astype(np.int32)
    pd, ld = jnp.asarray(prmu), jnp.asarray(limit1)
    if lb == "lb1":
        oracle = pfsp_device._lb1_chunk(pd, ld, t.ptm_t, t.min_heads, t.min_tails)
        got = pallas_kernels.pfsp_lb1_bounds(
            pd, ld, t.ptm_t, t.min_heads, t.min_tails, interpret=True
        )
    elif lb == "lb1_d":
        oracle = pfsp_device._lb1_d_chunk(pd, ld, t.ptm_t, t.min_heads, t.min_tails)
        got = pallas_kernels.pfsp_lb1_d_bounds(
            pd, ld, t.ptm_t, t.min_heads, t.min_tails, interpret=True
        )
    else:
        oracle = pfsp_device._lb2_chunk(
            pd, ld, t.ptm_t, t.min_heads, t.min_tails,
            t.pairs, t.lags, t.johnson_schedules,
        )
        got = pallas_kernels.pfsp_lb2_bounds(pd, ld, t, interpret=True)
    k = np.arange(jobs)[None, :]
    open_ = k >= limit1[:, None] + 1
    assert np.array_equal(np.asarray(oracle)[open_], np.asarray(got)[open_])


def test_auto_tile_shrinks_for_large_instances():
    """The VMEM model must shrink tiles monotonically with job count and
    never go below the floor of 8."""
    at = pallas_kernels._auto_tile
    assert at(20, 10, 64) == 64          # ta014: default fits
    assert at(500, 20, 64) >= 8          # ta111: must shrink but stay valid
    assert at(500, 20, 64) < 64
    sizes = [at(n, 20, 256) for n in (20, 50, 100, 200, 500)]
    assert sizes == sorted(sizes, reverse=True)
    # Non-power-of-two overrides stay sublane-aligned and above the floor.
    for n in (20, 100, 500):
        t = at(n, 20, 100)
        assert t >= 8 and (t == 100 or t % 8 == 0)


@pytest.mark.parametrize("bf16", [False, True])
@pytest.mark.parametrize(
    "inst,jobs,machines",
    [(14, 20, 10), (21, 20, 20), (1, 12, 5)],
)
def test_lb1_d_bounds_match_oracle(inst, jobs, machines, bf16):
    rng = np.random.default_rng(11)
    if jobs == 20:
        prob = PFSPProblem(inst=inst, lb="lb1_d", ub=1)
    else:
        ptm = taillard.reduced_instance(inst, jobs=jobs, machines=machines)
        prob = PFSPProblem(lb="lb1_d", ub=0, p_times=ptm)
    t = pfsp_device.PFSPDeviceTables(prob.lb1_data, prob.lb2_data)
    B = 300
    prmu = np.stack([rng.permutation(jobs).astype(np.int32) for _ in range(B)])
    limit1 = rng.integers(-1, jobs - 1, B).astype(np.int32)
    oracle = pfsp_device._lb1_d_chunk(
        jnp.asarray(prmu), jnp.asarray(limit1), t.ptm_t, t.min_heads,
        t.min_tails, bf16=bf16,
    )
    got = pallas_kernels.pfsp_lb1_d_bounds(
        jnp.asarray(prmu), jnp.asarray(limit1), t.ptm_t, t.min_heads, t.min_tails,
        interpret=True,
    )
    assert np.array_equal(np.asarray(oracle), np.asarray(got))


def _random_nodes(rng, jobs, R, min_limit1=0):
    prmu = np.stack([rng.permutation(jobs).astype(np.int32) for _ in range(R)])
    limit1 = rng.integers(min_limit1, jobs - 1, R).astype(np.int32)
    return prmu, limit1


def test_lb2_self_chunk_matches_host_oracle():
    """The vectorized self bound (a node's OWN Johnson bound — the staged
    evaluator's second stage) must equal the NumPy host oracle
    (`lb2_bound`, c_bound_johnson.c:239-254) node by node."""
    from tpu_tree_search.problems.pfsp import bounds as B

    rng = np.random.default_rng(17)
    jobs = 8
    ptm = taillard.reduced_instance(14, jobs=jobs, machines=5)
    prob = PFSPProblem(lb="lb2", ub=0, p_times=ptm)
    t = pfsp_device.PFSPDeviceTables(prob.lb1_data, prob.lb2_data)
    prmu, limit1 = _random_nodes(rng, jobs, 64)
    got = np.asarray(pfsp_device._lb2_self_chunk(
        jnp.asarray(prmu), jnp.asarray(limit1), t.ptm_t, t.min_heads,
        t.min_tails, t.pairs, t.lags, t.johnson_schedules,
    ))
    for r in range(64):
        want = B.lb2_bound(
            prob.lb1_data, prob.lb2_data, prmu[r], int(limit1[r]), jobs, 10**9
        )
        assert got[r] == want, (r, got[r], want)


def test_lb2_self_kernel_matches_chunk_with_gating():
    """Pallas self kernel (interpret mode) vs the jnp self chunk on the
    active prefix; rows beyond n_active live in skipped tiles and are
    unconstrained."""
    rng = np.random.default_rng(23)
    prob = PFSPProblem(inst=14, lb="lb2", ub=1)
    jobs = prob.jobs
    t = pfsp_device.PFSPDeviceTables(prob.lb1_data, prob.lb2_data)
    R = 600  # not a tile multiple: exercises padding
    prmu, limit1 = _random_nodes(rng, jobs, R)
    oracle = np.asarray(pfsp_device._lb2_self_chunk(
        jnp.asarray(prmu), jnp.asarray(limit1), t.ptm_t, t.min_heads,
        t.min_tails, t.pairs, t.lags, t.johnson_schedules,
    ))
    for n_active in (R, 97):
        got = np.asarray(pallas_kernels.pfsp_lb2_self_bounds(
            jnp.asarray(prmu), jnp.asarray(limit1), n_active, t,
            interpret=True,
        ))
        assert np.array_equal(got[:n_active], oracle[:n_active])


def test_lb2_dominates_lb1_on_device_evaluators():
    """The staging invariant: the device lb2 child bounds are >= the device
    lb1 child bounds pointwise (every machine's lb1 term is the one-machine
    term of some Johnson pair), so skipping lb2 where lb1 >= best is exact."""
    rng = np.random.default_rng(29)
    for inst, jobs in ((14, 20), (1, 12)):
        if jobs == 20:
            prob = PFSPProblem(inst=inst, lb="lb2", ub=1)
        else:
            ptm = taillard.reduced_instance(inst, jobs=jobs, machines=5)
            prob = PFSPProblem(lb="lb2", ub=0, p_times=ptm)
        t = pfsp_device.PFSPDeviceTables(prob.lb1_data, prob.lb2_data)
        prmu, limit1 = _random_nodes(rng, jobs, 128)
        pd, ld = jnp.asarray(prmu), jnp.asarray(limit1)
        b1 = np.asarray(pfsp_device._lb1_chunk(
            pd, ld, t.ptm_t, t.min_heads, t.min_tails
        ))
        b2 = np.asarray(pfsp_device._lb2_chunk(
            pd, ld, t.ptm_t, t.min_heads, t.min_tails,
            t.pairs, t.lags, t.johnson_schedules,
        ))
        open_ = np.arange(jobs)[None, :] >= (limit1[:, None] + 1)
        assert np.all(b2[open_] >= b1[open_])


def test_lb1_family_demoted_to_jnp_by_default(monkeypatch):
    """The documented lb1 routing decision (docs/HW_VALIDATION.md): even on
    a TPU target the lb1/lb1_d evaluators default to the fused jnp path
    (measured ~7x the Pallas kernel in-kernel), and TTS_PALLAS=force is
    the only spelling that re-arms the kernels for the A/B."""
    prob = PFSPProblem(inst=14, lb="lb1", ub=1)
    t = pfsp_device.PFSPDeviceTables(prob.lb1_data, prob.lb2_data)
    rng = np.random.default_rng(47)
    prmu, limit1 = _random_nodes(rng, prob.jobs, 16)
    pd, ld = jnp.asarray(prmu), jnp.asarray(limit1)

    monkeypatch.delenv("TTS_PALLAS", raising=False)
    monkeypatch.delenv("TTS_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(pallas_kernels, "use_pallas", lambda d=None: True)
    monkeypatch.setattr(
        pallas_kernels, "pfsp_lb1_bounds",
        lambda *a, **k: pytest.fail("lb1 kernel dispatched without force"),
    )
    monkeypatch.setattr(
        pallas_kernels, "pfsp_lb1_d_bounds",
        lambda *a, **k: pytest.fail("lb1_d kernel dispatched without force"),
    )
    oracle = np.asarray(pfsp_device._lb1_chunk(
        pd, ld, t.ptm_t, t.min_heads, t.min_tails
    ))
    got = np.asarray(pfsp_device.lb1_bounds(pd, ld, t))
    assert np.array_equal(got, oracle)
    assert pfsp_device.lb1_d_bounds(pd, ld, t) is not None

    sentinel = object()
    monkeypatch.setenv("TTS_PALLAS", "force")
    monkeypatch.setattr(
        pallas_kernels, "pfsp_lb1_bounds", lambda *a, **k: sentinel
    )
    assert pfsp_device.lb1_bounds(pd, ld, t) is sentinel
    # The force spelling is part of the routing token: flipping it must
    # rebuild cached programs, never reuse a stale one.
    tok_forced = pfsp_device.routing_cache_token(prob)
    monkeypatch.setenv("TTS_PALLAS", "1")
    assert pfsp_device.routing_cache_token(prob) != tok_forced


def test_lb2_family_kill_switch_spares_lb1(monkeypatch):
    """TTS_PALLAS_LB2=0 (bench.py's fallback when only the lb2-family probe
    fails) must route the lb2 child/self kernels AND auto-staging to the
    jnp path while the (force-armed) lb1 family keeps its Pallas route —
    an lb2 compile failure may never cost the lb1 kernel A/B (VERDICT r4
    weak #6). The lb1 family is demoted to jnp by DEFAULT now
    (docs/HW_VALIDATION.md decision record); TTS_PALLAS=force re-arms it,
    which is what this test pins alongside the kill switch."""
    prob = PFSPProblem(inst=14, lb="lb2", ub=1)
    t = pfsp_device.PFSPDeviceTables(prob.lb1_data, prob.lb2_data)
    rng = np.random.default_rng(43)
    prmu, limit1 = _random_nodes(rng, prob.jobs, 16)
    pd, ld = jnp.asarray(prmu), jnp.asarray(limit1)

    monkeypatch.setenv("TTS_PALLAS_LB2", "0")
    monkeypatch.setenv("TTS_PALLAS", "force")
    monkeypatch.setattr(pallas_kernels, "use_pallas", lambda d=None: True)
    monkeypatch.setattr(
        pallas_kernels, "pfsp_lb2_bounds",
        lambda *a, **k: pytest.fail("lb2 kernel dispatched despite =0"),
    )
    monkeypatch.setattr(
        pallas_kernels, "pfsp_lb2_self_bounds",
        lambda *a, **k: pytest.fail("lb2 self kernel dispatched despite =0"),
    )
    sentinel = object()
    monkeypatch.setattr(
        pallas_kernels, "pfsp_lb1_bounds", lambda *a, **k: sentinel
    )
    oracle = np.asarray(pfsp_device._lb2_chunk(
        pd, ld, t.ptm_t, t.min_heads, t.min_tails,
        t.pairs, t.lags, t.johnson_schedules,
    ))
    got = np.asarray(pfsp_device.lb2_bounds(pd, ld, t))  # jnp path
    open_ = np.arange(prob.jobs)[None, :] >= (limit1[:, None] + 1)
    assert np.array_equal(got[open_], oracle[open_])
    assert pfsp_device.lb2_self_bounds(pd, jnp.maximum(ld, 0), 16, t) is not None
    assert not pfsp_device.lb2_staged_enabled(None, prob.jobs)  # auto -> off
    assert pfsp_device.lb1_bounds(pd, ld, t) is sentinel  # lb1 unaffected


def test_lb2_self_mp_shard_maxes_combine_to_full():
    """The mp-sharded self bound's per-shard pieces (sliced ordered tables
    through the Pallas kernel, interpret mode) must pmax-combine to exactly
    the full-pair self bound — including a pair count that needs padding
    (max over duplicated pair 0 is idempotent)."""
    rng = np.random.default_rng(37)
    jobs = 8
    ptm = taillard.reduced_instance(14, jobs=jobs, machines=5)
    prob = PFSPProblem(lb="lb2", ub=0, p_times=ptm)
    t = pfsp_device.PFSPDeviceTables(prob.lb1_data, prob.lb2_data)
    R = 64
    prmu, limit1 = _random_nodes(rng, jobs, R)
    pd, ld = jnp.asarray(prmu), jnp.asarray(limit1)
    full = np.asarray(pfsp_device._lb2_self_chunk(
        pd, ld, t.ptm_t, t.min_heads, t.min_tails,
        t.pairs, t.lags, t.johnson_schedules,
    ))
    for mp_size in (2, 3):  # P=10 pairs: 3 forces padding to 12
        P_pad = -(-t.pairs.shape[0] // mp_size) * mp_size
        P_local = P_pad // mp_size
        ordered = t.johnson_ordered_mp(mp_size)
        parts = []
        for shard in range(mp_size):
            sliced = pfsp_device._OrderedSlice(
                ordered, shard * P_local, P_local
            )
            parts.append(np.asarray(pallas_kernels.pfsp_lb2_self_bounds_tables(
                pd, ld, R, t.ptm_t, sliced, interpret=True,
                bf16=t.exact_bf16,
            )))
        assert np.array_equal(np.maximum.reduce(parts), full), mp_size


def test_lb2_staged_mp_matches_full_inside_shard_map():
    """lb2_bounds_staged with mp_axis set, run inside a REAL shard_map over
    an mp-only mesh (2 CPU devices): the compaction runs per replica, the
    self bound slices its pair block per shard and pmax-combines — results
    must equal the full child evaluator on every candidate slot, on every
    replica."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    rng = np.random.default_rng(41)
    jobs = 8
    ptm = taillard.reduced_instance(14, jobs=jobs, machines=5)
    prob = PFSPProblem(lb="lb2", ub=0, p_times=ptm)
    t = pfsp_device.PFSPDeviceTables(prob.lb1_data, prob.lb2_data)
    B = 32
    prmu, limit1 = _random_nodes(rng, jobs, B)
    pd, ld = jnp.asarray(prmu), jnp.asarray(limit1)
    full = np.asarray(pfsp_device._lb2_chunk(
        pd, ld, t.ptm_t, t.min_heads, t.min_tails,
        t.pairs, t.lags, t.johnson_schedules,
    ))
    open_ = np.arange(jobs)[None, :] >= (limit1[:, None] + 1)
    leaf = open_ & ((limit1[:, None] + 2) == jobs)
    cand = open_ & ~leaf & (rng.random((B, jobs)) < 0.5)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("mp",))

    def body(pd, ld, cd):
        return pfsp_device.lb2_bounds_staged(
            pd, ld, cd, t, mp_axis="mp", mp_size=2
        )[None]

    got = np.asarray(jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P()), out_specs=P("mp"),
    ))(pd, ld, jnp.asarray(cand)))
    # Every mp replica computed identical full-pair bounds (lockstep).
    assert np.array_equal(got[0][cand], full[cand])
    assert np.array_equal(got[1][cand], full[cand])


def test_lb2_staged_bounds_match_full_on_candidates():
    """lb2_bounds_staged (compaction + self bound + scatter) equals the full
    child evaluator everywhere the candidate mask is set."""
    rng = np.random.default_rng(31)
    prob = PFSPProblem(inst=14, lb="lb2", ub=1)
    jobs = prob.jobs
    t = pfsp_device.PFSPDeviceTables(prob.lb1_data, prob.lb2_data)
    B = 48
    prmu, limit1 = _random_nodes(rng, jobs, B)
    pd, ld = jnp.asarray(prmu), jnp.asarray(limit1)
    full = np.asarray(pfsp_device._lb2_chunk(
        pd, ld, t.ptm_t, t.min_heads, t.min_tails,
        t.pairs, t.lags, t.johnson_schedules,
    ))
    open_ = np.arange(jobs)[None, :] >= (limit1[:, None] + 1)
    leaf = open_ & ((limit1[:, None] + 2) == jobs)
    cand = open_ & ~leaf & (rng.random((B, jobs)) < 0.4)
    got = np.asarray(pfsp_device.lb2_bounds_staged(
        pd, ld, jnp.asarray(cand), t
    ))
    assert np.array_equal(got[cand], full[cand])


@pytest.mark.parametrize("inst,staged,expect", [
    (14, "auto", "tpu"),   # staged lb2: lb1 prefilter + self kernel
    (14, "0", "tpu"),      # single-pass lb2 kernel
    (91, "0", "jnp"),      # 200 jobs: past the lb2 kernels' n <= 100 cap
])
def test_resident_reports_the_lb2_route_that_runs(monkeypatch, inst, staged,
                                                  expect):
    """The resident program's routing report follows the bound
    functions' own predicates, shape gates included: a shape the lb2
    kernels refuse reports the jnp route that runs, not Pallas."""
    import jax

    from tpu_tree_search.engine.resident import (_make_program,
                                                 resolve_capacity)

    monkeypatch.setenv("TTS_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("TTS_MEGAKERNEL", "0")
    monkeypatch.setenv("TTS_LB2_STAGED", staged)
    prob = PFSPProblem(inst=inst, lb="lb2", ub=1)
    capacity, M = resolve_capacity(prob, 64, None)
    prog = _make_program(prob, 5, M, 4, capacity, jax.devices()[0])
    assert prog.kernel_backend == expect
    assert prog.kernel_interpret is (expect != "jnp")
