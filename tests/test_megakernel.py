"""One-kernel resident cycle (ops/megakernel.py, TTS_MEGAKERNEL).

Interpret-mode bit-identity of the fused pop->eval->prune->compact->push
Pallas cycle against the fused-jnp resident across problem families,
compact modes, checkpoint cuts, and the batched engine; the lb2
bf16-exactness gate (bit-parity vs the f32 pair-blocked oracle on real
Taillard instances, refusal when the gate fails); and the program-cache
keying of the knob.  On CPU ``TTS_MEGAKERNEL=force`` arms the kernel in
Pallas interpret mode — same program structure, reference semantics —
so every claim here is about the real fused cycle body.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_tree_search.engine.batched import batched_search
from tpu_tree_search.engine.resident import resident_search
from tpu_tree_search.engine.sequential import sequential_search
from tpu_tree_search.ops import megakernel as MK
from tpu_tree_search.ops import pfsp_device as PD
from tpu_tree_search.problems import NQueensProblem, PFSPProblem
from tpu_tree_search.problems.base import INF_BOUND


def _ptm(seed: int, jobs: int = 7, machines: int = 5) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.ascontiguousarray(
        rng.integers(1, 100, size=(machines, jobs)).astype(np.int32)
    )


def _mk_problem(family: str):
    if family == "nqueens":
        return lambda: NQueensProblem(N=8)
    ptm = _ptm(311)
    lb = {"pfsp-lb1": "lb1", "pfsp-lb2": "lb2"}[family]
    return lambda: PFSPProblem(lb=lb, ub=0, p_times=ptm)


def _counts(res):
    return (res.explored_tree, res.explored_sol, res.best)


# -- force-vs-off bit identity across the family x compact matrix ----------

@pytest.mark.parametrize("family,compact", [
    ("nqueens", "auto"),
    ("nqueens", "dense"),
    ("nqueens", "scatter"),
    ("pfsp-lb1", "auto"),
    ("pfsp-lb1", "dense"),
    ("pfsp-lb1", "sort"),
    ("pfsp-lb2", "auto"),
    ("pfsp-lb2", "dense"),
    ("pfsp-lb2", "search"),
])
def test_force_matches_off_bit_identical(family, compact, monkeypatch):
    """The armed interpret-mode cycle lands the SAME explored_tree /
    explored_sol / best as the fused-jnp resident under every survivor
    compact mode (the off baseline varies; the fused cycle must not)."""
    monkeypatch.setenv("TTS_COMPACT", compact)
    mk = _mk_problem(family)
    monkeypatch.setenv("TTS_MEGAKERNEL", "0")
    off = resident_search(mk(), m=4, M=64, K=8)
    assert off.megakernel == "off"
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    on = resident_search(mk(), m=4, M=64, K=8)
    assert on.megakernel == "on", on.megakernel_reason
    assert _counts(on) == _counts(off)


def test_force_matches_sequential_goldens(monkeypatch):
    """Armed counts against the host-recursion goldens directly (not just
    the off resident) — catches an error common to both device paths."""
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    for family in ("nqueens", "pfsp-lb1", "pfsp-lb2"):
        mk = _mk_problem(family)
        opt = sequential_search(mk()).best
        seq = sequential_search(mk(), initial_best=opt)
        res = resident_search(mk(), m=4, M=64, K=8, initial_best=opt)
        assert res.megakernel == "on", res.megakernel_reason
        assert _counts(res) == _counts(seq)


# -- the cycle against the sequential oracle, across the routing axes -------

def _reduced(lb: str):
    from tpu_tree_search.problems.pfsp import taillard

    ptm = taillard.reduced_instance(14, jobs=8, machines=5)
    return lambda: PFSPProblem(lb=lb, ub=0, p_times=ptm)


def _armed_matches_sequential(mk, M: int = 64):
    opt = sequential_search(mk()).best
    seq = sequential_search(mk(), initial_best=opt)
    res = resident_search(mk(), m=4, M=M, K=8, initial_best=opt)
    assert res.megakernel == "on", res.megakernel_reason
    assert res.kernel_backend == "tpu" and res.kernel_interpret
    assert _counts(res) == _counts(seq)


@pytest.mark.parametrize("compact", ["auto", "dense", "scatter"])
@pytest.mark.parametrize("narrow", ["0", "auto"])
def test_cycle_lb1_matches_sequential(compact, narrow, monkeypatch):
    """The armed lb1 cycle, interpreted, on a reduced ta014 (8x5): the
    resident search lands the sequential tier's counts and optimum under
    every survivor compact mode the off path would take and with narrow
    node storage on and off."""
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    monkeypatch.setenv("TTS_COMPACT", compact)
    monkeypatch.setenv("TTS_NARROW", narrow)
    _armed_matches_sequential(_reduced("lb1"))


@pytest.mark.parametrize("pairblock", ["1", "auto"])
def test_cycle_lb2_matches_sequential(pairblock, monkeypatch):
    """The armed lb2 cycle at pair group 1 and at the policy's group."""
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    monkeypatch.setenv("TTS_LB2_PAIRBLOCK", pairblock)
    _armed_matches_sequential(_reduced("lb2"))


def test_cycle_nqueens_matches_sequential(monkeypatch):
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    _armed_matches_sequential(lambda: NQueensProblem(N=9), M=256)


# -- checkpoint cuts + the batched engine ----------------------------------

def _trajectory(mk, path):
    """Cut after every dispatch (max_steps=1, K=1) and resume until done;
    the per-slice counter trajectory is the strictest observable."""
    out = []
    res = resident_search(mk(), m=4, M=64, K=1, max_steps=1,
                          checkpoint_path=path)
    out.append(_counts(res) + (res.complete,))
    for _ in range(300):
        if res.complete:
            break
        res = resident_search(mk(), m=4, M=64, K=1, max_steps=1,
                              resume_from=path, checkpoint_path=path)
        out.append(_counts(res) + (res.complete,))
    assert res.complete
    return out


@pytest.mark.slow  # ~70 cut/resume program slices; CI tests-megakernel runs it unfiltered
def test_checkpoint_cut_resume_trajectory_matches(tmp_path, monkeypatch):
    """The armed cycle composes with checkpoint cuts: the full cut/resume
    trajectory (counters at EVERY slice boundary) is identical to the off
    build's — the megakernel changes where the work happens, never which
    state crosses a dispatch boundary."""
    ptm = _ptm(631, jobs=8)

    def mk():
        return PFSPProblem(lb="lb1", ub=0, p_times=ptm)

    monkeypatch.setenv("TTS_MEGAKERNEL", "0")
    t_off = _trajectory(mk, str(tmp_path / "off.ckpt"))
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    t_on = _trajectory(mk, str(tmp_path / "on.ckpt"))
    assert t_on == t_off


@pytest.mark.parametrize("lb", ["lb1", "lb2"])
def test_batched_engine_armed_matches_sequential(lb, monkeypatch):
    """B=2 batched program with the fused cycle armed per slot: every
    tenant lands the sequential goldens and reports the armed state."""
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    ptm = _ptm(911)

    def mk():
        return PFSPProblem(lb=lb, ub=0, p_times=ptm)

    opt = sequential_search(mk()).best
    seq = sequential_search(mk(), initial_best=opt)
    for res in batched_search(mk(), n_jobs=3, B=2, m=4, M=64, K=8,
                              initial_best=opt):
        assert res.megakernel == "on", res.megakernel_reason
        assert _counts(res) == _counts(seq)


def test_guard_green_armed(monkeypatch):
    """TTS_GUARD=1 runtime invariant checks stay green with the fused
    cycle armed (a guard trip raises)."""
    monkeypatch.setenv("TTS_GUARD", "1")
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    mk = _mk_problem("pfsp-lb1")
    opt = sequential_search(mk()).best
    seq = sequential_search(mk(), initial_best=opt)
    res = resident_search(mk(), m=4, M=64, K=8, initial_best=opt)
    assert res.megakernel == "on"
    assert _counts(res) == _counts(seq)


# -- the lb2 bf16-exactness gate -------------------------------------------

@pytest.mark.parametrize("inst", [14, 21])
def test_lb2_bf16_mxu_bit_parity_on_taillard(inst):
    """The lb2 bounds the megakernel arms with (``megakernel_lb2_bounds``:
    the int32 lane-dense tile) are BIT-equal to the f32 pair-blocked
    oracle (`pfsp_device._lb2_chunk`) on ta014/ta021 nodes, which pass
    `resolve`'s exactness gate — on open slots (closed slots carry garbage
    both engines mask)."""
    prob = PFSPProblem(inst=inst, lb="lb2", ub=1)
    t = prob.device_tables()
    assert t.exact_bf16  # the gate resolve() checks before arming
    n = prob.jobs
    rng = np.random.default_rng(5 + inst)
    rows = 32
    prmu = np.stack([rng.permutation(n) for _ in range(rows)]).astype(np.int32)
    lim = rng.integers(-1, n - 2, size=rows).astype(np.int32)
    got = np.asarray(MK.megakernel_lb2_bounds(
        jnp.asarray(prmu), jnp.asarray(lim), t, interpret=True))
    pb = PD.lb2_pairblock(t.pairs.shape[0], n)
    want = np.asarray(PD._lb2_chunk(
        jnp.asarray(prmu), jnp.asarray(lim), t.ptm_t, t.min_heads,
        t.min_tails, t.pairs, t.lags, t.johnson_schedules, pairblock=pb))
    open_ = np.arange(n)[None, :] > lim[:, None]
    np.testing.assert_array_equal(got[open_], want[open_])


def test_lb2_bf16_gate_refuses_and_falls_back(monkeypatch):
    """Processing times >= 256 break bf16 exactness: even under force the
    resolver refuses, the run falls back to the fused-jnp resident
    bit-correct, and the SearchResult records why."""
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    rng = np.random.default_rng(41)
    ptm = np.ascontiguousarray(
        rng.integers(200, 400, size=(4, 7)).astype(np.int32))
    ptm[0, 0] = 300  # guarantee the gate fails

    def mk():
        return PFSPProblem(lb="lb2", ub=0, p_times=ptm)

    opt = sequential_search(mk()).best
    seq = sequential_search(mk(), initial_best=opt)
    res = resident_search(mk(), m=4, M=64, K=8, initial_best=opt)
    assert res.megakernel == "off"
    assert res.megakernel_reason and "bf16" in res.megakernel_reason
    assert _counts(res) == _counts(seq)


def test_lb2_decision_state(monkeypatch):
    """An lb2 decision's state is ``on`` when armed and ``off`` when the
    knob turns the cycle off, and the search result carries it."""
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    prob = PFSPProblem(inst=14, lb="lb2", ub=1)
    dec = MK.resolve(prob, 1024)
    assert (dec.enabled, dec.state) == (True, "on")
    res = resident_search(_mk_problem("pfsp-lb2")(), m=4, M=64, K=8)
    assert res.megakernel == "on"
    monkeypatch.setenv("TTS_MEGAKERNEL", "0")
    dec = MK.resolve(prob, 1024)
    assert (dec.enabled, dec.state) == (False, "off")


def test_lb2_lanes_contract_catches_a_matmul_in_the_pair_loop(monkeypatch):
    """`megakernel-lb2-lanes` holds on the armed lb2 cell of the audit and
    names a dot_general that a loop of the kernel body picks up."""
    from tpu_tree_search.analysis import contracts, program_audit
    from tpu_tree_search.ops import pallas_kernels as PK

    program_audit.load_contracts()
    cell = program_audit.Cell("pfsp-lb2", pairblock="auto",
                              megakernel="force")
    art = program_audit.trace_cell(cell)
    assert contracts.run_one("megakernel-lb2-lanes", art, cell) == []
    real = PK._lb2_tile_lb

    def tampered(*args, **kw):
        lb = real(*args, **kw)
        eye = jnp.eye(lb.shape[1], dtype=jnp.float32)
        return jax.lax.fori_loop(
            0, 2, lambda i, x: jnp.dot(x.astype(jnp.float32), eye)
            .astype(jnp.int32), lb)

    monkeypatch.setattr(PK, "_lb2_tile_lb", tampered)
    MK._lb2_push_call.cache_clear()
    try:
        art = program_audit.trace_cell(cell)
    finally:
        MK._lb2_push_call.cache_clear()
    msgs = contracts.run_one("megakernel-lb2-lanes", art, cell)
    assert any("dot_general" in m for m in msgs), msgs


@pytest.mark.parametrize("family", ["pfsp-lb1", "pfsp-lb2"])
def test_bound_lanes_contract_catches_a_one_hot_prologue(family, monkeypatch):
    """`megakernel-pfsp-bound-lanes` holds on the armed lb1 and lb2 cells
    of the audit and names the (Mt, n, n) one-hot that a prologue building
    one brings back into the kernel's bound region."""
    from tpu_tree_search.analysis import contracts, program_audit
    from tpu_tree_search.ops import pallas_kernels as PK

    program_audit.load_contracts()
    pb = "auto" if family == "pfsp-lb2" else None
    cell = program_audit.Cell(family, pairblock=pb, megakernel="force")
    name = "megakernel-pfsp-bound-lanes"
    art = program_audit.trace_cell(cell)
    assert contracts.run_one(name, art, cell) == []
    real = PK._tile_parent_state

    def one_hot(prmu, *args, **kw):
        s = real(prmu, *args, **kw)
        T, n = prmu.shape
        iota = jax.lax.broadcasted_iota(jnp.int32, (T, n, n), 2)
        hits = jnp.sum((iota == prmu[:, :, None]).astype(jnp.int32), axis=2)
        return s._replace(cf=[s.cf[0] + 0 * hits.T, *s.cf[1:]])

    monkeypatch.setattr(PK, "_tile_parent_state", one_hot)
    MK._lb1_push_call.cache_clear()
    MK._lb2_push_call.cache_clear()
    try:
        art = program_audit.trace_cell(cell)
    finally:
        MK._lb1_push_call.cache_clear()
        MK._lb2_push_call.cache_clear()
    msgs = contracts.run_one(name, art, cell)
    assert any("rank-3" in m for m in msgs), msgs


def test_family_refusal_lb1d(monkeypatch):
    """lb1_d has no in-kernel bound formulation: force refuses with a
    recorded reason and the search still lands the goldens."""
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    ptm = _ptm(311)

    def mk():
        return PFSPProblem(lb="lb1_d", ub=0, p_times=ptm)

    opt = sequential_search(mk()).best
    seq = sequential_search(mk(), initial_best=opt)
    res = resident_search(mk(), m=4, M=64, K=8, initial_best=opt)
    assert res.megakernel == "off"
    assert res.megakernel_reason
    assert _counts(res) == _counts(seq)


# -- program-cache keying ---------------------------------------------------

def test_knob_flip_rebuilds_and_reset_hits_cache(monkeypatch):
    """TTS_MEGAKERNEL is baked into the compiled step via the routing
    token: a flip rebuilds (distinct program objects), re-setting the
    original value hits the cache (same object)."""
    from tpu_tree_search.engine.resident import _make_program, resolve_capacity

    prob = NQueensProblem(N=8)
    dev = jax.devices()[0]
    monkeypatch.setenv("TTS_MEGAKERNEL", "0")
    capacity, M = resolve_capacity(prob, 64, None)
    a = _make_program(prob, 5, M, 4, capacity, dev)
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    b = _make_program(prob, 5, M, 4, capacity, dev)
    assert a is not b
    assert b.megakernel.enabled and not a.megakernel.enabled
    monkeypatch.setenv("TTS_MEGAKERNEL", "0")
    c = _make_program(prob, 5, M, 4, capacity, dev)
    assert c is a  # cache hit — off really is the same program


def test_resolver_refusals_record_reasons():
    """Direct resolver checks: the correctness refusals hold even under
    force and each records a reason string."""
    dev = jax.devices()[0]
    ptm = _ptm(311)
    lb2 = PFSPProblem(lb="lb2", ub=0, p_times=ptm)
    # mp pair-axis sharding: the fused cycle is single-shard.
    d = MK.resolve(lb2, 64, dev, mp_axis="mp", mp_size=2)
    assert not d.enabled and "mp" in d.reason
    # chunk width must keep the sublane tiling exact.
    d = MK.resolve(NQueensProblem(N=8), 60, dev)
    assert not d.enabled and d.reason


# -- the streamed/tiled grid (TTS_MEGAKERNEL_MT) ----------------------------

@pytest.mark.parametrize("family", ["nqueens", "pfsp-lb1", "pfsp-lb2"])
def test_tiled_force_matches_off_bit_identical(family, monkeypatch):
    """A forced Mt=16 at M=64 streams the pool through a 4-step grid —
    per-tile dense compaction plus the SMEM-carried cross-tile offset
    (and the two-phase incumbent fold on PFSP) must land counts
    bit-identical to the off build, and the SearchResult must record the
    resolved tile width and the tiled state."""
    mk = _mk_problem(family)
    monkeypatch.setenv("TTS_MEGAKERNEL", "0")
    off = resident_search(mk(), m=4, M=64, K=8)
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    monkeypatch.setenv("TTS_MEGAKERNEL_MT", "16")
    on = resident_search(mk(), m=4, M=64, K=8)
    assert on.megakernel == "on", on.megakernel_reason
    assert on.megakernel_mt == 16 and on.megakernel_tiled
    assert not off.megakernel_tiled and off.megakernel_mt is None
    assert _counts(on) == _counts(off)


@pytest.mark.slow  # ~70 cut/resume program slices; CI tests-megakernel runs it unfiltered
def test_tiled_checkpoint_cut_resume_trajectory_matches(tmp_path,
                                                        monkeypatch):
    """The streamed grid composes with checkpoint cuts: the full
    cut/resume counter trajectory under forced Mt=16 is identical to the
    off build's — the cross-tile carry lives and dies inside one cycle,
    never across a dispatch boundary."""
    ptm = _ptm(631, jobs=8)

    def mk():
        return PFSPProblem(lb="lb1", ub=0, p_times=ptm)

    monkeypatch.setenv("TTS_MEGAKERNEL", "0")
    t_off = _trajectory(mk, str(tmp_path / "off.ckpt"))
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    monkeypatch.setenv("TTS_MEGAKERNEL_MT", "16")
    t_on = _trajectory(mk, str(tmp_path / "tiled.ckpt"))
    assert t_on == t_off


def test_auto_window_arms_tiled_past_limit(monkeypatch):
    """The pool size that used to be the auto refusal boundary now arms
    TILED: past the single-tile window the resolver streams the pool at a
    resolved Mt (multiple of 8, divides M) instead of refusing; inside
    the window the original single-tile form is kept verbatim.  The TPU
    backend gate is patched on — this is a decision-policy fact, not an
    execution one."""
    monkeypatch.setattr(MK, "_native_kind", lambda device=None: True)
    prob = NQueensProblem(N=8)
    n = int(prob.child_slots)
    small = MK.resolve(prob, 64)
    assert small.enabled and small.auto
    assert small.grid == 1 and small.mt == 64
    M_big = 1 << 16
    assert M_big * n > MK.SMALL_M_LIMIT  # past the old refusal boundary
    d = MK.resolve(prob, M_big)
    assert d.enabled and d.auto, d.reason
    assert d.tiled and d.grid > 1
    assert d.mt % 8 == 0 and M_big % d.mt == 0
    assert d.grid == M_big // d.mt


def test_mt_misalignment_refuses_and_bad_value_raises(monkeypatch):
    """A tile width that does not divide M is a recorded refusal (the run
    falls back bit-correct), held even under force; a non-integer or
    non-positive width is an operator error and raises."""
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    monkeypatch.setenv("TTS_MEGAKERNEL_MT", "24")  # %8 ok, 64 % 24 != 0
    mk = _mk_problem("pfsp-lb1")
    opt = sequential_search(mk()).best
    seq = sequential_search(mk(), initial_best=opt)
    res = resident_search(mk(), m=4, M=64, K=8, initial_best=opt)
    assert res.megakernel == "off"
    assert res.megakernel_reason and "divide" in res.megakernel_reason
    assert _counts(res) == _counts(seq)
    for bad in ("abc", "0", "-8"):
        monkeypatch.setenv("TTS_MEGAKERNEL_MT", bad)
        with pytest.raises(ValueError):
            MK.megakernel_mt()


def test_mt_knob_flip_rebuilds_and_reset_hits_cache(monkeypatch):
    """TTS_MEGAKERNEL_MT rides the routing token: under force a pinned
    width builds a DISTINCT program (tiled vs single-tile cycle bodies),
    and unsetting it again hits the original cached program."""
    from tpu_tree_search.engine.resident import _make_program, resolve_capacity

    prob = NQueensProblem(N=8)
    dev = jax.devices()[0]
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    monkeypatch.delenv("TTS_MEGAKERNEL_MT", raising=False)
    capacity, M = resolve_capacity(prob, 64, None)
    a = _make_program(prob, 5, M, 4, capacity, dev)
    assert a.megakernel.enabled and a.megakernel.grid == 1
    monkeypatch.setenv("TTS_MEGAKERNEL_MT", "16")
    b = _make_program(prob, 5, M, 4, capacity, dev)
    assert a is not b
    assert b.megakernel.tiled and b.megakernel.mt == 16
    monkeypatch.delenv("TTS_MEGAKERNEL_MT", raising=False)
    c = _make_program(prob, 5, M, 4, capacity, dev)
    assert c is a  # cache hit — the unset-knob build really is the same


# -- the in-kernel push: pool layout and pool rows after every dispatch ----

@pytest.mark.parametrize("n,slot", [(4, (8, 16)), (8, (8, 16)),
                                    (15, (16, 8)), (20, (32, 4)),
                                    (64, (64, 2)), (65, (128, 1)),
                                    (130, (256, 1))])
def test_push_pack_slots(n, slot):
    """A node takes a power-of-two slot of at least 8 lanes, as many to a
    128-lane pool row as fit; past 64 values it takes whole rows."""
    assert MK.push_pack(n) == slot


def test_capacity_budget_charges_the_stored_value_row():
    """The default pool capacity's ~1 GiB budget charges a value row at
    the slot width the TPU cycle's pool stores it in (ta111: 500 int16
    jobs in a 512-lane slot, plus two int16 scalars), not at n."""
    from tpu_tree_search.engine.resident import resolve_capacity

    prob = PFSPProblem(inst=111, lb="lb1", ub=1)
    capacity, _ = resolve_capacity(prob, 1024, None)
    assert capacity == (1 << 30) // (512 * 2 + 4 + 2 * (2 + 4))


def _warm_frontier(prob, target: int, copies: int = 1):
    """A host warm-up frontier of about ``target`` nodes, or ``copies``
    copies of the root when ``target`` is 0."""
    from tpu_tree_search.engine.device import warmup
    from tpu_tree_search.pool import SoAPool

    if not target:
        return {k: np.repeat(v, copies, axis=0)
                for k, v in prob.root().items()}
    pool = SoAPool(prob.node_fields())
    pool.push_back_bulk(prob.root())
    warmup(prob, pool, INF_BOUND, target)
    return pool.as_batch()


def _trail(mk, env, monkeypatch, *, frontier, M: int = 64,
           steps: int = 6, poison: int | None = None,
           capacity: int = 8192):
    """Dispatch a resident program one cycle at a time (K=1) from
    ``frontier`` and record, after each dispatch, the counts and the pool
    rows [0, size) as nodes.  ``poison`` first fills every pool row at and
    past ``size`` with that value."""
    from tpu_tree_search.engine.resident import (_make_program, node_rows,
                                                 resolve_capacity)

    for k in ("TTS_MEGAKERNEL", "TTS_MEGAKERNEL_MT"):
        monkeypatch.delenv(k, raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    prob = mk()
    n = prob.child_slots
    capacity, M = resolve_capacity(prob, M, capacity)
    prog = _make_program(prob, 1, M, 1, capacity, jax.devices()[0])
    state = prog.init_state(frontier(prob), INF_BOUND)
    W = prog.row_width
    if poison is not None:
        size = int(state[2])
        vals = np.array(state[0])
        vals.reshape(-1, W)[size:] = poison  # every node slot past size
        state = (jnp.asarray(vals),
                 state[1].at[size:].set(poison)) + tuple(state[2:])
    trail = []
    for _ in range(steps):
        out = prog.step(state)
        tree, sol, cycles, size, best, _ = prog.read_scalars(out)
        state = prog.carry(out)
        vals = node_rows(np.asarray(state[0]), (n,), W)[:size]
        aux = np.asarray(state[1])[:size]
        trail.append(((tree, sol, best, size), vals, aux))
        if cycles == 0:
            break
    return prog, trail


def _assert_same_trail(on, off):
    assert len(on) == len(off)
    for (c_on, v_on, a_on), (c_off, v_off, a_off) in zip(on, off):
        assert c_on == c_off
        np.testing.assert_array_equal(v_on, v_off)
        np.testing.assert_array_equal(a_on, a_off)


@pytest.mark.parametrize("narrow", ["auto", "0"])
@pytest.mark.parametrize("mt", [None, "16"])
@pytest.mark.parametrize("family", ["nqueens", "pfsp-lb1", "pfsp-lb2"])
def test_push_pool_rows_match_off_every_dispatch(family, mt, narrow,
                                                 monkeypatch):
    """The kernel's push lands the pool rows [0, size) — values and aux —
    and tree/sol/best bit-identical to the fused-jnp resident after every
    dispatch, single-tile and on a G=4 streamed grid, with narrow host
    storage on and off."""
    monkeypatch.setenv("TTS_NARROW", narrow)
    mk = _mk_problem(family)
    frontier = lambda prob: _warm_frontier(prob, 70)  # noqa: E731
    _, off = _trail(mk, {"TTS_MEGAKERNEL": "0"}, monkeypatch,
                    frontier=frontier)
    env = {"TTS_MEGAKERNEL": "force"}
    if mt:
        env["TTS_MEGAKERNEL_MT"] = mt
    prog, on = _trail(mk, env, monkeypatch, frontier=frontier)
    assert prog.megakernel.enabled and prog.megakernel.tiled == bool(mt)
    assert prog.row_pack > 1  # nodes share a pool row
    _assert_same_trail(on, off)


@pytest.mark.parametrize("lb", ["lb1", "lb2"])
def test_ta014_cycle_matches_off_at_the_cells_tile(lb, monkeypatch):
    """ta014 (20x10, ub = the optimum) through the armed cycle at the
    benchmark cells' tile, Mt=128 (two tiles of M=256): after each of a few
    dispatches the pool rows, size, solution count and incumbent equal
    the fused-jnp resident's."""
    mk = lambda: PFSPProblem(inst=14, lb=lb, ub=1)  # noqa: E731
    frontier = lambda prob: _warm_frontier(prob, 300)  # noqa: E731
    run = dict(frontier=frontier, M=256, steps=3, capacity=1 << 15)
    _, off = _trail(mk, {"TTS_MEGAKERNEL": "0"}, monkeypatch, **run)
    prog, on = _trail(mk, {"TTS_MEGAKERNEL": "force",
                           "TTS_MEGAKERNEL_MT": "128"}, monkeypatch, **run)
    assert (prog.megakernel.mt, prog.megakernel.grid) == (128, 2)
    assert len(on) == 3 and on[-1][0][3] > 0  # three cycles, a live pool
    _assert_same_trail(on, off)


#: (frontier nodes, root copies, tile width, what the first cycle holds)
_PUSH_EDGES = {
    # One parent in a G=8 grid: every tile but the first pushes nothing.
    "zero-survivor-tiles": (0, 1, "8"),
    # 64 depth-0 parents: each of the four tiles fills all Mt*n = 128 rows.
    "full-tiles": (0, 64, "16"),
    # 67 roots: the pop leaves size 3, so tile 0's 256 survivors start 3
    # rows into a row tile and cross two chunk boundaries mid-tile.
    "chunk-crossing": (0, 67, "32"),
    # 1064 roots: the writes start at node 1000, 24 short of an aux tile.
    "aux-tile-crossing": (0, 1064, "32"),
}


@pytest.mark.parametrize("case", sorted(_PUSH_EDGES))
def test_push_edge_cases_match_off(case, monkeypatch):
    """The push's edges on N=8 (no pruning, so every safe child of a
    depth-0 parent survives): empty tiles, full tiles, a live prefix
    that crosses chunk boundaries inside a tile, and one that crosses
    an aux tile — pool rows and counts equal the off build's."""
    target, copies, mt = _PUSH_EDGES[case]
    mk = _mk_problem("nqueens")
    frontier = lambda prob: _warm_frontier(prob, target, copies)  # noqa: E731
    _, off = _trail(mk, {"TTS_MEGAKERNEL": "0"}, monkeypatch,
                    frontier=frontier, steps=3)
    prog, on = _trail(mk, {"TTS_MEGAKERNEL": "force",
                           "TTS_MEGAKERNEL_MT": mt},
                      monkeypatch, frontier=frontier, steps=3)
    assert prog.megakernel.grid == 64 // int(mt)
    _assert_same_trail(on, off)


@pytest.mark.parametrize("family", ["nqueens", "pfsp-lb1", "pfsp-lb2"])
def test_push_ordering_poisoned_headroom(family, monkeypatch):
    """The one new way to corrupt the pool is a tile's chunk landing after
    the next tile's: poison every row past size, run one cycle on a G=4
    grid (the interpret mode lands a DMA only when it is waited on), and
    every row in [old size, new size) must equal the stitch path's row."""
    mk = _mk_problem(family)
    frontier = lambda prob: _warm_frontier(prob, 70)  # noqa: E731
    _, off = _trail(mk, {"TTS_MEGAKERNEL": "0"}, monkeypatch,
                    frontier=frontier, steps=1, poison=77)
    _, on = _trail(mk, {"TTS_MEGAKERNEL": "force", "TTS_MEGAKERNEL_MT": "16"},
                   monkeypatch, frontier=frontier, steps=1, poison=77)
    (counts, vals, aux), = on
    assert counts[0] > 0  # the cycle pushed rows
    _assert_same_trail(on, off)


@pytest.mark.parametrize("family", ["nqueens", "pfsp-lb1"])
def test_batched_engine_armed_streamed_matches_sequential(family,
                                                          monkeypatch):
    """B=2 batched program with the streamed push armed in every slot:
    each tenant lands the sequential goldens."""
    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    monkeypatch.setenv("TTS_MEGAKERNEL_MT", "16")
    mk = _mk_problem(family)
    opt = sequential_search(mk()).best
    seq = sequential_search(mk(), initial_best=opt)
    for res in batched_search(mk(), n_jobs=3, B=2, m=4, M=64, K=8,
                              initial_best=opt):
        assert res.megakernel == "on", res.megakernel_reason
        assert _counts(res) == _counts(seq)


# -- the Megacore evaluation-only split -------------------------------------

def test_streamed_eval_bounds_matches_oracle():
    """The parallel-semantics evaluation pass: multi-tile output is
    bit-identical to single-tile (tile independence — the property that
    makes the Megacore split legal), and the lb1 plane matches the
    fused-jnp evaluator oracle on open slots."""
    ptm = _ptm(311)
    prob = PFSPProblem(lb="lb1", ub=0, p_times=ptm)
    n = prob.jobs
    rng = np.random.default_rng(7)
    B = 64
    prmu = np.stack([rng.permutation(n) for _ in range(B)]).astype(np.int32)
    lim = rng.integers(-1, n - 2, size=B).astype(np.int32)
    one = np.asarray(MK.streamed_eval_bounds(prob, prmu, lim, interpret=True))
    for mt in (8, 16, 32):
        tiled = np.asarray(MK.streamed_eval_bounds(
            prob, prmu, lim, mt=mt, interpret=True))
        np.testing.assert_array_equal(tiled, one)
    t = prob.device_tables()
    want = np.asarray(PD.lb1_bounds(
        jnp.asarray(prmu), jnp.asarray(lim), t))
    open_ = np.arange(n)[None, :] > lim[:, None]
    np.testing.assert_array_equal(one[open_], want[open_])
    # tile-width validation is an operator error, not a refusal
    with pytest.raises(ValueError):
        MK.streamed_eval_bounds(prob, prmu, lim, mt=24, interpret=True)
    # N-Queens label plane: tile independence on the other family shape.
    nq = NQueensProblem(N=8)
    board = rng.integers(0, 8, size=(B, nq.child_slots)).astype(np.int32)
    depth = rng.integers(0, 4, size=B).astype(np.int32)
    nq_one = np.asarray(MK.streamed_eval_bounds(
        nq, board, depth, interpret=True))
    nq_tiled = np.asarray(MK.streamed_eval_bounds(
        nq, board, depth, mt=16, interpret=True))
    np.testing.assert_array_equal(nq_tiled, nq_one)
    assert nq_one.shape == board.shape


# -- stable names in a profiler trace ---------------------------------------

def _kernel_jaxprs(prog):
    """Every pallas_call eqn of the resident step's jaxpr."""
    from tpu_tree_search.analysis.contracts import prim_eqns

    state = prog.init_state({}, 0)
    jaxpr = jax.make_jaxpr(prog._step)(*state)
    return [eqn for name, eqn in prim_eqns(jaxpr) if name == "pallas_call"]


@pytest.mark.parametrize("mt", [None, "16"])
@pytest.mark.parametrize("family,name", [
    ("nqueens", "tts_cycle_nqueens"),
    ("pfsp-lb1", "tts_cycle_lb1"),
    ("pfsp-lb2", "tts_cycle_lb2"),
])
def test_cycle_kernel_carries_its_trace_names(family, name, mt, monkeypatch):
    """The armed step's one pallas_call is named ``tts_cycle_<family>``
    (the HLO instruction a profiler trace names the kernel by), single-tile
    and streamed alike, and its body holds the ``tts_bound`` and
    ``tts_compact`` regions."""
    from tpu_tree_search.engine.resident import _make_program, resolve_capacity

    monkeypatch.setenv("TTS_MEGAKERNEL", "force")
    if mt:
        monkeypatch.setenv("TTS_MEGAKERNEL_MT", mt)
    prob = _mk_problem(family)()
    capacity, M = resolve_capacity(prob, 64, None)
    prog = _make_program(prob, 4, M, 4, capacity, jax.devices()[0])
    assert prog.megakernel.enabled and prog.megakernel.tiled == bool(mt)
    from tpu_tree_search.analysis.contracts import prim_eqns

    (eqn,) = _kernel_jaxprs(prog)
    assert eqn.params["name"] == name
    scopes = {str(e.source_info.name_stack)
              for _, e in prim_eqns(eqn.params["jaxpr"])}
    assert {MK.BOUND_SCOPE, MK.COMPACT_SCOPE} <= scopes


# -- routing on a described v5e (no compile) --------------------------------

#: A stand-in for one v5e chip: routing reads only the device's platform.
_V5E = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")

#: case -> (problem, M, (enabled, Mt, grid) that ``auto`` resolves on a
#: v5e).  ta014 and N=15 at the CLI's chunk sizes are the benchmark cells.
_V5E_ROUTES = {
    "ta014-lb1": (lambda: PFSPProblem(inst=14, lb="lb1", ub=1), 1024,
                  (True, 128, 8)),
    "ta014-lb2": (lambda: PFSPProblem(inst=14, lb="lb2", ub=1), 1024,
                  (True, 128, 8)),
    "nqueens-15": (lambda: NQueensProblem(N=15), 50000, (True, 200, 250)),
    "ta021-lb1": (lambda: PFSPProblem(inst=21, lb="lb1", ub=1), 1024,
                  (True, 128, 8)),
    "ta021-lb2": (lambda: PFSPProblem(inst=21, lb="lb2", ub=1), 1024,
                  (True, 128, 8)),
    "ta031-lb1": (lambda: PFSPProblem(inst=31, lb="lb1", ub=1), 1024,
                  (True, 128, 8)),
    "ta051-lb1": (lambda: PFSPProblem(inst=51, lb="lb1", ub=1), 1024,
                  (True, 128, 8)),
    "ta051-lb2": (lambda: PFSPProblem(inst=51, lb="lb2", ub=1), 1024,
                  (True, 128, 8)),
}


def _unpin_routing(monkeypatch):
    for k in ("TTS_MEGAKERNEL", "TTS_MEGAKERNEL_MT", "TTS_NARROW",
              "TTS_PALLAS", "TTS_PALLAS_INTERPRET", "TTS_PALLAS_VMEM_MB",
              "TTS_COMPACT", "TTS_LB2_PAIRBLOCK"):
        monkeypatch.delenv(k, raising=False)


@pytest.mark.parametrize("case", sorted(_V5E_ROUTES))
def test_auto_routing_on_a_v5e(case, monkeypatch):
    """What ``auto`` arms on a TPU, read from the device's platform alone:
    the cycle armed and compiled (not interpreted), at the pool tile and
    grid the VMEM model picks for the shape."""
    _unpin_routing(monkeypatch)
    mk, M, want = _V5E_ROUTES[case]
    dec = MK.resolve(mk(), M, _V5E)
    assert (dec.enabled, dec.mt, dec.grid) == want, dec
    assert dec.auto and not dec.interpret and dec.reason is None


@pytest.mark.parametrize("case", ["ta014-lb1", "ta014-lb2", "nqueens-15"])
def test_compact_mode_on_a_v5e(case, monkeypatch):
    """The benchmark cells' survivor compaction on a TPU: ``dense`` (PFSP
    at M*n <= 64k, and N-Queens, which never prunes)."""
    from tpu_tree_search.ops import compaction

    _unpin_routing(monkeypatch)
    mk, M, _ = _V5E_ROUTES[case]
    prob = mk()
    assert compaction.resolve_compact_mode(prob, M, prob.child_slots,
                                           _V5E) == "dense"


@pytest.mark.parametrize("platform,expect", [("tpu", "tpu"), ("cpu", "jnp")])
def test_kernels_or_jnp_follows_the_platform(platform, expect, monkeypatch):
    """`use_pallas` answers "kernels or jnp" from the target device: a TPU
    routes to the Pallas kernels, a CPU device to the jnp oracles, and the
    program reports the route it builds (`SearchResult.kernel_backend`)."""
    from tpu_tree_search.engine.resident import _make_program, resolve_capacity
    from tpu_tree_search.ops import pallas_kernels as PK

    _unpin_routing(monkeypatch)
    dev = _V5E if platform == "tpu" else jax.devices("cpu")[0]
    assert PK.use_pallas(dev) is (platform == "tpu")
    prob = PFSPProblem(inst=14, lb="lb2", ub=1)
    capacity, M = resolve_capacity(prob, 1024, None)
    prog = _make_program(prob, 25, M, 4096, capacity, dev)
    assert prog.kernel_backend == expect
    assert prog.megakernel.enabled is (platform == "tpu")
    assert not prog.kernel_interpret
