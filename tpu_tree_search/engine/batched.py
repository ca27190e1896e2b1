"""Instance-axis batched resident engine.

The resident engine (engine/resident.py) runs ONE search instance per
compiled program: pool-in-HBM SoA arrays plus a `lax.while_loop` that
advances up to K chunk cycles per dispatch.  For a fleet of small
same-shape jobs that leaves the MXU idle between dispatches — each job
pays the full dispatch latency alone.  Following the batch-scheduling
architecture of arXiv:2002.07062, this module makes *instance* one more
axis of the compiled program: the while-loop carry becomes a tuple of B
per-slot sub-carries (each slot = its own pool, size, incumbent and
cycle/explored counters), and one dispatch advances every live slot.

Two design rules keep the batch bit-identical to solo execution:

  * **Unrolled slots, not vmap.**  The body applies the resident
    engine's own per-instance body (``loop_fns``) to each slot under that
    slot's own cond (``lax.cond(live, body, identity)``).  A frozen slot
    (terminated, stalled, or empty) runs no cycle — its carry, cycle
    counter included, passes through untouched, and its pools are neither
    read nor copied — so each slot executes *exactly* the cycle sequence
    its solo program would, in the same order, with the same reductions.
    vmap would rebuild the math with a batch axis and forfeit the B=1
    jaxpr identity that pins this claim.
  * **Admission is a transfer, not a trace.**  ``make_slot`` builds a
    slot's carry leaves on the host (zero-padded to pool capacity) and
    `jax.device_put`s them; the jit cache key is (avals, statics), and
    every slot's leaves have the same avals by construction, so splicing
    a job into a free slot between dispatches can never trigger a
    recompile.  Both rules are pinned by `tts check` contracts at the
    bottom of this file.

The loop's global cond is the OR of the per-slot conds: the program runs
while ANY slot is live, and empty slots (size=0) are just frozen slots.
Admission/retirement happens only at dispatch boundaries on the host —
a finished or preempted slot is cut out via ``residual_slot`` /
``snapshot_slot`` (same downloads the solo engine uses for phase 3 /
checkpoints), and a new same-shape job restores into the freed slot.

Phase profiling (TTS_PHASEPROF) is a solo-only diagnostic: the phase
clock block is per-program, not per-slot, so batched builds refuse it.
Per-slot device counter blocks (TTS_OBS) are supported — each slot
carries its own block, harvested per dispatch and attributable to the
job occupying the slot.
"""

from __future__ import annotations

import os
import time

import numpy as np

from ..obs import counters as obs_counters
from ..obs import phases as obs_phases
from ..pool import SoAPool
from ..problems.base import INF_BOUND, Problem, index_batch
from .device import drain, warmup
from .pipeline import resolve_k
from .resident import _make_program, resident_search, resolve_capacity
from .results import SearchResult

# Leaves per slot in the *dispatch argument* list: pool_vals, pool_aux,
# size, best.  (The in-loop carry additionally holds the tree/sol/cycle
# scalars and the optional counter block, all seeded to zero per dispatch
# exactly as the solo step does.)
SLOT_ARGS = 4


class _BatchedProgram:
    """B-slot batched wrapper around one resident program.

    Holds the inner `_ResidentProgram` for its loop body, field layout
    and snapshot/residual transforms; compiles a single jitted step whose
    carry is a B-tuple of per-slot sub-carries.  B is baked into the
    program (fixed at trace time) — the *occupancy* varies at runtime via
    masking, never the shape.
    """

    def __init__(self, problem: Problem, B: int, m: int, M: int, K: int,
                 capacity: int, device):
        if B < 1:
            raise ValueError(f"batch slots must be >= 1, got {B}")
        if obs_phases.phase_profiling_enabled():
            # The phase clock block is a per-program diagnostic with no
            # slot attribution; refusing beats silently misattributing.
            raise RuntimeError(
                "TTS_PHASEPROF is not supported in batched builds; "
                "profile with a solo run instead")
        self.problem = problem
        self.B = int(B)
        self.inner = _make_program(problem, m, M, K, capacity, device)
        self.m = m
        self.M = self.inner.M
        self.K = self.inner.K
        self.capacity = capacity
        self.device = device
        self.obs = self.inner.obs
        self._step = self._build()

    # -- compiled step -------------------------------------------------

    def _build(self):
        import jax
        import jax.numpy as jnp
        from jax import lax

        cond1, body1 = self.inner.loop_fns()
        B, obs = self.B, self.obs

        if B == 1:
            # Pure pytree nesting: one extra tuple level is invisible in
            # the flattened jaxpr, so B=1 compiles to byte-for-byte the
            # solo step (contract `batch-b1-identity`).
            def cond(carry):
                return cond1(carry[0])

            def body(carry):
                return (body1(carry[0]),)
        else:
            def cond(carry):
                live = cond1(carry[0])
                for i in range(1, B):
                    live = live | cond1(carry[i])
                return live

            def body(carry):
                return tuple(lax.cond(cond1(slot), body1, lambda s: s, slot)
                             for slot in carry)

        def step(*flat):
            zero = jnp.int32(0)
            slots = []
            for i in range(B):
                pv, pa, size, best = flat[SLOT_ARGS * i:SLOT_ARGS * (i + 1)]
                init = (pv, pa, size, best, zero, zero, zero)
                if obs:
                    init = init + (obs_counters.init_block(),)
                slots.append(init)
            return lax.while_loop(cond, body, tuple(slots))

        donate = tuple(x for i in range(B)
                       for x in (SLOT_ARGS * i, SLOT_ARGS * i + 1))
        return jax.jit(step, donate_argnums=donate)

    # -- slot construction (host -> device transfers only) -------------

    def make_slot(self, frontier: dict | None, best: int) -> tuple:
        """Build one slot's dispatch args from a host frontier: zero-pad
        each pool field to capacity and `device_put` the leaves.  Pure
        transfers — no traced ops — so admission can never compile
        (contract `batch-splice-no-recompile`)."""
        import jax

        k = 0
        if frontier is not None:
            k = int(np.asarray(frontier[self.inner.size_field]).shape[0])
        leaves = []
        for i, (name, dtype, shape) in enumerate(self.inner.pool_fields):
            rows = (np.asarray(frontier[name]) if k
                    else np.zeros((0,) + shape, dtype))
            buf = self.inner.pack_rows(i, rows, self.inner.pool_rows)
            leaves.append(jax.device_put(buf, self.device))
        leaves.append(jax.device_put(np.int32(k), self.device))
        leaves.append(jax.device_put(np.int32(best), self.device))
        return tuple(leaves)

    def empty_slot(self) -> tuple:
        """A frozen slot: size=0 fails the loop cond, so it is pure
        ballast.  Each empty slot needs its OWN buffers — donation
        rejects aliased arguments."""
        return self.make_slot(None, 0)

    def slot_avals(self) -> list:
        """The aval signature one slot's dispatch args must match — aval
        equality against the compiled step's inputs IS the zero-recompile
        guarantee (jit cache key = avals + statics)."""
        import jax

        out = [jax.ShapeDtypeStruct(full, np.dtype(dtype))
               for (_name, dtype, _shape), full in zip(
                   self.inner.pool_fields, self.inner.pool_shapes())]
        out.append(jax.ShapeDtypeStruct((), np.int32))
        out.append(jax.ShapeDtypeStruct((), np.int32))
        return out

    # -- dispatch + harvest --------------------------------------------

    def step(self, states: list) -> tuple:
        """One K-cycle dispatch over all B slots. `states` is a list of B
        slot arg tuples (SLOT_ARGS leaves each); returns the raw out
        carry (B sub-tuples)."""
        flat = [leaf for slot in states for leaf in slot]
        return self._step(*flat)

    def carry(self, out: tuple) -> list:
        """Next dispatch's per-slot args from a step's output."""
        return [tuple(slot[:SLOT_ARGS]) for slot in out]

    def read_slot_scalars(self, out: tuple, i: int):
        """(tree_inc, sol_inc, cycles, size, best, ctr) for slot i —
        mirrors the solo program's read_scalars."""
        slot = out[i]
        ctr = np.asarray(slot[7]) if self.obs else None
        return (int(slot[4]), int(slot[5]), int(slot[6]),
                int(slot[2]), int(slot[3]), ctr)

    def residual_slot(self, states: list, i: int):
        """Download slot i's remaining frontier for the host drain."""
        return self.inner.residual(states[i])

    def snapshot_slot(self, states: list, i: int):
        """Download slot i's full live frontier for a checkpoint cut."""
        return self.inner.snapshot(states[i])


def make_batched_program(problem: Problem, B: int, m: int, M: int, K: int,
                         capacity: int, device=None) -> _BatchedProgram:
    """Cached `_BatchedProgram` factory — one compiled program per
    (B, config); rebuilding would recompile the whole while-loop."""
    import jax

    if device is None:
        device = jax.devices()[0]
    cache = getattr(problem, "_batched_programs", None)
    if cache is None:
        cache = problem._batched_programs = {}
    from ..ops.pfsp_device import routing_cache_token

    key = (B, m, M, K, capacity, id(device),
           routing_cache_token(problem, device),
           obs_counters.device_counters_enabled())
    if key in cache:
        return cache[key]
    prog = _BatchedProgram(problem, B, m, M, K, capacity, device)
    cache[key] = prog
    return prog


def batched_search(
    problem: Problem,
    n_jobs: int,
    B: int,
    m: int = 25,
    M: int = 65536,
    K: int | str = 4096,
    capacity: int | None = None,
    device=None,
    initial_best: int | None = None,
) -> list[SearchResult]:
    """Run `n_jobs` identical searches through a B-slot batched program.

    The engine-level driver (the serve daemon's BatchExecutor is the
    multi-tenant variant): fill the slots, dispatch until a slot's pool
    drops below m, retire it (residual download + host drain, exactly the
    solo phase 3) and refill from the pending list.  Every job's result
    is bit-identical to a solo ``resident_search`` of the same spec —
    each slot's masked sub-carry executes the same cycle sequence.

    A capacity-stalled slot (frontier too big for a K-cycle fan-out) is
    cut to a checkpoint and finished by a solo ``resident_search`` resume
    — capacity can grow there, it cannot in a fixed batch slot.  Counters
    stay cumulative across the handoff, but the host-offload portion may
    order work differently than a solo run that stalled in place.
    """
    if n_jobs <= 0:
        return []
    import jax

    if device is None:
        device = jax.devices()[0]
    capacity, M = resolve_capacity(problem, M, capacity)
    _auto, k_value = resolve_k(K, default_max=4096)
    prog = make_batched_program(problem, B, m, M, k_value, capacity, device)
    best0 = (int(initial_best) if initial_best is not None
             else getattr(problem, "initial_ub", INF_BOUND))

    results: list[SearchResult | None] = [None] * n_jobs
    pending = list(range(n_jobs))
    slots: list[dict | None] = [None] * B
    states = [prog.empty_slot() for _ in range(B)]

    def admit(i: int, j: int) -> None:
        pool = SoAPool(problem.node_fields())
        pool.push_back(index_batch(problem.root(), 0))
        tree1, sol1, best = warmup(problem, pool, best0, m)
        states[i] = prog.make_slot(pool.as_batch(), best)
        slots[i] = {"job": j, "tree": tree1, "sol": sol1,
                    "t0": time.perf_counter()}

    def finish_solo(i: int, sl: dict, best: int) -> None:
        # Stall: checkpoint the slot and let the solo engine (which may
        # grow capacity on resume) finish the job.
        import tempfile

        from . import checkpoint as ckpt

        batch, _size, best = prog.snapshot_slot(states, i)
        fd, path = tempfile.mkstemp(suffix=".ckpt.npz")
        os.close(fd)
        try:
            ckpt.save(path, problem, batch, best, sl["tree"], sl["sol"])
            results[sl["job"]] = resident_search(
                problem, m=m, M=M, K=k_value, capacity=None, device=device,
                resume_from=path)
        finally:
            if os.path.exists(path):
                os.remove(path)

    for i in range(B):
        if pending:
            admit(i, pending.pop(0))

    while any(sl is not None for sl in slots):
        out = prog.step(states)
        carry = prog.carry(out)
        for i in range(B):
            states[i] = carry[i]
        for i in range(B):
            sl = slots[i]
            if sl is None:
                continue
            tree_inc, sol_inc, cycles, size, best, _ctr = \
                prog.read_slot_scalars(out, i)
            sl["tree"] += tree_inc
            sl["sol"] += sol_inc
            if _ctr is not None:
                sl["ctr"] = obs_counters.merge_host(sl.get("ctr"), _ctr)
            if size < m:
                batch, _size, best = prog.residual_slot(states, i)
                pool = SoAPool(problem.node_fields())
                if _size:
                    pool.reset_from(batch)
                tree3, sol3, best = drain(problem, pool, best)
                results[sl["job"]] = SearchResult(
                    explored_tree=sl["tree"] + tree3,
                    explored_sol=sl["sol"] + sol3,
                    best=best,
                    elapsed=time.perf_counter() - sl["t0"],
                    complete=True,
                    compact=prog.inner.compact,
                    compact_auto=prog.inner.compact_auto,
                    megakernel=prog.inner.megakernel.state,
                    megakernel_auto=prog.inner.megakernel.auto,
                    megakernel_reason=prog.inner.megakernel.reason,
                    k_resolved=prog.K,
                    obs=({"device_counters": sl["ctr"]}
                         if sl.get("ctr") is not None else None),
                )
                slots[i] = None
                if pending:
                    admit(i, pending.pop(0))
                # else: the retired carry stays as frozen ballast
                # (size < m fails its cond) — no fresh buffers needed.
            elif cycles == 0:
                finish_solo(i, sl, best)
                slots[i] = None
                if pending:
                    admit(i, pending.pop(0))
                else:
                    states[i] = prog.empty_slot()
    return [r for r in results if r is not None]


# -- contracts ---------------------------------------------------------

from ..analysis.contracts import contract  # noqa: E402


@contract(
    "batch-b1-identity",
    claim="the B=1 batched step's jaxpr is byte-identical to the solo "
          "resident step's: the instance axis is pure pytree nesting, "
          "invisible to the flattened program, so --batch-slots 1 IS "
          "today's path with zero structural drift",
    artifact="batched-step",
)
def _contract_b1_identity(art, cell):
    if art.get("b1_text") is None:
        return []
    if art["b1_text"] == art["resident_text"]:
        return []
    return ["B=1 batched jaxpr differs from the solo resident step jaxpr"]


@contract(
    "batch-splice-no-recompile",
    claim="slot admission is a device_put into the donated carry, never "
          "a new program: make_slot's leaf avals equal the compiled "
          "step's per-slot input avals exactly, and the jit cache key is "
          "(avals, statics) — aval equality IS the zero-recompile "
          "guarantee for mid-flight splices",
    artifact="batched-step",
)
def _contract_splice_no_recompile(art, cell):
    slot = art["slot_avals"]
    carry = art["carry_avals"]
    B = art["B"]
    msgs = []
    if len(carry) != len(slot) * B:
        msgs.append(
            f"step takes {len(carry)} leaves, expected "
            f"{len(slot)} x {B} slots")
        return msgs
    for b in range(B):
        for j, want in enumerate(slot):
            got = carry[b * len(slot) + j]
            if got != want:
                msgs.append(
                    f"slot {b} leaf {j}: splice aval {want} != "
                    f"carry aval {got}")
    return msgs
