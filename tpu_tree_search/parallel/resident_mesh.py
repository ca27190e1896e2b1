"""Mesh-resident search: the device-resident engine sharded over a TPU mesh.

The reference's multi-GPU tier is host-orchestrated: one CPU task per GPU,
lock-based pools in host memory, work stealing by locked bulk copies
(`pfsp_multigpu_chpl.chpl:375-496`). The TPU-native formulation inverts it:
**one SPMD program owns the whole search**. Every device holds a private pool
shard in HBM and runs the resident chunk loop (engine/resident.py) locally;
the cross-device coordination is pure XLA collectives riding ICI:

  * **incumbent all-reduce** — after every K-cycle block the per-shard
    incumbents fold with ``lax.pmin``. This is the mid-search UB broadcast
    the reference lacks entirely (it reconciles incumbents only in the
    terminal reduction, SURVEY.md §2.4.4; BASELINE.json names this the
    planned improvement).
  * **diffusion load balancing** — instead of lock-based stealing (which
    needs shared memory TPUs don't have), each balance round every shard may
    donate up to T of its *front* (oldest, shallowest — the same
    steal-half-from-front policy as `Pool_par.chpl:180-191`) nodes to its
    ring neighbor via ``lax.ppermute``. The donation amounts are computed by
    every shard from an ``all_gather`` of pool sizes, so sender and receiver
    agree without any handshake; a round moves work only toward shards that
    are starving (< m nodes) from shards that can spare it (>= 2m — the
    reference's steal threshold, `Pool_par.chpl:154-158`).
  * **termination** — the host loop stops when the all-gathered sizes show
    every shard below the chunk threshold m; the residual (< D*m nodes)
    drains on host, exactly like the single-device tier's phase 3. This
    replaces the idle-flag allIdle scan (`util.chpl:16-30`): in a bulk-
    synchronous SPMD program the size vector *is* the idle state.

Counting invariance: balancing moves pool nodes between shards but never
creates/destroys them, and pruning is against the pmin-folded incumbent, so
with a fixed incumbent (N-Queens; PFSP ub=1) exploredTree/exploredSol equal
the sequential tier exactly — the same cross-tier determinism the reference
relies on for validation (SURVEY.md §4.2).
"""

from __future__ import annotations

import time

import numpy as np

from ..engine.device import drain, warmup
from ..engine.resident import _emit_device_explored, _make_program, node_rows
from ..engine.results import Diagnostics, PhaseStats, SearchResult
from ..obs import counters as obs_counters
from ..obs import events as ev
from ..obs import flightrec as fr
from ..obs import phases as obs_phases
from ..obs import quality as obs_quality
from ..pool import SoAPool
from ..problems.base import INF_BOUND, Problem, index_batch


def make_dp_mp_mesh(devices, D: int, mp: int):
    """The one dp / (dp, mp) mesh-construction policy (device order,
    reshape, feasibility check) — shared by the mesh-resident and
    dist_mesh tiers so their layouts can never drift."""
    from jax.sharding import Mesh

    if mp > 1:
        need = D * mp
        if len(devices) < need:
            raise ValueError(
                f"dp={D} x mp={mp} needs {need} devices, have "
                f"{len(devices)}"
            )
        return Mesh(np.asarray(devices[:need]).reshape(D, mp), ("dp", "mp"))
    return Mesh(np.asarray(devices[:D]), ("dp",))


class _MeshResidentProgram:
    """Compiled SPMD step for (problem, mesh, m, M, K, rounds, T, C)."""

    def __init__(
        self,
        problem: Problem,
        mesh,
        m: int,
        M: int,
        K: int,
        rounds: int,
        T: int,
        capacity: int,
    ):
        import jax

        axes = list(mesh.axis_names)
        if len(axes) == 2:
            if axes[1] != "mp":
                raise ValueError(
                    "mesh-resident tier: two-axis meshes must be (dp, mp)"
                )
            if getattr(problem, "lb", None) != "lb2":
                raise ValueError(
                    "mp-axis sharding splits the lb2 Johnson pair loop; "
                    "use a single-axis mesh for other problems/bounds"
                )
        elif len(axes) != 1:
            raise ValueError("mesh-resident tier needs a (dp[, mp]) mesh")
        self.problem = problem
        self.mesh = mesh
        self.D = int(mesh.shape[axes[0]])
        self.mp = int(mesh.shape["mp"]) if len(axes) == 2 else 1
        self.m = m
        self.M = M
        n = problem.child_slots
        self.K = max(1, min(K, (2**31 - 1) // max(1, M * n * max(1, rounds))))
        self.rounds = rounds
        self.T = T
        self.capacity = capacity
        # Single-device program supplies the pool schema, hooks, and the
        # K-cycle loop body; its own jitted step is unused here. Built for
        # the mesh's device platform so the kernel routing (Pallas on TPU,
        # XLA elsewhere) matches where the shards actually run.
        # mp > 1: every (dp, i) shard redundantly owns the same dp pool
        # block and splits the Johnson pair loop over mp; the pmax inside
        # the evaluator keeps all mp replicas' prune decisions identical,
        # so they stay in lockstep without any extra collective.
        self.inner = _make_program(
            problem, m, M, K, capacity, mesh.devices.flat[0],
            mp_axis="mp" if self.mp > 1 else None, mp_size=self.mp,
            # Staged lb2 runs per-shard in BOTH mesh modes (the compaction
            # is pure local ops, no collectives; Pallas-inside-shard_map is
            # already how the lb1/lb2 kernels execute in this tier). Under
            # mp > 1 the compacted self bound shards its pair loop over mp
            # and pmax-combines, so every replica prunes identically
            # (`pfsp_device.lb2_self_bounds_mp`).
            allow_staged=True,
        )
        self._build()

    # -- construction ------------------------------------------------------

    def _build(self):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh
        axis = mesh.axis_names[0]
        D, m, M, T, C = self.D, self.m, self.M, self.T, self.capacity
        K = self.K
        n = self.problem.child_slots
        Mn = M * n
        vals_dt = self.inner.pool_fields[0][1]
        aux_dt = self.inner.pool_fields[1][1]
        # Per-shard pool arrays as the device holds them (`pool_shapes`:
        # where the TPU cycle pushes, kq nodes to a lane-dense value row
        # of W-lane slots, and slack nodes).
        shape_v, shape_a = self.inner.pool_shapes()
        W, kq = self.inner.row_width, self.inner.row_pack
        cond, body = self.inner.loop_fns(K, sharded=True)

        # Node-granular moves on the value pool. With kq nodes to a row
        # they go through the pool's flat view, where node i is the W
        # values from i*W on.
        def front_nodes(pv, k):
            return pv[: -(-k // kq)]

        def roll_nodes(pv, k):
            if kq == 1:
                return jnp.roll(pv, k, axis=0)
            return jnp.roll(pv.reshape(-1), k * W).reshape(pv.shape)

        def put_nodes(pv, blk, at, k):
            if kq == 1:
                return lax.dynamic_update_slice(pv, blk, (at, 0))
            flat = lax.dynamic_update_slice(
                pv.reshape(-1), blk.reshape(-1)[: k * W], (at * W,))
            return flat.reshape(pv.shape)

        rounds = self.rounds
        obs = self.inner.obs
        phaseprof = self.inner.phaseprof
        perm = [(i, (i + 1) % D) for i in range(D)]  # ring, static

        def shard_step(pool_vals, pool_aux, size, best):
            # per-shard views: (C, n), (C,), (1,), (1,)
            sz = size[0]
            bst = best[0]
            # Zeros derived from a varying value: under shard_map the while
            # carry's varying-manual-axes types must match (scan-vma rule).
            tree = sz * 0
            sol = sz * 0
            cycles = sz * 0
            if obs:
                # Counter block accumulates across the dispatch's rounds
                # (carried back in each round); varying like the scalars.
                ctr = obs_counters.init_block() + (sz * 0)
            if phaseprof:
                # Phase-clock block (obs/phases.py): seeded once per
                # dispatch, accumulated across the rounds; varying like
                # the scalars (the callback clock runs per shard).
                ph = obs_phases.seed_block(
                    sz.astype(jnp.uint32)
                ) + (sz * 0).astype(jnp.uint32)
            for _ in range(rounds):
                init = (pool_vals, pool_aux, sz, bst, sz * 0, sz * 0, sz * 0)
                if obs:
                    init = init + (ctr,)
                if phaseprof:
                    init = init + (ph,)
                carry = lax.while_loop(cond, body, init)
                pool_vals, pool_aux, sz, bst, ti, si, cy = carry[:7]
                if obs:
                    ctr = carry[7]
                if phaseprof:
                    ph = carry[-1]
                tree += ti
                sol += si
                cycles += cy
                if phaseprof:
                    # Loop exit -> balance section: the gap (cond fails,
                    # carry unwinds) is `loop` time; the pmin fold + the
                    # diffusion round below are charged to `balance`.
                    ph, (pool_vals, pool_aux, sz, bst) = obs_phases.boundary(
                        ph, "loop", pool_vals, pool_aux, sz, bst,
                        tag="mesh_loop",
                    )
                # Incumbent all-reduce over ICI (north-star improvement).
                # pcast re-marks the reduced (axis-invariant) value as
                # varying so the next round's while-loop carry types match.
                bst = lax.pcast(lax.pmin(bst, axis), (axis,), to="varying")
                if D > 1:
                    # -- diffusion balance round -------------------------------
                    sizes = lax.all_gather(sz, axis)  # (D,)
                    me = lax.axis_index(axis)
                    right = (me + 1) % D
                    # Donations computed identically on every shard from the
                    # gathered size vector: shard i gives to i+1 iff the
                    # receiver starves (< m) and the donor can spare (>= 2m,
                    # the reference's steal threshold), capped by the block
                    # size and the receiver's free space.
                    recv_sz = jnp.take(sizes, right)
                    recv_room = recv_sz + T + Mn <= C
                    my_give = jnp.where(
                        (recv_sz < m) & (sz >= 2 * m) & recv_room,
                        jnp.minimum(sz // 2, T),
                        0,
                    )
                    # The amount arriving from the left neighbor, recomputed
                    # from the same gathered vector (no handshake needed).
                    left = (me - 1) % D
                    left_sz = jnp.take(sizes, left)
                    my_room = sz + T + Mn <= C
                    incoming = jnp.where(
                        (sz < m) & (left_sz >= 2 * m) & my_room,
                        jnp.minimum(left_sz // 2, T),
                        0,
                    )
                    # Donate the pool *front* (oldest, shallowest subtrees —
                    # `Pool_par.chpl:180-191`): the first T rows are a static
                    # slice; rows beyond my_give are garbage the receiver
                    # never marks live.
                    blk_vals = lax.ppermute(front_nodes(pool_vals, T), axis,
                                            perm)
                    blk_aux = lax.ppermute(pool_aux[:T], axis, perm)
                    # Remove donated front rows by rolling them to the dead
                    # tail region — gated: the dynamic-shift roll copies the
                    # whole pool, so skip it in the common no-donation case.
                    def _shed(pv, pa):
                        return (
                            roll_nodes(pv, -my_give),
                            jnp.roll(pa, -my_give, axis=0),
                        )

                    pool_vals, pool_aux = lax.cond(
                        my_give > 0, _shed, lambda pv, pa: (pv, pa),
                        pool_vals, pool_aux,
                    )
                    sz = sz - my_give
                    # Append the incoming block only when this shard has T
                    # rows of dead space (my_room; incoming is gated on the
                    # same predicate) — an unconditional write could clobber
                    # live rows of a nearly-full pool.
                    def _append(pv, pa):
                        pv = put_nodes(pv, blk_vals, sz, T)
                        pa = lax.dynamic_update_slice(pa, blk_aux, (sz,))
                        return pv, pa

                    pool_vals, pool_aux = lax.cond(
                        my_room, _append, lambda pv, pa: (pv, pa),
                        pool_vals, pool_aux,
                    )
                    sz = sz + incoming
                if phaseprof:
                    # Close the balance segment (incumbent fold + ppermute
                    # diffusion — the mesh tiers' steal/exchange phase).
                    ph, (pool_vals, pool_aux, sz, bst) = obs_phases.boundary(
                        ph, "balance", pool_vals, pool_aux, sz, bst,
                    )
            out = (
                pool_vals,
                pool_aux,
                sz[None],
                bst[None],
                tree[None],
                sol[None],
                cycles[None],
            )
            if obs:
                out = out + (ctr[None],)
            if phaseprof:
                out = out + (ph[None],)
            return out

        specs_pool = P(axis, None)
        specs_vec = P(axis)
        out_specs = (
            specs_pool, specs_vec, specs_vec, specs_vec,
            specs_vec, specs_vec, specs_vec,
        )
        if obs:
            out_specs = out_specs + (P(axis, None),)
        if phaseprof:
            out_specs = out_specs + (P(axis, None),)
        mapped = jax.shard_map(
            shard_step,
            mesh=mesh,
            in_specs=(specs_pool, specs_vec, specs_vec, specs_vec),
            out_specs=out_specs,
            # pallas_call inside shard_map does not yet satisfy jax's vma
            # checker (out_shapes carry no vma; the kernel body mixes
            # varying batch blocks with replicated table blocks) — with the
            # default check_vma=True the TPU path dies at trace time the
            # moment the evaluator routes to a Pallas kernel (round-5
            # hardware session, test_mesh_staged_lb2_runs_on_tpu). jax's
            # own error message prescribes this flag. Disabled ONLY when
            # a Pallas kernel is on the path (the evaluator or the
            # one-kernel cycle — `kernel_backend`), so the checker keeps
            # guarding the ppermute/diffusion logic on the jnp path; the
            # Pallas composition is pinned by the interpret-mode regression
            # (test_mesh_pallas_inside_shard_map) + the CPU parity suite.
            # TRACKING (ADVICE r5): the disable covers the WHOLE body, so on
            # TPU the checker also stops guarding the ppermute/diffusion
            # logic — re-scope it to the pallas_call alone once jax lets
            # pallas_call declare vma on out_shapes (jax#21577 direction);
            # until then a collective-logic regression there is only caught
            # by the jnp-path CPU tests.
            check_vma=self.inner.kernel_backend == "jnp",
        )
        self._step = jax.jit(mapped, donate_argnums=(0, 1))

        sh_vec = NamedSharding(mesh, specs_vec)

        def init(fr_vals, fr_aux, counts, best0):
            # fr_*: (D, F, ...) stride-partitioned warm frontier, small.
            def shard_init(fr_v, fr_a, cnt, b0):
                pv = jnp.zeros(shape_v, vals_dt)
                pa = jnp.zeros(shape_a, aux_dt)
                pv = lax.dynamic_update_slice(pv, fr_v[0].astype(vals_dt), (0, 0))
                pa = lax.dynamic_update_slice(pa, fr_a[0].astype(aux_dt), (0,))
                return pv, pa, cnt, b0

            return jax.shard_map(
                shard_init,
                mesh=mesh,
                in_specs=(P(axis, None, None), P(axis, None), specs_vec, specs_vec),
                out_specs=(specs_pool, specs_vec, specs_vec, specs_vec),
            )(fr_vals, fr_aux, counts, best0)

        self._init = jax.jit(init)
        self._sh_vec = sh_vec

        def residual(pool_vals, pool_aux):
            # After termination every shard holds < m live rows; ship the
            # first 2m rows of each shard to host (static, tiny).
            R = min(2 * m, C)

            def shard_res(pv, pa):
                return front_nodes(pv, R)[None], pa[None, :R]

            return jax.shard_map(
                shard_res,
                mesh=mesh,
                in_specs=(specs_pool, specs_vec),
                out_specs=(P(axis, None, None), P(axis, None)),
            )(pool_vals, pool_aux)

        self._residual = jax.jit(residual)

    # -- host API ----------------------------------------------------------

    def init_state(self, shard_batches: list[dict], best: int):
        import jax

        D = self.D
        name_v, _, shape_v = self.inner.pool_fields[0]
        name_a = self.inner.pool_fields[1][0]
        counts = np.array(
            [b[name_a].shape[0] for b in shard_batches], dtype=np.int32
        )
        F = max(1, int(counts.max()))
        if F > self.capacity:
            raise ValueError(
                f"warm frontier ({F} nodes/shard) exceeds pool capacity "
                f"{self.capacity}"
            )
        # Bucket the staging width to a power of two (capped at capacity):
        # ``_init`` is jitted per (D, F) shape, and callers that re-upload
        # repeatedly (the dist_mesh donation rounds) would otherwise pay a
        # fresh XLA compile for every distinct frontier size.
        F = min(1 << (F - 1).bit_length(), self.capacity)
        # Stage at the host storage dtypes (TTS_NARROW, problems/base.py):
        # `_init` widens to the device pool dtypes on-chip, so the H2D
        # upload ships narrow bytes.
        # The value rows go up laid out as the pool holds them
        # (`pack_rows`: row_pack nodes to a row).
        fields = self.inner.problem.node_fields()
        kq = self.inner.row_pack
        Fv = -(-F // kq) * kq
        fr_v = np.stack([
            self.inner.pack_rows(
                0, b[name_v] if counts[w]
                else np.zeros((0,) + shape_v, fields[name_v][1]), Fv)
            for w, b in enumerate(shard_batches)])
        fr_a = np.zeros((D, F), dtype=fields[name_a][1])
        for w, b in enumerate(shard_batches):
            k = counts[w]
            if k:
                fr_a[w, :k] = b[name_a]
        best0 = np.full((D,), best, dtype=np.int32)
        return self._init(fr_v, fr_a, jax.device_put(counts, self._sh_vec), best0)

    def step(self, state):
        return self._step(*state)

    def carry(self, out):
        """The dispatch's carried state ``(pool_vals, pool_aux, size,
        best)`` — the next dispatch's input. Nothing is forced, so a
        speculative dispatch can chain on it while still in flight."""
        return tuple(out[:4])

    def read_scalars(self, out):
        """Blocks on the small per-shard outputs only — returns
        ``(tree, sol, cycles, sizes, best, tree_vec, ctr)``. The donated
        pool leaves (``out[0:2]``) are never touched: under pipelined
        dispatch they were already donated into the next speculative
        dispatch. ``sizes``/``best`` are (D,) vectors carried outside the
        donation set."""
        tree, sol, cycles = out[4], out[5], out[6]
        ctr = np.asarray(out[7]) if self.inner.obs else None
        sizes = np.asarray(out[2])
        best = int(np.asarray(out[3]).min())
        return (
            int(np.asarray(tree).sum()),
            int(np.asarray(sol).sum()),
            int(np.asarray(cycles).sum()),
            sizes,
            best,
            np.asarray(tree),
            ctr,
        )

    def read_phase_block(self, out):
        """The dispatch's harvested (D, NSLOTS+1) phase-clock block (np
        array) when the profiler variant is armed, else None — the final,
        non-donated output leaf (same readback contract as the scalars)."""
        return np.asarray(out[-1]) if self.inner.phaseprof else None

    def read_stats(self, out):
        """(state, tree, sol, cycles, sizes, best, tree_vec, ctr) — the
        synchronous read (carry + scalars); ``ctr`` is the harvested
        (D, NSLOTS) counter block when device counters are on, else None
        (same dispatch-boundary readback as the scalars)."""
        return (self.carry(out),) + self.read_scalars(out)

    def residual_batch(self, state) -> dict:
        pool_vals, pool_aux, size, _ = state
        rv, ra = self._residual(pool_vals, pool_aux)
        return self._collect(np.asarray(rv), np.asarray(ra), np.asarray(size))

    def full_batch(self, state) -> dict:
        """Every live node of every shard (saturation-fallback download)."""
        pool_vals, pool_aux, size, _ = state
        sizes = np.asarray(size)
        lanes = self.inner.pool_shapes()[0][1]
        rv = np.asarray(pool_vals).reshape(self.D, -1, lanes)
        ra = np.asarray(pool_aux).reshape(self.D, self.inner.pool_rows)
        return self._collect(rv, ra, sizes)

    def _collect(self, rv, ra, sizes) -> dict:
        name_v, _, node_v = self.inner.pool_fields[0]
        name_a = self.inner.pool_fields[1][0]
        fields = self.problem.node_fields()
        width = self.inner.row_width
        parts_v = [node_rows(rv[w], node_v, width)[: sizes[w]]
                   for w in range(self.D)]
        parts_a = [ra[w, : sizes[w]] for w in range(self.D)]
        batch = {
            name_v: np.concatenate(parts_v).astype(fields[name_v][1]),
            name_a: np.concatenate(parts_a).astype(fields[name_a][1]),
        }
        return self.inner.derive_fields(batch)


def get_mesh_program(problem, mesh, m: int, M: int, K: int, rounds: int,
                     T: int, capacity: int) -> _MeshResidentProgram:
    """The one per-problem cache of compiled SPMD mesh programs (a rebuild
    costs ~30s on TPU), shared by the mesh and dist_mesh tiers. Keys carry
    the env-dependent kernel-routing decisions (`routing_cache_token`) and
    the obs state, so a knob flip rebuilds instead of silently reusing a
    stale step — and the adaptive-K ladder (TTS_K=auto) resolves each rung
    through this cache, so re-selecting a rung is a hit, not a recompile."""
    cache = getattr(problem, "_mesh_programs", None)
    if cache is None:
        cache = problem._mesh_programs = {}
    from ..ops.pfsp_device import routing_cache_token

    key = (
        tuple(id(d) for d in mesh.devices.flat), mesh.devices.shape,
        m, M, K, rounds, T, capacity,
        routing_cache_token(problem, mesh.devices.flat[0]),
        obs_counters.device_counters_enabled(),
        obs_phases.phase_profiling_enabled(),
    )
    program = cache.get(key)
    if program is None:
        program = cache[key] = _MeshResidentProgram(
            problem, mesh, m, M, K, rounds, T, capacity
        )
    return program


def mesh_resident_search(
    problem: Problem,
    m: int = 25,
    M: int = 16384,
    K: int | str = 16,
    rounds: int = 2,
    T: int | None = None,
    capacity: int | None = None,
    mesh=None,
    devices=None,
    D: int | None = None,
    mp: int = 1,
    initial_best: int | None = None,
    warmup_target: int | None = None,
    max_steps: int | None = None,
    checkpoint_path: str | None = None,
    checkpoint_interval_s: float = 60.0,
    resume_from: str | None = None,
    guard: bool | None = None,
    yield_fn=None,
) -> SearchResult:
    """SPMD multi-device search: 3 phases like every tier, with phase 2 one
    sharded resident program (see module docstring). Checkpoint/resume as in
    ``resident_search`` (a mesh snapshot merges every shard's frontier, and a
    resumed frontier re-partitions stride-D, so D may change across runs);
    ``yield_fn`` is the same cooperative-preemption seam (a True return
    cuts the run at the next dispatch boundary like a ``max_steps``
    cutoff — the serve daemon's scheduler rides it).
    ``guard``/TTS_GUARD=1 asserts zero recompiles + zero implicit transfers
    per steady-state dispatch, exactly as in ``resident_search``. Dispatch
    is pipelined (TTS_PIPELINE) and ``K="auto"``/TTS_K=auto enables the
    adaptive ladder with the tighter mesh target band — see
    ``resident_search`` and engine/pipeline.py."""
    import jax
    from jax.sharding import Mesh

    if mesh is not None and mp != 1:
        raise ValueError(
            "pass either mesh= (with its own (dp, mp) axes) or mp=, not "
            "both — mp would be silently ignored"
        )
    if mesh is None:
        if devices is None:
            devices = jax.devices()
        if D is None:
            D = max(1, len(devices) // mp)
        mesh = make_dp_mp_mesh(devices, D, mp)
    D = int(mesh.shape[mesh.axis_names[0]])
    n = problem.child_slots
    from ..engine.resident import resolve_capacity

    capacity, M = resolve_capacity(problem, M, capacity)
    if T is None:
        T = max(2 * m, min(M, 8192))

    best = (
        initial_best
        if initial_best is not None
        else getattr(problem, "initial_ub", INF_BOUND)
    )
    from ..engine import checkpoint as ckpt

    pool = SoAPool(problem.node_fields())
    diagnostics = Diagnostics()
    phases: list[PhaseStats] = []
    t0 = time.perf_counter()

    # -- phase 1: host warm-up to D*m (`nqueens_multigpu_chpl.chpl:173`),
    # or checkpoint restore --------------------------------------------------
    if resume_from is not None:
        saved = ckpt.load(resume_from, problem)
        pool.push_back_bulk(saved.batch)
        tree1, sol1 = saved.tree, saved.sol
        # Keep the tighter incumbent (cf. resident_search resume).
        best = min(best, saved.best)
        # The resumed frontier re-partitions stride-D; grow the per-shard
        # capacity so the largest shard plus one fan-out fits even when D
        # shrank since the checkpoint.
        capacity = max(capacity, -(-pool.size // D) + 2 * M * n)
    else:
        pool.push_back(index_batch(problem.root(), 0))
        target = D * m if warmup_target is None else warmup_target
        tree1, sol1, best = warmup(problem, pool, best, target)
    t1 = time.perf_counter()
    phases.append(PhaseStats(t1 - t0, tree1, sol1))
    ev.counter("explored", tree=tree1, sol=sol1, phase=1)

    # -- phase 2: SPMD resident loop ---------------------------------------
    from ..engine.pipeline import (
        AdaptiveK,
        DispatchQueue,
        MESH_TARGET,
        resolve_k,
        resolve_pipeline_depth,
        resolve_target_band,
    )

    k_auto, k_value = resolve_k(K, default_max=16)
    # The mesh tier's K is bounded by balancing responsiveness: incumbent
    # pmin folds and diffusion rounds happen per dispatch, so the ladder
    # targets a tighter host period than the single-device tier — and that
    # band IS the tier's steal (diffusion) period. With TTS_COSTMODEL it
    # resolves from the measured dispatch-latency fit instead of the
    # fixed default (engine/pipeline.py resolve_target_band).
    band, band_src = resolve_target_band(
        "mesh", MESH_TARGET, problem, topology=f"mesh-D{D}"
    )
    ctl = AdaptiveK(k_value, target=band) if k_auto else None
    depth = resolve_pipeline_depth()
    program = get_mesh_program(problem, mesh, m, M,
                               ctl.K if ctl else k_value, rounds, T, capacity)

    def upload(warm_batch):
        # Static stride-D partition (`nqueens_multigpu_chpl.chpl:221-225`).
        shards = [{k: v[w::D] for k, v in warm_batch.items()} for w in range(D)]
        return program.init_state(shards, best)

    state = upload(pool.as_batch())
    pool.clear()
    diagnostics.host_to_device += 1

    tree2 = 0
    sol2 = 0
    per_worker = np.zeros(D, dtype=np.int64)
    sizes = np.zeros(D, dtype=np.int32)
    prev_sizes = None
    offloader = None

    from ..analysis.guard import SteadyStateGuard, guard_enabled

    genabled = guard_enabled(guard)
    guards: dict[int, SteadyStateGuard] = {}

    def guard_of(prog) -> SteadyStateGuard:
        g = guards.get(id(prog))
        if g is None:
            g = guards[id(prog)] = SteadyStateGuard(
                prog._step, "mesh-resident step", enabled=genabled
            )
        return g

    ctr_total: dict | None = None
    ph_total: dict | None = None  # per-phase ns totals (TTS_PHASEPROF=1)
    fb_tree = fb_sol = 0  # saturation-fallback host increments (obs parity)
    prev_best = best
    # Anytime quality: None on the off path; otherwise records the
    # incumbent trajectory from scalars consume() already reads.
    qt = obs_quality.tracker(problem)
    n_disp = 0  # completed-dispatch sequence (flight-recorder registry)
    queue = DispatchQueue(depth)

    def obs_result() -> dict | None:
        parts = {}
        if ctr_total is not None:
            parts["device_counters"] = ctr_total
        if ph_total is not None:
            parts["device_phases"] = ph_total
        return parts or None

    def enqueue() -> None:
        nonlocal state
        t_enq = ev.now_us()
        with guard_of(program).step():
            out = program.step(state)
        state = program.carry(out)
        queue.push(out, t_enq)

    def consume(out, t_enq) -> tuple[int, int, int]:
        nonlocal tree2, sol2, sizes, best, ctr_total, ph_total, prev_best
        nonlocal per_worker, n_disp
        t_wait = ev.now_us()
        ti, si, cy, sizes, best, tree_vec, ctr = program.read_scalars(out)
        phb = program.read_phase_block(out)
        tree2 += ti
        sol2 += si
        n_disp += 1
        per_worker += tree_vec.astype(np.int64)
        diagnostics.kernel_launches += cy
        if ctr is not None:
            ctr_total = obs_counters.merge_host(ctr_total, ctr)
        if phb is not None:
            ph_total = obs_phases.merge_host(ph_total, phb)
        fr.heartbeat("mesh", seq=n_disp, cycles=cy, size=int(sizes.sum()),
                     best=best, tree=tree2, sol=sol2, depth=depth,
                     K=program.K, inflight=len(queue),
                     phases=ph_total)
        if qt is not None:
            qt.observe(best, n_disp, tree1 + tree2)
        if ev.enabled():
            now = ev.now_us()
            ev.emit("dispatch", ph="X", ts=t_enq,
                    dur=max(0.0, now - t_enq), args={
                        "cycles": cy, "tree": ti, "sol": si,
                        "size": int(sizes.sum()), "best": best,
                        "shard_sizes": sizes.tolist(),
                        "enqueue_us": t_enq, "read_wait_us": now - t_wait,
                        "pipeline_depth": depth,
                    })
            if ctr is not None:
                ev.counter("device_counters", **obs_counters.as_args(ctr))
            if phb is not None:
                ev.counter("device_phases", **obs_phases.as_args(phb))
            if best < prev_best:
                ev.emit("incumbent", args={"best": best})
        prev_best = best
        return ti, si, cy

    def drain_queue() -> tuple[int, int]:
        dt = ds = 0
        for out, t_enq in queue.drain():
            ti, si, _ = consume(out, t_enq)
            dt += ti
            ds += si
        return dt, ds

    def snapshot_fn():
        batch = program.full_batch(state)
        diagnostics.device_to_host += 1
        return batch, best

    controller = ckpt.RunController(
        problem, checkpoint_path, checkpoint_interval_s, max_steps,
        snapshot_fn, drain_fn=drain_queue, yield_fn=yield_fn,
    )

    fr.arm("mesh")
    ev.emit("pipeline", args={
        "depth": depth, "K": program.K, "k_auto": k_auto, "tier": "mesh",
    })
    if band_src is not None:
        ev.emit("costmodel", args={
            "source": band_src, "lo_ms": round(1e3 * band[0], 1),
            "hi_ms": round(1e3 * band[1], 1), "tier": "mesh",
        })
    last_ready = time.monotonic()

    while True:
        while not queue.full:
            enqueue()
        out, t_enq = queue.pop()
        ti, si, cy = consume(out, t_enq)
        now = time.monotonic()
        period, last_ready = now - last_ready, now
        if int(sizes.max()) < m:
            drain_queue()  # speculative no-ops; state passes through
            break
        if controller.after_step(tree1 + tree2, sol1 + sol2):
            drain_queue()  # no-op if the cutoff save already drained
            t2 = time.perf_counter()
            phases.append(PhaseStats(t2 - t1, tree2, sol2))
            ev.emit("checkpoint", args={"cutoff": True})
            _emit_device_explored(ctr_total, tree2, sol2, fb_tree, fb_sol)
            return SearchResult(
                explored_tree=tree1 + tree2,
                explored_sol=sol1 + sol2,
                best=best,
                elapsed=t2 - t0,
                phases=phases,
                diagnostics=diagnostics,
                per_worker_tree=per_worker.tolist(),
                complete=False,
                steps=controller.steps,
                compact=program.inner.compact,
                compact_auto=program.inner.compact_auto,
                megakernel=program.inner.megakernel.state,
                megakernel_auto=program.inner.megakernel.auto,
                megakernel_reason=program.inner.megakernel.reason,
                megakernel_mt=program.inner.megakernel.mt or None,
                megakernel_tiled=program.inner.megakernel.tiled,
                kernel_backend=program.inner.kernel_backend,
                kernel_interpret=program.inner.kernel_interpret,
                pipeline_depth=depth,
                k_resolved=program.K,
                k_auto=k_auto,
                obs=obs_result(),
                phase_profile=ph_total,
                quality=qt.result() if qt is not None else None,
            )
        if ctl is not None and cy > 0 and ctl.observe(period, cy):
            drain_queue()
            program = get_mesh_program(problem, mesh, m, M, ctl.K, rounds,
                                       T, capacity)
            ev.emit("k_resize", args={"K": program.K})
            last_ready = time.monotonic()
            prev_sizes = None
            if int(sizes.max()) < m:
                break
            continue
        if cy == 0 and prev_sizes is not None and np.array_equal(sizes, prev_sizes):
            # Saturation: no shard ran a cycle and balancing moved nothing.
            # Fall back to host offload cycles (same guarantee as the
            # single-device tier) until the frontier fits again.
            from ..engine.device import DeviceOffloader, bucket_size

            drain_queue()  # saturated speculative dispatches are no-ops too
            t_fb = ev.now_us()
            fb_tree0, fb_sol0 = tree2, sol2
            pool.reset_from(program.full_batch(state))
            diagnostics.device_to_host += 1
            if offloader is None:
                offloader = DeviceOffloader(problem, program.mesh.devices.flat[0])
            chunk_buf = problem.empty_batch(M)
            fits = D * max(0, capacity - 2 * M * n)
            while pool.size >= m and pool.size > fits:
                count = pool.pop_back_bulk(m, M, chunk_buf)
                if count == 0:
                    break
                bucket = bucket_size(count, m, M)
                snapshot = {k: v[:count].copy() for k, v in chunk_buf.items()}
                dev = offloader.dispatch(snapshot, count, bucket, best)
                res = problem.generate_children(
                    snapshot, count, offloader.collect(dev), best
                )
                tree2 += res.tree_inc
                sol2 += res.sol_inc
                best = res.best
                pool.push_back_bulk(res.children)
            diagnostics.kernel_launches += offloader.diagnostics.kernel_launches
            diagnostics.host_to_device += offloader.diagnostics.host_to_device
            diagnostics.device_to_host += offloader.diagnostics.device_to_host
            offloader.diagnostics = Diagnostics()
            state = upload(pool.as_batch())
            pool.clear()
            diagnostics.host_to_device += 1
            # Sanctioned re-upload; next dispatch is a fresh warm one.
            guard_of(program).rearm()
            last_ready = time.monotonic()
            fb_tree += tree2 - fb_tree0
            fb_sol += sol2 - fb_sol0
            ev.complete("overflow_fallback", t_fb, args={
                "tree": tree2 - fb_tree0, "sol": sol2 - fb_sol0,
            })
            prev_sizes = None
            continue
        prev_sizes = sizes
    batch = program.residual_batch(state)
    diagnostics.device_to_host += 1
    pool.reset_from(batch)
    t2 = time.perf_counter()
    phases.append(PhaseStats(t2 - t1, tree2, sol2))
    _emit_device_explored(ctr_total, tree2, sol2, fb_tree, fb_sol)

    # -- phase 3: host drain ------------------------------------------------
    tree3, sol3, best = drain(problem, pool, best)
    t3 = time.perf_counter()
    phases.append(PhaseStats(t3 - t2, tree3, sol3))
    ev.counter("explored", tree=tree3, sol=sol3, phase=3)
    if qt is not None:
        # The host drain can improve the incumbent one last time.
        qt.observe(best, n_disp, tree1 + tree2 + tree3)

    return SearchResult(
        explored_tree=tree1 + tree2 + tree3,
        explored_sol=sol1 + sol2 + sol3,
        best=best,
        elapsed=t3 - t0,
        phases=phases,
        diagnostics=diagnostics,
        per_worker_tree=per_worker.tolist(),
        steps=controller.steps,
        compact=program.inner.compact,
        compact_auto=program.inner.compact_auto,
        megakernel=program.inner.megakernel.state,
        megakernel_auto=program.inner.megakernel.auto,
        megakernel_reason=program.inner.megakernel.reason,
        megakernel_mt=program.inner.megakernel.mt or None,
        megakernel_tiled=program.inner.megakernel.tiled,
        kernel_backend=program.inner.kernel_backend,
        kernel_interpret=program.inner.kernel_interpret,
        pipeline_depth=depth,
        k_resolved=program.K,
        k_auto=k_auto,
        obs=obs_result(),
        phase_profile=ph_total,
        quality=qt.result() if qt is not None else None,
    )
