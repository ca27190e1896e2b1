"""Device-resident search engine — the fully TPU-native tier.

The reference's offload loop round-trips host<->device once per chunk
(`pfsp_gpu_chpl.chpl:373-396`: H2D parents, kernel, D2H bounds, host
prune/branch).  On TPU the dominant cost of that design is not the kernel but
the dispatch + transfer latency of every cycle (hundreds of ms over a remote
runtime, vs sub-ms of device compute for a 64k-node chunk).  This engine
inverts the ownership: the **pool itself lives in HBM** as fixed-capacity SoA
arrays, and one jitted step advances the search by up to K chunk cycles
inside a `lax.while_loop` — pop, evaluate, prune, compact, push are all
device ops; the host only re-dispatches the step and reads back four scalars
every K cycles.

Semantics per cycle are exactly the reference's chunk cycle (SURVEY.md
Appendix A):

  * pop the back `cnt = min(size, M)` nodes, only while `size >= m`;
  * evaluate all `cnt * child_slots` children in one batch;
  * PFSP: a child with depth == jobs is a leaf -> exploredSol++, folds the
    incumbent with a min; a non-leaf child is pushed iff `bound < best`
    strictly, counting exploredTree (`pfsp_chpl.chpl:100-111`);
  * N-Queens: a parent popped at depth == N counts one solution; safe
    children are always pushed (no pruning), depth-N leaves included
    (`nqueens_chpl.chpl:70-89`).

The push is a fused prune+push (the device-side equivalent of the
prune+compact improvement suggested in SURVEY.md §7.3): survivors are
ranked with hierarchical prefix sums, the rank map is inverted by the
compaction mode baked in at build time (`ops/compaction.py` — scatter /
sort / binary-search / shift-based dense, `TTS_COMPACT=auto` picks per
problem shape), and each surviving child row is rebuilt *at its
destination slot* from one gather of its parent's (row, aux) — the
(M, n, n) child cube is materialized only on the rare overflow fallback.

Capacity safety: the loop only runs a cycle while `size + M*child_slots <=
capacity`, so a cycle can never lose children.  If the pool outgrows that
headroom the step returns early and the host falls back to classic offload
cycles (pop via the host pool) until the frontier shrinks — correctness never
depends on the capacity heuristic.
"""

from __future__ import annotations

import time

import numpy as np

from ..obs import counters as obs_counters
from ..obs import events as ev
from ..obs import flightrec as fr
from ..obs import phases as obs_phases
from ..obs import quality as obs_quality
from ..obs import roofline as obs_roofline
from ..pool import SoAPool
from ..problems.base import INF_BOUND, Problem
from ..problems.nqueens import NQueensProblem
from ..problems.pfsp.problem import PFSPProblem
from .device import DeviceOffloader, bucket_size, drain, warmup
from .results import Diagnostics, PhaseStats, SearchResult


def _pool_int_dtype(n: int):
    import jax.numpy as jnp

    if n <= 127:
        return jnp.int8
    if n <= 32767:
        return jnp.int16
    return jnp.int32


def _swap_children(chunk_vals, depth):
    """All single-swap children of each parent row.

    chunk_vals: (M, n) permutation rows; depth: (M,) swap position.
    Returns (M, n, n): row (i, k) = parent i with positions depth_i and k
    swapped (identity when k == depth_i) — the branching rule shared by both
    problems (`pfsp_chpl.chpl:91-96`, `nqueens_chpl.chpl:78-87`).
    """
    import jax.numpy as jnp

    # A child differs from its parent at exactly two positions, so the cube
    # is three elementwise selects over (M, n, n) — no gather (a full
    # take_along_axis over the cube costs ~40x more on TPU).
    iota = jnp.arange(chunk_vals.shape[1], dtype=jnp.int32)[None, None, :]
    kcol = iota.transpose(0, 2, 1)  # (1, n, 1)
    d = depth[:, None, None]
    val_at_k = chunk_vals[:, :, None]  # parent[i, k] per (i, k, *)
    val_at_d = jnp.take_along_axis(chunk_vals, depth[:, None], axis=1)[:, :, None]
    base = chunk_vals[:, None, :]  # parent[i, *, j]
    return jnp.where(iota == d, val_at_k, jnp.where(iota == kcol, val_at_d, base))


def _compact_ids(keep, S: int, mode: str | None = None):
    """Stream-compaction ids of the surviving (parent, slot) pairs — the
    engine-side entry point for `ops/compaction.compact_ids` (which owns
    the four rank inversions and their contract).  ``mode=None`` resolves
    the ``TTS_COMPACT`` knob without problem context (bare/oracle calls in
    tests); the resident programs pass their baked-in resolved mode."""
    from ..ops.compaction import compact_ids, resolve_compact_mode

    if mode is None:
        mode = resolve_compact_mode()
    return compact_ids(keep, S, mode)


def node_rows(host: np.ndarray, shape: tuple, width: int) -> np.ndarray:
    """A pool array's leading rows as nodes: the value pool's rows split
    into ``width``-lane slots, one node each, with their dead lanes
    dropped (``shape`` is the field's node shape from ``pool_fields``;
    `megakernel.push_layout`)."""
    return host.reshape(-1, width)[:, : shape[0]] if shape else host


class _ResidentProgram:
    """Compiled device-resident step for one (problem, m, M, K, capacity).

    Pool layout (both problems): ``vals`` (C, n) — the permutation rows —
    plus one scalar ``aux`` column (C,) (PFSP: limit1; N-Queens: depth);
    where the TPU cycle pushes, ``vals`` holds several nodes to a
    lane-dense row (`pool_shapes`).
    Subclasses provide the chunk evaluator and the swap position.
    """

    def __init__(self, problem, m: int, M: int, K: int, capacity: int, device,
                 mp_axis: str | None = None, mp_size: int = 1,
                 allow_staged: bool = True):
        import jax

        self.problem = problem
        self.m = m
        self.M = M
        self.capacity = capacity
        # Mesh-resident mp sharding of the lb2 pair loop (read by
        # _make_eval); harmless None/1 everywhere else.
        self.mp_axis = mp_axis
        self.mp_size = mp_size
        # Staged lb2 (lb1 prefilter + compacted self bound) — disabled by
        # the mesh tier (the compaction runs inside shard_map; unvalidated
        # there) and anywhere the evaluator must stay single-pass.
        self.allow_staged = allow_staged
        n = problem.child_slots
        # Counter headroom: every step call accumulates at most K*M*n into
        # int32 counters.
        self.K = max(1, min(K, (2**31 - 1) // max(1, M * n)))
        self.device = device if device is not None else jax.devices()[0]
        # Survivor-path selection (ops/compaction.py): resolved once at
        # build time from the TTS_COMPACT knob / auto policy and baked into
        # the compiled step.  Surfaced through SearchResult.compact so a
        # stats line can prove which path ran.
        from ..ops.compaction import compact_mode, resolve_compact_mode

        self.compact = resolve_compact_mode(problem, M, n, self.device)
        self.compact_auto = compact_mode() == "auto"
        # The while condition reserves exactly M*n rows of headroom, so the
        # survivor budget must never exceed it (a small M would otherwise
        # make the fused-path write overrun the reservation and corrupt
        # live rows).
        self.S = min(max(64 * n, M * n // self.survivor_budget_div), M * n)
        # On-device cycle counters (TTS_OBS=1, obs/counters.py): baked in at
        # build time — when off, the carry/body/jaxpr are byte-identical to
        # a counter-free build (compiled out, not branched). _make_program
        # keys its cache on this flag.
        self.obs = obs_counters.device_counters_enabled()
        # Per-phase cycle clocks (TTS_PHASEPROF=1, obs/phases.py): a
        # separate cache-keyed program variant for `tts profile` — when
        # off, nothing below is traced and the jaxpr is byte-identical.
        self.phaseprof = obs_phases.phase_profiling_enabled()
        # One-kernel cycle (TTS_MEGAKERNEL, ops/megakernel.py): the whole
        # pop->bound->prune->compact->push cycle as a single pallas_call,
        # resolved once at build time like the compact auto policy (TPU +
        # small-M + VMEM fit; correctness refusals recorded in
        # .megakernel.reason for the banner/SearchResult). The raw knob
        # rides routing_cache_token, so a flip rebuilds; when off, nothing
        # in loop_fns traces differently (contract megakernel-off-identity).
        from ..ops import megakernel as MK

        self.megakernel = MK.resolve(problem, M, self.device,
                                     mp_axis=mp_axis, mp_size=mp_size)
        # The pool as the device holds it: the TPU cycle pushes whole
        # 128-lane rows by DMA, so where it is armed the value pool holds
        # `row_pack` nodes to a row, each in a `row_width`-lane slot, and
        # the pools carry slack nodes (MK.push_layout); elsewhere
        # (capacity, n) exactly.
        self.row_width, self.row_pack, self.pool_rows = MK.push_layout(
            self.megakernel, n, capacity)
        # The route this build runs: "tpu" when the cycle or the
        # evaluator routes to a Pallas kernel, "jnp" when neither does,
        # and whether those kernels run in interpret mode. Surfaced
        # through SearchResult.
        from ..ops import pallas_kernels as PK

        kernels = self.megakernel.enabled or self._evaluator_uses_kernels()
        self.kernel_backend = "tpu" if kernels else "jnp"
        self.kernel_interpret = kernels and (
            self.megakernel.interpret if self.megakernel.enabled
            else PK.pallas_interpret())
        self._step = self._build()
        self._fill = self._build_fill()

    def _evaluator_uses_kernels(self) -> bool:
        from ..ops import pallas_kernels as PK

        return PK.use_pallas(self.device)

    def loop_fns(self, K: int | None = None, sharded: bool = False):
        """(cond, body) of the K-cycle device loop over the carry
        ``(pool_vals, pool_aux, size, best, tree, sol, cycles)`` — reused by
        the single-device step and, per shard (``sharded``), by the
        mesh-resident tier.
        With ``self.obs`` the carry gains one trailing ``(NSLOTS,)`` int32
        counter block (obs/counters.py), accumulated per cycle and harvested
        at the dispatch boundary; with ``self.phaseprof`` a final
        ``(phases.NSLOTS + 1,)`` uint32 phase-clock block rides behind it
        (obs/phases.py — clock reads fenced by ``lax.optimization_barrier``
        at the pop/eval/compact/push boundaries); when both are off the
        carry is exactly the 7-tuple above."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from ..ops.compaction import shift_compact, survivor_ranks

        n = self.problem.child_slots
        m, M, C = self.m, self.M, self.capacity
        K = self.K if K is None else K
        Mn = M * n
        obs = self.obs
        phaseprof = self.phaseprof
        S = self.S
        mode = self.compact
        W, kq = self.row_width, self.row_pack
        vals_dt = self.pool_fields[0][1]
        aux_dt = self.pool_fields[1][1]
        evaluate = self._make_eval()
        swap_of = self._swap_pos
        mk_cycle = None
        if self.megakernel.enabled:
            from ..ops import megakernel as MK

            mk_cycle = MK.make_cycle(self.problem, M, self.device,
                                     self.megakernel, sharded=sharded)

        # tts-lint: traced (returned to lax.while_loop via loop_fns)
        def body(carry):
            pool_vals, pool_aux, size, best, tree, sol, cycles = carry[:7]
            ctr = carry[7] if obs else None
            ph = carry[-1] if phaseprof else None
            if phaseprof:
                # Cycle start: the gap since the previous boundary (cond +
                # carry plumbing, or the pre-loop seed) is `loop` time;
                # the reading stored here is the cycle's t0 for `total`.
                ph, (pool_vals, pool_aux, size) = obs_phases.boundary(
                    ph, "loop", pool_vals, pool_aux, size
                )
                t_cycle0 = ph[obs_phases.TPREV]
            # XLA scopes (tts_pop, tts_push) name the loop's own ops in a
            # profiler trace; they are op metadata only.
            with jax.named_scope("tts_pop"):
                cnt = jnp.minimum(size, M)
                start = size - cnt
                start2 = jnp.clip(start, 0, C - M)
                idx = start2 + jnp.arange(M, dtype=jnp.int32)
                valid = (idx >= start) & (idx < size)
                if kq == 1:
                    vals8_c = lax.dynamic_slice(pool_vals, (start2, 0),
                                                (M, W))
                else:
                    # kq nodes to a pool row: slice the rows that hold
                    # the chunk, split them into node slots, and slice
                    # the chunk out of those.
                    L = -(-M // kq) + 1
                    blk = lax.dynamic_slice(pool_vals, (start2 // kq, 0),
                                            (L, kq * W))
                    vals8_c = lax.dynamic_slice(
                        blk.reshape(L * kq, W), (start2 % kq, 0), (M, W))
                if W != n:
                    vals8_c = vals8_c[:, :n]
                vals_c = vals8_c.astype(jnp.int32)
                aux_c = lax.dynamic_slice(pool_aux, (start2,),
                                          (M,)).astype(jnp.int32)
                size = size - cnt
            if phaseprof:
                ph, (vals8_c, vals_c, aux_c, size, valid) = obs_phases.boundary(
                    ph, "pop", vals8_c, vals_c, aux_c, size, valid
                )

            if mk_cycle is not None:
                # Armed one-kernel cycle (ops/megakernel.py): bound + prune
                # + shift-compact + push run inside ONE pallas_call, which
                # places the survivors at [size, size + tree_inc) of the
                # pools in their own dtypes, by DMA into the aliased pools.
                # The engine only advances size. The phase profiler
                # reports the collapse honestly: everything lands in
                # `eval`, and the pop+eval+...+overflow == total telescope
                # still holds.
                pool_vals, pool_aux, tree_inc, sol_inc, best, push_rows = (
                    mk_cycle(pool_vals, pool_aux, size, vals_c, aux_c, valid,
                             best))
                fits = tree_inc <= S  # survivor-budget overflow counter
                size = size + tree_inc
                if phaseprof:
                    ph, (pool_vals, pool_aux, size) = obs_phases.boundary(
                        ph, "eval", pool_vals, pool_aux, size
                    )
                    ph = obs_phases.close_total(ph, t_cycle0)
                out = (
                    pool_vals, pool_aux, size, best,
                    tree + tree_inc, sol + sol_inc, cycles + 1,
                )
                if obs:
                    # push_rows: the pool rows the cycle's push wrote.
                    ctr = obs_counters.update(
                        ctr, cnt, n, tree_inc, sol_inc, fits, size,
                        push_rows,
                    )
                    out = out + (ctr,)
                if phaseprof:
                    out = out + (ph,)
                return out

            keep, sol_inc, best = evaluate(vals_c, aux_c, valid, best)
            d = swap_of(aux_c)  # (M,) swap position per parent
            if phaseprof:
                ph, (keep, sol_inc, best, d) = obs_phases.boundary(
                    ph, "eval", keep, sol_inc, best, d
                )

            ids, tree_inc = _compact_ids(keep, S, mode)
            fits = tree_inc <= S
            if phaseprof:
                ph, (ids, tree_inc, fits) = obs_phases.boundary(
                    ph, "compact", ids, tree_inc, fits
                )

            def small(pool_vals, pool_aux):
                # Fused prune+push: ONE gather of the survivor budget —
                # parent row and parent aux ride the same augmented
                # (M, n+1) gather (aux fits the pool value dtype: limit1
                # in [-1, n) and depth in [0, N] are in range) — and the
                # child row is rebuilt at its destination slot by pure
                # selects over the gathered row (the `_swap_children`
                # structure: a child differs from its parent at exactly
                # the two swapped positions), so the (M, n, n) child cube
                # is never materialized and never gathered twice.  Rows
                # beyond tree_inc are garbage past the new size (dead by
                # the pool contract).
                pi = ids // n
                kj = ids % n
                aug = jnp.concatenate(
                    [vals8_c, aux_c.astype(vals_dt)[:, None]], axis=1
                )
                g = aug[pi]  # (S, n+1): the cycle's one child-value gather
                rows = g[:, :n]
                pa = g[:, n].astype(jnp.int32)  # parent aux
                dp = swap_of(pa)
                iota = jnp.arange(n, dtype=jnp.int32)[None, :]
                ohd = iota == dp[:, None]
                ohk = iota == kj[:, None]
                # One-hot extraction instead of take_along_axis: exactly
                # one lane is selected per row, so the sum is exact.
                v_k = jnp.sum(jnp.where(ohk, rows, 0), axis=1,
                              dtype=jnp.int32)
                v_d = jnp.sum(jnp.where(ohd, rows, 0), axis=1,
                              dtype=jnp.int32)
                crows = jnp.where(
                    ohd,
                    v_k[:, None].astype(vals_dt),
                    jnp.where(ohk, v_d[:, None].astype(vals_dt), rows),
                )
                pool_vals = lax.dynamic_update_slice(
                    pool_vals, crows, (size, jnp.int32(0))
                )
                pool_aux = lax.dynamic_update_slice(
                    pool_aux, (pa + 1).astype(aux_dt), (size,)
                )
                return pool_vals, pool_aux

            def big(pool_vals, pool_aux):
                # Overflow fallback (rare — only when a chunk keeps more
                # than S children): materialize the child cube and place
                # all survivors at once.
                child = _swap_children(vals_c, d).astype(vals_dt)
                ranks, _ = survivor_ranks(keep)
                caux = jnp.repeat(aux_c + 1, n).astype(aux_dt)
                if mode == "dense":
                    # Scatter-free overflow: shift-compact the child rows
                    # themselves (ops/compaction.py), then one contiguous
                    # write of the reserved Mn headroom — rows past
                    # tree_inc are dead by the pool contract.
                    flat_idx = jnp.arange(Mn, dtype=jnp.int32)
                    dist = jnp.where(
                        keep.reshape(Mn), flat_idx - ranks.reshape(Mn), 0
                    )
                    rowsc, auxc = shift_compact(
                        dist, (child.reshape(Mn, n), caux)
                    )
                    pool_vals = lax.dynamic_update_slice(
                        pool_vals, rowsc, (size, jnp.int32(0))
                    )
                    pool_aux = lax.dynamic_update_slice(
                        pool_aux, auxc, (size,)
                    )
                    return pool_vals, pool_aux
                dest = jnp.where(keep.reshape(Mn), size + ranks.reshape(Mn), C)
                pool_vals = pool_vals.at[dest].set(
                    child.reshape(Mn, n), mode="drop"
                )
                pool_aux = pool_aux.at[dest].set(caux, mode="drop")
                return pool_vals, pool_aux

            with jax.named_scope("tts_push"):
                pool_vals, pool_aux = lax.cond(fits, small, big, pool_vals,
                                               pool_aux)
            size = size + tree_inc
            if phaseprof:
                # The cond ran exactly one branch: charge its time to the
                # slot the predicate names, then close the cycle's total
                # (`pop+eval+compact+push+overflow == total` telescopes).
                slot = jnp.where(
                    fits,
                    jnp.int32(obs_phases.IDX["push"]),
                    jnp.int32(obs_phases.IDX["overflow"]),
                )
                ph, (pool_vals, pool_aux, size) = obs_phases.boundary(
                    ph, slot, pool_vals, pool_aux, size, tag="push"
                )
                ph = obs_phases.close_total(ph, t_cycle0)
            out = (
                pool_vals, pool_aux, size, best,
                tree + tree_inc, sol + sol_inc, cycles + 1,
            )
            if obs:
                # push_rows: rows the push stage processed this cycle —
                # the maintenance-work series (the fused path always
                # touches its full S budget; the overflow path the whole
                # Mn reservation), vs the evaluator's cnt*n child evals.
                push_rows = jnp.where(fits, jnp.int32(S), jnp.int32(Mn))
                ctr = obs_counters.update(
                    ctr, cnt, n, tree_inc, sol_inc, fits, size, push_rows
                )
                out = out + (ctr,)
            if phaseprof:
                out = out + (ph,)
            return out

        # tts-lint: traced (returned to lax.while_loop via loop_fns)
        def cond(carry):
            size, cycles = carry[2], carry[6]
            return (size >= m) & (size + Mn <= C) & (cycles < K)

        return cond, body

    def _build(self):
        import jax
        import jax.numpy as jnp
        from jax import lax

        cond, body = self.loop_fns()
        obs = self.obs
        phaseprof = self.phaseprof

        def step(pool_vals, pool_aux, size, best):
            zero = jnp.int32(0)
            init = (pool_vals, pool_aux, size, best, zero, zero, zero)
            if obs:
                init = init + (obs_counters.init_block(),)
            if phaseprof:
                # Pre-loop clock seed: base of the first cycle's `loop`
                # delta (dep on `size` orders it after the inputs).
                init = init + (obs_phases.seed_block(size.astype(jnp.uint32)),)
            return lax.while_loop(cond, body, init)

        return jax.jit(step, donate_argnums=(0, 1))

    # -- state layout: (pool..., size, best, tree_inc, sol_inc, cycles) ----

    def pool_shapes(self) -> list[tuple[int, ...]]:
        """The device shape of each ``pool_fields`` array: ``pool_rows``
        nodes, the value pool ``row_pack`` nodes to a row of
        ``row_width``-lane slots (lanes past the node's own are dead;
        `megakernel.push_layout`)."""
        kq, W = self.row_pack, self.row_width
        return [(self.pool_rows // kq, kq * W) if shape else (self.pool_rows,)
                for _name, _dt, shape in self.pool_fields]

    def pack_rows(self, field: int, rows: np.ndarray, nodes: int) -> np.ndarray:
        """Host node rows of pool field ``field`` laid out as the first
        ``nodes`` nodes of its device array (`pool_shapes`), zero past
        them."""
        _name, dtype, shape = self.pool_fields[field]
        k = rows.shape[0]
        if not shape:
            buf = np.zeros(nodes, dtype)
            buf[:k] = rows
            return buf
        kq, W = self.row_pack, self.row_width
        buf = np.zeros((nodes, W), dtype)
        buf[:k, : shape[0]] = rows
        return buf.reshape(nodes // kq, kq * W)

    def _build_fill(self):
        """``fill(*heads) -> pools``: the zeroed pools with each field's
        packed leading rows written in, as one program."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        shapes = self.pool_shapes()
        dtypes = [dt for _name, dt, _shape in self.pool_fields]

        def fill(*heads):
            return tuple(
                lax.dynamic_update_slice(jnp.zeros(sh, dt), h, (0,) * len(sh))
                for sh, dt, h in zip(shapes, dtypes, heads))

        return jax.jit(fill)

    def init_state(self, frontier: dict, best: int):
        import jax
        import jax.numpy as jnp

        k = frontier[self.size_field].shape[0] if frontier else 0
        # One upload of the frontier's rows and one program that lays
        # them into zeroed pools. The upload is sized to a power of two so
        # that a new frontier size seldom compiles `fill` anew.
        nodes = min(max(128, 1 << max(k - 1, 0).bit_length()),
                    self.pool_rows)
        heads = [
            self.pack_rows(i, np.asarray(frontier[name]) if k
                           else np.zeros((0,) + shape), nodes)
            for i, (name, _dt, shape) in enumerate(self.pool_fields)]
        with jax.default_device(self.device):
            return (
                *self._fill(*heads),
                jnp.int32(k),
                jnp.int32(best),
            )

    def step(self, state):
        """One dispatch: up to K device-side chunk cycles."""
        return self._step(*state)

    def carry(self, out):
        """The dispatch's carried state ``(pool_vals, pool_aux, size,
        best)`` — the next dispatch's input.  Pure tuple slicing: nothing
        is forced, so a speculative dispatch can be enqueued on it while
        the producing computation is still in flight."""
        return tuple(out[:4])

    def read_scalars(self, out):
        """Blocks on the dispatch's SCALAR outputs only: returns
        ``(tree, sol, cycles, size, best, ctr)``.  Never touches the pool
        leaves — under pipelined dispatch those buffers were already
        donated into the next speculative dispatch and are dead; the
        scalar outputs are not donated and stay readable.  This is the
        sanctioned per-dispatch readback (a few ints + the optional obs
        counter block), same bytes as the synchronous path always read."""
        ctr = np.asarray(out[7]) if self.obs else None
        return (int(out[4]), int(out[5]), int(out[6]),
                int(out[2]), int(out[3]), ctr)

    def read_phase_block(self, out):
        """The dispatch's harvested phase-clock block (np array) when the
        profiler variant is armed, else None — same dispatch-boundary
        readback contract as ``read_scalars`` (the block is the final,
        non-donated output leaf)."""
        return np.asarray(out[-1]) if self.phaseprof else None

    def read(self, out):
        """Blocks on the step result; returns ``(state, tree, sol, cycles,
        ctr)`` where ``ctr`` is the harvested counter block (np array) when
        device counters are on, else None. The reads happen at the dispatch
        boundary, outside the steady-state guard — the same sanctioned
        scalar readback the engine always performed."""
        state = tuple(out[:4])
        tree, sol, cycles = int(out[4]), int(out[5]), int(out[6])
        ctr = np.asarray(out[7]) if self.obs else None
        return state, tree, sol, cycles, ctr

    def residual(self, state) -> tuple[dict, int, int]:
        """Downloads the remaining pool -> (host NodeBatch, size, best)."""
        *pools, size, best = state
        size = int(size)
        best = int(best)
        # Static-shape slice: residual after a completed run is < m nodes, so
        # one padded transfer; the overflow fallback passes larger sizes.
        batch = {}
        fields = self.problem.node_fields()
        for (name, _, shape), buf in zip(self.pool_fields, pools):
            lead = max(size, 1)
            if shape:  # the value pool's rows hold row_pack nodes each
                lead = -(-lead // self.row_pack)
            host = node_rows(np.asarray(buf[:lead]), shape, self.row_width)
            batch[name] = host[:size].astype(fields[name][1])
        return self.derive_fields(batch), size, best

    def snapshot(self, state) -> tuple[dict, int, int]:
        """Full live-frontier download (checkpointing): one whole-pool
        transfer, sliced to the live rows."""
        *pools, size, best = state
        size = int(size)
        best = int(best)
        fields = self.problem.node_fields()
        batch = {}
        for (name, _, shape), buf in zip(self.pool_fields, pools):
            host = node_rows(np.asarray(buf), shape, self.row_width)
            batch[name] = host[:size].astype(fields[name][1])
        return self.derive_fields(batch), size, best


class _PFSPResident(_ResidentProgram):
    size_field = "prmu"
    # Deep PFSP chunks prune heavily (closed slots + bound cuts); comfortably
    # under a quarter of the slot grid in practice.
    survivor_budget_div = 4

    def __init__(self, problem: PFSPProblem, *a, **kw):
        import jax.numpy as jnp

        n = problem.jobs
        self._dt = _pool_int_dtype(n)
        self.pool_fields = (
            ("prmu", self._dt, (n,)),
            ("limit1", jnp.int8 if n <= 127 else jnp.int32, ()),
        )
        super().__init__(problem, *a, **kw)

    def derive_fields(self, batch: dict) -> dict:
        # depth == limit1 + 1 for every node the engine ever pushes (forward
        # branching; the root depth=0/limit1=-1 satisfies it too).
        batch["depth"] = (batch["limit1"] + 1).astype(
            self.problem.node_fields()["depth"][1]
        )
        return batch

    def _swap_pos(self, aux_c):
        return aux_c + 1  # parent depth = limit1 + 1

    def _evaluator_uses_kernels(self) -> bool:
        """Whether `_make_eval`'s evaluator routes to a Pallas kernel, by
        the bound functions' own routing predicates (shape gates
        included), following the same branches."""
        from ..ops import pfsp_device as P

        prob, device = self.problem, self.device
        n, m = prob.jobs, prob.machines
        lb1 = P.lb1_kernel_routed(n, m, device)
        if prob.lb != "lb2":
            return lb1
        tables = prob.device_tables()
        pairs = tables.pairs.shape[0]
        if self._staged():
            if self.mp_axis is not None:
                pairs = (tables.mp_padded(self.mp_size)[0].shape[0]
                         // self.mp_size)
            return lb1 or P.lb2_kernel_routed(n, m, pairs, device,
                                              self_bound=True)
        # lb2_bounds_mp (the mp pair-sharded child bound) is jnp-only.
        return (self.mp_axis is None
                and P.lb2_kernel_routed(n, m, pairs, device))

    def _staged(self) -> bool:
        from ..ops import pfsp_device as P

        return (self.problem.lb == "lb2" and self.allow_staged
                and P.lb2_staged_enabled(self.device, self.problem.jobs))

    def _make_eval(self):
        import jax.numpy as jnp

        from ..ops import pfsp_device as P

        prob = self.problem
        t = prob.device_tables()
        lb = prob.lb
        n = prob.jobs
        device = self.device
        # Set by the mesh-resident program when the Johnson pair axis is
        # sharded over a second mesh axis (lb2 only).
        mp_axis = self.mp_axis
        mp_size = self.mp_size

        # Staging composes with the mp pair-axis sharding: the lb1
        # prefilter + compaction are pure shard-local ops (identical on
        # every mp replica), and the compacted self bound shards its pair
        # loop with a pmax combine (`lb2_self_bounds_mp`).
        staged = self._staged()

        # tts-lint: traced (called from the while-loop body's evaluate hook)
        def evaluate(prmu_c, limit1_c, valid, best):
            pdepth = limit1_c + 1
            kk = jnp.arange(n, dtype=jnp.int32)[None, :]
            open_ = (kk >= pdepth[:, None]) & valid[:, None]
            leaf = open_ & ((pdepth[:, None] + 1) == n)
            sol_inc = jnp.sum(leaf, dtype=jnp.int32)
            if staged:
                # Incumbent-aware staging: the cheap lb1 pass decides leaves
                # and the candidate set; lb2 runs only on compacted
                # candidates (exact: lb2 >= lb1 pointwise, so lb1-dead
                # children are lb2-dead too). Leaf bounds under lb1 ARE the
                # makespan (complete schedule), so the incumbent fold is
                # identical to the single-pass path.
                bounds1 = P.lb1_bounds(prmu_c, limit1_c, t, device)
                best = jnp.minimum(
                    best, jnp.min(jnp.where(leaf, bounds1, INF_BOUND))
                )
                cand = open_ & (~leaf) & (bounds1 < best)
                bounds2 = P.lb2_bounds_staged(prmu_c, limit1_c, cand, t,
                                              device, mp_axis=mp_axis,
                                              mp_size=mp_size)
                keep = cand & (bounds2 < best)
                return keep, sol_inc, best
            if lb == "lb1":
                bounds = P.lb1_bounds(prmu_c, limit1_c, t, device)
            elif lb == "lb1_d":
                bounds = P.lb1_d_bounds(prmu_c, limit1_c, t, device)
            elif mp_axis is not None:
                bounds = P.lb2_bounds_mp(
                    prmu_c, limit1_c, t, mp_axis, mp_size, device
                )
            else:
                bounds = P.lb2_bounds(prmu_c, limit1_c, t, device)
            # Leaf makespans fold into the incumbent before the prune test,
            # exactly like the host generate_children (`pfsp_chpl.chpl:100-111`).
            best = jnp.minimum(best, jnp.min(jnp.where(leaf, bounds, INF_BOUND)))
            keep = open_ & (~leaf) & (bounds < best)
            return keep, sol_inc, best

        return evaluate


class _NQueensResident(_ResidentProgram):
    size_field = "board"
    # No pruning: every safe slot survives, so give the compactor half the
    # slot grid before it falls back to the full scatter.
    survivor_budget_div = 2

    def __init__(self, problem: NQueensProblem, *a, **kw):
        import jax.numpy as jnp

        self.pool_fields = (
            ("board", jnp.uint8, (problem.N,)),
            ("depth", jnp.int8 if problem.N <= 127 else jnp.int32, ()),
        )
        super().__init__(problem, *a, **kw)

    def derive_fields(self, batch: dict) -> dict:
        return batch

    def _swap_pos(self, aux_c):
        return aux_c  # swap position is the parent depth itself

    def _make_eval(self):
        import jax.numpy as jnp

        from ..ops import nqueens_device

        N = self.problem.N
        core = nqueens_device.make_labels(N, self.problem.g, self.device)

        # tts-lint: traced (called from the while-loop body's evaluate hook)
        def evaluate(board_c, depth_c, valid, best):
            # A popped node at depth == N is a solution (`nqueens_chpl.chpl:74`).
            sol_inc = jnp.sum(valid & (depth_c == N), dtype=jnp.int32)
            labels = core(board_c, depth_c).astype(bool)  # k >= depth folded in
            keep = labels & valid[:, None] & (depth_c < N)[:, None]
            return keep, sol_inc, best

        return evaluate


def _make_program(
    problem: Problem, m, M, K, capacity, device,
    mp_axis: str | None = None, mp_size: int = 1,
    allow_staged: bool = True,
) -> _ResidentProgram:
    # One compiled program per (problem, config): rebuilding the jit closure
    # would recompile the whole while-loop program on every search (~30 s on
    # TPU), so programs are cached on the problem instance.
    cache = getattr(problem, "_resident_programs", None)
    if cache is None:
        cache = problem._resident_programs = {}
    # Kernel-routing decisions (Pallas vs jnp, lb2 kill switch, staging)
    # are baked in at trace time but depend on env knobs — key them, or
    # flipping a knob between searches on the same problem instance would
    # silently reuse the stale program.
    from ..ops.pfsp_device import routing_cache_token

    key = (m, M, K, capacity, id(device), mp_axis, mp_size, allow_staged,
           routing_cache_token(problem, device),
           # Counter-block / phase-clock programs are distinct
           # compilations: flipping TTS_OBS or TTS_PHASEPROF between
           # searches must rebuild, not reuse.
           obs_counters.device_counters_enabled(),
           obs_phases.phase_profiling_enabled())
    if key in cache:
        return cache[key]
    if isinstance(problem, PFSPProblem):
        prog = _PFSPResident(problem, m, M, K, capacity, device,
                             mp_axis=mp_axis, mp_size=mp_size,
                             allow_staged=allow_staged)
    elif isinstance(problem, NQueensProblem):
        prog = _NQueensResident(problem, m, M, K, capacity, device,
                                mp_axis=mp_axis, mp_size=mp_size,
                                allow_staged=allow_staged)
    else:
        raise TypeError(f"no resident program for {type(problem).__name__}")
    cache[key] = prog
    return prog


def default_capacity(M: int, child_slots: int, node_bytes: int) -> int:
    """Pool capacity heuristic: at least two full chunk fan-outs of headroom,
    capped by a ~1 GiB HBM budget of ``node_bytes`` a node. Correctness
    never depends on it (overflow falls back to host offload cycles)."""
    want = max(2 * M * child_slots, 1 << 21)
    budget = (1 << 30) // max(1, node_bytes)
    return max(4 * M, min(want, budget))


def resolve_capacity(problem: Problem, M: int, capacity: int | None) -> tuple[int, int]:
    """Shared (capacity, M) resolution for the resident tiers: apply the
    default_capacity heuristic when unset, then clamp M so one chunk fan-out
    always fits in half the pool."""
    from ..ops.megakernel import push_pack

    n = problem.child_slots
    if capacity is None:
        # A node's bytes as the pools may store it: a value row in the
        # slot the TPU cycle's lane-dense pool gives it (`push_pack`, at
        # most twice its own width).
        fields = problem.node_fields()
        node_bytes = sum(
            (push_pack(shape[0])[0] if shape else 1) * dt.itemsize + 4
            for shape, dt in fields.values()
        )
        capacity = default_capacity(M, n, node_bytes)
    M = min(M, max(64, (capacity // 2) // n))
    # If the 64-chunk floor binds, grow the pool instead of leaving
    # M*n > capacity/2 — that would make the device loop's headroom check
    # (`size + M*n <= capacity`) unsatisfiable and silently run the whole
    # search through the host-offload fallback.
    if 2 * M * n > capacity:
        capacity = 2 * M * n
    return capacity, M


def _emit_device_explored(ctr_total: dict | None, tree2: int, sol2: int,
                          fb_tree: int, fb_sol: int, host: int = 0) -> None:
    """Phase-2 ``explored`` counter samples. When device counters ran, the
    device part comes from the harvested block (so the obs totals exercise
    the counter path, not the engine's own sums — tests pin exact parity)
    and the overflow-fallback host part is emitted separately; otherwise
    one sample carries the engine counts."""
    if not ev.enabled():
        return
    if ctr_total is not None:
        ev.counter("explored", host=host, tree=ctr_total["pushed"],
                   sol=ctr_total["leaves"], phase=2)
        if fb_tree or fb_sol:
            ev.counter("explored", host=host, tree=fb_tree, sol=fb_sol,
                       phase=2)
    else:
        ev.counter("explored", host=host, tree=tree2, sol=sol2, phase=2)


def resident_search(
    problem: Problem,
    m: int = 25,
    M: int = 65536,
    K: int | str = 4096,
    capacity: int | None = None,
    device=None,
    initial_best: int | None = None,
    warmup_target: int | None = None,
    max_steps: int | None = None,
    checkpoint_path: str | None = None,
    checkpoint_interval_s: float = 60.0,
    resume_from: str | None = None,
    guard: bool | None = None,
    yield_fn=None,
) -> SearchResult:
    """3-phase search with a device-resident hot loop.

    Phase 1 (host warm-up) and phase 3 (host drain) are identical to
    `device_search`; phase 2 runs on-device in blocks of up to K chunk
    cycles per dispatch.

    Dispatch is **pipelined** (``TTS_PIPELINE``, engine/pipeline.py):
    up to depth speculative K-cycle dispatches ride the device queue
    while the host reads lagged scalars — exact, because a dispatch on a
    terminated/stalled pool is a zero-cycle no-op. ``K="auto"`` (or
    ``TTS_K=auto``) enables the adaptive geometric-ladder K controller;
    an explicit K pins it. Under pipelining a ``max_steps`` cutoff drains
    the (up to depth-1) in-flight speculative dispatches, so the counted
    work can exceed ``max_steps`` blocks by that margin — the checkpoint
    stays coherent either way.

    Checkpointing (absent from the reference, SURVEY.md §5): with
    ``checkpoint_path`` the live frontier + counters are saved every
    ``checkpoint_interval_s`` and at a ``max_steps`` cutoff (which returns
    ``complete=False``); ``resume_from`` seeds the search from a saved file
    and keeps counting. ``yield_fn`` is the cooperative-preemption seam
    (checkpoint.RunController): checked at every dispatch boundary, True
    cuts the run exactly like a ``max_steps`` cutoff — the serve daemon
    uses it to make a long job yield to its queue and resume
    bit-identically (``tpu_tree_search/serve/``).

    Guard mode (``guard=True`` or TTS_GUARD=1, docs/ANALYSIS.md): every
    steady-state dispatch is asserted to reuse the compiled step (zero
    recompilations) and to run under ``jax.transfer_guard("disallow")`` —
    a regression that re-introduces a per-cycle host round trip raises
    ``GuardViolation`` instead of silently costing ~360 ms per cycle.
    """
    best = (
        initial_best
        if initial_best is not None
        else getattr(problem, "initial_ub", INF_BOUND)
    )
    n = problem.child_slots
    capacity, M = resolve_capacity(problem, M, capacity)

    from ..problems.base import index_batch
    from . import checkpoint as ckpt

    pool = SoAPool(problem.node_fields())
    diagnostics = Diagnostics()
    phases: list[PhaseStats] = []
    # The search's host steps as profiler spans (docs/OBSERVABILITY.md
    # leg 2), each carrying the search's number.
    search = ev.search_seq()

    def span(name: str, **args) -> ev.span:
        return ev.span(name, args={"search": search, **args})

    # -- phase 1: host warm-up (or checkpoint restore) ---------------------
    with span("tts.warmup"):
        t0 = time.perf_counter()
        if resume_from is not None:
            saved = ckpt.load(resume_from, problem)
            pool.push_back_bulk(saved.batch)
            tree1, sol1 = saved.tree, saved.sol
            # Keep the tighter incumbent: the resumed run may supply a
            # better one (e.g. ub=1 after a ub=0 checkpoint).
            best = min(best, saved.best)
            # A resumed frontier can exceed the warm-up-sized pool: grow
            # the capacity so the whole frontier plus one fan-out fits.
            capacity = max(capacity, pool.size + 2 * M * n)
        else:
            pool.push_back(index_batch(problem.root(), 0))
            target = m if warmup_target is None else warmup_target
            tree1, sol1, best = warmup(problem, pool, best, target)
        t1 = time.perf_counter()
    phases.append(PhaseStats(t1 - t0, tree1, sol1))
    ev.counter("explored", tree=tree1, sol=sol1, phase=1)

    # -- phase 2: device-resident loop ------------------------------------
    from .pipeline import (
        AdaptiveK,
        DispatchQueue,
        RESIDENT_TARGET,
        resolve_k,
        resolve_pipeline_depth,
        resolve_target_band,
    )

    k_auto, k_value = resolve_k(K, default_max=4096)
    # TTS_COSTMODEL: a measured-profile band replaces the fixed target
    # (engine/pipeline.py resolve_target_band; fixed band is the fallback).
    band, band_src = resolve_target_band(
        "resident", RESIDENT_TARGET, problem, topology="device-D1"
    )
    ctl = AdaptiveK(k_value, target=band) if k_auto else None
    depth = resolve_pipeline_depth()
    with span("tts.program"):
        program = _make_program(problem, m, M, ctl.K if ctl else k_value,
                                capacity, device)
    with span("tts.upload"):
        state = program.init_state(pool.as_batch(), best)
    pool.clear()
    diagnostics.host_to_device += 1
    tree2 = 0
    sol2 = 0
    size = pool.size
    offloader = None

    from ..analysis.guard import SteadyStateGuard, guard_enabled

    genabled = guard_enabled(guard)
    guards: dict[int, SteadyStateGuard] = {}

    def guard_of(prog) -> SteadyStateGuard:
        # One guard per compiled program: each ladder rung's first dispatch
        # is its sanctioned warm one; re-selecting a rung reuses its guard
        # (and its cached executable — zero steady-state recompiles).
        g = guards.get(id(prog))
        if g is None:
            g = guards[id(prog)] = SteadyStateGuard(
                prog._step, "resident step", enabled=genabled
            )
        return g

    ctr_total: dict | None = None
    ph_total: dict | None = None  # per-phase ns totals (TTS_PHASEPROF=1)
    cycles_total = 0  # device chunk cycles consumed (roofline denominator)
    fb_tree = fb_sol = 0  # overflow-fallback host increments (obs parity)
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
        # Speculative pipelined dispatch: the carry chains device-side from
        # one dispatch's output into the next's input (donated), so up to
        # `depth` K-cycle blocks ride the device queue while the host is
        # still reading lagged scalars.  Exact: a dispatch on a terminated
        # or stalled pool is a zero-cycle no-op (see pipeline.py).
        nonlocal state
        t_enq = ev.now_us()
        # The FIFO consumes in enqueue order: this is dispatch number
        # (consumed + in flight + 1), the number its tts.wait carries.
        seq = n_disp + len(queue) + 1
        with span("tts.dispatch", dispatch=seq), guard_of(program).step():
            out = program.step(state)
        state = program.carry(out)
        queue.push(out, t_enq)

    def consume(out, t_enq) -> tuple[int, int, int]:
        nonlocal tree2, sol2, size, best, ctr_total, ph_total, prev_best
        nonlocal n_disp, cycles_total
        t_wait = ev.now_us()
        with span("tts.wait", dispatch=n_disp + 1):
            tree_inc, sol_inc, cycles, size, best, ctr = \
                program.read_scalars(out)
        phb = program.read_phase_block(out)
        tree2 += tree_inc
        sol2 += sol_inc
        n_disp += 1
        cycles_total += cycles
        diagnostics.kernel_launches += cycles
        if ctr is not None:
            ctr_total = obs_counters.merge_host(ctr_total, ctr)
        if phb is not None:
            ph_total = obs_phases.merge_host(ph_total, phb)
        fr.heartbeat("resident", seq=n_disp, cycles=cycles, size=size,
                     best=best, tree=tree2, sol=sol2, depth=depth,
                     K=program.K, inflight=len(queue),
                     phases=ph_total)
        if qt is not None:
            qt.observe(best, n_disp, tree1 + tree2)
        if ev.enabled():
            now = ev.now_us()
            # Span semantics under pipelining (docs/OBSERVABILITY.md): the
            # span covers enqueue -> scalars-ready (spans overlap at
            # depth > 1; `tts report` merges overlaps for the busy
            # fraction); read_wait_us is the blocked portion alone.
            ev.emit("dispatch", ph="X", ts=t_enq,
                    dur=max(0.0, now - t_enq), args={
                        "cycles": cycles, "tree": tree_inc, "sol": sol_inc,
                        "size": size, "best": best,
                        "enqueue_us": t_enq, "read_wait_us": now - t_wait,
                        "pipeline_depth": depth,
                    })
            if ctr is not None:
                ev.counter("device_counters", **obs_counters.as_args(ctr))
            if phb is not None:
                # One Perfetto counter track per phase (ns this dispatch).
                ev.counter("device_phases", **obs_phases.as_args(phb))
            if best < prev_best:
                ev.emit("incumbent", args={"best": best})
        prev_best = best
        return tree_inc, sol_inc, cycles

    def drain_queue() -> tuple[int, int]:
        # Read every in-flight speculative dispatch before any action that
        # needs coherent totals or the final carried state (termination,
        # checkpoint cuts, K resizes, the capacity-stall fallback).
        dt = ds = 0
        for out, t_enq in queue.drain():
            ti, si, _ = consume(out, t_enq)
            dt += ti
            ds += si
        return dt, ds

    def snapshot_fn():
        batch, _, bst = program.snapshot(state)
        diagnostics.device_to_host += 1
        return batch, bst

    controller = ckpt.RunController(
        problem, checkpoint_path, checkpoint_interval_s, max_steps,
        snapshot_fn, drain_fn=drain_queue, yield_fn=yield_fn,
    )

    fr.arm("resident")
    ev.emit("pipeline", args={
        "depth": depth, "K": program.K, "k_auto": k_auto, "tier": "resident",
    })
    if ev.enabled():
        # Static shape/routing facts for the trace-side roofline audit
        # (`tts report --roofline`, obs/roofline.py): paired with the
        # dispatch spans' cycle counts and the device_phases counters, a
        # trace alone can rebuild the per-phase byte floors.
        ev.emit("roofline_meta", args=obs_roofline.meta_args(program))
    if band_src is not None:
        ev.emit("costmodel", args={
            "source": band_src, "lo_ms": round(1e3 * band[0], 1),
            "hi_ms": round(1e3 * band[1], 1), "tier": "resident",
        })
    last_ready = time.monotonic()

    while True:
        while not queue.full:
            enqueue()
        out, t_enq = queue.pop()
        tree_inc, sol_inc, cycles = consume(out, t_enq)
        now = time.monotonic()
        period, last_ready = now - last_ready, now
        if size < m:
            drain_queue()  # speculative no-ops: zero counts, state intact
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
                complete=False,
                steps=controller.steps,
                compact=program.compact,
                compact_auto=program.compact_auto,
                megakernel=program.megakernel.state,
                megakernel_auto=program.megakernel.auto,
                megakernel_reason=program.megakernel.reason,
                megakernel_mt=program.megakernel.mt or None,
                megakernel_tiled=program.megakernel.tiled,
                kernel_backend=program.kernel_backend,
                kernel_interpret=program.kernel_interpret,
                pipeline_depth=depth,
                k_resolved=program.K,
                k_auto=k_auto,
                obs=obs_result(),
                phase_profile=ph_total,
                roofline=obs_roofline.result_audit(
                    program, ph_total, cycles_total),
                quality=qt.result() if qt is not None else None,
            )
        if ctl is not None and cycles > 0 and ctl.observe(period, cycles):
            # Geometric-ladder K resize: drain, then swap in the rung's
            # cached program (same pool state arrays — capacity does not
            # depend on K; at most len(ladder) compiles ever happen).
            drain_queue()
            with span("tts.program"):
                program = _make_program(problem, m, M, ctl.K, capacity,
                                        device)
            ev.emit("k_resize", args={"K": program.K})
            last_ready = time.monotonic()
            if size < m:
                # The drained speculative dispatches finished the search.
                break
            continue
        if cycles == 0:
            # Capacity stall: pool too full for another device fan-out. Run
            # classic offload cycles through a host pool until there is
            # headroom again (rare; guarantees progress at any capacity).
            drain_queue()  # stalled speculative dispatches are no-ops too
            t_fb = ev.now_us()
            fb_tree0, fb_sol0 = tree2, sol2
            with span("tts.download"):
                batch, size, best = program.residual(state)
            diagnostics.device_to_host += 1
            pool.reset_from(batch)
            if offloader is None:
                offloader = DeviceOffloader(problem, program.device)
            chunk_buf = problem.empty_batch(M)
            while pool.size >= m and pool.size + M * n > capacity:
                count = pool.pop_back_bulk(m, M, chunk_buf)
                if count == 0:
                    break
                bucket = bucket_size(count, m, M)
                snapshot = {k: v[:count].copy() for k, v in chunk_buf.items()}
                res_dev = offloader.dispatch(snapshot, count, bucket, best)
                res = problem.generate_children(
                    snapshot, count, offloader.collect(res_dev), best
                )
                tree2 += res.tree_inc
                sol2 += res.sol_inc
                best = res.best
                pool.push_back_bulk(res.children)
            diagnostics.kernel_launches += offloader.diagnostics.kernel_launches
            diagnostics.host_to_device += offloader.diagnostics.host_to_device
            diagnostics.device_to_host += offloader.diagnostics.device_to_host
            offloader.diagnostics = Diagnostics()
            with span("tts.upload"):
                state = program.init_state(pool.as_batch(), best)
            pool.clear()
            diagnostics.host_to_device += 1
            # The re-upload is a sanctioned host round trip; the next
            # dispatch is a fresh warm one for the guard.
            guard_of(program).rearm()
            last_ready = time.monotonic()
            fb_tree += tree2 - fb_tree0
            fb_sol += sol2 - fb_sol0
            ev.complete("overflow_fallback", t_fb, args={
                "tree": tree2 - fb_tree0, "sol": sol2 - fb_sol0,
            })
    with span("tts.download"):
        batch, size, best = program.residual(state)
    diagnostics.device_to_host += 1
    pool.reset_from(batch)

    # -- phase 3: host drain ----------------------------------------------
    with span("tts.drain"):
        t2 = time.perf_counter()
        tree3, sol3, best = drain(problem, pool, best)
        t3 = time.perf_counter()
    phases.append(PhaseStats(t2 - t1, tree2, sol2))
    _emit_device_explored(ctr_total, tree2, sol2, fb_tree, fb_sol)
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
        steps=controller.steps,
        compact=program.compact,
        compact_auto=program.compact_auto,
        megakernel=program.megakernel.state,
        megakernel_auto=program.megakernel.auto,
        megakernel_reason=program.megakernel.reason,
        megakernel_mt=program.megakernel.mt or None,
        megakernel_tiled=program.megakernel.tiled,
        kernel_backend=program.kernel_backend,
        kernel_interpret=program.kernel_interpret,
        pipeline_depth=depth,
        k_resolved=program.K,
        k_auto=k_auto,
        obs=obs_result(),
        phase_profile=ph_total,
        roofline=obs_roofline.result_audit(program, ph_total, cycles_total),
        quality=qt.result() if qt is not None else None,
    )


# -- compiled-program contracts (`tts check`, analysis/contracts.py) --------
# The fused-push / donation / steady-state-purity claims of this engine,
# declared here and verified for EVERY knob-matrix cell by
# analysis/program_audit.py (previously scattered one-cell jaxpr pins in
# tests/test_compaction.py and runtime-only guard assertions).

from ..analysis.contracts import child_value_gathers, contract  # noqa: E402


@contract(
    "fused-push-single-gather",
    claim="in EVERY survivor-path mode the compiled step contains at most "
          "ONE gather big enough to be moving child values (>= S rows of "
          "n lanes in the pool value dtype) — the single augmented "
          "(row, aux) gather of the fused prune+push; mask gathers move "
          "no node data and are exempt",
    artifact="resident-step",
)
def _contract_single_gather(art, cell):
    prog = art.prog
    n = prog.problem.child_slots
    vals_dt = np.dtype(prog.pool_fields[0][1])
    big = child_value_gathers(art.prims, prog.S, n, vals_dt)
    if len(big) <= 1:
        return []
    return [
        f"{len(big)} child-value-sized gathers in the step (budget is 1): "
        + "; ".join(str(e).splitlines()[0][:120] for e in big)
    ]


@contract(
    "pool-donation",
    claim="the resident step donates its pool buffers (input/output "
          "aliasing present in the lowered program) — pipelined dispatch "
          "chains the carry device-side and correctness of the memory "
          "budget depends on the donation never silently disappearing",
    artifact="resident-step",
)
def _contract_pool_donation(art, cell):
    txt = art.lowered_text
    if "tf.aliasing_output" in txt or "jax.buffer_donor" in txt:
        return []
    return ["no input-output aliasing in the lowered step (donation lost)"]


@contract(
    "step-callback-armed-only",
    claim="the steady-state step program contains no host callbacks and no "
          "infeed/outfeed — EXCEPT the phase-profiler variant, whose "
          "pure_callback clock reads are the armed instrument and must be "
          "present there (and only there)",
    artifact="resident-step",
)
def _contract_callbacks(art, cell):
    cbs = sorted(
        n for n in art.prim_names
        if "callback" in n or n in ("infeed", "outfeed")
    )
    armed = cell is not None and getattr(cell, "phaseprof", "0") == "1"
    if armed:
        if any("callback" in n for n in cbs):
            return []
        return ["armed phase-profiler variant lowered without its clock "
                "callback (the instrument is silently gone)"]
    if cbs:
        return [f"host-callback ops in an unarmed steady-state step: {cbs}"]
    return []


@contract(
    "program-cache-key-sound",
    claim="knobs baked into the compiled program (TTS_COMPACT, TTS_OBS, "
          "TTS_PHASEPROF, TTS_LB2_PAIRBLOCK) key the resident program "
          "cache — a flip rebuilds, never reuses stale structure; "
          "host-only knobs (TTS_PIPELINE, TTS_GUARD) hit the same cached "
          "program — they must not fork compilations",
    artifact="cache-key",
)
def _contract_cache_key(art, cell):
    out = []
    for knob, (a, b) in art.distinct.items():
        if a is b:
            out.append(f"{knob} flip reused the same cached program "
                       "(stale structure would run)")
    for knob, (a, b) in art.shared.items():
        if a is not b:
            out.append(f"{knob} flip rebuilt the program (a host-only knob "
                       "leaks into the cache key and forks compilations)")
    return out


@contract(
    "narrow-knob-inert",
    claim="TTS_NARROW never changes the compiled resident step: the device "
          "pools were always narrow (`_pool_int_dtype`) — the knob governs "
          "HOST storage/transfer/checkpoint dtypes only, so the unset "
          "(auto) build and the =0 build produce byte-identical step "
          "jaxprs with identical carry widths",
    artifact="variants",
)
def _contract_narrow_inert(art, cell):
    if not art.has("off", "narrow0"):
        return []
    out = []
    if art.text("off") != art.text("narrow0"):
        out.append("TTS_NARROW=0 build differs from the unset (auto) build "
                   "(narrow host storage leaked into the device program)")
    if art.outvars("narrow0") != art.outvars("off"):
        out.append("TTS_NARROW=0 build changed the carry width")
    return out
