"""Pallas TPU kernels for the hot chunk evaluators.

The XLA (jnp) evaluators in `nqueens_device.py` / `pfsp_device.py` are the
semantic oracles and the portable path; these kernels are the hand-scheduled
TPU variants: one VMEM-resident pass per batch tile — the instance tables
(processing times, min heads/tails) sit in SMEM for the whole grid, every
intermediate (the processing-time gather, the O(n) schedule_front scan,
the per-child bound chain) lives in registers/VMEM on lane-dense slabs
(the tile's parents on the 128 lanes), and nothing round-trips through HBM
between fusion boundaries.

Reference counterparts: `evaluate_gpu` (`nqueens_gpu_chpl.chpl:97-123`) and
`evaluate_gpu_lb1` (`evaluate.cu:25-49`, device math `c_bounds_gpu.cu:15-195`)
— one SIMT thread per (parent, child); here one grid step per TILE_B parents
with all children vectorized on the VPU/MXU.

Selection: ``use_pallas(device)`` is the one answer to "kernels or jnp":
True when the target device is a TPU, unless disabled via ``TTS_PALLAS=0``;
``TTS_PALLAS_INTERPRET=1`` routes to the kernels in interpret mode on any
device.  Tests force ``interpret=True`` on CPU to check the kernels
bit-for-bit against the jnp oracles.
"""

from __future__ import annotations

import dataclasses
import os
from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def platform(device=None) -> str:
    """The platform of the target device (``tpu``, ``cpu``, ...): an
    explicit device wins, else the process default backend.  The routing
    tables (`use_pallas`, `megakernel.resolve`, `compaction._auto_compact`,
    `cli.resolve_chunk_size`) and the cost-model keys read this string."""
    if device is not None:
        return device.platform
    return jax.default_backend()


def use_pallas(device=None) -> bool:
    """Route to the Pallas kernels only when the *target device* is a TPU
    (or ``TTS_PALLAS_INTERPRET=1`` runs them interpreted anywhere).

    The reference's dispatcher selects per device context
    (`evaluate.cu:93-119`); keying on the process default backend instead
    breaks any CPU-device execution inside a TPU-default process (e.g. the
    driver's virtual-CPU multichip dryrun). Callers that own a device thread
    it through; ``None`` falls back to the default backend.
    """
    if os.environ.get("TTS_PALLAS", "1") == "0":
        return False
    return pallas_interpret() or platform(device) == "tpu"


def pallas_forced() -> bool:
    """``TTS_PALLAS=force``: re-arm the demoted lb1-family kernel routing
    (see ``lb1_pallas_enabled``) — the armed-session A/B spelling."""
    return os.environ.get("TTS_PALLAS", "") == "force"


def lb1_pallas_enabled() -> bool:
    """lb1-family demotion (decision record: docs/HW_VALIDATION.md).

    The round-5 on-chip microbench measured the fused jnp/XLA lb1 path at
    ~7x the hand-written Pallas kernel on the production chunk shapes
    (315M vs 41M bound-evals/s — XLA's own fusion wins on this op), and
    the bench had been empirically demoting the headline to jnp every
    round. This makes that measurement the default: the lb1/lb1_d
    evaluators route to the fused jnp path everywhere, and the kernels
    stay reachable for the A/B via ``TTS_PALLAS=force`` (interpret mode
    also still routes through them — it exists to exercise kernel/
    composition code paths, not to be fast). The lb2 family is NOT
    demoted: its kernel keeps the whole Johnson pair loop in VMEM and
    measures faster than jnp on chip."""
    return pallas_forced() or pallas_interpret()


def pallas_interpret() -> bool:
    """``TTS_PALLAS_INTERPRET=1`` routes the evaluators to the Pallas
    kernels in interpret mode on ANY backend. This is the off-chip way to
    drive compositions the CPU suite otherwise cannot reach — above all
    pallas_call inside the mesh tiers' ``shard_map`` (the round-5 hardware
    session caught a vma trace failure there that every CPU test missed
    because ``use_pallas`` is False off-TPU). Kernel *math* runs
    interpreted; routing, tracing, and the shard_map composition are the
    real path. ``TTS_PALLAS=0`` still wins."""
    return os.environ.get("TTS_PALLAS_INTERPRET", "0") == "1"


def _round_up(x: int, k: int) -> int:
    return (x + k - 1) // k * k


def _vmem_limit_bytes() -> int | None:
    """Scoped VMEM ceiling for the PFSP kernels: the Mosaic scoped-VMEM
    charge. The Mosaic default (16 MB) is below what the one-kernel
    cycle's compaction holds at its 128-wide pool tile (about 12 MB at 20
    jobs, 31 MB at 50); v5e has 128 MB of VMEM, so raising the scope to
    96 MB is safe for a standalone pallas_call and lets the tiles grow."""
    mb = int(os.environ.get("TTS_PALLAS_VMEM_MB", "96"))
    if mb < 0:
        raise ValueError(f"TTS_PALLAS_VMEM_MB must be >= 0 (0 disables), got {mb}")
    return mb * 2**20 if mb else None


def _compiler_params(ndims: int = 1, parallel: bool = False):
    """``pallas_call`` compiler params: ``dimension_semantics`` sized to
    the grid rank (``parallel`` marks every axis Megacore-splittable — only
    safe for carry-free kernels, see megakernel.streamed_eval_bounds) and
    the scoped-VMEM ceiling."""
    sem = ("parallel" if parallel else "arbitrary",) * ndims
    return pltpu.CompilerParams(dimension_semantics=sem,
                                vmem_limit_bytes=_vmem_limit_bytes())


def _bs(shape, index_map, space: str = "vmem"):
    """A BlockSpec pinned to VMEM or SMEM (``space``)."""
    ms = pltpu.SMEM if space == "smem" else pltpu.VMEM
    return pl.BlockSpec(shape, index_map, memory_space=ms)


def _env_tile(name: str, default: int) -> int:
    tile = int(os.environ.get(name, str(default)))
    if tile < 1:
        raise ValueError(f"{name} must be a positive batch-tile size, got {tile}")
    return tile


def _r8(x: int) -> int:
    return _round_up(x, 8)


def _r128(x: int) -> int:
    return _round_up(x, 128)


def _model_bytes(t: int, n: int, m: int, extra_bytes: int,
                 tn2_copies: int, pair_copies: int = 0,
                 pair_group: int = 1, lane_slabs: int = 0) -> int:
    """The kernels' modeled VMEM footprint at batch tile ``t`` — the single
    source of truth shared by the tile chooser and the routing gate.
    ``m`` machines charge the PFSP tile prologue (`_tile_parent_state`,
    `_prologue_slabs`), ``m = 0`` none (the N-Queens labels).
    ``tn2_copies`` counts (T, n, n)-class f32 live values (the N-Queens
    clash planes, the self-bound kernel's free-flag one-hot);
    ``pair_copies`` the per-pair ones (the self-bound kernel's matmul pair
    body: u_o/mp0/mp1/cum0/suf1), charged once per member of the unrolled
    pair group (conservative: Mosaic may overlap the unrolled bodies'
    temporaries, so the model assumes they are all live). ``lane_slabs``
    counts further (n, T) int32 slabs, the parents on the lanes (the lb2
    pair loop, `_lb2_lane_slabs`). ``extra_bytes`` adds tile-independent
    residents (lb2's per-pair tables)."""
    tn2 = (tn2_copies + pair_copies * pair_group) * t * _r8(n) * _r128(n) * 4
    slab = _r8(n) * _r128(t) * 4
    prologue = (_prologue_slabs(m) * slab + m * 8 * _r128(t) * 4) if m else 0
    return tn2 + prologue + lane_slabs * slab + extra_bytes


def _vmem_budget() -> int:
    return (_vmem_limit_bytes() or 16 * 2**20) // 2


def _auto_tile(n: int, m: int, default: int, extra_bytes: int = 0,
               tn2_copies: int = 3, pair_copies: int = 0,
               pair_group: int = 1, lane_slabs: int = 0) -> int:
    """Shrink the batch tile until the kernel's modeled VMEM footprint
    fits the budget (`_vmem_limit_bytes`).

    The reference rebuilds with bigger compile-time params for large
    instances (`Taillard.chpl:29-52`); here the same kernel covers 20-500
    jobs by trading batch-tile size for job count. The model
    (``_model_bytes``) is checked against half the scoped budget, halving
    the tile until it fits (floor 8)."""
    budget = _vmem_budget()
    tile = default
    while tile > 8 and _model_bytes(tile, n, m, extra_bytes, tn2_copies,
                                    pair_copies, pair_group,
                                    lane_slabs) > budget:
        # Halve, then align down to the sublane quantum (a non-power-of-two
        # env override must not walk below the floor or mis-align the
        # (tile, n) BlockSpec).
        tile = max(8, (tile // 2) // 8 * 8)
    return tile


def _auto_tile_fits(n: int, m: int, default: int, extra_bytes: int = 0,
                    tn2_copies: int = 3, pair_copies: int = 0,
                    pair_group: int = 1, lane_slabs: int = 0) -> bool:
    """True iff the kernel fits the memory model even at the smallest tile
    — the routing gate: shapes that do not fit must stay on the jnp path
    instead of dying inside a Mosaic VMEM OOM."""
    tile = _auto_tile(n, m, default, extra_bytes, tn2_copies, pair_copies,
                      pair_group, lane_slabs)
    return _model_bytes(tile, n, m, extra_bytes, tn2_copies, pair_copies,
                        pair_group, lane_slabs) <= _vmem_budget()


def _lb2_static_extra(n: int, m: int, P: int) -> int:
    return (P * _r8(n) * _r128(n) + 3 * P * _r128(n) + 2 * P * _r128(m)) * 4


def _prologue_slabs(m: int) -> int:
    """(n, T) int32 slabs the PFSP tile prologue holds: the (m, n, T)
    times scratch, the gather's m running slabs, the m child-front slabs,
    the job and open-slot slabs and two chain temporaries.  Its m (1, T)
    front rows are charged apart (`_model_bytes`)."""
    return 3 * m + 4


# The single source of truth for each kernel's VMEM-model parameters:
# (tile env knob, tile default, tn2_copies, needs per-pair extra,
# per-pair tn2 copies — charged once per unrolled pair-group member).
# Tile defaults: lb1 64 and lb1d 256 were MEASURED on the real v5e with
# the (T, n, n) one-hot prologue (docs/HW_VALIDATION.md; lb1 at 128
# compiled >270s then). The lb2 kernel puts the tile's parents on the 128
# lanes of its pair loop (`_lb2_tile_lb`), so its tile is 128: the width
# that fills them. The lb1-family and lb2 kernels hold no (T, n, n) value;
# the prologue's slabs (`_prologue_slabs`) and lb2's (`_lb2_lane_slabs`)
# are charged per (n, T) slab. The self-bound kernel keeps a (T, n, n)
# free-flag one-hot and a matmul pair body and starts in the
# compile-time-safe class lb1 proved (64). scripts/tile_sweep.py
# re-measures per (kernel, tile) so the defaults can be raised with data.
_KERNEL_MODEL = {
    "lb1": ("TTS_TILE_LB1", 64, 0, False, 0),
    "lb1d": ("TTS_TILE_LB1D", 256, 0, False, 0),
    "lb2": ("TTS_TILE_LB2", 128, 0, False, 0),
    "lb2self": ("TTS_TILE_LB2SELF", 64, 1, True, 5),
}


def _lb2_lane_slabs(n: int, m: int, pg: int) -> int:
    """(n, T) int32 slabs the lb2 pair loop holds beyond the prologue's:
    the m staged child-front slabs and the n free-job flag slabs (VMEM
    scratch), two chains per unrolled pair, the running max and one
    temporary."""
    return m + n + 2 * pg + 2


def _resolve_pair_group(kernel: str, n: int, P: int | None,
                        pair_group: int | None) -> int:
    """The pair-group unroll a kernel will compile with: an explicit value
    wins; otherwise the lb2-family kernels resolve the shared knob
    (`pfsp_device.lb2_kernel_pair_group` — lazy import, both modules load
    each other lazily so there is no cycle)."""
    if pair_group is not None:
        return pair_group
    if kernel in ("lb2", "lb2self") and P is not None:
        from . import pfsp_device

        return pfsp_device.lb2_kernel_pair_group(P, n)
    return 1


def _kernel_tile_args(kernel: str, n: int, m: int, P: int | None,
                      pg: int):
    env, default, copies, pairwise, pair_copies = _KERNEL_MODEL[kernel]
    extra = _lb2_static_extra(n, m, P) if pairwise else 0
    slabs = _lb2_lane_slabs(n, m, pg) if kernel == "lb2" else 0
    return dict(default=_env_tile(env, default), extra_bytes=extra,
                tn2_copies=copies, pair_copies=pair_copies, pair_group=pg,
                lane_slabs=slabs)


def effective_tile(kernel: str, n: int, m: int, P: int | None = None,
                   batch: int | None = None,
                   pair_group: int | None = None) -> int:
    """The batch tile a kernel will actually use for shape (n, m[, P]) —
    shared by the feasibility gates, the kernel callers, and
    scripts/tile_sweep.py so the model constants live in exactly one
    place."""
    pg = _resolve_pair_group(kernel, n, P, pair_group)
    tile = _auto_tile(n, m, **_kernel_tile_args(kernel, n, m, P, pg))
    return tile if batch is None else min(tile, batch)


def _kernel_feasible(kernel: str, n: int, m: int, P: int | None,
                     pair_group: int | None = None) -> bool:
    pg = _resolve_pair_group(kernel, n, P, pair_group)
    return _auto_tile_fits(n, m, **_kernel_tile_args(kernel, n, m, P, pg))


def lb1_kernel_feasible(n: int, m: int) -> bool:
    return _kernel_feasible("lb1", n, m, None)


def lb2_kernel_feasible(n: int, m: int, P: int) -> bool:
    return _kernel_feasible("lb2", n, m, P)


def lb2_self_kernel_feasible(n: int, m: int, P: int) -> bool:
    return _kernel_feasible("lb2self", n, m, P)


# ---------------------------------------------------------------------------
# N-Queens safety labels
# ---------------------------------------------------------------------------


def _nqueens_tile_labels(board, depth, *, N: int, g: int):
    """Bool safety labels of one VMEM tile — the body of `_nqueens_kernel`,
    shared with the one-kernel cycle (`ops/megakernel.py`)."""
    qk = board[:, None, :]  # candidate rows (T, 1, N)
    bi = board[:, :, None]  # placed queens  (T, N, 1)
    i = jax.lax.broadcasted_iota(jnp.int32, (1, N, 1), 1)
    d = depth[:, None, None] - i  # (T, N, 1)
    placed = i < depth[:, None, None]

    def one_round(_, safe):
        clash = (bi == qk - d) | (bi == qk + d)
        return safe & ~jnp.any(clash & placed, axis=1)

    safe = one_round(0, jnp.ones(board.shape, dtype=bool))
    if g > 1:
        safe = jax.lax.fori_loop(0, g - 1, one_round, safe)
    k = jax.lax.broadcasted_iota(jnp.int32, board.shape, 1)
    return safe & (k >= depth[:, None])


def _nqueens_kernel(board_ref, depth_ref, out_ref, *, N: int, g: int):
    """labels[b, k] = 1 iff board[b, k] placed at column depth_b clashes with
    no placed queen on either diagonal (`nqueens_gpu_chpl.chpl:99-123`)."""
    board = board_ref[:].astype(jnp.int32)  # (T, N)
    depth = depth_ref[:, 0].astype(jnp.int32)  # (T,)
    out_ref[:] = _nqueens_tile_labels(board, depth, N=N, g=g).astype(jnp.uint8)


@lru_cache(maxsize=None)
def _nqueens_call(N: int, g: int, B: int, tile: int, interpret: bool):
    kernel = partial(_nqueens_kernel, N=N, g=g)
    grid = (B // tile,)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.uint8),
        name="tts_eval_nqueens",
        grid=grid,
        in_specs=[
            _bs((tile, N), lambda i: (i, 0)),
            _bs((tile, 1), lambda i: (i, 0)),
        ],
        out_specs=_bs((tile, N), lambda i: (i, 0)),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )


def nqueens_labels(board, depth, N: int, g: int = 1,
                   interpret: bool | None = None):
    """(B, N) uint8 labels; same contract as `nqueens_device.make_core`."""
    interpret = pallas_interpret() if interpret is None else interpret
    B = board.shape[0]
    tile = min(512, B)
    Bp = _round_up(B, tile)
    if Bp != B:
        board = jnp.pad(board, ((0, Bp - B), (0, 0)))
        depth = jnp.pad(depth, ((0, Bp - B),))
    out = _nqueens_call(N, g, Bp, tile, interpret)(
        board.astype(jnp.int32), depth.astype(jnp.int32)[:, None]
    )
    return out[:B]


# ---------------------------------------------------------------------------
# PFSP bound tiles: the shared lane-dense prologue, lb1 and lb1_d
# ---------------------------------------------------------------------------


def _hp_dot(a, b, bf16: bool = False):
    """Exact MXU matmul. ``bf16=False``: f32 at HIGHEST precision (the
    default single bf16 pass rounds ints > 256). ``bf16=True`` (set when
    every operand value < 2^8 — one-hot/0-1 masks and Taillard times): a
    single bf16 x bf16 -> f32 pass, bit-exact and ~3x cheaper."""
    if bf16:
        a = a.astype(jnp.bfloat16)
        b = b.astype(jnp.bfloat16)
        precision = None
    else:
        precision = jax.lax.Precision.HIGHEST
    return jax.lax.dot_general(
        a, b,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision,
    )


def _pfsp_table_specs(n: int, m: int, index):
    """in_specs of the instance tables the PFSP bound kernels read as SMEM
    scalars (`pfsp_kernel_tables`): the processing times, job-major and
    flat (``n*m``: job q on machine j at ``q*m + j``), the min heads and
    the min tails (``m`` each).  A kernel that reads fewer takes a prefix.
    ``index`` is the grid's constant index map of a 1-D block."""
    return [pl.BlockSpec((n * m,), index, memory_space=pltpu.SMEM),
            pl.BlockSpec((m,), index, memory_space=pltpu.SMEM),
            pl.BlockSpec((m,), index, memory_space=pltpu.SMEM)]


def _flat_times(ptm_t):
    """The (n, m) job-major processing times as the flat int32 table of
    `_pfsp_table_specs`."""
    return jnp.reshape(jnp.asarray(ptm_t).astype(jnp.int32), (-1,))


def pfsp_kernel_tables(ptm_t, min_heads, min_tails) -> tuple:
    """The operands of `_pfsp_table_specs`, in its order."""
    return (_flat_times(ptm_t), jnp.asarray(min_heads).astype(jnp.int32),
            jnp.asarray(min_tails).astype(jnp.int32))


def _times_scratch(n: int, m: int, t: int):
    """The prologue's (m, n, T) int32 VMEM scratch: the processing times
    at each position by machine (`_gather_times`)."""
    return pltpu.VMEM((m, n, t), jnp.int32)


class _TileState(NamedTuple):
    """The PFSP tile prologue (`_tile_parent_state`) on lane-dense slabs:
    position or child slot k on the sublanes, the tile's T parents on the
    lanes."""

    job: object     # (n, T) int32: the job at position k
    free: object    # (n, T) int32: 1 where position k is open (k > limit1)
    pt: list        # m (n, T): that job's processing time on machine j
    front: list     # m (1, T): the parent's schedule front on machine j
    remain: list    # m (1, T): the open jobs' total time on machine j
    cf: list        # m (n, T): child k's front on machine j


def _gather_times(job, pt, times, *, n: int, m: int):
    """The processing times at every position of the tile, as m (n, T)
    slabs (one per machine): a select per job and machine over the (n, T)
    job slab, the time read from the SMEM table ``pt`` — no one-hot, no
    matmul.  A job id outside [0, n) reads 0, as a one-hot row of zeros
    would.  The slabs are also staged into the (m, n, T) scratch
    ``times``, where `_front_scan` reads them a position row at a time."""

    def body(q, acc):
        at = job == q
        return tuple(jnp.where(at, pt[q * m + j], a)
                     for j, a in enumerate(acc))

    zero = jnp.zeros(job.shape, jnp.int32)
    slabs = jax.lax.fori_loop(0, n, body, (zero,) * m)
    for j in range(m):
        times[j] = slabs[j]
    return list(slabs)


def _front_scan(lim, times, *, n: int, m: int):
    """The masked schedule_front scan (`c_bound_simple.c:51-69`) on (1, T)
    lane rows, one per machine: step i reads position i's times as row i
    of the ``times`` slabs (a dynamic sublane index into the scratch
    `_gather_times` filled) and runs the add_forward chain over the
    machines; parent b takes the step while ``i <= lim[b]``.  Returns the
    m (1, T) front rows (zero where ``lim`` is -1)."""

    def step(i, front):
        new = []
        for j in range(m):
            base = front[0] if j == 0 else jnp.maximum(new[-1], front[j])
            new.append(base + times[j, pl.ds(i, 1), :])
        take = i <= lim
        return tuple(jnp.where(take, a, f) for a, f in zip(new, front))

    zero = jnp.zeros(lim.shape, jnp.int32)
    return list(jax.lax.fori_loop(0, n, step, (zero,) * m))


def _tile_parent_state(prmu, limit1, pt, heads, times, *, n: int,
                       m: int) -> _TileState:
    """Shared tile prologue of the PFSP bound kernels, on lane-dense int32
    slabs: the (T, n) permutation tile is transposed once, so that its T
    parents sit on the 128 lanes and the positions on the sublanes.  The
    processing times are gathered per machine (`_gather_times`), the
    parents' fronts scanned on (1, T) rows (`_front_scan`; ``heads`` at
    the root, limit1 == -1, `c_bound_simple.c:58-61`), and the remaining
    work (sum_unscheduled, `c_bound_simple.c:108-124`) and every child's
    add_forward front are computed on the (n, T) slabs.  No value is 3-D
    and none is a one-hot: nothing moves between sublanes and lanes but
    the transposes of the two tile operands in and the caller's one
    transpose of its (n, T) bound out.  ``limit1`` is the tile's (T, 1)
    column as its block holds it: transposed, its (1, T) row takes the
    slabs' (8, 128) layout.  A row made from a 1-D value
    (``limit1[:, 0][None, :]``) keeps Mosaic's implicit-dimension layout,
    and every slab computed from it inherits that layout: compiled for a
    v5e, the lb1 tile at 20 jobs x 10 machines, T = 128, then asks for
    23 MB of scoped VMEM instead of under 1 MB.  ``pt``/``heads`` are
    SMEM tables (`_pfsp_table_specs`), ``times`` the (m, n, T) scratch
    (`_times_scratch`)."""
    job = prmu.T
    lim = limit1.T  # (1, T)
    slot = jax.lax.broadcasted_iota(jnp.int32, job.shape, 0)
    free = (slot > lim).astype(jnp.int32)
    pts = _gather_times(job, pt, times, n=n, m=m)
    root = lim == -1
    front = [jnp.where(root, heads[j], f)
             for j, f in enumerate(_front_scan(lim, times, n=n, m=m))]
    remain = [jnp.sum(p * free, axis=0, keepdims=True) for p in pts]
    cf = []
    for j in range(m):
        base = front[0] if j == 0 else jnp.maximum(cf[-1], front[j])
        cf.append(base + pts[j])
    return _TileState(job, free, pts, front, remain, cf)


def _lb1_tile_lb(prmu, limit1, pt, heads, tails, times, *, n: int, m: int):
    """(T, n) int32 lb1 bound of every child in the tile — the body of
    `_lb1_kernel`, shared with the one-kernel cycle (`ops/megakernel.py`):
    the machine bound chain (`c_bound_simple.c:126-141`) of every child
    on the prologue's (n, T) slabs, transposed back once."""
    s = _tile_parent_state(prmu, limit1, pt, heads, times, n=n, m=m)
    tmp0 = s.cf[0] + (s.remain[0] - s.pt[0])
    lb = tmp0 + tails[0]
    for i in range(1, m):
        tmp1 = jnp.maximum(tmp0, s.cf[i] + (s.remain[i] - s.pt[i]))
        lb = jnp.maximum(lb, tmp1 + tails[i])
        tmp0 = tmp1
    return lb.T


def _lb1_kernel(prmu_ref, limit1_ref, pt_ref, heads_ref, tails_ref, out_ref,
                times_ref, *, n: int, m: int):
    """Full lb1 bound of every child of every parent in the tile.

    Math identical to `ops/pfsp_device._lb1_chunk` (itself the batched form
    of `c_bound_simple.c:51-141` + one incremental `add_forward` per child);
    here the whole chain runs on one VMEM tile.
    """
    out_ref[:] = _lb1_tile_lb(
        prmu_ref[:].astype(jnp.int32), limit1_ref[:].astype(jnp.int32),
        pt_ref, heads_ref, tails_ref, times_ref, n=n, m=m,
    )


@lru_cache(maxsize=None)
def _lb1_family_call(kernel_fn, n: int, m: int, B: int, tile: int,
                     interpret: bool, name: str = "tts_eval_lb1"):
    """Shared pallas_call factory for the lb1-shaped kernels (lb1 / lb1_d):
    same operand layout — (prmu, limit1, the SMEM tables) -> (B, n) — same
    tiling, same times scratch."""
    kernel = partial(kernel_fn, n=n, m=m)
    grid = (B // tile,)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, n), jnp.int32),
        name=name,
        grid=grid,
        in_specs=[
            _bs((tile, n), lambda i: (i, 0)),
            _bs((tile, 1), lambda i: (i, 0)),
            *_pfsp_table_specs(n, m, lambda i: (0,)),
        ],
        out_specs=_bs((tile, n), lambda i: (i, 0)),
        scratch_shapes=[_times_scratch(n, m, tile)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )


def _lb1_family_bounds(
    kernel_fn, prmu, limit1, ptm_t, min_heads, min_tails, interpret: bool,
    kernel_name: str = "lb1",
):
    B, n = prmu.shape
    m = ptm_t.shape[1]
    # Per-kernel tile defaults (_KERNEL_MODEL); large instances shrink the
    # tile further until the VMEM model fits.
    tile = effective_tile(kernel_name, n, m, batch=B)
    Bp = _round_up(B, tile)
    if Bp != B:
        prmu = jnp.pad(prmu, ((0, Bp - B), (0, 0)))
        limit1 = jnp.pad(limit1, ((0, Bp - B),))
    name = "tts_eval_lb1_d" if kernel_name == "lb1d" else "tts_eval_lb1"
    out = _lb1_family_call(kernel_fn, n, m, Bp, tile, interpret, name)(
        prmu.astype(jnp.int32),
        limit1.astype(jnp.int32)[:, None],
        *pfsp_kernel_tables(ptm_t, min_heads, min_tails),
    )
    return out[:B]


def _lb1_d_kernel(prmu_ref, limit1_ref, pt_ref, heads_ref, tails_ref,
                  out_ref, times_ref, *, n: int, m: int):
    """lb1_d bounds of every child in the tile: the O(m)-per-child weak bound
    from the parent's front/remain (`add_front_and_bound`,
    `c_bound_simple.c:213-244`; device: `evaluate.cu:51-71`). Math identical
    to `ops/pfsp_device._lb1_d_chunk`; shares the tile prologue and runs
    on its (n, T) slabs."""
    s = _tile_parent_state(
        prmu_ref[:].astype(jnp.int32), limit1_ref[:].astype(jnp.int32),
        pt_ref, heads_ref, times_ref, n=n, m=m,
    )
    lb = s.front[0] + s.remain[0] + tails_ref[0]  # (1, T)
    tmp0 = s.front[0] + s.pt[0]  # (n, T)
    for i in range(1, m):
        tmp1 = jnp.maximum(tmp0, s.front[i])
        lb = jnp.maximum(lb, tmp1 + s.remain[i] + tails_ref[i])
        tmp0 = tmp1 + s.pt[i]
    out_ref[:] = jnp.broadcast_to(lb, s.job.shape).T


def pfsp_lb1_d_bounds(
    prmu, limit1, ptm_t, min_heads, min_tails, interpret: bool | None = None,
):
    """(B, n) int32 lb1_d child bounds; same contract as `_lb1_d_chunk`."""
    interpret = pallas_interpret() if interpret is None else interpret
    return _lb1_family_bounds(
        _lb1_d_kernel, prmu, limit1, ptm_t, min_heads, min_tails, interpret,
        kernel_name="lb1d",
    )


#: Johnson slots one iteration of the lb2 slot loop runs, at most
#: (`_lb2_slot_unroll`).  The unrolled body is traced in Python at every
#: program build, so a full unroll costs set-up time on every run.
_LB2_SLOT_UNROLL = 4


def _lb2_group_stride(n: int, pg: int) -> int:
    """int32 words between pair groups in the lb2 kernel's HBM slot
    table and halves of its SMEM buffer: a group's 4·n·pg words rounded up
    to the 1024-word tile Mosaic slices a 1-D HBM array on."""
    return _round_up(pg * 4 * n, 1024)


def _lb2_slot_unroll(n: int) -> int:
    """Johnson slots per iteration of the lb2 slot loop: the largest
    divisor of ``n`` up to `_LB2_SLOT_UNROLL`."""
    return max(d for d in range(1, min(n, _LB2_SLOT_UNROLL) + 1)
               if n % d == 0)


@dataclasses.dataclass(frozen=True)
class _Lb2Lanes:
    """The refs of the lb2 pair loop (`_lb2_tile_lb`).

    ``slots`` (groups*stride,) int32 in HBM: pair q's Johnson slot t at
    ``q*4n + f*n + t`` for f = job, p0, p1, lag within its pair group,
    group g at ``g * stride`` (`_lb2_group_stride`), DMA'd into ``buf``
    (2*stride,) SMEM half g % 2 under ``sems`` while the group before it
    runs.  ``pairs`` (P*4,) int32 SMEM: ``4q + f`` for f = machine 0,
    machine 1, tail 0, tail 1.  ``cf`` (m, n, T) and ``u`` (n, n, T) int32
    VMEM scratch: the child fronts by machine and the free-job flags by
    job, child slot on the sublanes and the tile's parents on the lanes."""

    slots: object
    pairs: object
    cf: object
    u: object
    buf: object
    sems: object


def _lb2_lane_specs(P: int, index):
    """in_specs of the lb2 kernel's two tables; ``index`` is the
    grid's constant index map of a 1-D block."""
    return [pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((P * 4,), index, memory_space=pltpu.SMEM)]


def _lb2_lane_scratch(n: int, m: int, t: int, pg: int):
    """scratch_shapes of the lb2 pair loop at tile ``t``, in
    `_Lb2Lanes` order after ``pairs``."""
    return [pltpu.VMEM((m, n, t), jnp.int32), pltpu.VMEM((n, n, t), jnp.int32),
            pltpu.SMEM((2 * _lb2_group_stride(n, pg),), jnp.int32),
            pltpu.SemaphoreType.DMA((2,))]


def _lb2_tile_lb(prmu, limit1, pt, heads, lanes: _Lb2Lanes, times,
                 *, n: int, m: int, P: int, pg: int = 1):
    """(T, n) int32 lb2 bound of every child in the tile — the body of
    `_lb2_kernel`, shared with the one-kernel cycle (`ops/megakernel.py`).

    The shared prologue (`_tile_parent_state`) already holds the tile as
    (n, T) slabs, the child slot k on the sublanes and the T parents on
    the lanes: its m child-front slabs are staged as they are into
    ``lanes.cf``, and the free-job flags ``U[j][k, b]`` (job j open in
    parent b and not the job child k schedules) are built from its job and
    open-slot slabs into ``lanes.u``.  Each pair then runs the
    reference's Johnson scan (`c_bound_johnson.c:190-209`) slot by slot
    (`_lb2_slot_unroll` slots to a loop iteration), reading the job and
    its times from SMEM, where each pair group's tables arrive by DMA while
    the group before it runs:

        tmp0 += p0[q, t] * u_j
        tmp1  = u_j ? max(tmp1, tmp0 + lag[q, t]) + p1[q, t] : tmp1

    on (n, T) int32 slabs — elementwise work, no matmul, no lane
    reduction — with the ``pg`` pairs of a group interleaved, and folds
    ``max(tmp1 + tail1, tmp0 + tail0)`` into a running (n, T) max that is
    transposed back once.  The early exit (`c_bound_johnson.c:231-234`) is
    dropped as in `pfsp_device._lb2_chunk`; the bound is bit-equal to it
    on open slots."""
    T = prmu.shape[0]
    i32 = jnp.int32
    S = 4 * n  # one pair's slot table
    groups = P // pg
    stride = lanes.buf.shape[0] // 2

    def fetch(g, half):
        return pltpu.make_async_copy(
            lanes.slots.at[pl.ds(g * stride, stride)],
            lanes.buf.at[pl.ds(half * stride, stride)],
            lanes.sems.at[half])

    fetch(0, 0).start()  # the first group's tables arrive behind the prologue
    s = _tile_parent_state(prmu, limit1, pt, heads, times, n=n, m=m)
    for i in range(m):
        lanes.cf[i] = s.cf[i]
    job, free = s.job, s.free

    def flags(j, carry):
        eq = job == j
        open_j = jnp.max(jnp.where(eq, free, 0), axis=0, keepdims=True)
        lanes.u[j] = jnp.where(eq, 0, open_j)
        return carry

    jax.lax.fori_loop(0, n, flags, 0)

    def group_body(g, lb):
        half = g % 2
        fetch(g, half).wait()

        @pl.when(g + 1 < groups)
        def _():
            fetch(g + 1, 1 - half).start()

        tab, base = lanes.buf, half * stride
        q0 = g * pg
        chains = tuple(
            (lanes.cf[lanes.pairs[4 * (q0 + j)]],
             lanes.cf[lanes.pairs[4 * (q0 + j) + 1]])
            for j in range(pg)
        )

        def step(t, chains):
            out = []
            for j, (tmp0, tmp1) in enumerate(chains):
                r = base + j * S + t
                u = lanes.u[tab[r]] != 0
                tmp0 = tmp0 + jnp.where(u, tab[r + n], 0)
                tmp1 = jnp.where(
                    u, jnp.maximum(tmp1, tmp0 + tab[r + 3 * n]) + tab[r + 2 * n],
                    tmp1)
                out.append((tmp0, tmp1))
            return tuple(out)

        k = _lb2_slot_unroll(n)

        def steps(c, chains):
            for i in range(k):
                chains = step(c * k + i, chains)
            return chains

        chains = jax.lax.fori_loop(0, n // k, steps, chains)
        for j, (tmp0, tmp1) in enumerate(chains):
            q = q0 + j
            lb = jnp.maximum(lb, jnp.maximum(tmp1 + lanes.pairs[4 * q + 3],
                                             tmp0 + lanes.pairs[4 * q + 2]))
        return lb

    lb = jax.lax.fori_loop(0, groups, group_body, jnp.zeros((n, T), i32))
    return lb.T


def _lb2_kernel(prmu_ref, limit1_ref, pt_ref, heads_ref, slots_ref,
                pairs_ref, out_ref, times_ref, *lane_scratch, n: int, m: int,
                P: int, pg: int = 1):
    """Full lb2 (two-machine Johnson) bound of every child in the tile.

    Math identical to `ops/pfsp_device._lb2_chunk` (`c_bound_johnson.c:
    190-234`, early exit dropped — see that module's docstring).  The
    whole pair loop runs against VMEM-resident tile state (`_lb2_tile_lb`:
    child fronts and free-job flags as lane-dense slabs, the Johnson
    tables as SMEM scalars), so no per-pair intermediate touches HBM.

    ``pg``: pair-group unroll — the loop runs over P/pg pair GROUPS
    (caller pads P to a multiple) with pg interleaved pair chains per
    iteration, so independent chains hide each other's latency."""
    lanes = _Lb2Lanes(slots_ref, pairs_ref, *lane_scratch)
    out_ref[:] = _lb2_tile_lb(
        prmu_ref[:].astype(jnp.int32), limit1_ref[:].astype(jnp.int32),
        pt_ref, heads_ref, lanes, times_ref, n=n, m=m, P=P, pg=pg,
    )


@lru_cache(maxsize=None)
def _lb2_call(n: int, m: int, P: int, B: int, tile: int, interpret: bool,
              pg: int = 1, parallel: bool = False):
    """The standalone lb2 kernel over ``B // tile`` tiles; ``parallel``
    marks the grid Megacore-splittable (it carries nothing across tiles —
    `megakernel.streamed_eval_bounds`)."""
    return pl.pallas_call(
        partial(_lb2_kernel, n=n, m=m, P=P, pg=pg),
        out_shape=jax.ShapeDtypeStruct((B, n), jnp.int32),
        name="tts_eval_lb2",
        grid=(B // tile,),
        in_specs=[
            _bs((tile, n), lambda i: (i, 0)),
            _bs((tile, 1), lambda i: (i, 0)),
            *_pfsp_table_specs(n, m, lambda i: (0,))[:2],
            *_lb2_lane_specs(P, lambda i: (0,)),
        ],
        out_specs=_bs((tile, n), lambda i: (i, 0)),
        scratch_shapes=[_times_scratch(n, m, tile),
                        *_lb2_lane_scratch(n, m, tile, pg)],
        compiler_params=_compiler_params(parallel=parallel),
        interpret=interpret,
    )


def lb2_kernel_tables(ordered, pg: int) -> tuple:
    """The pair-table operands of an lb2 kernel over ``ordered`` (padded to
    a multiple of the pair group ``pg``), after the times and the heads
    (`_Lb2Lanes`: the slot table holds each group at its
    `_lb2_group_stride`, then the per-pair table)."""
    P, n = ordered.lag_o.shape
    rows = jnp.reshape(ordered.slot_tab, (P // pg, pg * 4 * n))
    slots = jnp.pad(rows, ((0, 0), (0, _lb2_group_stride(n, pg)
                                     - pg * 4 * n))).reshape(-1)
    return (slots, ordered.pair_tab)


def _eager_context() -> bool:
    """True outside any jax trace — the only context where device-cached
    table uploads are safe to build (a trace would capture tracers)."""
    from jax._src import core as _core

    return bool(_core.trace_state_clean())


def pfsp_lb2_bounds(prmu, limit1, tables, interpret: bool | None = None,
                    pair_group: int | None = None):
    """(B, n) int32 lb2 child bounds; same contract as `_lb2_chunk`.
    ``pair_group``: pair-group unroll per grid step (None resolves the
    shared TTS_LB2_PAIRBLOCK knob); the pair tables are padded to a
    multiple of it with copies of pair 0 (max is idempotent)."""
    interpret = pallas_interpret() if interpret is None else interpret
    B, n = prmu.shape
    m = tables.ptm_t.shape[1]
    P = tables.pairs.shape[0]
    pg = _resolve_pair_group("lb2", n, P, pair_group)
    tile = effective_tile("lb2", n, m, P, batch=B, pair_group=pg)
    Bp = _round_up(B, tile)
    if Bp != B:
        prmu = jnp.pad(prmu, ((0, Bp - B), (0, 0)))
        limit1 = jnp.pad(limit1, ((0, Bp - B),))
    # Eager calls reuse once-uploaded device tables; traced calls bake the
    # numpy tables as executable constants (and must NOT touch the device
    # cache — building it under a trace would capture tracers). Both are
    # padded to a pair-group multiple (johnson_ordered_mp's policy).
    ordered = (tables.johnson_ordered_device(pg) if _eager_context()
               else tables.johnson_ordered_mp(pg))
    Pp = ordered.lag_o.shape[0]
    out = _lb2_call(n, m, Pp, Bp, tile, interpret, pg)(
        prmu.astype(jnp.int32),
        limit1.astype(jnp.int32)[:, None],
        *pfsp_kernel_tables(tables.ptm_t, tables.min_heads,
                            tables.min_tails)[:2],
        *lb2_kernel_tables(ordered, pg),
    )
    return out[:B]


def pfsp_lb1_bounds(
    prmu, limit1, ptm_t, min_heads, min_tails, interpret: bool | None = None,
):
    """(B, n) int32 lb1 child bounds; same contract as `_lb1_chunk`."""
    interpret = pallas_interpret() if interpret is None else interpret
    return _lb1_family_bounds(
        _lb1_kernel, prmu, limit1, ptm_t, min_heads, min_tails, interpret,
    )


# ---------------------------------------------------------------------------
# PFSP lb2 self bound (staged evaluation)
# ---------------------------------------------------------------------------


def _lb2_self_kernel(
    prmu_ref, limit1_ref, nact_ref, pt_ref,
    p0_ref, p1_ref, lag_ref, t0_ref, t1_ref, msel0_ref, msel1_ref, jorder_ref,
    out_ref, times_ref, *, n: int, m: int, P: int, tile: int, pg: int = 1,
    bf16: bool = False,
):
    """Johnson bound of each ROW's own partial schedule (the staged
    evaluator's compacted child nodes) — `_lb2_kernel` with the
    child-expansion axis dropped, including its ``pg`` pair-group
    unrolling (fori_loop over P/pg groups, pg unrolled pair bodies each).
    Tiles whose rows are all beyond ``n_active`` skip the entire body:
    this is where the incumbent-driven work reduction lands (the
    reference's per-thread early exit, `evaluate.cu:73-91`, becomes
    whole-tile skipping on the sequential TPU grid)."""

    @pl.when(pl.program_id(0) * tile < nact_ref[0])
    def _active():
        prmu = prmu_ref[:].astype(jnp.int32)  # (T, n)
        limit1 = limit1_ref[:, 0].astype(jnp.int32)  # (T,) — always >= 0
        T = prmu.shape[0]
        hp = _hp_dot

        # schedule_front: the prologue's gather and scan on the transposed
        # tile (`_gather_times`, `_front_scan`), its m (1, T) rows laid
        # out as an (m, T) tile and transposed once to (T, m).
        _gather_times(prmu.T, pt_ref, times_ref, n=n, m=m)
        rows = _front_scan(limit1_ref[:].astype(jnp.int32).T, times_ref,
                           n=n, m=m)
        machine = jax.lax.broadcasted_iota(jnp.int32, (m, T), 0)
        front = jnp.zeros((m, T), jnp.int32)
        for j, row in enumerate(rows):
            front = jnp.where(machine == j, row, front)
        front = front.T.astype(jnp.float32)

        # Free flags by job id.
        jobs_iota = jax.lax.broadcasted_iota(jnp.int32, (T, n, n), 2)
        onehot = (jobs_iota == prmu[:, :, None]).astype(jnp.float32)
        slot_iota = jax.lax.broadcasted_iota(jnp.int32, (T, n), 1)
        unsched = (slot_iota >= (limit1 + 1)[:, None]).astype(jnp.float32)
        u = jnp.sum(onehot * unsched[:, :, None], axis=1)  # (T, job)

        neg = jnp.float32(-(2.0**30))
        ri = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        ci = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        tri_incl = (ri <= ci).astype(jnp.float32)
        tri_suf = (ri >= ci).astype(jnp.float32)

        def pair_body(q, lb):
            jord = jorder_ref[q]  # (n, n)
            u_o = hp(u, jord.T, bf16)  # (T, n) ordered free flags
            p0 = p0_ref[q][0].astype(jnp.float32)  # (n,)
            p1 = p1_ref[q][0].astype(jnp.float32)
            lag = lag_ref[q][0].astype(jnp.float32)
            s0 = msel0_ref[q][0].astype(jnp.float32)  # (m,)
            s1 = msel1_ref[q][0].astype(jnp.float32)
            tmp0_0 = jnp.sum(front * s0[None, :], axis=-1, keepdims=True)
            tmp1_0 = jnp.sum(front * s1[None, :], axis=-1, keepdims=True)
            mp0 = u_o * p0[None, :]
            mp1 = u_o * p1[None, :]
            cum0 = hp(mp0, tri_incl, bf16)
            suf1 = hp(mp1, tri_suf, bf16)
            a = jnp.where(u_o > 0, tmp0_0 + cum0 + lag[None, :] + suf1, neg)
            tmp1 = jnp.maximum(
                tmp1_0 + jnp.sum(mp1, axis=-1, keepdims=True),
                jnp.max(a, axis=-1, keepdims=True),
            )
            tmp0 = tmp0_0 + jnp.sum(mp0, axis=-1, keepdims=True)
            pair_lb = jnp.maximum(
                tmp1 + t1_ref[q].astype(jnp.float32),
                tmp0 + t0_ref[q].astype(jnp.float32),
            )
            return jnp.maximum(lb, pair_lb)

        lb0 = jnp.zeros((T, 1), jnp.float32)
        if pg > 1:
            def group_body(g, lb):
                q0 = g * pg
                for j in range(pg):  # static unroll within the group
                    lb = pair_body(q0 + j, lb)
                return lb

            lb = jax.lax.fori_loop(0, P // pg, group_body, lb0)
        else:
            lb = jax.lax.fori_loop(0, P, pair_body, lb0)
        out_ref[:] = lb.astype(jnp.int32)


@lru_cache(maxsize=None)
def _lb2_self_call(n: int, m: int, P: int, R: int, tile: int, interpret: bool,
                   bf16: bool = False, pg: int = 1):
    kernel = partial(_lb2_self_kernel, n=n, m=m, P=P, tile=tile, pg=pg,
                     bf16=bf16)
    grid = (R // tile,)
    full3 = lambda i: (0, 0, 0)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((R, 1), jnp.int32),
        name="tts_eval_lb2_self",
        grid=grid,
        in_specs=[
            _bs((tile, n), lambda i: (i, 0)),
            _bs((tile, 1), lambda i: (i, 0)),
            _bs((1,), lambda i: (0,), space="smem"),
            *_pfsp_table_specs(n, m, lambda i: (0,))[:1],
            _bs((P, 1, n), full3),
            _bs((P, 1, n), full3),
            _bs((P, 1, n), full3),
            _bs((P,), lambda i: (0,), space="smem"),
            _bs((P,), lambda i: (0,), space="smem"),
            _bs((P, 1, m), full3),
            _bs((P, 1, m), full3),
            _bs((P, n, n), full3),
        ],
        out_specs=_bs((tile, 1), lambda i: (i, 0)),
        scratch_shapes=[_times_scratch(n, m, tile)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )


_ORDERED_FIELDS = ("p0_o", "p1_o", "lag_o", "tails0", "tails1",
                   "msel0", "msel1", "jorder")


class _PaddedOrdered:
    """Ordered tables padded to a pair-group multiple with copies of pair 0
    (max over pairs is idempotent). Works on traced fields — the mp-sharded
    path passes dynamic slices — and the pads are (reps, ...) slivers."""

    def __init__(self, ordered, reps: int):
        for f in _ORDERED_FIELDS:
            arr = jnp.asarray(getattr(ordered, f))
            setattr(self, f, jnp.concatenate(
                [arr, jnp.repeat(arr[:1], reps, axis=0)], axis=0
            ))


def pfsp_lb2_self_bounds_tables(prmu, limit1, n_active, ptm_t, ordered,
                                interpret: bool | None = None,
                                bf16: bool = False,
                                pair_group: int | None = None):
    """`pfsp_lb2_self_bounds` over EXPLICIT ordered tables (possibly traced
    slices of the full pair set — the mp-sharded staged path slices each
    shard's contiguous pair block before the call; pallas_call takes traced
    operands like any other op). ``ordered`` needs p0_o/p1_o/lag_o (P, n),
    tails0/tails1 (P,), msel0/msel1 (P, m), jorder (P, n, n)."""
    interpret = pallas_interpret() if interpret is None else interpret
    R, n = prmu.shape
    m = ptm_t.shape[1]
    P = ordered.lag_o.shape[0]
    pg = _resolve_pair_group("lb2self", n, P, pair_group)
    reps = _round_up(P, pg) - P
    if reps:
        ordered = _PaddedOrdered(ordered, reps)
    tile = effective_tile("lb2self", n, m, P, batch=R, pair_group=pg)
    Rp = _round_up(R, tile)
    if Rp != R:
        prmu = jnp.pad(prmu, ((0, Rp - R), (0, 0)))
        limit1 = jnp.pad(limit1, ((0, Rp - R),))
    out = _lb2_self_call(n, m, P + reps, Rp, tile, interpret, bf16, pg)(
        prmu.astype(jnp.int32),
        limit1.astype(jnp.int32)[:, None],
        jnp.asarray(n_active, dtype=jnp.int32).reshape(1),
        _flat_times(ptm_t),
        ordered.p0_o[:, None, :],
        ordered.p1_o[:, None, :],
        ordered.lag_o[:, None, :],
        ordered.tails0,
        ordered.tails1,
        ordered.msel0[:, None, :],
        ordered.msel1[:, None, :],
        ordered.jorder,
    )
    return out[:R, 0]


def pfsp_lb2_self_bounds(prmu, limit1, n_active, tables,
                         interpret: bool | None = None,
                         bf16: bool | None = None,
                         pair_group: int | None = None):
    """(R,) int32 self lb2 bounds; rows >= n_active are garbage (their
    tiles are skipped entirely). Same contract as `_lb2_self_chunk` on the
    first n_active rows."""
    if bf16 is None:
        bf16 = getattr(tables, "exact_bf16", False)
    n = prmu.shape[-1]
    pg = _resolve_pair_group("lb2self", n, tables.pairs.shape[0], pair_group)
    # Tables pre-padded to the pair-group multiple: the cached device copy
    # (eager) / host numpy (traced) avoid a per-call concat.
    ordered = (tables.johnson_ordered_device(pg) if _eager_context()
               else tables.johnson_ordered_mp(pg))
    return pfsp_lb2_self_bounds_tables(
        prmu, limit1, n_active, tables.ptm_t, ordered, interpret, bf16,
        pair_group=pg,
    )
