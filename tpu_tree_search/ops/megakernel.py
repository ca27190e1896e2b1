"""One-kernel resident cycle: a grid-tiled, streamed Pallas megakernel.

The resident engine's inner loop (pop -> bound -> prune -> compact -> push,
the offload cycle of `pfsp_gpu_chpl.chpl:276-298`) normally compiles as a
chain of XLA ops inside the `lax.while_loop`: each op boundary is a
dispatch, and every intermediate (the child cube, the keep plane, the
compacted rows) round-trips through HBM.  At the headline shapes (M around
1024) `tts profile` shows the cycle is dominated by exactly those
boundaries.  This module fuses the whole cycle into a SINGLE `pallas_call`:
the popped chunk streams through VMEM in pool tiles of width ``Mt``
(``grid=(M//Mt,)`` — the pipelined grid double-buffers each tile's
HBM->VMEM copy under the previous tile's bound evaluation, the in-kernel
form of the PR 5 `DeviceOffloader.stage/dispatch_staged` host overlap),
bounds are evaluated with the same tile math as the standalone kernels
(`_nqueens_tile_labels` / `_lb1_tile_lb` / `_lb2_tile_lb` in
`ops/pallas_kernels.py` — shared helpers, so the bound values are the
already-pinned-exact kernel values), pruning, the LSB-first binary-shift
survivor compaction of `ops/compaction.shift_compact`, and the push all
happen against the tile in VMEM, and only the compacted child rows leave.

Tiling (``TTS_MEGAKERNEL_MT``, auto-resolved like `_auto_compact`):

* ``Mt == M`` (one tile) keeps the original pool-resident form verbatim:
  ``grid=(1,)``, no streaming, the whole chunk lives in VMEM.
* ``Mt < M`` streams ``G = M//Mt`` tiles.  Survivor compaction becomes
  two-phase across tiles: each tile dense-ranks its own survivors in VMEM
  (`_compact_push` at width Mt), and an SMEM carry accumulates the
  cross-tile survivor offset so push destinations stay collision-free and
  the concatenation of tiles is exactly the dense-mode global
  (parent, slot) order of `ops/compaction.py`.  The engine stitches the
  per-tile blocks with G overlapping `dynamic_update_slice` writes at the
  carried offsets — bit-identical to the single-tile emit.
* The PFSP families need the incumbent fold over ALL leaves before any
  tile's keep test (`best = min(best, leaf bounds)` is global), so their
  grid is ``(2, G)``: phase 0 streams every tile, evaluates bounds into a
  per-tile VMEM stash and folds the global leaf-min; phase 1 re-streams
  the tiles (bounds are NOT recomputed — they are read back from the
  stash) and runs prune/compact/emit against the final incumbent.
  N-Queens has no bound pruning and keeps the single sweep ``grid=(G,)``.
* The cross-tile carry forces sequential grid order, so the full cycle
  kernels declare ``dimension_semantics=("arbitrary", ...)``; the
  evaluation-only pass has no carry and ships as a separate
  Megacore-parallel variant (:func:`streamed_eval_bounds`,
  ``dimension_semantics=("parallel",)`` — one chip's two TensorCores
  split the tiles).

Exactness:

* survivor ranks are triangular MXU matmuls over the 0/1 keep plane at
  HIGHEST precision — counts are < 2^24, so f32 accumulation is exact;
* lb1 is the int32 chain of `_lb1_tile_lb` (bit-exact vs `_lb1_chunk` on
  open slots);
* lb2 rides the max-plus closed form as bf16 MXU matmuls and is only
  allowed to arm when the instance passes the bf16-exactness gate
  (`PFSPDeviceTables.exact_bf16`: every processing time < 2^8, so every
  matmul operand is exactly representable in bf16) — otherwise
  :func:`resolve` refuses and records why (banner + SearchResult).

Routing (`TTS_MEGAKERNEL=auto|0|force`, resolved like the compact auto
policy): ``auto`` arms only on a real TPU backend and when the per-tile
VMEM model fits — inside the small-M window the single-tile resident form
is kept verbatim; past it `Mt` shrinks `_auto_tile`-style (halving, sublane
aligned, dividing M) until each tile fits the window and the per-tile +
double-buffer + stash charge of `_mega_pool_bytes`, so ``auto`` arms far
past the old ``M*n <= 2^16`` ceiling and refuses only when even the
smallest tile (or the PFSP bound stash, which scales with M) cannot fit.
``force`` arms everywhere (interpret mode off-TPU — the CI/CPU parity
spelling).  The raw TTS_MEGAKERNEL and TTS_MEGAKERNEL_MT knobs are keyed
into `routing_cache_token`, so a flip rebuilds the resident program and
``0`` is a byte-identical jaxpr (contracts `megakernel-off-identity`,
`megakernel-tiled-identity`).

Keep/retire: the lb1 Pallas kernel lost 7x to fused jnp and was demoted
(docs/HW_VALIDATION.md) — this kernel ships with the same decision
procedure (docs/HW_VALIDATION.md "Megakernel keep/retire",
`hw_session.sh` stage 8): it either beats the measured phase split on chip
— now quantified per phase by the roofline audit (`obs/roofline.py`,
`tts report --roofline`) — or dies quickly.
"""

from __future__ import annotations

import dataclasses
import os
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..analysis.contracts import contract
from . import backend as BK
from . import pallas_kernels as PK

#: single-tile window on the Mt*n product — within it the original
#: pool-resident form (grid=(1,), no streaming) is kept verbatim; past it
#: the pool axis tiles down so each STREAMED TILE stays inside the regime
#: the dense shift-compact was validated in (same window as the
#: dense-compact policy).
SMALL_M_LIMIT = 1 << 16

#: the same window expressed in POOL BYTES (2^16 int32 elements): with
#: narrow node storage armed (TTS_NARROW, problems/base.py) the write-back
#: that bounds the small-M regime moves pool-dtype bytes, so the window
#: widens by the narrowing factor — an int8 pool admits 4x the
#: Mt*n product at the same byte traffic. TTS_NARROW=0 keeps the
#: element-count window verbatim (`narrow-knob-inert`).
SMALL_M_BYTES = SMALL_M_LIMIT * 4


def _pool_itemsize(fam: str, n: int) -> int:
    """Bytes per pool value element for the resident pool this cycle runs
    against — the `engine/resident._pool_int_dtype` ladder (int8/int16/
    int32 by n) for PFSP, the uint8 board for N-Queens. Mirrored here so
    the kernel module keeps its lazy-import relationship with the engine
    package."""
    if fam == "nqueens":
        return 1
    return 1 if n <= 127 else (2 if n <= 32767 else 4)

#: mirrors problems.base.INF_BOUND without importing the problems package
#: into a kernel module (the packages import each other lazily).
_INF_BOUND = 2**31 - 1


def megakernel_mode() -> str:
    """The TTS_MEGAKERNEL knob: ``auto`` (default — TPU + per-tile VMEM
    fit), ``0`` (off, byte-identical jaxpr), ``force`` (arm everywhere;
    interpret mode off-TPU)."""
    mode = os.environ.get("TTS_MEGAKERNEL", "auto")
    if mode not in ("auto", "0", "force"):
        raise ValueError(
            f"TTS_MEGAKERNEL must be auto|0|force, got {mode!r}"
        )
    return mode


def megakernel_mt() -> int | None:
    """The TTS_MEGAKERNEL_MT knob: force the streamed pool-tile width
    ``Mt`` (None — unset — resolves it from the VMEM budget).  Alignment
    (multiple of 8, divides M) is a per-shape property and is checked in
    :func:`resolve`, which refuses with a recorded reason instead of
    raising."""
    raw = os.environ.get("TTS_MEGAKERNEL_MT")
    if raw is None or raw == "":
        return None
    mt = int(raw)
    if mt <= 0:
        raise ValueError(
            f"TTS_MEGAKERNEL_MT must be a positive tile width, got {raw!r}"
        )
    return mt


@dataclasses.dataclass(frozen=True)
class Decision:
    """The resolved megakernel routing for one resident program build.

    ``reason`` records why the kernel did NOT arm (auto declined, or a
    correctness refusal that even ``force`` honors) — surfaced in the
    `tts` banner and carried in SearchResult.megakernel_reason.
    ``mt``/``grid`` record the resolved pool-tile width and tile count
    (``grid == 1`` is the original single-tile resident form; ``grid > 1``
    streams the pool through VMEM tile by tile).  ``backend`` is the
    kernel flavor the cycle builds with (`ops/backend.py` — ``gpu`` is
    the Triton lowering, single-tile only: the cross-tile SMEM carry
    needs the TPU's sequential grid)."""

    enabled: bool
    auto: bool
    interpret: bool
    reason: str | None
    mt: int = 0
    grid: int = 1
    backend: str = "tpu"

    @property
    def state(self) -> str:
        return "on" if self.enabled else "off"

    @property
    def tiled(self) -> bool:
        return self.enabled and self.grid > 1


def _family(problem) -> str | None:
    name = getattr(problem, "name", None)
    if name == "nqueens":
        return "nqueens"
    if name == "pfsp":
        return getattr(problem, "lb", None)
    return None


def _native_kind(device) -> str | None:
    """The kernel flavor the resolved backend compiles NATIVELY on the
    target device — 'tpu' or 'gpu' — else None (interpret territory:
    forced/interpret builds, CPU processes).  Replaces the old hard
    ``platform == "tpu"`` gate with the `ops/backend.py` seam."""
    b = BK.resolve_backend(device)
    return b.kind if (b.native and b.kind in ("tpu", "gpu")) else None


def _mega_pool_bytes(M: int, n: int, pool_itemsize: int = 4,
                     mt: int | None = None, lb_stash: bool = False) -> int:
    """The cycle's VMEM charge on top of the bound kernels' own
    `_model_bytes` model (the ``extra_bytes`` the feasibility gate adds).

    Single tile (``mt`` None or == M): the original pool-resident charge —
    the batch tile IS M (grid=(1,)), so `_compact_push`'s (M*n, M)
    selection matrix (f32 and its bf16 copy), the (M*n, n) gathered
    parent rows, the per-row columns, and the two triangular rank
    operands are all live inside one grid step.

    Tiled (``mt < M``): the same intermediates at tile width Mt, PLUS a 2x
    double-buffer charge on every streamed block (the pipelined grid
    prefetches tile i+1's HBM->VMEM copies under tile i's compute — in
    and out blocks both carry two live buffers), PLUS, with ``lb_stash``
    (the PFSP two-phase grid), the (G, Mt, n) int32 bound stash that holds
    phase 0's evaluations for phase 1 — the one charge that scales with M,
    not Mt, and therefore the one that can still refuse a shape.

    ``pool_itemsize`` charges the pool-dtype tiles (the popped values
    entering) at their storage width; the in-kernel intermediates stay
    int32/f32 regardless."""
    r8, r128 = PK._r8, PK._r128
    if mt is not None and mt >= M:
        mt = None
    if mt is None:
        Mn = M * n
        sel = r8(Mn) * r128(M) * 6              # (Mn, M) selection, f32+bf16
        flat = 3 * r8(Mn) * r128(n) * 4         # (Mn, n) gathered rows
        cols = 4 * r8(Mn) * 128 * 4             # side/offset/slot columns
        tri = r8(M) * r128(M) * 4 + r8(n) * r128(n) * 4  # rank triangles
        # popped pool tile + its narrow copy, keep plane, scalar lanes
        io = (2 * r8(M) * r128(n) * pool_itemsize
              + r8(M) * r128(n) * 4 + 128 * 4)
        return sel + flat + cols + tri + io
    G = M // mt
    Mtn = mt * n
    sel = r8(Mtn) * r128(mt) * 6
    flat = 3 * r8(Mtn) * r128(n) * 4
    cols = 4 * r8(Mtn) * 128 * 4
    tri = r8(mt) * r128(mt) * 4 + r8(n) * r128(n) * 4
    # Streamed blocks are double-buffered by the pipelined grid: two live
    # copies of each in block (pool tile + narrow copy + keep plane +
    # lanes) and each out block (compacted rows + aux column + scalar row).
    stream_in = 2 * (2 * r8(mt) * r128(n) * pool_itemsize
                     + r8(mt) * r128(n) * 4 + 128 * 4)
    stream_out = 2 * (r8(Mtn) * r128(n) * 4 + r8(Mtn) * 128 * 4 + 128 * 4)
    total = sel + flat + cols + tri + stream_in + stream_out
    if lb_stash:
        total += G * r8(mt) * r128(n) * 4
    return total


def _tile_window_ok(fam: str, mt: int, n: int) -> bool:
    """Per-tile small-M window: the regime the dense compaction was
    validated in, which each streamed tile must stay inside (byte-based
    with narrow storage)."""
    from ..problems.base import narrow_enabled

    if narrow_enabled():
        return mt * n * _pool_itemsize(fam, n) <= SMALL_M_BYTES
    return mt * n <= SMALL_M_LIMIT


def _fits(problem, fam: str, M: int, n: int, mt: int | None = None,
          backend: str = "tpu") -> tuple[bool, str | None]:
    """Fast-memory feasibility at pool-tile width ``mt`` (None or M — the
    single-tile resident form; smaller — the streamed per-tile +
    double-buffer + stash charge of `_mega_pool_bytes`), against the
    backend's budget (`pallas_kernels._vmem_limit_bytes`)."""
    from ..problems.base import narrow_enabled

    if mt is not None and mt >= M:
        mt = None
    t = mt or M
    itemsize = _pool_itemsize(fam, n) if narrow_enabled() else 4
    extra = _mega_pool_bytes(M, n, itemsize, mt=mt,
                             lb_stash=(fam != "nqueens"))
    if fam == "nqueens":
        need = PK._model_bytes(t, n, 1, extra, 3)
    elif fam == "lb1":
        need = PK._model_bytes(t, n, problem.machines, extra, 3)
    else:  # lb2
        from . import pfsp_device as PD

        m = problem.machines
        P = problem.lb2_data.pairs.shape[0]
        pg = PD.lb2_kernel_pair_group(P, n)
        need = PK._model_bytes(
            t, n, m, extra + PK._lb2_static_extra(n, m, P + (-P) % pg), 3,
            pair_copies=5, pair_group=pg,
        )
    budget = PK._vmem_budget(backend)
    if need > budget:
        if mt is None:
            return False, (
                f"auto: VMEM model {need // 2**20} MiB exceeds the "
                f"{budget // 2**20} MiB budget at M={M} "
                "(single-tile resident cycle)"
            )
        return False, (
            f"auto: VMEM model {need // 2**20} MiB exceeds the "
            f"{budget // 2**20} MiB budget even at pool tile Mt={t} "
            "(per-tile charge + double-buffered streams + the (G, Mt, n) "
            "bound stash, which scales with M)"
        )
    return True, None


def _resolve_mt(problem, fam: str, M: int, n: int) -> int | None:
    """Resolve the streamed pool-tile width `_auto_compact`-style: the
    largest halving-ladder Mt (multiple of 8, divides M) whose tile stays
    inside the small-M window AND whose per-tile VMEM model fits.  None
    when even the smallest tile cannot fit (the caller records the
    refusal)."""
    mt = M
    while True:
        if _tile_window_ok(fam, mt, n) and _fits(problem, fam, M, n, mt)[0]:
            return mt
        if mt <= 8:
            return None
        nxt = max(8, (mt // 2) // 8 * 8)
        while M % nxt:
            nxt -= 8
        mt = nxt


def resolve(problem, M: int, device=None, mp_axis: str | None = None,
            mp_size: int = 1) -> Decision:
    """Resolve the megakernel routing for one resident program build —
    the `_auto_compact`-style policy.  Correctness refusals (unsupported
    bound family, mp pair sharding, the lb2 bf16-exactness gate, tile
    misalignment — including a TTS_MEGAKERNEL_MT that does not divide M,
    and tiled streaming on the gpu flavor, whose cross-tile SMEM carry
    only the TPU's sequential grid can run) hold even under ``force``;
    the remaining gates (native TPU/GPU backend, per-tile memory fit)
    apply to ``auto`` only."""
    mode = megakernel_mode()
    if mode == "0":
        return Decision(False, False, False, None)
    auto = mode == "auto"
    fam = _family(problem)
    n = int(problem.child_slots)
    kb = BK.kernel_kind(device)  # 'gpu' only when the seam resolves gpu
    if fam not in ("nqueens", "lb1", "lb2"):
        return Decision(False, auto, False,
                        f"unsupported bound family {fam!r} (the megakernel "
                        "ports nqueens/lb1/lb2 only)", backend=kb)
    if mp_axis is not None or mp_size > 1:
        return Decision(False, auto, False,
                        "mp pair-axis sharding (the fused cycle is "
                        "single-shard)", backend=kb)
    if M % 8 != 0:
        return Decision(False, auto, False,
                        f"M={M} not a multiple of the sublane quantum (8)",
                        backend=kb)
    if fam == "lb2":
        t = problem.device_tables()
        if not getattr(t, "exact_bf16", False):
            return Decision(False, auto, False,
                            "lb2 bf16-exactness gate: max processing time "
                            ">= 256, the max-plus MXU formulation is not "
                            "bit-exact (f32 pair-blocked oracle keeps the "
                            "cycle)", backend=kb)
    mt_env = megakernel_mt()
    if mt_env is not None and (mt_env % 8 != 0 or M % mt_env != 0):
        return Decision(False, auto, False,
                        f"TTS_MEGAKERNEL_MT={mt_env} must be a multiple of "
                        f"the sublane quantum (8) and divide M={M}",
                        backend=kb)
    if kb == "gpu" and mt_env is not None and mt_env < M:
        return Decision(False, auto, False,
                        f"gpu backend: TTS_MEGAKERNEL_MT={mt_env} < M={M} "
                        "requests tiled streaming, whose cross-tile SMEM "
                        "carry needs the TPU's sequential grid (Triton "
                        "blocks are parallel)", backend=kb)
    native = _native_kind(device)
    if not auto:
        interpret = PK.pallas_interpret() or native is None
        if kb == "gpu":
            return Decision(True, False, interpret, None, mt=M, grid=1,
                            backend="gpu")
        mt = mt_env or _resolve_mt(problem, fam, M, n) or M
        return Decision(True, False, interpret, None, mt=mt, grid=M // mt)
    if native is None or PK.pallas_interpret():
        reason = ("auto: not on a TPU backend" if kb != "gpu" else
                  "auto: kernel backend gpu is not native here (no GPU in "
                  "this process — TTS_MEGAKERNEL=force runs it interpreted)")
        return Decision(False, True, False, reason, backend=kb)
    if kb == "gpu":
        # Single tile or nothing: tiled streaming is a TPU-only construct.
        ok, why = _fits(problem, fam, M, n, backend="gpu")
        if _tile_window_ok(fam, M, n) and ok:
            return Decision(True, True, False, None, mt=M, grid=1,
                            backend="gpu")
        why = why or (
            f"gpu backend: M*n={M * n} exceeds the single-tile window and "
            "tiled streaming needs the TPU's sequential-grid SMEM carry")
        return Decision(False, True, False, why, backend="gpu")
    if mt_env is not None:
        ok, why = _fits(problem, fam, M, n, mt_env)
        if not ok:
            return Decision(False, True, False, why)
        return Decision(True, True, False, None, mt=mt_env,
                        grid=M // mt_env)
    # Single-tile fast path: inside the small-M window the original
    # pool-resident form (grid=(1,), no 2x PFSP re-stream) is kept
    # verbatim when it fits.
    if _tile_window_ok(fam, M, n) and _fits(problem, fam, M, n)[0]:
        return Decision(True, True, False, None, mt=M, grid=1)
    mt = _resolve_mt(problem, fam, M, n)
    if mt is None:
        _, why = _fits(problem, fam, M, n, 8)
        return Decision(False, True, False, why)
    return Decision(True, True, False, None, mt=mt, grid=M // mt)


# ---------------------------------------------------------------------------
# in-kernel cycle epilogue: prune -> rank -> shift-compact -> emit
# ---------------------------------------------------------------------------

#: Trace regions inside the cycle kernels (``jax.named_scope`` lowers to
#: Mosaic ``trace_start``/``trace_stop``): the tile bound, and the prune +
#: compaction + emit epilogue (docs/OBSERVABILITY.md leg 7).
BOUND_SCOPE = "tts_bound"
COMPACT_SCOPE = "tts_compact"


def _scalar_lanes(tree_inc, sol_inc, best):
    """(1, 128) int32 scalar output row: lanes 0/1/2 = tree_inc / sol_inc /
    best (Mosaic wants a full lane register, not three scalars)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    return jnp.where(
        lane == 0, tree_inc,
        jnp.where(lane == 1, sol_inc, jnp.where(lane == 2, best, 0)),
    )


def _tile_scalar_lanes(offs, cnt, sol_cum, best):
    """(1, 128) int32 PER-TILE scalar row of the streamed grid: lanes
    0/1/2/3 = cross-tile survivor offset before this tile / this tile's
    survivor count / cumulative sol_inc through this tile / incumbent.
    The engine reads the last tile's row for the cycle scalars and the
    offset column for the stitch destinations."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    return jnp.where(
        lane == 0, offs,
        jnp.where(lane == 1, cnt,
                  jnp.where(lane == 2, sol_cum,
                            jnp.where(lane == 3, best, 0))),
    )


def _compact_push(vals, aux, d, keep, *, n: int, M: int):
    """Survivor compaction entirely in VMEM, as one-hot MXU matmuls.

    Survivor ranks are exclusive prefix counts (triangular matmuls over
    the 0/1 keep plane).  Output row ``k`` is filled by the selection
    matrix ``T (M*n, M)``, ``T[k, p] = 1`` iff parent ``p`` owns survivor
    rank ``k``: ``T @ vals`` gives each output row its parent's values,
    and the parent's keep/lane-rank rows and scalars come the same way.
    The surviving slot ``j`` is the kept slot whose within-parent rank is
    ``k - offs[p]``, and the child is the parent with positions ``d`` and
    ``j`` swapped (`_swap_children`'s structure) — all lane-wise selects
    on ``(M*n, n)`` tiles.  Nothing is reshaped between sublanes and lanes
    (Mosaic refuses those shape casts) and no slice is unaligned.

    Returns (rows (Mn, n) i32, caux (Mn, 1) i32, tree_inc) with rows
    beyond ``tree_inc`` zero (dead by the pool contract — the engine
    advances ``size`` by tree_inc only).  On the streamed path this runs
    per tile at ``M = Mt``; the cross-tile offset carry makes the
    concatenation of tiles the dense-mode global order."""
    i32, f32 = jnp.int32, jnp.float32
    Mn = M * n
    keep_f = keep.astype(f32)  # (M, n)
    # Every operand value of the big selection matmuls (0/1 masks, lane
    # ranks < n, job or column indices < n) is exact in bf16 when n < 256.
    small = n < 256

    # Exclusive prefix counts, within the row along lanes and across
    # parents.  Counts are < 2^24, so f32 accumulation is exact.
    rl = jax.lax.broadcasted_iota(i32, (n, n), 0)
    cl = jax.lax.broadcasted_iota(i32, (n, n), 1)
    lane = PK._hp_dot(keep_f, (rl < cl).astype(f32), bf16=small)  # (M, n)
    cnt = jnp.sum(keep_f, axis=1, keepdims=True)  # (M, 1)
    rm = jax.lax.broadcasted_iota(i32, (M, M), 0)
    cm = jax.lax.broadcasted_iota(i32, (M, M), 1)
    offs = PK._hp_dot((cm < rm).astype(f32), cnt)  # (M, 1)
    tree_inc = jnp.sum(keep, dtype=i32)
    # The same counts as ROWS (1, M), for the selection matrix: an A @ B^T
    # matmul of a ones block against the keep plane (no transpose op).
    cnt_row = jax.lax.dot_general(
        jnp.ones((8, n), f32), keep_f, (((1,), (1,)), ((), ())),
        preferred_element_type=f32,
    )[0:1]  # (1, M)
    offs_row = PK._hp_dot(cnt_row, (rm < cm).astype(f32))  # (1, M)

    k = jax.lax.broadcasted_iota(i32, (Mn, M), 0).astype(f32)
    sel = ((k >= offs_row) & (k < offs_row + cnt_row)).astype(f32)
    par = PK._hp_dot(sel, vals.astype(f32), bf16=small)   # (Mn, n)
    pkeep = PK._hp_dot(sel, keep_f, bf16=small)           # (Mn, n)
    plane = PK._hp_dot(sel, lane, bf16=small)             # (Mn, n)
    # Per-parent scalars in lanes 0/1/2 of one side table: offset, swap
    # position, child aux.  Offsets may exceed 2^8: full precision.
    li = jax.lax.broadcasted_iota(i32, (M, 8), 1)
    side = jnp.where(li == 0, offs,
                     jnp.where(li == 1, d.astype(f32)[:, None],
                               jnp.where(li == 2,
                                         (aux + 1).astype(f32)[:, None],
                                         0.0)))
    pside = PK._hp_dot(sel, side)  # (Mn, 8)
    lo = jax.lax.broadcasted_iota(i32, (Mn, 8), 1)
    poffs = jnp.sum(jnp.where(lo == 0, pside, 0.0), axis=1, keepdims=True)
    pd = jnp.sum(jnp.where(lo == 1, pside, 0.0), axis=1,
                 keepdims=True).astype(i32)
    caux = jnp.sum(jnp.where(lo == 2, pside, 0.0), axis=1,
                   keepdims=True).astype(i32)

    # The surviving slot j of output row k: the kept slot whose rank
    # within its parent is k - offs[p].
    kc = jax.lax.broadcasted_iota(i32, (Mn, 1), 0).astype(f32)
    jl = jax.lax.broadcasted_iota(i32, (Mn, n), 1)
    hit = (pkeep > 0.5) & (plane == kc - poffs)
    j = jnp.sum(jnp.where(hit, jl, 0), axis=1, keepdims=True)  # (Mn, 1)
    par = par.astype(i32)
    v_d = jnp.sum(jnp.where(jl == pd, par, 0), axis=1, keepdims=True)
    v_j = jnp.sum(jnp.where(jl == j, par, 0), axis=1, keepdims=True)
    rows = jnp.where(jl == pd, v_j, jnp.where(jl == j, v_d, par))
    return rows, caux, tree_inc


def _pfsp_epilogue(prmu, limit1, valid, best, lb, *, n: int, M: int):
    """The `_PFSPResident` evaluate fold (open/leaf/incumbent/keep — the
    unstaged branch; see the staged-equivalence note in `make_cycle`) +
    compaction.  ``lb`` int32 per child slot; swap position and child
    limit1 are both ``limit1 + 1``.  On the streamed grid ``best`` arrives
    already folded over ALL tiles' leaves (phase 0), so the local re-fold
    here is idempotent and the keep test prunes against the same global
    incumbent every tile — the reason the PFSP grid is two-phase."""
    i32 = jnp.int32
    pdepth = limit1 + 1
    kk = jax.lax.broadcasted_iota(i32, (M, n), 1)
    open_ = (kk >= pdepth[:, None]) & valid[:, None]
    leaf = open_ & ((pdepth[:, None] + 1) == n)
    sol_inc = jnp.sum(leaf, dtype=i32)
    best = jnp.minimum(best, jnp.min(jnp.where(leaf, lb, i32(_INF_BOUND))))
    keep = open_ & (~leaf) & (lb < best)
    rows, caux, tree_inc = _compact_push(prmu, limit1, pdepth, keep, n=n, M=M)
    return rows, caux, tree_inc, sol_inc, best


def _pfsp_leaf_min(limit1, valid, lb, *, n: int, M: int):
    """Phase 0's contribution to the global incumbent fold: the min bound
    over this tile's leaves (INF when none)."""
    i32 = jnp.int32
    pdepth = limit1 + 1
    kk = jax.lax.broadcasted_iota(i32, (M, n), 1)
    open_ = (kk >= pdepth[:, None]) & valid[:, None]
    leaf = open_ & ((pdepth[:, None] + 1) == n)
    return jnp.min(jnp.where(leaf, lb, i32(_INF_BOUND)))


# ---------------------------------------------------------------------------
# family cycle kernels — single tile (grid=(1,), pool resident)
# ---------------------------------------------------------------------------


def _mega_nqueens_kernel(board_ref, depth_ref, valid_ref, best_ref,
                         out_vals_ref, out_aux_ref, scal_ref,
                         *, N: int, g: int, M: int):
    board = board_ref[:].astype(jnp.int32)  # (M, N)
    depth = depth_ref[:, 0].astype(jnp.int32)  # (M,)
    valid = valid_ref[:, 0] != 0
    best = best_ref[0]
    with jax.named_scope(BOUND_SCOPE):
        labels = PK._nqueens_tile_labels(board, depth, N=N, g=g)
    # The `_NQueensResident` evaluate fold: swap position is the depth.
    keep = labels & valid[:, None] & (depth < N)[:, None]
    sol_inc = jnp.sum(valid & (depth == N), dtype=jnp.int32)
    with jax.named_scope(COMPACT_SCOPE):
        rows, caux, tree_inc = _compact_push(board, depth, depth, keep,
                                             n=N, M=M)
    out_vals_ref[:] = rows
    out_aux_ref[:] = caux
    scal_ref[:] = _scalar_lanes(tree_inc, sol_inc, best)


def _mega_lb1_kernel(prmu_ref, limit1_ref, valid_ref, best_ref,
                     ptm_ref, heads_ref, tails_ref,
                     out_vals_ref, out_aux_ref, scal_ref, scan_ref,
                     *, n: int, m: int, M: int, bf16: bool):
    prmu = prmu_ref[:].astype(jnp.int32)
    limit1 = limit1_ref[:, 0].astype(jnp.int32)
    valid = valid_ref[:, 0] != 0
    best = best_ref[0]
    ptm = ptm_ref[:].astype(jnp.float32)
    with jax.named_scope(BOUND_SCOPE):
        lb = PK._lb1_tile_lb(prmu, limit1, ptm, heads_ref[:], tails_ref[:],
                             scan_ref, n=n, m=m, bf16=bf16)
    with jax.named_scope(COMPACT_SCOPE):
        rows, caux, tree_inc, sol_inc, best = _pfsp_epilogue(
            prmu, limit1, valid, best, lb, n=n, M=M)
    out_vals_ref[:] = rows
    out_aux_ref[:] = caux
    scal_ref[:] = _scalar_lanes(tree_inc, sol_inc, best)


def _mega_lb2_kernel(prmu_ref, limit1_ref, valid_ref, best_ref,
                     ptm_ref, heads_ref,
                     p0_ref, p1_ref, lag_ref, t0_ref, t1_ref,
                     msel0_ref, msel1_ref, jorder_ref,
                     out_vals_ref, out_aux_ref, scal_ref, scan_ref,
                     *, n: int, m: int, P: int, M: int, pg: int, bf16: bool):
    prmu = prmu_ref[:].astype(jnp.int32)
    limit1 = limit1_ref[:, 0].astype(jnp.int32)
    valid = valid_ref[:, 0] != 0
    best = best_ref[0]
    ptm = ptm_ref[:].astype(jnp.float32)
    with jax.named_scope(BOUND_SCOPE):
        lb = PK._lb2_tile_lb(
            prmu, limit1, ptm, heads_ref[:],
            p0_ref, p1_ref, lag_ref, t0_ref, t1_ref, msel0_ref, msel1_ref,
            jorder_ref, scan_ref, n=n, m=m, P=P, pg=pg, bf16=bf16,
        ).astype(jnp.int32)
    with jax.named_scope(COMPACT_SCOPE):
        rows, caux, tree_inc, sol_inc, best = _pfsp_epilogue(
            prmu, limit1, valid, best, lb, n=n, M=M)
    out_vals_ref[:] = rows
    out_aux_ref[:] = caux
    scal_ref[:] = _scalar_lanes(tree_inc, sol_inc, best)


def _mega_lb1_kernel_gpu(prmu_ref, limit1_ref, valid_ref, best_ref,
                         ptm_ref, heads_ref, tails_ref,
                         out_vals_ref, out_aux_ref, scal_ref,
                         *, n: int, m: int, M: int, bf16: bool):
    """The lb1 cycle without its scan scratch — the Triton flavor
    (`pallas_kernels._front_scan` unrolls statically where the TPU kernel
    staged; the epilogue is shift/select math, backend-neutral)."""
    _mega_lb1_kernel(prmu_ref, limit1_ref, valid_ref, best_ref,
                     ptm_ref, heads_ref, tails_ref,
                     out_vals_ref, out_aux_ref, scal_ref, None,
                     n=n, m=m, M=M, bf16=bf16)


def _mega_lb2_kernel_gpu(prmu_ref, limit1_ref, valid_ref, best_ref,
                         ptm_ref, heads_ref,
                         p0_ref, p1_ref, lag_ref, t0_ref, t1_ref,
                         msel0_ref, msel1_ref, jorder_ref,
                         out_vals_ref, out_aux_ref, scal_ref,
                         *, n: int, m: int, P: int, M: int, pg: int,
                         bf16: bool):
    _mega_lb2_kernel(prmu_ref, limit1_ref, valid_ref, best_ref,
                     ptm_ref, heads_ref,
                     p0_ref, p1_ref, lag_ref, t0_ref, t1_ref,
                     msel0_ref, msel1_ref, jorder_ref,
                     out_vals_ref, out_aux_ref, scal_ref, None,
                     n=n, m=m, P=P, M=M, pg=pg, bf16=bf16)


# ---------------------------------------------------------------------------
# family cycle kernels — streamed (grid over pool tiles, SMEM offset carry)
# TPU-only: the cross-tile carry needs the sequential grid; `resolve`
# refuses tiled streaming on the gpu flavor.
# ---------------------------------------------------------------------------
#
# SMEM carry layout (persists across sequential grid steps):
#   [0] cross-tile survivor offset   [1] cumulative sol_inc
#   [2] globally folded incumbent (PFSP phase 0)


def _mega_nqueens_tiled_kernel(board_ref, depth_ref, valid_ref, best_ref,
                               out_vals_ref, out_aux_ref, scal_ref,
                               carry_ref, *, N: int, g: int, Mt: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _seed():
        carry_ref[0] = 0
        carry_ref[1] = 0

    board = board_ref[:].astype(jnp.int32)  # (Mt, N)
    depth = depth_ref[:, 0].astype(jnp.int32)
    valid = valid_ref[:, 0] != 0
    best = best_ref[0]
    with jax.named_scope(BOUND_SCOPE):
        labels = PK._nqueens_tile_labels(board, depth, N=N, g=g)
    keep = labels & valid[:, None] & (depth < N)[:, None]
    sol_inc = jnp.sum(valid & (depth == N), dtype=jnp.int32)
    with jax.named_scope(COMPACT_SCOPE):
        rows, caux, cnt = _compact_push(board, depth, depth, keep, n=N,
                                        M=Mt)
    offs = carry_ref[0]
    sol_cum = carry_ref[1] + sol_inc
    out_vals_ref[:] = rows
    out_aux_ref[:] = caux
    scal_ref[:] = jnp.broadcast_to(
        _tile_scalar_lanes(offs, cnt, sol_cum, best), (8, 128))
    carry_ref[0] = offs + cnt
    carry_ref[1] = sol_cum


def _mega_lb1_tiled_kernel(prmu_ref, limit1_ref, valid_ref, best_ref,
                           ptm_ref, heads_ref, tails_ref,
                           out_vals_ref, out_aux_ref, scal_ref,
                           scan_ref, lb_ref, carry_ref,
                           *, n: int, m: int, Mt: int, bf16: bool):
    p = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when((p == 0) & (i == 0))
    def _seed():
        carry_ref[0] = 0
        carry_ref[1] = 0
        carry_ref[2] = best_ref[0]

    prmu = prmu_ref[:].astype(jnp.int32)
    limit1 = limit1_ref[:, 0].astype(jnp.int32)
    valid = valid_ref[:, 0] != 0

    @pl.when(p == 0)
    def _sweep():
        # Phase 0: evaluate this tile's bounds into the stash and fold its
        # leaf-min into the global incumbent — no tile may prune before
        # every tile's leaves have been folded (PFSP exactness rule).
        with jax.named_scope(BOUND_SCOPE):
            lb = PK._lb1_tile_lb(prmu, limit1,
                                 ptm_ref[:].astype(jnp.float32),
                                 heads_ref[:], tails_ref[:], scan_ref,
                                 n=n, m=m, bf16=bf16)
        lb_ref[i] = lb
        carry_ref[2] = jnp.minimum(
            carry_ref[2], _pfsp_leaf_min(limit1, valid, lb, n=n, M=Mt))

    @pl.when(p == 1)
    def _emit():
        # Phase 1: re-stream the tile (bounds come back from the stash,
        # not recomputed) and prune/compact against the final incumbent.
        lb = lb_ref[i]
        with jax.named_scope(COMPACT_SCOPE):
            rows, caux, cnt, sol_inc, best = _pfsp_epilogue(
                prmu, limit1, valid, carry_ref[2], lb, n=n, M=Mt)
        offs = carry_ref[0]
        sol_cum = carry_ref[1] + sol_inc
        out_vals_ref[:] = rows
        out_aux_ref[:] = caux
        scal_ref[:] = jnp.broadcast_to(
            _tile_scalar_lanes(offs, cnt, sol_cum, best), (8, 128))
        carry_ref[0] = offs + cnt
        carry_ref[1] = sol_cum


def _mega_lb2_tiled_kernel(prmu_ref, limit1_ref, valid_ref, best_ref,
                           ptm_ref, heads_ref,
                           p0_ref, p1_ref, lag_ref, t0_ref, t1_ref,
                           msel0_ref, msel1_ref, jorder_ref,
                           out_vals_ref, out_aux_ref, scal_ref,
                           scan_ref, lb_ref, carry_ref,
                           *, n: int, m: int, P: int, Mt: int, pg: int,
                           bf16: bool):
    p = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when((p == 0) & (i == 0))
    def _seed():
        carry_ref[0] = 0
        carry_ref[1] = 0
        carry_ref[2] = best_ref[0]

    prmu = prmu_ref[:].astype(jnp.int32)
    limit1 = limit1_ref[:, 0].astype(jnp.int32)
    valid = valid_ref[:, 0] != 0

    @pl.when(p == 0)
    def _sweep():
        with jax.named_scope(BOUND_SCOPE):
            lb = PK._lb2_tile_lb(
                prmu, limit1, ptm_ref[:].astype(jnp.float32), heads_ref[:],
                p0_ref, p1_ref, lag_ref, t0_ref, t1_ref, msel0_ref,
                msel1_ref, jorder_ref, scan_ref, n=n, m=m, P=P, pg=pg,
                bf16=bf16,
            ).astype(jnp.int32)
        lb_ref[i] = lb
        carry_ref[2] = jnp.minimum(
            carry_ref[2], _pfsp_leaf_min(limit1, valid, lb, n=n, M=Mt))

    @pl.when(p == 1)
    def _emit():
        lb = lb_ref[i]
        with jax.named_scope(COMPACT_SCOPE):
            rows, caux, cnt, sol_inc, best = _pfsp_epilogue(
                prmu, limit1, valid, carry_ref[2], lb, n=n, M=Mt)
        offs = carry_ref[0]
        sol_cum = carry_ref[1] + sol_inc
        out_vals_ref[:] = rows
        out_aux_ref[:] = caux
        scal_ref[:] = jnp.broadcast_to(
            _tile_scalar_lanes(offs, cnt, sol_cum, best), (8, 128))
        carry_ref[0] = offs + cnt
        carry_ref[1] = sol_cum


# ---------------------------------------------------------------------------
# pallas_call factories
# ---------------------------------------------------------------------------


def _cycle_out(M: int, n: int, backend: str = "tpu"):
    """Single-tile out plumbing (grid=(1,) — the pool tile IS the grid)."""
    Mn = M * n
    full = lambda i: (0, 0)
    shapes = (
        jax.ShapeDtypeStruct((Mn, n), jnp.int32),
        jax.ShapeDtypeStruct((Mn, 1), jnp.int32),
        jax.ShapeDtypeStruct((1, 128), jnp.int32),
    )
    specs = (
        PK._bs((Mn, n), full, backend=backend),
        PK._bs((Mn, 1), full, backend=backend),
        PK._bs((1, 128), full, backend=backend),
    )
    return shapes, specs


def _chunk_specs(M: int, n: int, backend: str = "tpu"):
    full = lambda i: (0, 0)
    return [
        PK._bs((M, n), full, backend=backend),   # vals
        PK._bs((M, 1), full, backend=backend),   # aux
        PK._bs((M, 1), full, backend=backend),   # valid
        PK._bs((1,), lambda i: (0,), space="smem", backend=backend),  # best
    ]


def _tiled_out(M: int, n: int, mt: int, two_phase: bool):
    """Streamed out plumbing: each tile owns its (Mt*n)-row block of the
    (M*n, n) reservation plus one (8, 128) block of the (G*8, 128)
    per-tile scalar output.  On the two-phase PFSP grid the out index map pins every
    phase-0 step to block 0 (``p * i``): no block boundary is crossed
    before the first real write at step (1, 0), so the phase-0 sweep never
    flushes an unwritten buffer over the output."""
    G = M // mt
    Mtn = mt * n
    if two_phase:
        tm = lambda p, i: (p * i, 0)
    else:
        tm = lambda i: (i, 0)
    shapes = (
        jax.ShapeDtypeStruct((M * n, n), jnp.int32),
        jax.ShapeDtypeStruct((M * n, 1), jnp.int32),
        jax.ShapeDtypeStruct((G * 8, 128), jnp.int32),
    )
    specs = (
        pl.BlockSpec((Mtn, n), tm, memory_space=pltpu.VMEM),
        pl.BlockSpec((Mtn, 1), tm, memory_space=pltpu.VMEM),
        # One full (8, 128) int32 tile per grid step: Mosaic refuses a
        # (1, 128) block of a (G, 128) array (second-minor block dim must
        # be a multiple of 8). Each tile's scalar row is broadcast over
        # its 8 sublanes; `make_cycle` reads sublane 0.
        pl.BlockSpec((8, 128), tm, memory_space=pltpu.VMEM),
    )
    return shapes, specs


def _tiled_chunk_specs(mt: int, n: int, two_phase: bool):
    """Streamed in plumbing: the (i)-th grid step's BlockSpec index maps
    fetch pool tile i's rows HBM->VMEM — the pipelined grid prefetches
    tile i+1 under tile i's compute (the double buffer).  The two-phase
    PFSP grid re-fetches each tile in phase 1 (bounds are stashed; node
    fields are cheaper to re-stream than to hold for the whole sweep)."""
    if two_phase:
        tile = lambda p, i: (i, 0)
        smem = lambda p, i: (0,)
    else:
        tile = lambda i: (i, 0)
        smem = lambda i: (0,)
    return [
        pl.BlockSpec((mt, n), tile, memory_space=pltpu.VMEM),   # vals
        pl.BlockSpec((mt, 1), tile, memory_space=pltpu.VMEM),   # aux
        pl.BlockSpec((mt, 1), tile, memory_space=pltpu.VMEM),   # valid
        pl.BlockSpec((1,), smem, memory_space=pltpu.SMEM),      # best
    ]


@lru_cache(maxsize=None)
def _nqueens_cycle_call(N: int, g: int, M: int, interpret: bool,
                        backend: str = "tpu"):
    # The N-Queens cycle body holds no scratch, so the gpu flavor reuses it
    # verbatim — only the specs/params change spelling.
    shapes, out_specs = _cycle_out(M, N, backend)
    return pl.pallas_call(
        partial(_mega_nqueens_kernel, N=N, g=g, M=M),
        out_shape=shapes,
        name="tts_cycle_nqueens",
        grid=(1,),
        in_specs=_chunk_specs(M, N, backend),
        out_specs=out_specs,
        compiler_params=PK._compiler_params(backend=backend),
        interpret=interpret,
    )


@lru_cache(maxsize=None)
def _nqueens_tiled_call(N: int, g: int, M: int, mt: int, interpret: bool):
    shapes, out_specs = _tiled_out(M, N, mt, two_phase=False)
    return pl.pallas_call(
        partial(_mega_nqueens_tiled_kernel, N=N, g=g, Mt=mt),
        out_shape=shapes,
        name="tts_cycle_nqueens",
        grid=(M // mt,),
        in_specs=_tiled_chunk_specs(mt, N, two_phase=False),
        out_specs=out_specs,
        scratch_shapes=[pltpu.SMEM((4,), jnp.int32)],
        compiler_params=PK._compiler_params(),
        interpret=interpret,
    )


@lru_cache(maxsize=None)
def _lb1_cycle_call(n: int, m: int, M: int, bf16: bool, interpret: bool,
                    backend: str = "tpu"):
    full = lambda i: (0, 0)
    shapes, out_specs = _cycle_out(M, n, backend)
    kernel = _mega_lb1_kernel_gpu if backend == "gpu" else _mega_lb1_kernel
    return pl.pallas_call(
        partial(kernel, n=n, m=m, M=M, bf16=bf16),
        out_shape=shapes,
        name="tts_cycle_lb1",
        grid=(1,),
        in_specs=_chunk_specs(M, n, backend) + [
            PK._bs((n, m), full, backend=backend),
            PK._bs((1, m), full, backend=backend),
            PK._bs((1, m), full, backend=backend),
        ],
        out_specs=out_specs,
        scratch_shapes=PK._scratch(backend, pltpu.VMEM((n, M, m), jnp.int32)),
        compiler_params=PK._compiler_params(backend=backend),
        interpret=interpret,
    )


@lru_cache(maxsize=None)
def _lb1_tiled_call(n: int, m: int, M: int, mt: int, bf16: bool,
                    interpret: bool):
    G = M // mt
    full = lambda p, i: (0, 0)
    shapes, out_specs = _tiled_out(M, n, mt, two_phase=True)
    return pl.pallas_call(
        partial(_mega_lb1_tiled_kernel, n=n, m=m, Mt=mt, bf16=bf16),
        out_shape=shapes,
        name="tts_cycle_lb1",
        grid=(2, G),
        in_specs=_tiled_chunk_specs(mt, n, two_phase=True) + [
            pl.BlockSpec((n, m), full, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m), full, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m), full, memory_space=pltpu.VMEM),
        ],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((n, mt, m), jnp.int32),
                        pltpu.VMEM((G, mt, n), jnp.int32),
                        pltpu.SMEM((4,), jnp.int32)],
        compiler_params=PK._compiler_params(ndims=2),
        interpret=interpret,
    )


@lru_cache(maxsize=None)
def _lb2_cycle_call(n: int, m: int, P: int, M: int, pg: int, bf16: bool,
                    interpret: bool, backend: str = "tpu"):
    full = lambda i: (0, 0)
    full3 = lambda i: (0, 0, 0)
    bs = partial(PK._bs, backend=backend)
    shapes, out_specs = _cycle_out(M, n, backend)
    kernel = _mega_lb2_kernel_gpu if backend == "gpu" else _mega_lb2_kernel
    return pl.pallas_call(
        partial(kernel, n=n, m=m, P=P, M=M, pg=pg, bf16=bf16),
        out_shape=shapes,
        name="tts_cycle_lb2",
        grid=(1,),
        in_specs=_chunk_specs(M, n, backend) + [
            bs((n, m), full),
            bs((1, m), full),
            # Per-pair table layout matches `_lb2_call` exactly — see the
            # leading-axis / SMEM notes there.
            bs((P, 1, n), full3),
            bs((P, 1, n), full3),
            bs((P, 1, n), full3),
            bs((P,), lambda i: (0,), space="smem"),
            bs((P,), lambda i: (0,), space="smem"),
            bs((P, 1, m), full3),
            bs((P, 1, m), full3),
            bs((P, n, n), full3),
        ],
        out_specs=out_specs,
        scratch_shapes=PK._scratch(backend, pltpu.VMEM((n, M, m), jnp.int32)),
        compiler_params=PK._compiler_params(backend=backend),
        interpret=interpret,
    )


@lru_cache(maxsize=None)
def _lb2_tiled_call(n: int, m: int, P: int, M: int, mt: int, pg: int,
                    bf16: bool, interpret: bool):
    G = M // mt
    full = lambda p, i: (0, 0)
    full3 = lambda p, i: (0, 0, 0)
    smem1 = lambda p, i: (0,)
    shapes, out_specs = _tiled_out(M, n, mt, two_phase=True)
    return pl.pallas_call(
        partial(_mega_lb2_tiled_kernel, n=n, m=m, P=P, Mt=mt, pg=pg,
                bf16=bf16),
        out_shape=shapes,
        name="tts_cycle_lb2",
        grid=(2, G),
        in_specs=_tiled_chunk_specs(mt, n, two_phase=True) + [
            pl.BlockSpec((n, m), full, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m), full, memory_space=pltpu.VMEM),
            pl.BlockSpec((P, 1, n), full3, memory_space=pltpu.VMEM),
            pl.BlockSpec((P, 1, n), full3, memory_space=pltpu.VMEM),
            pl.BlockSpec((P, 1, n), full3, memory_space=pltpu.VMEM),
            pl.BlockSpec((P,), smem1, memory_space=pltpu.SMEM),
            pl.BlockSpec((P,), smem1, memory_space=pltpu.SMEM),
            pl.BlockSpec((P, 1, m), full3, memory_space=pltpu.VMEM),
            pl.BlockSpec((P, 1, m), full3, memory_space=pltpu.VMEM),
            pl.BlockSpec((P, n, n), full3, memory_space=pltpu.VMEM),
        ],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((n, mt, m), jnp.int32),
                        pltpu.VMEM((G, mt, n), jnp.int32),
                        pltpu.SMEM((4,), jnp.int32)],
        compiler_params=PK._compiler_params(ndims=2),
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# engine entry
# ---------------------------------------------------------------------------


def make_cycle(problem, M: int, device, decision: Decision):
    """Build ``cycle(vals_c, aux_c, valid, best) -> (rows (Mn, n) i32,
    caux (Mn,) i32, offs (G,) i32, tree_inc, sol_inc, best)`` — the armed
    alternate body `engine/resident.py loop_fns` splices in after the pop.
    ``offs`` carries each tile's cross-tile survivor offset (all-zero on
    the single-tile path, G == 1): the engine writes tile t's (Mt*n)-row
    block at ``size + offs[t]``, in tile order, so each write's garbage
    tail is overwritten by the next tile's rows and the surviving layout
    is exactly the dense-mode global (parent, slot) order.

    lb2 note: the kernel always evaluates the UNSTAGED fold, even when the
    two-pass staged evaluator is enabled for the jnp path.  They are
    value-identical: at a leaf the lb1 and lb2 makespans coincide (nothing
    is unscheduled), and for interior nodes ``lb2 >= lb1`` pointwise, so
    the staged keep ``open & ~leaf & (lb1 < best) & (lb2 < best)``
    equals the unstaged ``open & ~leaf & (lb2 < best)``.
    """
    fam = _family(problem)
    interpret = decision.interpret
    tiled = decision.grid > 1
    mt = decision.mt or M
    G = decision.grid
    # Tiled streaming is TPU-only (resolve refuses it on gpu), so only the
    # single-tile factories take the flavor.
    kb = decision.backend

    def _legacy(rows, caux, scal):
        zero_offs = jnp.zeros((1,), jnp.int32)
        return (rows, caux[:, 0], zero_offs,
                scal[0, 0], scal[0, 1], scal[0, 2])

    def _streamed(rows, caux, scal):
        scal = scal[::8]  # sublane 0 of each tile's (8, 128) block
        last = scal[G - 1]
        return (rows, caux[:, 0], scal[:, 0],
                last[0] + last[1], last[2], last[3])

    if fam == "nqueens":
        if tiled:
            call = _nqueens_tiled_call(problem.N, problem.g, M, mt,
                                       interpret)
        else:
            call = _nqueens_cycle_call(problem.N, problem.g, M, interpret,
                                       kb)

        def cycle(vals_c, aux_c, valid, best):
            rows, caux, scal = call(
                vals_c, aux_c[:, None], valid.astype(jnp.int32)[:, None],
                jnp.reshape(best, (1,)),
            )
            return (_streamed if tiled else _legacy)(rows, caux, scal)

        return cycle

    t = problem.device_tables()
    n = problem.jobs
    m = problem.machines
    bf16 = bool(getattr(t, "exact_bf16", False))
    if fam == "lb1":
        if tiled:
            call = _lb1_tiled_call(n, m, M, mt, bf16, interpret)
        else:
            call = _lb1_cycle_call(n, m, M, bf16, interpret, kb)

        def cycle(vals_c, aux_c, valid, best):
            rows, caux, scal = call(
                vals_c, aux_c[:, None], valid.astype(jnp.int32)[:, None],
                jnp.reshape(best, (1,)),
                t.ptm_t, t.min_heads[None, :], t.min_tails[None, :],
            )
            return (_streamed if tiled else _legacy)(rows, caux, scal)

        return cycle

    # lb2 — Johnson-ordered tables resolved exactly like `pfsp_lb2_bounds`
    # (device cache when eager, numpy constants under a trace).
    from . import pfsp_device as PD

    P = t.pairs.shape[0]
    pg = PD.lb2_kernel_pair_group(P, n)
    ordered = (t.johnson_ordered_device(pg) if PK._eager_context()
               else t.johnson_ordered_mp(pg))
    Pp = ordered.lag_o.shape[0]
    if tiled:
        call = _lb2_tiled_call(n, m, Pp, M, mt, pg, bf16, interpret)
    else:
        call = _lb2_cycle_call(n, m, Pp, M, pg, bf16, interpret, kb)

    def cycle(vals_c, aux_c, valid, best):
        rows, caux, scal = call(
            vals_c, aux_c[:, None], valid.astype(jnp.int32)[:, None],
            jnp.reshape(best, (1,)),
            t.ptm_t, t.min_heads[None, :],
            ordered.p0_o[:, None, :],
            ordered.p1_o[:, None, :],
            ordered.lag_o[:, None, :],
            ordered.tails0,
            ordered.tails1,
            ordered.msel0[:, None, :],
            ordered.msel1[:, None, :],
            ordered.jorder,
        )
        return (_streamed if tiled else _legacy)(rows, caux, scal)

    return cycle


# ---------------------------------------------------------------------------
# Megacore-parallel evaluation-only pass
# ---------------------------------------------------------------------------


def _eval_nqueens_kernel(board_ref, depth_ref, out_ref, *, N: int, g: int):
    labels = PK._nqueens_tile_labels(
        board_ref[:].astype(jnp.int32), depth_ref[:, 0].astype(jnp.int32),
        N=N, g=g)
    out_ref[:] = labels.astype(jnp.int32)


def _eval_lb1_kernel(prmu_ref, limit1_ref, ptm_ref, heads_ref, tails_ref,
                     out_ref, scan_ref, *, n: int, m: int, bf16: bool):
    out_ref[:] = PK._lb1_tile_lb(
        prmu_ref[:].astype(jnp.int32), limit1_ref[:, 0].astype(jnp.int32),
        ptm_ref[:].astype(jnp.float32), heads_ref[:], tails_ref[:],
        scan_ref, n=n, m=m, bf16=bf16)


def _eval_lb2_kernel(prmu_ref, limit1_ref, ptm_ref, heads_ref,
                     p0_ref, p1_ref, lag_ref, t0_ref, t1_ref,
                     msel0_ref, msel1_ref, jorder_ref,
                     out_ref, scan_ref,
                     *, n: int, m: int, P: int, pg: int, bf16: bool):
    out_ref[:] = PK._lb2_tile_lb(
        prmu_ref[:].astype(jnp.int32), limit1_ref[:, 0].astype(jnp.int32),
        ptm_ref[:].astype(jnp.float32), heads_ref[:],
        p0_ref, p1_ref, lag_ref, t0_ref, t1_ref, msel0_ref, msel1_ref,
        jorder_ref, scan_ref, n=n, m=m, P=P, pg=pg, bf16=bf16,
    ).astype(jnp.int32)


def _eval_lb1_kernel_gpu(prmu_ref, limit1_ref, ptm_ref, heads_ref, tails_ref,
                         out_ref, *, n: int, m: int, bf16: bool):
    _eval_lb1_kernel(prmu_ref, limit1_ref, ptm_ref, heads_ref, tails_ref,
                     out_ref, None, n=n, m=m, bf16=bf16)


def _eval_lb2_kernel_gpu(prmu_ref, limit1_ref, ptm_ref, heads_ref,
                         p0_ref, p1_ref, lag_ref, t0_ref, t1_ref,
                         msel0_ref, msel1_ref, jorder_ref,
                         out_ref, *, n: int, m: int, P: int, pg: int,
                         bf16: bool):
    _eval_lb2_kernel(prmu_ref, limit1_ref, ptm_ref, heads_ref,
                     p0_ref, p1_ref, lag_ref, t0_ref, t1_ref,
                     msel0_ref, msel1_ref, jorder_ref,
                     out_ref, None, n=n, m=m, P=P, pg=pg, bf16=bf16)


@lru_cache(maxsize=None)
def _eval_nqueens_call(N: int, g: int, B: int, mt: int, interpret: bool,
                       backend: str = "tpu"):
    tm = lambda i: (i, 0)
    bs = partial(PK._bs, backend=backend)
    return pl.pallas_call(
        partial(_eval_nqueens_kernel, N=N, g=g),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.int32),
        name="tts_eval_nqueens",
        grid=(B // mt,),
        in_specs=[bs((mt, N), tm), bs((mt, 1), tm)],
        out_specs=bs((mt, N), tm),
        compiler_params=PK._compiler_params(parallel=True, backend=backend),
        interpret=interpret,
    )


@lru_cache(maxsize=None)
def _eval_lb1_call(n: int, m: int, B: int, mt: int, bf16: bool,
                   interpret: bool, backend: str = "tpu"):
    tm = lambda i: (i, 0)
    full = lambda i: (0, 0)
    bs = partial(PK._bs, backend=backend)
    kernel = _eval_lb1_kernel_gpu if backend == "gpu" else _eval_lb1_kernel
    return pl.pallas_call(
        partial(kernel, n=n, m=m, bf16=bf16),
        out_shape=jax.ShapeDtypeStruct((B, n), jnp.int32),
        name="tts_eval_lb1",
        grid=(B // mt,),
        in_specs=[
            bs((mt, n), tm),
            bs((mt, 1), tm),
            bs((n, m), full),
            bs((1, m), full),
            bs((1, m), full),
        ],
        out_specs=bs((mt, n), tm),
        scratch_shapes=PK._scratch(backend,
                                   pltpu.VMEM((n, mt, m), jnp.int32)),
        compiler_params=PK._compiler_params(parallel=True, backend=backend),
        interpret=interpret,
    )


@lru_cache(maxsize=None)
def _eval_lb2_call(n: int, m: int, P: int, B: int, mt: int, pg: int,
                   bf16: bool, interpret: bool, backend: str = "tpu"):
    tm = lambda i: (i, 0)
    full = lambda i: (0, 0)
    full3 = lambda i: (0, 0, 0)
    smem1 = lambda i: (0,)
    bs = partial(PK._bs, backend=backend)
    kernel = _eval_lb2_kernel_gpu if backend == "gpu" else _eval_lb2_kernel
    return pl.pallas_call(
        partial(kernel, n=n, m=m, P=P, pg=pg, bf16=bf16),
        out_shape=jax.ShapeDtypeStruct((B, n), jnp.int32),
        name="tts_eval_lb2",
        grid=(B // mt,),
        in_specs=[
            bs((mt, n), tm),
            bs((mt, 1), tm),
            bs((n, m), full),
            bs((1, m), full),
            bs((P, 1, n), full3),
            bs((P, 1, n), full3),
            bs((P, 1, n), full3),
            bs((P,), smem1, space="smem"),
            bs((P,), smem1, space="smem"),
            bs((P, 1, m), full3),
            bs((P, 1, m), full3),
            bs((P, n, n), full3),
        ],
        out_specs=bs((mt, n), tm),
        scratch_shapes=PK._scratch(backend,
                                   pltpu.VMEM((n, mt, m), jnp.int32)),
        compiler_params=PK._compiler_params(parallel=True, backend=backend),
        interpret=interpret,
    )


def streamed_eval_bounds(problem, vals, aux, mt: int | None = None,
                         interpret: bool | None = None):
    """Evaluation-only streamed pass over a (B, n) chunk — the Megacore
    split of the tiled megakernel.  Unlike the full cycle there is no
    cross-tile carry, so every grid axis is declared
    ``dimension_semantics=("parallel",)`` and Mosaic is free to split the
    pool tiles across a chip's two TensorCores.  Returns the (B, n) int32
    bound plane (lb1/lb2) or keep-label plane (N-Queens) — bit-identical
    to the carried kernels' phase-0 values (shared tile bodies).  ``mt``
    defaults to one tile (B); tests force small multi-tile widths."""
    fam = _family(problem)
    if fam not in ("nqueens", "lb1", "lb2"):
        raise ValueError(f"streamed_eval_bounds: unsupported family {fam!r}")
    B = int(vals.shape[0])
    mt = mt or B
    if B % mt or mt % 8:
        raise ValueError(
            f"streamed_eval_bounds: tile {mt} must divide B={B} and be a "
            "multiple of the sublane quantum (8)")
    kb = BK.kernel_kind(None)
    if interpret is None:
        interpret = PK.pallas_interpret() or _native_kind(None) is None
    vals_c = jnp.asarray(vals).astype(jnp.int32)
    aux_c = jnp.asarray(aux).astype(jnp.int32)[:, None]
    if fam == "nqueens":
        call = _eval_nqueens_call(problem.N, problem.g, B, mt, interpret, kb)
        return call(vals_c, aux_c)
    t = problem.device_tables()
    n, m = problem.jobs, problem.machines
    bf16 = bool(getattr(t, "exact_bf16", False))
    if fam == "lb1":
        call = _eval_lb1_call(n, m, B, mt, bf16, interpret, kb)
        return call(vals_c, aux_c, t.ptm_t, t.min_heads[None, :],
                    t.min_tails[None, :])
    from . import pfsp_device as PD

    P = t.pairs.shape[0]
    pg = PD.lb2_kernel_pair_group(P, n)
    ordered = (t.johnson_ordered_device(pg) if PK._eager_context()
               else t.johnson_ordered_mp(pg))
    Pp = ordered.lag_o.shape[0]
    call = _eval_lb2_call(n, m, Pp, B, mt, pg, bf16, interpret, kb)
    return call(vals_c, aux_c, t.ptm_t, t.min_heads[None, :],
                ordered.p0_o[:, None, :], ordered.p1_o[:, None, :],
                ordered.lag_o[:, None, :], ordered.tails0, ordered.tails1,
                ordered.msel0[:, None, :], ordered.msel1[:, None, :],
                ordered.jorder)


def megakernel_lb2_bounds(prmu, limit1, tables, interpret: bool | None = None):
    """The lb2 bound values the megakernel arms with, as a standalone (B, n)
    call — the bf16 max-plus MXU formulation over the shared
    `_lb2_tile_lb` body.  The bf16-exactness gate test bit-compares this
    against the f32 pair-blocked oracle (`pfsp_device._lb2_chunk`) on real
    Taillard instances; a mismatch means :func:`resolve`'s gate is wrong
    and the kernel must refuse to arm."""
    return PK.pfsp_lb2_bounds(prmu, limit1, tables, interpret=interpret,
                              bf16=True)


# ---------------------------------------------------------------------------
# contracts (tts check)
# ---------------------------------------------------------------------------


@contract(
    "megakernel-off-identity",
    claim="TTS_MEGAKERNEL unset (auto, unarmed on the audit's CPU traces) "
          "and =0 build byte-identical resident step jaxprs — the armed "
          "body is compiled out when off, never branched",
    artifact="variants",
)
def _contract_megakernel_off_identity(art, cell):
    if not art.has("off", "mk0"):
        return []
    out = []
    if art.text("off") != art.text("mk0"):
        out.append("TTS_MEGAKERNEL=0 build differs from the unset build "
                   "(the armed cycle body leaked into the off path)")
    if art.outvars("mk0") != art.outvars("off"):
        out.append("TTS_MEGAKERNEL=0 build changed the carry width")
    return out


@contract(
    "megakernel-tiled-identity",
    claim="the Mt knob is inert when the kernel is off (TTS_MEGAKERNEL=0 "
          "with TTS_MEGAKERNEL_MT set is byte-identical to the off build) "
          "and the tiled armed build keeps the off build's carry width — "
          "the tile count never leaks into the step signature",
    artifact="variants",
)
def _contract_megakernel_tiled_identity(art, cell):
    out = []
    if art.has("off", "mk0-mt"):
        if art.text("mk0-mt") != art.text("off"):
            out.append("TTS_MEGAKERNEL_MT leaked into the TTS_MEGAKERNEL=0 "
                       "build (off must stay a byte-identical jaxpr)")
        if art.outvars("mk0-mt") != art.outvars("off"):
            out.append("TTS_MEGAKERNEL_MT changed the off build's carry "
                       "width")
    if art.has("off", "mk-tiled"):
        if art.outvars("mk-tiled") != art.outvars("off"):
            out.append("tiled armed build changed the carry width vs off "
                       "(per-tile offsets must stay inside the kernel)")
    return out


@contract(
    "megakernel-single-call",
    claim="the armed cycle body is ONE pallas_call — single- and "
          "multi-tile alike — no sort, no searchsorted, and no scatter "
          "beyond the phase profiler's clock-block updates; a build that "
          "refused to arm recorded why",
    artifact="resident-step",
    applies=lambda cell: cell is not None
    and getattr(cell, "megakernel", None) == "force",
)
def _contract_megakernel_single_call(art, cell):
    dec = getattr(art.prog, "megakernel", None)
    if dec is None:
        return ["resident program carries no megakernel decision"]
    ncalls = sum(1 for name, _ in art.prims if name == "pallas_call")
    if not dec.enabled:
        out = []
        if not dec.reason:
            return ["megakernel refused to arm without recording a reason"]
        if ncalls:
            out.append(
                f"refused build ({dec.reason}) still contains "
                f"{ncalls} pallas_call(s)"
            )
        return out
    out = []
    if ncalls != 1:
        out.append(f"armed cycle body contains {ncalls} pallas_call eqns "
                   "(expected exactly 1)")
    banned = {"sort", "searchsorted"} & art.prim_names
    if banned:
        out.append(f"armed cycle body contains banned primitives: "
                   f"{sorted(banned)}")
    # The phase profiler's clock block updates (.at[].add on the
    # (NSLOTS+1,) uint32 block) lower to tiny scatters — exempt; any
    # node-data-sized scatter breaks the claim.
    from ..obs import phases as obs_phases

    for name, eqn in art.prims:
        if not name.startswith("scatter"):
            continue
        if any(v.aval.size > obs_phases.NSLOTS + 1 for v in eqn.outvars):
            out.append(
                f"armed cycle body contains a node-sized {name} "
                f"({[tuple(v.aval.shape) for v in eqn.outvars]})"
            )
    return out
