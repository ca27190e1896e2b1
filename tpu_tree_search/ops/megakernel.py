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
happen against the tile in VMEM, and only the compacted child rows leave:
the kernel DMAs them, in the pool dtypes, straight into the donated pool
(`_push_emit`).

Tiling (``TTS_MEGAKERNEL_MT``, auto-resolved like `_auto_compact`):

* ``Mt == M`` (one tile): ``grid=(1,)``, no streaming, the whole chunk
  lives in VMEM.
* ``Mt < M`` streams ``G = M//Mt`` tiles.  Survivor compaction becomes
  two-phase across tiles: each tile dense-ranks its own survivors in VMEM
  (`_compact_push` at width Mt), and an SMEM carry accumulates the
  cross-tile survivor offset so push destinations stay collision-free and
  the concatenation of tiles is exactly the dense-mode global
  (parent, slot) order of `ops/compaction.py`.  Each tile writes its
  live prefix at ``size + offset`` itself, in tile order (the ordering
  rule above `_PushRefs`) — bit-identical to the single-tile push.
* The PFSP families need the incumbent fold over ALL leaves before any
  tile's keep test (`best = min(best, leaf bounds)` is global), so their
  grid is ``(2, G)``: phase 0 streams every tile, evaluates bounds into a
  per-tile VMEM stash and folds the global leaf-min; phase 1 re-streams
  the tiles (bounds are NOT recomputed — they are read back from the
  stash) and runs prune/compact/emit against the final incumbent (at
  G == 1 too).  N-Queens has no bound pruning and keeps the single sweep
  ``grid=(G,)``.
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
* the PFSP bound prologue (`pallas_kernels._tile_parent_state`) and
  lb2's pair loop (`pallas_kernels._lb2_tile_lb`) are int32 selects and
  max-plus on lane-dense slabs, with no matmul; lb2 still arms only when
  the instance passes the bf16-exactness gate
  (`PFSPDeviceTables.exact_bf16`: every processing time < 2^8), the
  condition its prologue's one-hot matmul gathers once needed —
  otherwise :func:`resolve` refuses and records why (banner +
  SearchResult).

Routing (`TTS_MEGAKERNEL=auto|0|force`, resolved like the compact auto
policy): ``auto`` arms only on a TPU device and when the per-tile
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

#: Widest streamed pool tile of a PFSP cycle (`_resolve_mt`): the bound
#: prologue puts the tile's parents on the 128 lanes of its slabs
#: (`pallas_kernels._tile_parent_state`), and the compaction's (Mt*n, Mt)
#: selection does Mt times the work per node; the VMEM model alone admits
#: wider tiles at 20 jobs.
PFSP_MAX_MT = 128

#: mirrors problems.base.INF_BOUND without importing the problems package
#: into a kernel module (the packages import each other lazily).
_INF_BOUND = 2**31 - 1

#: Nodes one push DMA writes, at least: the TPU cycle issues a tile's live
#: prefix in chunks, so the bytes written follow the survivors, not the
#: M*n reservation.  A chunk is also at least 32 pool rows (the 8-bit
#: staging tile), so with many nodes to a pool row it holds more nodes
#: (`_chunk_nodes`).
PUSH_CHUNK = 128

#: Row quantum of a DMA into the value pool: Mosaic tiles the 8/16-bit
#: pool layouts by 8 rows, and a DMA starts on a whole tile.
_ROW_TILE = 8

#: The aux pool is written through its lane-dense (rows/128, 128) view,
#: in whole (8, 128) tiles: 1024 nodes per DMA, starting on a tile.
_AUX_TILE = _ROW_TILE * 128

#: Nodes past ``size + M*n`` a push may touch (its last chunk and aux tile
#: are whole; a chunk is at most 512 nodes): the armed pool carries
#: this much slack past the capacity, so the while-cond's
#: ``size + M*n <= C`` stays the one rule.
PUSH_SLACK = _AUX_TILE


def push_pack(n: int) -> tuple[int, int]:
    """(slot width ``W``, nodes per pool row ``kq``) of the value pool the
    cycle pushes into.  A node of ``n`` values takes a slot of ``W``
    lanes — ``n`` rounded up to a power of two (at least 8) — and
    ``kq = 128 // W`` nodes share one 128-lane row; past 64 values a node
    takes whole 128-lane rows alone (``kq`` 1)."""
    if n > 64:
        return PK._r128(n), 1
    w = max(8, 1 << (n - 1).bit_length())
    return w, 128 // w


def push_layout(decision, n: int, capacity: int) -> tuple[int, int, int]:
    """(slot width ``W``, nodes per row ``kq``, node rows) of the device
    pool a resident program allocates; the value pool's array is
    ``(rows // kq, kq * W)``.

    The cycle pushes its survivors into the pool by DMA, and Mosaic
    slices a DMA destination only on whole (8, 128) tiles: a (C, n) pool
    with n < 128 cannot be sliced by row at all.  So where the cycle is
    armed the value pool is lane-dense: `push_pack`'s ``kq`` nodes to a
    128-lane row, each in a ``W``-lane slot whose lanes past ``n`` are
    dead — ``W/n`` times the bytes of a compact (C, n) pool (16/15 for
    N=15, 32/20 for a 20-job PFSP instance).  Both pools get
    :data:`PUSH_SLACK` nodes past the capacity, rounded to whole aux tiles
    so the aux pool has its (rows/128, 128) view.  Interpret mode runs the
    same layout.  Every other build (off, refused) stores (capacity, n)."""
    if not decision.enabled:
        return n, 1, capacity
    width, kq = push_pack(n)
    return width, kq, PK._round_up(capacity + PUSH_SLACK, _AUX_TILE)


def _chunk_nodes(kq: int) -> int:
    """Nodes of one value chunk: PUSH_CHUNK, or 32 pool rows where more
    than 4 nodes share a row."""
    return max(PUSH_CHUNK, 32 * kq)


def _push_sizes(mt: int, n: int) -> tuple[int, int, int]:
    """Static node counts of one tile's push: the compaction's output rows
    ``rc`` (the Mt*n reservation plus one row tile of ``8*kq`` nodes, for
    a destination that starts inside its tile; packed-int8 aligned), the
    value staging nodes ``rs`` (whole chunks) and the aux staging rows
    ``ra`` (whole aux tiles, for a destination up to 1023 nodes into its
    tile)."""
    kq = push_pack(n)[1]
    rc = PK._round_up(mt * n + _ROW_TILE * kq, 32)
    rs = PK._round_up(rc, _chunk_nodes(kq))
    ra = _ROW_TILE * -(-(_AUX_TILE - 1 + rc) // _AUX_TILE)
    return rc, rs, ra


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
    streams the pool through VMEM tile by tile)."""

    enabled: bool
    auto: bool
    interpret: bool
    reason: str | None
    mt: int = 0
    grid: int = 1

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


def _native_kind(device) -> bool:
    """True when the target device is a TPU, where the cycle compiles;
    elsewhere it can only run interpreted."""
    return PK.platform(device) == "tpu"


def _push_bytes(mt: int, n: int, pool_itemsize: int) -> int:
    """VMEM of one tile's in-kernel push: the value and aux staging (pool
    dtypes; the aux charged at int32, its widest), the two head tiles, the
    double-buffered (1, 128) scalar block, the one-hot operands of the aux
    lane-dense transform and of one chunk's row packing (f32 and their
    bf16 copies)."""
    r8, r128 = PK._r8, PK._r128
    rc, rs, ra = _push_sizes(mt, n)
    w, kq = push_pack(n)
    ch = _chunk_nodes(kq)
    row = kq * w  # lanes of one pool row
    staging = ((rs // kq + _ROW_TILE) * row * pool_itemsize
               + (ra + 8) * 128 * 4)
    lanes = (r8(ra) * r128(rc) + rc * 128) * 6
    pack = (ch // kq * r128(ch) + 2 * ch * 128) * 6 if kq > 1 else 0
    return staging + lanes + pack + 2 * 128 * 4


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
    double-buffer charge on every streamed in block (the pipelined grid
    prefetches tile i+1's HBM->VMEM copies under tile i's compute), PLUS,
    with ``lb_stash`` (the PFSP two-phase grid), the (G, Mt, n) int32
    bound stash that holds phase 0's evaluations for phase 1 — the one
    charge that scales with M, not Mt, and therefore the one that can
    still refuse a shape.

    Both forms charge the in-kernel push's staging (`_push_bytes`), and
    with ``lb_stash`` the single tile charges the PFSP stash too (its
    grid is two-phase there as well).  ``pool_itemsize`` charges the
    pool-dtype tiles at their storage width; the in-kernel intermediates
    stay int32/f32."""
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
        total = sel + flat + cols + tri + io + _push_bytes(M, n, pool_itemsize)
        if lb_stash:
            total += r8(M) * r128(n) * 4
        return total
    G = M // mt
    Mtn = mt * n
    sel = r8(Mtn) * r128(mt) * 6
    flat = 3 * r8(Mtn) * r128(n) * 4
    cols = 4 * r8(Mtn) * 128 * 4
    tri = r8(mt) * r128(mt) * 4 + r8(n) * r128(n) * 4
    # Streamed in blocks are double-buffered by the pipelined grid: two
    # live copies of each (pool tile + narrow copy + keep plane + lanes).
    stream_in = 2 * (2 * r8(mt) * r128(n) * pool_itemsize
                     + r8(mt) * r128(n) * 4 + 128 * 4)
    total = sel + flat + cols + tri + stream_in
    total += _push_bytes(mt, n, pool_itemsize)
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


def _fits(problem, fam: str, M: int, n: int,
          mt: int | None = None) -> tuple[bool, str | None]:
    """VMEM feasibility at pool-tile width ``mt`` (None or M — the
    single-tile resident form; smaller — the streamed per-tile +
    double-buffer + stash charge of `_mega_pool_bytes`), against the
    budget (`pallas_kernels._vmem_limit_bytes`)."""
    from ..problems.base import narrow_enabled

    if mt is not None and mt >= M:
        mt = None
    t = mt or M
    itemsize = _pool_itemsize(fam, n) if narrow_enabled() else 4
    extra = _mega_pool_bytes(M, n, itemsize, mt=mt,
                             lb_stash=(fam != "nqueens"))
    if fam == "nqueens":
        need = PK._model_bytes(t, n, 0, extra, 3)
    elif fam == "lb1":
        need = PK._model_bytes(t, n, problem.machines, extra, 0)
    else:  # lb2
        from . import pfsp_device as PD

        m = problem.machines
        P = problem.lb2_data.pairs.shape[0]
        pg = PD.lb2_kernel_pair_group(P, n)
        # The lane-dense pair body: its tables stream through SMEM.
        need = PK._model_bytes(
            t, n, m, extra, 0, lane_slabs=PK._lb2_lane_slabs(n, m, pg))
    budget = PK._vmem_budget()
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


def _widest_mt(fam: str, M: int) -> int:
    """The widest streamed pool tile a cycle may take: M, or for PFSP the
    largest multiple of 8 that divides M up to :data:`PFSP_MAX_MT`."""
    if fam == "nqueens" or M <= PFSP_MAX_MT:
        return M
    mt = PFSP_MAX_MT
    while M % mt:
        mt -= 8
    return mt


def _resolve_mt(problem, fam: str, M: int, n: int) -> int | None:
    """Resolve the streamed pool-tile width `_auto_compact`-style: the
    largest halving-ladder Mt (multiple of 8, divides M, at most
    `_widest_mt`) whose tile stays inside the small-M window AND whose
    per-tile VMEM model fits.  None when even the smallest tile cannot fit
    (the caller records the refusal)."""
    mt = _widest_mt(fam, M)
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
    misalignment — including a TTS_MEGAKERNEL_MT that does not divide M)
    hold even under ``force``; the remaining gates (a TPU device, per-tile
    memory fit) apply to ``auto`` only."""
    mode = megakernel_mode()
    if mode == "0":
        return Decision(False, False, False, None)
    auto = mode == "auto"
    fam = _family(problem)
    n = int(problem.child_slots)
    if fam not in ("nqueens", "lb1", "lb2"):
        return Decision(False, auto, False,
                        f"unsupported bound family {fam!r} (the megakernel "
                        "ports nqueens/lb1/lb2 only)")
    if mp_axis is not None or mp_size > 1:
        return Decision(False, auto, False,
                        "mp pair-axis sharding (the fused cycle is "
                        "single-shard)")
    if M % 8 != 0:
        return Decision(False, auto, False,
                        f"M={M} not a multiple of the sublane quantum (8)")
    if fam == "lb2":
        t = problem.device_tables()
        if not getattr(t, "exact_bf16", False):
            return Decision(False, auto, False,
                            "lb2 bf16-exactness gate: max processing time "
                            ">= 256; the cycle arms only on instances that "
                            "pass it (the f32 pair-blocked oracle keeps "
                            "the cycle)")
    mt_env = megakernel_mt()
    if mt_env is not None and (mt_env % 8 != 0 or M % mt_env != 0):
        return Decision(False, auto, False,
                        f"TTS_MEGAKERNEL_MT={mt_env} must be a multiple of "
                        f"the sublane quantum (8) and divide M={M}")
    native = _native_kind(device)
    if not auto:
        interpret = PK.pallas_interpret() or not native
        mt = mt_env or _resolve_mt(problem, fam, M, n) or M
        return Decision(True, False, interpret, None, mt=mt, grid=M // mt)
    if not native or PK.pallas_interpret():
        return Decision(False, True, False, "auto: not on a TPU backend")
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
#: Mosaic ``trace_start``/``trace_stop``): the tile bound, the prune +
#: compaction epilogue, and the push of the compacted rows into the pool
#: (docs/OBSERVABILITY.md leg 7).
BOUND_SCOPE = "tts_bound"
COMPACT_SCOPE = "tts_compact"
EMIT_SCOPE = "tts_emit"


def _scalar_lanes(tree_inc, sol_inc, best, pushed=0):
    """(1, 128) int32 scalar output row: lanes 0/1/2/3 = tree_inc /
    sol_inc / best / pool rows the push wrote (Mosaic wants a full lane
    register, not four scalars)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    return jnp.where(
        lane == 0, tree_inc,
        jnp.where(lane == 1, sol_inc,
                  jnp.where(lane == 2, best,
                            jnp.where(lane == 3, pushed, 0))),
    )


def _compact_push(vals, aux, d, keep, *, n: int, M: int, shift,
                  width: int):
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

    ``shift`` is the push destination's node offset inside an 8-row pool
    tile (traced) and ``width`` the pool's slot width.  Returns (rows
    (rc, width) i32, caux (rc, 1) i32, tree_inc) over `_push_sizes`'
    ``rc`` rows: row ``k`` holds survivor ``k - shift`` — rows below
    ``shift`` empty, for the pool nodes already there, and rows past the
    survivors zero (dead by the pool contract — the engine advances
    ``size`` by tree_inc only) — with lanes past ``n`` zero: one pool slot
    each, still with nothing reshaped.  On the streamed path this runs per
    tile at ``M = Mt``; the cross-tile offset carry makes the concatenation
    of tiles the dense-mode global order."""
    i32, f32 = jnp.int32, jnp.float32
    R, W = _push_sizes(M, n)[0], width
    keep_f = keep.astype(f32)  # (M, n)
    # Every operand value of the big selection matmuls (0/1 masks, lane
    # ranks < n, job or column indices < n) is exact in bf16 when n < 256.
    small = n < 256

    def ranks(shape):
        return (jax.lax.broadcasted_iota(i32, shape, 0) - shift).astype(f32)

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

    k = ranks((R, M))
    sel = ((k >= offs_row) & (k < offs_row + cnt_row)).astype(f32)
    vals_f = vals.astype(f32)
    if W != n:
        # The parents placed at the pool's row width by a one-hot (n, W)
        # matmul: (M, n) and (M, W) rows take the same vregs up to 128
        # lanes, so the selection matmul below costs what it did.
        place = (jax.lax.broadcasted_iota(i32, (n, W), 0)
                 == jax.lax.broadcasted_iota(i32, (n, W), 1)).astype(f32)
        vals_f = PK._hp_dot(vals_f, place, bf16=small)
    par = PK._hp_dot(sel, vals_f, bf16=small)             # (R, W)
    pkeep = PK._hp_dot(sel, keep_f, bf16=small)           # (R, n)
    plane = PK._hp_dot(sel, lane, bf16=small)             # (R, n)
    # Per-parent scalars in lanes 0/1/2 of one side table: offset, swap
    # position, child aux.  Offsets may exceed 2^8: full precision.
    li = jax.lax.broadcasted_iota(i32, (M, 8), 1)
    side = jnp.where(li == 0, offs,
                     jnp.where(li == 1, d.astype(f32)[:, None],
                               jnp.where(li == 2,
                                         (aux + 1).astype(f32)[:, None],
                                         0.0)))
    pside = PK._hp_dot(sel, side)  # (R, 8)
    lo = jax.lax.broadcasted_iota(i32, (R, 8), 1)
    poffs = jnp.sum(jnp.where(lo == 0, pside, 0.0), axis=1, keepdims=True)
    pd = jnp.sum(jnp.where(lo == 1, pside, 0.0), axis=1,
                 keepdims=True).astype(i32)
    caux = jnp.sum(jnp.where(lo == 2, pside, 0.0), axis=1,
                   keepdims=True).astype(i32)

    # The surviving slot j of output row k: the kept slot whose rank
    # within its parent is k - offs[p].
    kc = ranks((R, 1))
    jl = jax.lax.broadcasted_iota(i32, (R, n), 1)
    hit = (pkeep > 0.5) & (plane == kc - poffs)
    j = jnp.sum(jnp.where(hit, jl, 0), axis=1, keepdims=True)  # (R, 1)
    jw = jl if W == n else jax.lax.broadcasted_iota(i32, (R, W), 1)
    par = par.astype(i32)
    v_d = jnp.sum(jnp.where(jw == pd, par, 0), axis=1, keepdims=True)
    v_j = jnp.sum(jnp.where(jw == j, par, 0), axis=1, keepdims=True)
    rows = jnp.where(jw == pd, v_j, jnp.where(jw == j, v_d, par))
    return rows, caux, tree_inc


def _pfsp_epilogue(prmu, limit1, valid, best, lb, *, n: int, M: int,
                   shift, width: int):
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
    rows, caux, tree_inc = _compact_push(prmu, limit1, pdepth, keep, n=n, M=M,
                                         shift=shift, width=width)
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
# the cycle kernels — the pool tiles stream through VMEM and the kernel
# pushes each tile's survivors into the donated pool by DMA
# ---------------------------------------------------------------------------
#
# One grid step per pool tile (``grid=(G,)``; PFSP ``(2, G)``, see the
# module docstring); the single-tile form is the same kernel at G == 1.
# Each emit step compacts its tile at the pool's slot width, packs the
# live prefix chunk by chunk into the pool's rows (``kq`` nodes to a
# 128-lane row, `push_pack`), stages it (and the aux column, laid out
# lane-dense) in the pool dtypes, and writes it at ``size + offs`` in
# whole chunks: value rows from the 8-row tile (``8*kq`` nodes) the
# destination starts in, aux in (8, 128) tiles.  The nodes of those first
# tiles that are already in the pool (live nodes below ``size``, or the
# previous tile's tail) are read once per cycle at the first step and then
# carried in VMEM from tile to tile, merged in before the stage.  Nodes
# past the live prefix land in the reserved M*n headroom (+ PUSH_SLACK):
# dead by the pool contract.  Ordering rule: a tile's last chunk and the
# next tile's first share a tile of the pool, so each emit waits for the
# previous tile's DMAs before it stages or issues anything.
#
# SMEM carry (persists across the sequential grid steps):
#   [0] survivors pushed so far this cycle (the next tile's offset)
#   [1] cumulative sol_inc          [2] folded incumbent (PFSP phase 0)
#   [3] pool nodes written           [4]/[5] value/aux DMAs in flight
#   [6]/[7] first value row / first aux-view row of the DMAs in flight
# DMA semaphores: [0] value chunks, [1] aux tiles, [2]/[3] head reads.

_CARRY = 8


@dataclasses.dataclass(frozen=True)
class _PushRefs:
    size: object      # SMEM (1,): the pool size after the pop
    pv: object        # ANY (rows/kq, kq*W): the value pool, aliased in place
    pa: object        # ANY (rows/128, 128): the aux pool's view, aliased
    stage_v: object   # VMEM (rs/kq, kq*W), pool value dtype
    stage_a: object   # VMEM (ra, 128), pool aux dtype
    head_v: object    # VMEM (8, kq*W): the row tile the next write starts in
    head_a: object    # VMEM (8, 128): the aux tile the next write starts in
    sems: object      # DMA semaphores (4,)
    carry: object     # SMEM (_CARRY,) int32
    kq: int           # nodes per pool row (`push_pack`)

    @property
    def quantum(self) -> int:
        """Nodes of one 8-row pool tile, the start quantum of a DMA."""
        return _ROW_TILE * self.kq

    @property
    def chunk(self) -> int:
        return _chunk_nodes(self.kq)

    @property
    def chunk_rows(self) -> int:
        return self.chunk // self.kq


def _vals_chunk(r: _PushRefs, row0, k: int):
    cr = r.chunk_rows
    start = pl.multiple_of(row0 + k * cr, _ROW_TILE)
    return pltpu.make_async_copy(
        r.stage_v.at[pl.ds(k * cr, cr)],
        r.pv.at[pl.ds(start, cr)], r.sems.at[0])


def _aux_chunk(r: _PushRefs, arow0, k: int):
    start = pl.multiple_of(arow0 + k * _ROW_TILE, _ROW_TILE)
    return pltpu.make_async_copy(
        r.stage_a.at[pl.ds(k * _ROW_TILE, _ROW_TILE)],
        r.pa.at[pl.ds(start, _ROW_TILE)], r.sems.at[1])


def _head_reads(r: _PushRefs):
    """The pool tiles the cycle's first write starts in (value and aux
    nodes below ``size`` there are live and go back unchanged)."""
    s = r.size[0]
    row0 = pl.multiple_of(s // r.quantum * _ROW_TILE, _ROW_TILE)
    arow0 = pl.multiple_of(s // _AUX_TILE * _ROW_TILE, _ROW_TILE)
    return (
        pltpu.make_async_copy(r.pv.at[pl.ds(row0, _ROW_TILE)], r.head_v,
                              r.sems.at[2]),
        pltpu.make_async_copy(r.pa.at[pl.ds(arow0, _ROW_TILE)], r.head_a,
                              r.sems.at[3]),
    )


def _push_seed(r: _PushRefs):
    """First grid step: zero the carry and start the head reads (waited
    at the first emit, so they overlap the first tile's bound)."""
    for s in range(_CARRY):
        r.carry[s] = 0
    for c in _head_reads(r):
        c.start()


def _push_shift(r: _PushRefs):
    """This tile's first destination node, modulo its 8-row pool tile."""
    return (r.size[0] + r.carry[0]) % r.quantum


def _push_wait(r: _PushRefs):
    """Wait for the DMAs the previous emit left in flight."""
    nv, na = r.carry[4], r.carry[5]
    row0, arow0 = r.carry[6], r.carry[7]
    for k in range(r.stage_v.shape[0] // r.chunk_rows):
        @pl.when(k < nv)
        def _():
            _vals_chunk(r, row0, k).wait()

    for k in range(r.stage_a.shape[0] // _ROW_TILE):
        @pl.when(k < na)
        def _():
            _aux_chunk(r, arow0, k).wait()

    r.carry[4] = 0
    r.carry[5] = 0


def _aux_lanes(caux, delta, *, ra: int, bf16: bool):
    """The (rc, 1) aux column laid out for the aux pool's lane-dense
    (rows/128, 128) view: row ``k`` lands at flat position ``k + delta``
    of an (ra, 128) block.  Two one-hot MXU operands do it without a
    reshape — ``A[i, k] = [(k + delta) // 128 == i]`` and
    ``B[k, l] = caux[k] * [(k + delta) % 128 == l]`` — and each output
    element sums exactly one aux value or none (exact in bf16 below 256)."""
    i32, f32 = jnp.int32, jnp.float32
    rc = caux.shape[0]
    pos = jax.lax.broadcasted_iota(i32, (ra, rc), 1) + delta
    a = (jnp.right_shift(pos, 7)
         == jax.lax.broadcasted_iota(i32, (ra, rc), 0)).astype(f32)
    posc = jax.lax.broadcasted_iota(i32, (rc, 128), 0) + delta
    lane = jax.lax.broadcasted_iota(i32, (rc, 128), 1)
    b = jnp.where(jnp.bitwise_and(posc, 127) == lane, caux.astype(f32), 0.0)
    return PK._hp_dot(a, b, bf16=bf16).astype(i32)


def _pack_rows(x, *, kq: int, rows: int, bf16: bool):
    """(r, W) node rows laid out as ``rows`` pool rows of ``kq`` slots:
    node ``k`` goes to row ``k // kq``, lanes ``(k % kq) * W`` on.  Two
    one-hot matmuls: ``x @ T`` repeats each node in all ``kq`` slots
    (``T[j, l] = [l % W == j]``), a select keeps it in its own slot, and
    ``S @ ·`` sums each row's ``kq`` nodes (``S[i, k] = [k // kq == i]``);
    every output element takes one value (exact in bf16 below 256).
    ``kq`` 1 is the rows themselves."""
    if kq == 1:
        return x
    i32, f32 = jnp.int32, jnp.float32
    r, w = x.shape
    lanes = kq * w
    sh = w.bit_length() - 1  # W and kq are powers of two

    def iota(shape, d):
        return jax.lax.broadcasted_iota(i32, shape, d)

    t = jnp.bitwise_and(iota((w, lanes), 1), w - 1) == iota((w, lanes), 0)
    rep = PK._hp_dot(x.astype(f32), t.astype(f32), bf16=bf16)  # (r, kq*W)
    own = (jnp.right_shift(iota((r, lanes), 1), sh)
           == jnp.bitwise_and(iota((r, lanes), 0), kq - 1))
    rep = jnp.where(own, rep, 0.0)
    s = (jnp.right_shift(iota((rows, r), 1), kq.bit_length() - 1)
         == iota((rows, r), 0))
    return PK._hp_dot(s.astype(f32), rep, bf16=bf16).astype(i32)


def _push_emit(r: _PushRefs, rows, caux, cnt, *, first, last, bf16: bool):
    """Push one tile's compacted survivors into the pool.

    ``rows``/``caux`` come from `_compact_push` with ``shift`` =
    `_push_shift` at the pool's slot width: row ``k`` is the survivor
    bound for pool node ``node0 + k`` (``node0`` the first node of the
    8-row pool tile the destination starts in).  ``first``/``last`` mark
    the cycle's first and last emit step."""
    i32 = jnp.int32
    rc, w = rows.shape
    kq, ch, cr = r.kq, r.chunk, r.chunk_rows
    ra = r.stage_a.shape[0]
    pos = r.size[0] + r.carry[0]  # pool node of this tile's first survivor
    rv = pos % r.quantum
    row0 = pos // r.quantum * _ROW_TILE  # its pool row tile
    ra_off = pos % _AUX_TILE
    arow0 = pos // _AUX_TILE * _ROW_TILE
    # The ordering rule (module comment above): the previous tile's
    # chunks land before this tile refills the staging they read from and
    # before its first chunk rewrites their tail.
    _push_wait(r)

    @pl.when(first)
    def _():
        for c in _head_reads(r):
            c.wait()

    lanes = _aux_lanes(caux, ra_off - rv, ra=ra, bf16=bf16)
    flat = (jax.lax.broadcasted_iota(i32, (_ROW_TILE, 128), 0) * 128
            + jax.lax.broadcasted_iota(i32, (_ROW_TILE, 128), 1))
    ahead = jnp.where(flat < ra_off, r.head_a[...].astype(i32),
                      lanes[:_ROW_TILE])
    lanes = jnp.concatenate([ahead, lanes[_ROW_TILE:]], axis=0)
    r.stage_a[...] = lanes.astype(r.stage_a.dtype)

    nv = (rv + cnt + ch - 1) // ch
    na = (ra_off + cnt + _AUX_TILE - 1) // _AUX_TILE
    for k in range(r.stage_v.shape[0] // cr):
        @pl.when(k < nv)
        def _():
            packed = _pack_rows(rows[k * ch:min((k + 1) * ch, rc)], kq=kq,
                                rows=cr, bf16=bf16)
            if k == 0:
                # The pool nodes below this tile's first survivor go back
                # as they were.
                shape = (_ROW_TILE, packed.shape[1])
                node = (jax.lax.broadcasted_iota(i32, shape, 0) * kq
                        + jax.lax.broadcasted_iota(i32, shape, 1) // w)
                head = jnp.where(node < rv, r.head_v[...].astype(i32),
                                 packed[:_ROW_TILE])
                packed = jnp.concatenate([head, packed[_ROW_TILE:]], axis=0)
            r.stage_v[k * cr:k * cr + packed.shape[0]] = packed.astype(
                r.stage_v.dtype)
            _vals_chunk(r, row0, k).start()

    for k in range(ra // _ROW_TILE):
        @pl.when(k < na)
        def _():
            _aux_chunk(r, arow0, k).start()

    r.carry[0] = r.carry[0] + cnt
    r.carry[3] = r.carry[3] + nv * ch
    r.carry[4] = nv
    r.carry[5] = na
    r.carry[6] = row0
    r.carry[7] = arow0
    # The next tile's writes start in the row tile and the aux tile this
    # tile's live prefix ends in: carry them over as its heads.
    q = pl.multiple_of((rv + cnt) // r.quantum * _ROW_TILE, _ROW_TILE)
    r.head_v[...] = r.stage_v[pl.ds(q, _ROW_TILE), :]
    b = pl.multiple_of((ra_off + cnt) // _AUX_TILE * _ROW_TILE, _ROW_TILE)
    r.head_a[...] = r.stage_a[pl.ds(b, _ROW_TILE), :]

    @pl.when(last)
    def _():
        _push_wait(r)


def _mega_nqueens_push_kernel(board_ref, depth_ref, valid_ref, best_ref,
                              size_ref, pv_in, pa_in, pv_ref, pa_ref,
                              scal_ref, stage_v, stage_a, head_v, head_a,
                              sems, carry_ref, *, N: int, g: int, Mt: int,
                              G: int):
    del pv_in, pa_in  # aliased to pv_ref / pa_ref
    w, kq = push_pack(N)
    r = _PushRefs(size_ref, pv_ref, pa_ref, stage_v, stage_a, head_v,
                  head_a, sems, carry_ref, kq)
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _seed():
        _push_seed(r)

    board = board_ref[:].astype(jnp.int32)  # (Mt, N)
    depth = depth_ref[:, 0].astype(jnp.int32)
    valid = valid_ref[:, 0] != 0
    best = best_ref[0]
    with jax.named_scope(BOUND_SCOPE):
        labels = PK._nqueens_tile_labels(board, depth, N=N, g=g)
    # The `_NQueensResident` evaluate fold: swap position is the depth.
    keep = labels & valid[:, None] & (depth < N)[:, None]
    sol_inc = jnp.sum(valid & (depth == N), dtype=jnp.int32)
    with jax.named_scope(COMPACT_SCOPE):
        rows, caux, cnt = _compact_push(board, depth, depth, keep, n=N,
                                        M=Mt, shift=_push_shift(r),
                                        width=w)
    with jax.named_scope(EMIT_SCOPE):
        _push_emit(r, rows, caux, cnt, first=i == 0, last=i == G - 1,
                   bf16=N < 256)
    sol_cum = carry_ref[1] + sol_inc
    carry_ref[1] = sol_cum
    scal_ref[:] = _scalar_lanes(carry_ref[0], sol_cum, best, carry_ref[3])


def _pfsp_push_cycle(bound, prmu_ref, limit1_ref, valid_ref, best_ref,
                     r: _PushRefs, scal_ref, lb_ref, *, n: int, Mt: int,
                     G: int):
    """The PFSP two-phase grid ``(2, G)``: phase 0 evaluates every tile's
    bounds into the stash and folds the global leaf-min into the
    incumbent — no tile may prune before every tile's leaves are folded
    (PFSP exactness rule); phase 1 re-streams the tiles (bounds come back
    from the stash, not recomputed), prunes and compacts against the
    final incumbent, and pushes.  ``bound(prmu, limit1)`` takes the tile's
    (Mt, 1) limit column as its block holds it
    (`pallas_kernels._tile_parent_state`)."""
    p = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when((p == 0) & (i == 0))
    def _seed():
        _push_seed(r)
        r.carry[2] = best_ref[0]

    prmu = prmu_ref[:].astype(jnp.int32)
    limit1 = limit1_ref[:, 0].astype(jnp.int32)
    valid = valid_ref[:, 0] != 0

    @pl.when(p == 0)
    def _sweep():
        with jax.named_scope(BOUND_SCOPE):
            lb = bound(prmu, limit1_ref[:].astype(jnp.int32))
        lb_ref[i] = lb
        r.carry[2] = jnp.minimum(
            r.carry[2], _pfsp_leaf_min(limit1, valid, lb, n=n, M=Mt))

    @pl.when(p == 1)
    def _emit():
        with jax.named_scope(COMPACT_SCOPE):
            rows, caux, cnt, sol_inc, best = _pfsp_epilogue(
                prmu, limit1, valid, r.carry[2], lb_ref[i], n=n, M=Mt,
                shift=_push_shift(r), width=push_pack(n)[0])
        with jax.named_scope(EMIT_SCOPE):
            _push_emit(r, rows, caux, cnt, first=i == 0, last=i == G - 1,
                       bf16=n < 256)
        sol_cum = r.carry[1] + sol_inc
        r.carry[1] = sol_cum
        scal_ref[:] = _scalar_lanes(r.carry[0], sol_cum, best, r.carry[3])


def _mega_lb1_push_kernel(prmu_ref, limit1_ref, valid_ref, best_ref,
                          size_ref, pt_ref, heads_ref, tails_ref,
                          pv_in, pa_in, pv_ref, pa_ref, scal_ref,
                          times_ref, lb_ref, stage_v, stage_a, head_v, head_a,
                          sems, carry_ref, *, n: int, m: int, Mt: int,
                          G: int):
    del pv_in, pa_in  # aliased to pv_ref / pa_ref

    def bound(prmu, limit1):
        return PK._lb1_tile_lb(prmu, limit1, pt_ref, heads_ref, tails_ref,
                               times_ref, n=n, m=m)

    _pfsp_push_cycle(
        bound, prmu_ref, limit1_ref, valid_ref, best_ref,
        _PushRefs(size_ref, pv_ref, pa_ref, stage_v, stage_a, head_v, head_a,
                  sems, carry_ref, push_pack(n)[1]),
        scal_ref, lb_ref, n=n, Mt=Mt, G=G)


def _mega_lb2_push_kernel(prmu_ref, limit1_ref, valid_ref, best_ref,
                          size_ref, pt_ref, heads_ref, slots_ref, pairs_ref,
                          pv_in, pa_in, pv_ref, pa_ref, scal_ref,
                          times_ref, lb_ref, stage_v, stage_a, head_v, head_a,
                          sems, carry_ref, *lane_scratch, n: int, m: int,
                          P: int, Mt: int, pg: int, G: int):
    del pv_in, pa_in  # aliased to pv_ref / pa_ref
    lanes = PK._Lb2Lanes(slots_ref, pairs_ref, *lane_scratch)

    def bound(prmu, limit1):
        return PK._lb2_tile_lb(prmu, limit1, pt_ref, heads_ref, lanes,
                               times_ref, n=n, m=m, P=P, pg=pg)

    _pfsp_push_cycle(
        bound, prmu_ref, limit1_ref, valid_ref, best_ref,
        _PushRefs(size_ref, pv_ref, pa_ref, stage_v, stage_a, head_v, head_a,
                  sems, carry_ref, push_pack(n)[1]),
        scal_ref, lb_ref, n=n, Mt=Mt, G=G)


# ---------------------------------------------------------------------------
# pallas_call factories
# ---------------------------------------------------------------------------


def _interpret_mode(interpret: bool, sharded: bool) -> str | None:
    """How an interpreted push cycle runs: "dma", the TPU interpret mode
    that models the DMAs and their semaphores (a DMA lands when it is
    waited on, so a missing wait or an ordering fault shows off-chip too);
    or "eager" inside the mesh tier's shard_map (``sharded``), where that
    mode's cross-device barrier deadlocks against the CPU runtime's
    collectives — the plain interpreter, which lands each DMA as it
    starts.  None: compiled."""
    if not interpret:
        return None
    return "eager" if sharded else "dma"


def _interpret(mode: str | None):
    if mode is None:
        return False
    return pltpu.InterpretParams() if mode == "dma" else True


def _push_plumbing(mt: int, n: int, W: int, rows: int, vdt, adt,
                   two_phase: bool):
    """(in_specs, out_shape, out_specs, scratch) of a push cycle into a
    pool of ``rows`` nodes whose value array is ``W`` lanes wide
    (`push_layout`).

    In: the streamed tile of the popped chunk (the (i)-th grid step's
    index maps fetch pool tile i HBM->VMEM; the pipelined grid prefetches
    tile i+1 under tile i's compute; the two-phase PFSP grid re-fetches
    each tile in phase 1 — node fields are cheaper to re-stream than to
    hold for the whole sweep), then ``best`` and ``size`` in SMEM.  The
    two pools go in and out in place (``pl.ANY``, aliased by the caller)
    with one (1, 128) scalar block, resident across the grid."""
    if two_phase:
        tile = lambda p, i: (i, 0)
        one = lambda p, i: (0,)
        whole = lambda p, i: (0, 0)
    else:
        tile = lambda i: (i, 0)
        one = lambda i: (0,)
        whole = lambda i: (0, 0)
    in_specs = [
        pl.BlockSpec((mt, n), tile, memory_space=pltpu.VMEM),   # vals
        pl.BlockSpec((mt, 1), tile, memory_space=pltpu.VMEM),   # aux
        pl.BlockSpec((mt, 1), tile, memory_space=pltpu.VMEM),   # valid
        pl.BlockSpec((1,), one, memory_space=pltpu.SMEM),       # best
        pl.BlockSpec((1,), one, memory_space=pltpu.SMEM),       # size
    ]
    kq = push_pack(n)[1]
    out_shape = (
        jax.ShapeDtypeStruct((rows // kq, W), vdt),
        jax.ShapeDtypeStruct((rows // 128, 128), adt),
        jax.ShapeDtypeStruct((1, 128), jnp.int32),
    )
    out_specs = (
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec((1, 128), whole, memory_space=pltpu.VMEM),
    )
    _, rs, ra = _push_sizes(mt, n)
    scratch = [
        pltpu.VMEM((rs // kq, W), vdt),
        pltpu.VMEM((ra, 128), adt),
        pltpu.VMEM((_ROW_TILE, W), vdt),
        pltpu.VMEM((_ROW_TILE, 128), adt),
        pltpu.SemaphoreType.DMA((4,)),
        pltpu.SMEM((_CARRY,), jnp.int32),
    ]
    return in_specs, out_shape, out_specs, scratch


@lru_cache(maxsize=None)
def _nqueens_push_call(N: int, g: int, M: int, mt: int, W: int, rows: int,
                       vdt, adt, interpret: str | None):
    G = M // mt
    in_specs, out_shape, out_specs, scratch = _push_plumbing(
        mt, N, W, rows, vdt, adt, two_phase=False)
    k = len(in_specs)
    return pl.pallas_call(
        partial(_mega_nqueens_push_kernel, N=N, g=g, Mt=mt, G=G),
        out_shape=out_shape,
        name="tts_cycle_nqueens",
        grid=(G,),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=out_specs,
        scratch_shapes=scratch,
        input_output_aliases={k: 0, k + 1: 1},
        compiler_params=PK._compiler_params(),
        interpret=_interpret(interpret),
    )


@lru_cache(maxsize=None)
def _lb1_push_call(n: int, m: int, M: int, mt: int, W: int, rows: int, vdt,
                   adt, interpret: str | None):
    G = M // mt
    in_specs, out_shape, out_specs, scratch = _push_plumbing(
        mt, n, W, rows, vdt, adt, two_phase=True)
    in_specs = in_specs + PK._pfsp_table_specs(n, m, lambda p, i: (0,))
    k = len(in_specs)
    return pl.pallas_call(
        partial(_mega_lb1_push_kernel, n=n, m=m, Mt=mt, G=G),
        out_shape=out_shape,
        name="tts_cycle_lb1",
        grid=(2, G),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=out_specs,
        scratch_shapes=[PK._times_scratch(n, m, mt),
                        pltpu.VMEM((G, mt, n), jnp.int32)] + scratch,
        input_output_aliases={k: 0, k + 1: 1},
        compiler_params=PK._compiler_params(ndims=2),
        interpret=_interpret(interpret),
    )


@lru_cache(maxsize=None)
def _lb2_push_call(n: int, m: int, P: int, M: int, mt: int, pg: int, W: int,
                   rows: int, vdt, adt, interpret: str | None):
    G = M // mt
    in_specs, out_shape, out_specs, scratch = _push_plumbing(
        mt, n, W, rows, vdt, adt, two_phase=True)
    in_specs = in_specs + [
        *PK._pfsp_table_specs(n, m, lambda p, i: (0,))[:2],
        *PK._lb2_lane_specs(P, lambda p, i: (0,)),
    ]
    k = len(in_specs)
    return pl.pallas_call(
        partial(_mega_lb2_push_kernel, n=n, m=m, P=P, Mt=mt, pg=pg, G=G),
        out_shape=out_shape,
        name="tts_cycle_lb2",
        grid=(2, G),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=out_specs,
        scratch_shapes=[PK._times_scratch(n, m, mt),
                        pltpu.VMEM((G, mt, n), jnp.int32)] + scratch
        + PK._lb2_lane_scratch(n, m, mt, pg),
        input_output_aliases={k: 0, k + 1: 1},
        compiler_params=PK._compiler_params(ndims=2),
        interpret=_interpret(interpret),
    )


# ---------------------------------------------------------------------------
# engine entry
# ---------------------------------------------------------------------------


def make_cycle(problem, M: int, device, decision: Decision,
               sharded: bool = False):
    """Build ``cycle(pool_vals, pool_aux, size, vals_c, aux_c, valid,
    best) -> (pool_vals, pool_aux, tree_inc, sol_inc, best, push_rows)``
    — the armed alternate body `engine/resident.py loop_fns` splices in
    after the pop (``size`` is the size after it).  The pools come back
    with the cycle's survivors at nodes ``[size, size + tree_inc)`` in the
    dense-mode global (parent, slot) order; ``push_rows`` counts the pool
    nodes the push wrote.  ``sharded``: the cycle runs inside the mesh
    tier's shard_map (how interpret mode runs it, `_interpret_mode`).

    The kernel writes the pools itself (aliased, laid out by
    `push_layout`); the aux pool goes in through its (rows/128, 128) view,
    a bitcast.

    lb2 note: the kernel always evaluates the UNSTAGED fold, even when the
    two-pass staged evaluator is enabled for the jnp path.  They are
    value-identical: at a leaf the lb1 and lb2 makespans coincide (nothing
    is unscheduled), and for interior nodes ``lb2 >= lb1`` pointwise, so
    the staged keep ``open & ~leaf & (lb1 < best) & (lb2 < best)``
    equals the unstaged ``open & ~leaf & (lb2 < best)``.
    """
    fam = _family(problem)
    mt = decision.mt or M
    n = int(problem.child_slots)

    if fam == "nqueens":
        tables = ()

        def push_call(W, rows, vdt, adt, mode):
            return _nqueens_push_call(problem.N, problem.g, M, mt, W,
                                      rows, vdt, adt, mode)
    else:
        t = problem.device_tables()
        m = problem.machines
        inst = PK.pfsp_kernel_tables(t.ptm_t, t.min_heads, t.min_tails)
        if fam == "lb1":
            tables = inst

            def push_call(W, rows, vdt, adt, mode):
                return _lb1_push_call(n, m, M, mt, W, rows, vdt, adt, mode)
        else:
            # lb2 — Johnson-ordered tables resolved exactly like
            # `pfsp_lb2_bounds` (device cache when eager, numpy constants
            # under a trace).
            from . import pfsp_device as PD

            P = t.pairs.shape[0]
            pg = PD.lb2_kernel_pair_group(P, n)
            ordered = (t.johnson_ordered_device(pg) if PK._eager_context()
                       else t.johnson_ordered_mp(pg))
            Pp = ordered.lag_o.shape[0]
            tables = (*inst[:2], *PK.lb2_kernel_tables(ordered, pg))

            def push_call(W, rows, vdt, adt, mode):
                return _lb2_push_call(n, m, Pp, M, mt, pg, W, rows, vdt,
                                      adt, mode)

    def chunk(vals_c, aux_c, valid, best):
        return (vals_c, aux_c[:, None], valid.astype(jnp.int32)[:, None],
                jnp.reshape(best, (1,)))

    mode = _interpret_mode(decision.interpret, sharded)

    def cycle(pool_vals, pool_aux, size, vals_c, aux_c, valid, best):
        rows = pool_aux.shape[0]
        call = push_call(pool_vals.shape[1], rows, pool_vals.dtype,
                         pool_aux.dtype, mode)
        pool_vals, aux_view, scal = call(
            *chunk(vals_c, aux_c, valid, best), jnp.reshape(size, (1,)),
            *tables, pool_vals, jnp.reshape(pool_aux, (rows // 128, 128)))
        return (pool_vals, jnp.reshape(aux_view, (rows,)), scal[0, 0],
                scal[0, 1], scal[0, 2], scal[0, 3])

    return cycle


# ---------------------------------------------------------------------------
# Megacore-parallel evaluation-only pass
# ---------------------------------------------------------------------------


def _eval_nqueens_kernel(board_ref, depth_ref, out_ref, *, N: int, g: int):
    labels = PK._nqueens_tile_labels(
        board_ref[:].astype(jnp.int32), depth_ref[:, 0].astype(jnp.int32),
        N=N, g=g)
    out_ref[:] = labels.astype(jnp.int32)


def _eval_lb1_kernel(prmu_ref, limit1_ref, pt_ref, heads_ref, tails_ref,
                     out_ref, times_ref, *, n: int, m: int):
    out_ref[:] = PK._lb1_tile_lb(
        prmu_ref[:].astype(jnp.int32), limit1_ref[:].astype(jnp.int32),
        pt_ref, heads_ref, tails_ref, times_ref, n=n, m=m)


@lru_cache(maxsize=None)
def _eval_nqueens_call(N: int, g: int, B: int, mt: int, interpret: bool):
    tm = lambda i: (i, 0)
    bs = PK._bs
    return pl.pallas_call(
        partial(_eval_nqueens_kernel, N=N, g=g),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.int32),
        name="tts_eval_nqueens",
        grid=(B // mt,),
        in_specs=[bs((mt, N), tm), bs((mt, 1), tm)],
        out_specs=bs((mt, N), tm),
        compiler_params=PK._compiler_params(parallel=True),
        interpret=interpret,
    )


@lru_cache(maxsize=None)
def _eval_lb1_call(n: int, m: int, B: int, mt: int, interpret: bool):
    tm = lambda i: (i, 0)
    bs = PK._bs
    return pl.pallas_call(
        partial(_eval_lb1_kernel, n=n, m=m),
        out_shape=jax.ShapeDtypeStruct((B, n), jnp.int32),
        name="tts_eval_lb1",
        grid=(B // mt,),
        in_specs=[
            bs((mt, n), tm),
            bs((mt, 1), tm),
            *PK._pfsp_table_specs(n, m, lambda i: (0,)),
        ],
        out_specs=bs((mt, n), tm),
        scratch_shapes=[PK._times_scratch(n, m, mt)],
        compiler_params=PK._compiler_params(parallel=True),
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
    if interpret is None:
        interpret = PK.pallas_interpret() or not _native_kind(None)
    vals_c = jnp.asarray(vals).astype(jnp.int32)
    aux_c = jnp.asarray(aux).astype(jnp.int32)[:, None]
    if fam == "nqueens":
        call = _eval_nqueens_call(problem.N, problem.g, B, mt, interpret)
        return call(vals_c, aux_c)
    t = problem.device_tables()
    n, m = problem.jobs, problem.machines
    inst = PK.pfsp_kernel_tables(t.ptm_t, t.min_heads, t.min_tails)
    if fam == "lb1":
        call = _eval_lb1_call(n, m, B, mt, interpret)
        return call(vals_c, aux_c, *inst)
    from . import pfsp_device as PD

    P = t.pairs.shape[0]
    pg = PD.lb2_kernel_pair_group(P, n)
    ordered = (t.johnson_ordered_device(pg) if PK._eager_context()
               else t.johnson_ordered_mp(pg))
    Pp = ordered.lag_o.shape[0]
    # The carry-free lb2 pass: the standalone kernel at tile ``mt`` with
    # every grid axis Megacore-parallel.
    call = PK._lb2_call(n, m, Pp, B, mt, interpret, pg, parallel=True)
    return call(vals_c, aux_c, *inst[:2], *PK.lb2_kernel_tables(ordered, pg))


def megakernel_lb2_bounds(prmu, limit1, tables, interpret: bool | None = None):
    """The lb2 bound values the megakernel arms with, as a standalone
    (B, n) call — the shared `_lb2_tile_lb` body, all int32.  The
    bf16-exactness gate test bit-compares this against the f32
    pair-blocked oracle (`pfsp_device._lb2_chunk`) on real Taillard
    instances; a mismatch means the kernel must refuse to arm."""
    return PK.pfsp_lb2_bounds(prmu, limit1, tables, interpret=interpret)


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


def _outer_eqns(jaxpr, out=None):
    """``(name, eqn)`` of a jaxpr and its sub-jaxprs, short of the bodies
    of the pallas_calls: the XLA program around the kernels."""
    from ..analysis.contracts import subjaxprs

    if hasattr(jaxpr, "jaxpr"):  # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        out.append((eqn.primitive.name, eqn))
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in subjaxprs(v):
                _outer_eqns(sub, out)
    return out


@contract(
    "megakernel-push-in-kernel",
    claim="the armed cycle places its survivors itself: outside the "
          "kernel the loop body holds no dynamic_update_slice on a pool and "
          "no convert_element_type of an (M*n, ...) array, and the kernel "
          "aliases both pool operands to its outputs",
    artifact="resident-step",
    applies=lambda cell: cell is not None
    and getattr(cell, "megakernel", None) == "force",
)
def _contract_megakernel_push_in_kernel(art, cell):
    prog = art.prog
    dec = getattr(prog, "megakernel", None)
    if dec is None or not dec.enabled:
        return []
    pools = {tuple(s) for s in prog.pool_shapes()}
    Mn = prog.M * int(prog.problem.child_slots)
    eqns = _outer_eqns(art.jaxpr)
    dus = [e for name, e in eqns if name == "dynamic_update_slice"
           and tuple(e.invars[0].aval.shape) in pools]
    out = []
    if dus:
        out.append(f"armed loop body writes a pool with {len(dus)} "
                   "dynamic_update_slice eqn(s): the kernel must push")
    wide = [tuple(e.invars[0].aval.shape) for name, e in eqns
            if name == "convert_element_type" and e.invars[0].aval.ndim
            and e.invars[0].aval.shape[0] == Mn]
    if wide:
        out.append(f"armed loop body converts (M*n, ...) arrays {wide}")
    calls = [e for name, e in eqns if name == "pallas_call"]
    want = {tuple(prog.pool_shapes()[0]), (prog.pool_rows // 128, 128)}
    for e in calls:
        aliased = {tuple(e.invars[i].aval.shape)
                   for i, _ in e.params["input_output_aliases"]}
        if aliased != want:
            out.append(f"cycle kernel aliases operands {sorted(aliased)}, "
                       f"not both pools {sorted(want)}")
    return out


def _loop_bodies(jaxpr, out=None):
    """The body jaxprs of every ``scan``/``while`` inside ``jaxpr``, the
    loops nested in other loops included."""
    from ..analysis.contracts import prim_eqns, subjaxprs

    out = [] if out is None else out
    for name, eqn in prim_eqns(jaxpr):
        if name in ("scan", "while"):
            for v in eqn.params.values():
                out.extend(subjaxprs(v))
    return out


@contract(
    "megakernel-lb2-lanes",
    claim="the armed lb2 cycle runs the lane-dense pair loop: the "
          "kernel takes no (P, n, n) one-hot order operand, and no loop "
          "inside it holds a dot_general or a reduction along the lanes",
    artifact="resident-step",
    applies=lambda cell: cell is not None
    and getattr(cell, "megakernel", None) == "force"
    and cell.family == "pfsp-lb2",
)
def _contract_megakernel_lb2_lanes(art, cell):
    from ..analysis.contracts import prim_eqns

    prog = art.prog
    dec = getattr(prog, "megakernel", None)
    if dec is None or not dec.enabled:
        return []
    out = []
    n = int(prog.problem.child_slots)
    for name, e in _outer_eqns(art.jaxpr):
        if name != "pallas_call":
            continue
        cubes = [tuple(v.aval.shape) for v in e.invars
                 if v.aval.ndim == 3 and tuple(v.aval.shape[1:]) == (n, n)]
        if cubes:
            out.append(f"cycle kernel takes (P, n, n) operands {cubes}")
        for body in _loop_bodies(e.params["jaxpr"]):
            for pname, eqn in prim_eqns(body):
                if pname == "dot_general":
                    out.append("a loop in the cycle kernel holds a "
                               "dot_general")
                elif pname.startswith("reduce_") and eqn.invars[0].aval.ndim \
                        and eqn.invars[0].aval.ndim - 1 in eqn.params.get(
                            "axes", ()):
                    out.append(f"a loop in the cycle kernel holds a lane "
                               f"reduction ({pname})")
    return sorted(set(out))


def _scope_eqns(jaxpr, scope: str, inside: bool = False, out=None):
    """The eqns of a kernel body and its sub-jaxprs traced under the named
    scope ``scope``, or under an eqn that was."""
    from ..analysis.contracts import subjaxprs

    if hasattr(jaxpr, "jaxpr"):  # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        here = inside or scope in str(eqn.source_info.name_stack).split("/")
        if here:
            out.append(eqn)
        for v in eqn.params.values():
            for sub in subjaxprs(v):
                _scope_eqns(sub, scope, here, out)
    return out


@contract(
    "megakernel-pfsp-bound-lanes",
    claim="the armed PFSP cycle (lb1 and lb2) bounds its tile on "
          "lane-dense slabs: inside the kernel's tts_bound region no value "
          "has rank 3 and none has Mt*n rows — no (Mt, n, n) one-hot, no "
          "(Mt*n, n) gather operand, no (Mt, n, m) times",
    artifact="resident-step",
    applies=lambda cell: cell is not None
    and getattr(cell, "megakernel", None) == "force"
    and cell.family in ("pfsp-lb1", "pfsp-lb2"),
)
def _contract_megakernel_pfsp_bound_lanes(art, cell):
    prog = art.prog
    dec = getattr(prog, "megakernel", None)
    if dec is None or not dec.enabled:
        return []
    flat = dec.mt * int(prog.problem.child_slots)
    out = []
    for name, e in _outer_eqns(art.jaxpr):
        if name != "pallas_call":
            continue
        eqns = _scope_eqns(e.params["jaxpr"], BOUND_SCOPE)
        if not eqns:
            out.append(f"cycle kernel has no {BOUND_SCOPE} region")
        for eqn in eqns:
            for v in eqn.outvars:
                shape = tuple(getattr(v.aval, "shape", ()))
                if len(shape) >= 3:
                    out.append(f"{BOUND_SCOPE} holds a rank-3 value "
                               f"{shape} ({eqn.primitive.name})")
                elif shape and shape[0] == flat:
                    out.append(f"{BOUND_SCOPE} holds an (Mt*n, ...) value "
                               f"{shape} ({eqn.primitive.name})")
    return sorted(set(out))
