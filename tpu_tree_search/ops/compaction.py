"""Survivor-path stream compaction for the resident engines.

One chunk cycle ends by pushing the surviving children contiguously onto
the pool in (parent, slot) order — the reference's child push order
(`pfsp_gpu_chpl.chpl:276-298`).  Computing each survivor's *rank* is a pair
of prefix sums (cheap); inverting the rank map (which (parent, slot) is
the rank-s survivor?) is the expensive part, and round-5 hardware numbers
put it — not bound evaluation — at ~85% of resident-cycle time (VERDICT r5
"What's weak" #1-3).  This module owns every rank-inversion implementation
and the policy that picks between them:

  * ``scatter`` — one int32-id scatter to unique destinations.  Fast on
    CPU (gather-like); XLA:TPU lowers large general scatters to a
    mostly-serial loop.
  * ``sort``    — stable argsort of ranked keys (TPU's vectorized sort).
  * ``search``  — binary-search inverse: log2(M) gather rounds plus one
    (S, n) lane pass.  No sort, no scatter.
  * ``dense``   — the dense-child fast path: stream compaction by
    **LSB-first binary shifts**.  Every survivor must move left by
    ``dist = flat_index - rank``; between consecutive survivors the
    distance grows by exactly the gap between them, so shifting by
    2^b (bit b of the remaining distance, b ascending) keeps positions
    strictly increasing and never collides — the zero-conflict
    compaction of the N-Queens DFS line of work (arXiv 2511.12009)
    expressed as log2(M*n) rounds of static slice + select.  The
    compiled program contains **no sort, no scatter, no searchsorted,
    and no gather** (jaxpr-pinned by tests/test_compaction.py); its cost
    is ~(M*n*log2(M*n)) fully-vectorized selects, independent of the
    survivor count — which is exactly the regime where survivors are
    *dense* (N-Queens keeps most slots; the PFSP ub=inf warm-up regime
    prunes nothing) and the S-proportional gather modes pay the most.

All four produce identical ids in identical order (pinned).  ``auto`` (the
default) resolves per (problem, M, n, prune-rate regime, backend) from the
measured table in ``_auto_compact`` — the same self-tuning contract as
``--lb2-pairblock auto``; the raw knob rides ``routing_cache_token`` and
the resolved mode is baked into compiled programs at trace time.

The streamed megakernel (ops/megakernel.py) runs the **tiled** form of
``dense``: each pool tile of width Mt compacts its own (Mt*n) plane with
the same LSB-first shifts (rank base 0 per tile), and a cross-tile
survivor offset carried in SMEM across sequential grid steps restores the
global dense order when the engine stitches the tiles back at
``size + offset[t]``.  Per-tile rank + carried base is exactly the global
dense rank, so the tiled kernel is bit-identical to this module's
single-shot dense mode (pinned by tests/test_megakernel.py).
"""

from __future__ import annotations

MODES = ("scatter", "sort", "search", "dense")


def compact_mode() -> str:
    """The raw ``TTS_COMPACT`` knob: one of ``MODES`` or ``auto`` (the
    default — resolved per shape by ``resolve_compact_mode``).  Baked into
    compiled programs at trace time, so the engines carry it in
    ``routing_cache_token``."""
    import os

    mode = os.environ.get("TTS_COMPACT", "auto")
    if mode != "auto" and mode not in MODES:
        raise ValueError(
            "TTS_COMPACT must be 'auto', 'scatter', 'sort', 'search', or "
            f"'dense', got {mode!r}"
        )
    return mode


def _auto_compact(problem, M: int | None, n: int | None, platform: str) -> str:
    """The measured ``auto`` table.  Provisional entries come from the
    round-5 cycle arithmetic (docs/HW_VALIDATION.md) and are updated from
    BENCH artifacts — ``bench.py pick_compact`` measures all four modes on
    chip and records what ``auto`` would have picked:

      * N-Queens never prunes: survivors are dense, the scatter serializes
        on the full M*n grid, and the shift compaction's cost is flat in
        the survivor count -> ``dense`` (every backend: the CPU tiers only
        see test-sized chunks).
      * non-TPU backends: ``scatter`` is a fast gather-like op on
        CPU and sort LOSES ~2x (the original measured default)
        -> unchanged.
      * TPU, small grids (M*n <= 64k — the tuned PFSP M=1024 class): the
        log-shift passes are near-free and dodge the serialized scatter
        -> ``dense``.
      * TPU, no-prune PFSP regime (ub=inf warm-up): dense survivors
        -> ``dense``.
      * TPU, large pruned grids: survivors are sparse, so the
        S-proportional binary-search inverse does the least work
        -> ``search``.

    ``platform`` is the target device's platform
    (`pallas_kernels.platform`).
    """
    if getattr(problem, "name", None) == "nqueens":
        return "dense"
    if platform != "tpu":
        return "scatter"
    if M is not None and n is not None and M * n <= (1 << 16):
        return "dense"
    from ..problems.base import INF_BOUND

    if getattr(problem, "initial_ub", 0) >= INF_BOUND:
        return "dense"
    return "search"


def resolve_compact_mode(problem=None, M: int | None = None,
                         n: int | None = None, device=None) -> str:
    """The resolved compaction mode a resident program bakes in: the
    explicit knob when set, else the ``auto`` policy.  Every input that
    shapes the decision is already part of the engines' program cache keys
    (problem instance, M, device), so a knob flip or shape change rebuilds
    instead of reusing a stale path."""
    mode = compact_mode()
    if mode != "auto":
        return mode
    from .pallas_kernels import platform

    return _auto_compact(problem, M, n, platform(device))


def _shift_left(x, s: int):
    """x shifted s positions toward index 0 along axis 0, zero-filled at
    the tail (a static concat+slice — never a gather)."""
    import jax.numpy as jnp

    pad = jnp.zeros((s,) + x.shape[1:], x.dtype)
    return jnp.concatenate([x[s:], pad], axis=0)


def shift_compact(dist, payloads: tuple):
    """Stable left-packing by LSB-first binary shifts.

    ``dist``: (L,) int32 — how far each element must move toward index 0
    (0 for non-survivors, ``index - rank`` for survivors; between
    consecutive survivors dist grows by exactly their index gap, which is
    what makes the per-bit shifts collision-free — see module docstring).
    ``payloads``: arrays with leading axis L, moved in lockstep.

    Invariant per round b (ascending): every survivor sits at
    ``index - (dist mod 2^(b+1))`` and survivor positions stay strictly
    increasing; a vacated position that nothing lands on is marked dead
    (dist 0) so its stale copy can never move again and shadow a live
    element.  After the last round, ranks 0..count-1 hold the survivors in
    order; everything past them is garbage (dead by the pool contract).
    """
    import jax.numpy as jnp

    L = dist.shape[0]
    for b in range(max(1, int(L - 1).bit_length())):
        s = 1 << b
        if s >= L:
            break
        sh_d = _shift_left(dist, s)
        take = (sh_d & s) != 0
        moving = (dist & s) != 0
        payloads = tuple(
            jnp.where(take.reshape((-1,) + (1,) * (p.ndim - 1)),
                      _shift_left(p, s), p)
            for p in payloads
        )
        dist = jnp.where(take, sh_d - s, jnp.where(moving, 0, dist))
    return payloads


def survivor_ranks(keep):
    """Hierarchical survivor ranks of a (M, n) keep mask — lane scan +
    per-parent prefix, much cheaper than a flat M*n cumsum.  Returns
    ``(ranks, tree_inc)``: ranks (M, n) int32 in (parent, slot) order and
    the survivor count."""
    import jax.numpy as jnp

    cnt = jnp.sum(keep, axis=1, dtype=jnp.int32)  # (M,)
    offs = jnp.cumsum(cnt) - cnt  # exclusive prefix
    lane = jnp.cumsum(keep.astype(jnp.int32), axis=1) - keep
    return offs[:, None] + lane, offs[-1] + cnt[-1]


def compact_ids(keep, S: int, mode: str):
    """Stream-compaction indices of the surviving (parent, slot) pairs.

    keep: (M, n) bool.  Returns (ids, tree_inc): ids (S,) int32 such that
    ids[s] = flat index i*n+k of the s-th survivor in (parent, slot) order
    for s < tree_inc (the reference's child push order,
    `pfsp_gpu_chpl.chpl:276-298`); rows past tree_inc resolve arbitrarily
    but stay in-bounds.  ``mode`` selects the rank inversion (module
    docstring); all modes return identical ids in identical order
    (pinned by tests/test_compaction.py and CI's per-mode tier-1 runs).
    """
    import jax.numpy as jnp

    M, n = keep.shape
    Mn = M * n
    ranks, tree_inc = survivor_ranks(keep)
    flat = keep.reshape(Mn)
    if mode == "dense":
        flat_idx = jnp.arange(Mn, dtype=jnp.int32)
        dist = jnp.where(flat, flat_idx - ranks.reshape(Mn), 0)
        (ids,) = shift_compact(dist, (flat_idx,))
        return ids[:S], tree_inc
    if mode == "sort":
        key = jnp.where(flat, ranks.reshape(Mn), jnp.int32(Mn))
        ids = jnp.argsort(key, stable=True)[:S].astype(jnp.int32)
        return ids, tree_inc
    if mode == "search":
        # Binary-search inverse: for output rank s, its parent is the last
        # p with offs[p] <= s (zero-count parents share the next parent's
        # offs, so side='right' skips them), and its slot is the lane
        # whose exclusive cumsum equals the within-parent rank. log2(M)
        # vectorized gather rounds + one (S, n) lane pass — no scatter, no
        # sort; the clips keep dead rows in-bounds.
        cnt = jnp.sum(keep, axis=1, dtype=jnp.int32)
        offs = jnp.cumsum(cnt) - cnt
        lane = ranks - offs[:, None]
        pos = jnp.arange(S, dtype=jnp.int32)
        parent = jnp.clip(
            jnp.searchsorted(offs, pos, side="right").astype(jnp.int32) - 1,
            0, M - 1,
        )
        r = pos - offs[parent]  # within-parent rank
        krows = keep[parent]  # (S, n)
        lane_s = lane[parent]  # (S, n) exclusive lane cumsum
        slot = jnp.argmax((lane_s == r[:, None]) & krows, axis=1)
        ids = (parent * n + slot).astype(jnp.int32)
        return ids, tree_inc
    if mode != "scatter":
        raise ValueError(f"unknown compaction mode {mode!r}")
    flat_idx = jnp.arange(Mn, dtype=jnp.int32)
    # Non-survivors get distinct out-of-bounds destinations so the scatter
    # is genuinely unique-indexed (mode="drop" discards them).
    dst = jnp.where(flat, ranks.reshape(Mn), S + flat_idx)
    ids = (
        jnp.zeros((S,), jnp.int32)
        .at[dst]
        .set(flat_idx, mode="drop", unique_indices=True)
    )
    return ids, tree_inc


# -- compiled-program contracts (`tts check`, analysis/contracts.py) --------
# The survivor-path performance claims, declared next to the code that
# makes them and verified over the whole knob matrix by
# analysis/program_audit.py (these used to live as one-off jaxpr pins in
# tests/test_compaction.py, each guarding a single knob combination).

from ..analysis.contracts import contract, prim_eqns  # noqa: E402


@contract(
    "dense-step-no-sort-scatter",
    claim="a resident step whose resolved survivor path is `dense` adds "
          "ZERO sort and ZERO scatter ops beyond the bound evaluator's "
          "own — compaction, fused push, and the overflow fallback branch "
          "are all shift/select-structured (searchsorted has no primitive "
          "of its own; this plus the gather ban in dense-ids-shift-only "
          "covers every lowering it could take).  The budget is the BARE "
          "evaluator's histogram (lb2's one-hot free-flag scatter is the "
          "evaluator's business), plus the armed phase profiler's "
          "accumulation into its own (NSLOTS+1,) clock block — anything "
          "else is survivor-path structure and is banned",
    artifact="resident-step",
)
def _contract_dense_step(art, cell):
    if art.prog.compact != "dense":
        return []
    from ..obs.phases import NSLOTS as _PH_NSLOTS

    out = []
    allowed = {
        n: c for n, c in art.eval_counts.items()
        if n == "sort" or n.startswith("scatter")
    }
    seen: dict[str, int] = {}
    armed = cell is not None and getattr(cell, "phaseprof", "0") == "1"
    for name, eqn in art.prims:
        if name != "sort" and not name.startswith("scatter"):
            continue
        sizes = [int(v.aval.size) for v in eqn.outvars]
        if armed and all(s <= _PH_NSLOTS + 1 for s in sizes):
            continue  # the sanctioned phase-clock block accumulation
        seen[name] = seen.get(name, 0) + 1
    for name, cnt in sorted(seen.items()):
        if cnt > allowed.get(name, 0):
            out.append(
                f"dense step contains {cnt}x {name} but the bare evaluator "
                f"accounts for {allowed.get(name, 0)} — the survivor path "
                "re-introduced a banned op"
            )
    return out


@contract(
    "dense-ids-shift-only",
    claim="the dense rank inversion (`compact_ids` mode='dense') is pure "
          "shifts + selects: no sort, no scatter, and not even a gather "
          "(the fused write performs the cycle's single gather)",
    artifact="compact-ids",
)
def _contract_dense_ids(art, cell):
    if art["mode"] != "dense":
        return []
    names = {n for n, _ in prim_eqns(art["jaxpr"])}
    bad = sorted(
        n for n in names
        if n in ("sort", "gather") or n.startswith("scatter")
    )
    return [f"dense compact_ids contains banned ops {bad}"] if bad else []


@contract(
    "scatter-ids-unique",
    claim="the scatter rank inversion's destination scatter is genuinely "
          "unique-indexed (XLA owes it no conflict resolution — the mode's "
          "whole cost model rests on that)",
    artifact="compact-ids",
)
def _contract_scatter_ids(art, cell):
    if art["mode"] != "scatter":
        return []
    scatters = [
        (n, e) for n, e in prim_eqns(art["jaxpr"]) if n.startswith("scatter")
    ]
    if not scatters:
        return ["scatter mode lowered without any scatter op"]
    bad = [
        n for n, e in scatters if not e.params.get("unique_indices", False)
    ]
    return (
        [f"non-unique-indexed scatter(s) in scatter compact_ids: {bad}"]
        if bad else []
    )


@contract(
    "compact-auto-identity",
    claim="TTS_COMPACT=auto bakes in a byte-identical program to the "
          "explicitly spelled mode it resolves to — the policy layer adds "
          "zero behavior of its own",
    artifact="variants",
)
def _contract_auto_identity(art, cell):
    explicit = [
        lb for lb in art.variants
        if lb.startswith("compact-") and lb != "compact-auto"
    ]
    if "compact-auto" not in art.variants or not explicit:
        return []  # variant set traced without the compact labels
    out = []
    for lb in explicit:
        if art.text("compact-auto") != art.text(lb):
            out.append(
                f"auto-resolved program differs from explicit {lb[8:]!r}"
            )
    return out
