"""On-device per-phase cycle-clock block (``TTS_PHASEPROF=1``).

Leg 1 (``counters.py``) counts WORK per dispatch; this leg measures TIME
per phase *inside* the resident ``lax.while_loop`` — which of
pop / bound-evaluation / compaction / fused-push / overflow-fallback /
mesh-balance actually dominates a chunk cycle.  That decomposition is the
gate on ROADMAP item 3 (the one-kernel resident cycle): `bench.py`'s
``eval_cycle_ms`` subtraction prices the evaluator against everything
else at dispatch granularity, but cannot split the remaining ~85% of the
cycle into its phases (BASELINE r5).  The measured per-link performance
models this repo leans on (arXiv:1904.06825; the PFSP scale-out study
arXiv:2012.09511) are built from exactly this kind of phase-attributed
timing.

Design — the counter-block pattern with a clock instead of an adder:

  * the loop carry gains one fixed-shape ``(NSLOTS + 1,)`` uint32 block:
    per-phase accumulated nanoseconds plus the last clock reading
    (``TPREV``), reset per dispatch and harvested only at the existing
    K-cycle dispatch boundaries (no new transfers; ``TTS_GUARD=1`` green);
  * each phase boundary routes the phase's outputs through
    ``lax.optimization_barrier`` together with the previous reading, then
    reads the clock with a data dependence on the barrier output — XLA
    cannot hoist the read before the phase or sink the phase past it
    (caveats below);
  * phase deltas telescope: within a cycle the same readings bound
    adjacent phases, so ``pop + eval + compact + push + overflow ==
    total`` holds EXACTLY on the harvested block (tests pin it) — the
    unattributed remainder (while-loop cond, carry plumbing, inter-round
    gaps) lands in ``loop``/``balance``, outside ``total``.

Clock source: jax exposes no portable on-device cycle-counter primitive
(this jaxlib's Mosaic TPU dialect has no timestamp op either), so the
clock is a ``jax.pure_callback`` reading ``time.perf_counter_ns()`` on
the host — truncated to uint32 so deltas wrap correctly (one phase
segment must stay under ~4.29 s; the K clamp keeps dispatches far under
that on every measured config).  On CPU the callback is nanoseconds-cheap;
on TPU each read is a host round trip, which is exactly why the armed
program is a **separate cache-keyed variant** (``TTS_PHASEPROF`` rides
the program caches next to ``TTS_OBS``): it is a profiling build for
`tts profile`, never the headline-measurement program.  When a device
cycle-counter op lands in jax, ``read_clock`` is the single seam to swap.

Barrier-placement caveats (docs/OBSERVABILITY.md leg 7): the barrier
fences only the values passed through it, so ops that feed nothing at the
next boundary can still be scheduled across it; XLA may also fuse less
across barriers, perturbing the very schedule being measured.  Phase
shares are therefore attribution estimates; the telescoped ``total`` and
the armed-vs-off bit-identity of search results are the hard guarantees.

Zero-cost disabled path: enablement is decided at program build time
(``phase_profiling_enabled()``); when off, carry/body/jaxpr are
byte-identical to a build without this module (tests/test_phases.py).
"""

from __future__ import annotations

import functools
import os
import time

import numpy as np

#: Phase slots. The first five partition the chunk cycle exactly
#: (``total`` is their telescoped sum); ``balance`` (mesh diffusion +
#: incumbent fold, per round) and ``loop`` (while-cond + carry plumbing
#: between cycles) sit outside the cycle.
SLOTS = (
    "pop",       # chunk pop/select: dynamic_slice of the pool back
    "eval",      # bound evaluation (lb1/lb2/N-Queens labels)
    "compact",   # survivor ranks + rank inversion (ops/compaction.py)
    "push",      # fused prune+push fast path (fits == True cycles)
    "overflow",  # overflow-branch push (fits == False cycles)
    "balance",   # mesh tiers: pmin fold + ppermute diffusion, per round
    "loop",      # inter-cycle remainder: cond, carry, loop entry/exit
    "total",     # per-cycle end - start (== pop+eval+compact+push+overflow)
)
NSLOTS = len(SLOTS)

#: SLOTS index lookup, e.g. ``IDX["compact"]``.
IDX = {name: i for i, name in enumerate(SLOTS)}

#: Block index of the carried last clock reading (not a phase slot).
TPREV = NSLOTS

#: The slots that partition the chunk cycle (their sum == ``total``).
CYCLE_SLOTS = ("pop", "eval", "compact", "push", "overflow")


def phase_profiling_enabled() -> bool:
    """True only for ``TTS_PHASEPROF=1`` — the armed program variant."""
    return os.environ.get("TTS_PHASEPROF", "0") == "1"


def clock_source() -> str:
    """The active clock implementation. Only ``"callback"`` exists today
    (see module docstring); a future hardware cycle-counter op slots in
    here without touching any call site."""
    return "callback"


def _read_ns(tag, *deps):
    # Host side of the clock: deps are ignored (they exist to order the
    # read after the fenced phase and to defeat CSE between boundaries).
    return np.uint32(time.perf_counter_ns() & 0xFFFFFFFF)


# tts-lint: traced (called from the resident while-loop body when armed)
def read_clock(dep, tag: str):
    """One uint32 clock reading, data-dependent on ``dep``. ``tag`` is
    static and baked into the callback identity (a distinct partial per
    boundary), so XLA cannot dedup two boundaries into one read."""
    import jax
    import jax.numpy as jnp

    return jax.pure_callback(
        functools.partial(_read_ns, tag),
        jax.ShapeDtypeStruct((), jnp.uint32), dep,
    )


def init_block():
    """Fresh all-zeros phase block (``(NSLOTS + 1,)`` uint32)."""
    import jax.numpy as jnp

    return jnp.zeros((NSLOTS + 1,), jnp.uint32)


# tts-lint: traced (runs inside the jitted step, before the while loop)
def seed_block(dep=None):
    """A fresh block whose ``TPREV`` holds a pre-loop clock reading — the
    base of the first cycle's ``loop`` delta. ``dep`` (any traced value)
    orders the read after the dispatch's inputs are live."""
    import jax.numpy as jnp

    block = init_block()
    t0 = read_clock(jnp.uint32(0) if dep is None else dep, "seed")
    return block.at[TPREV].set(t0)


# tts-lint: traced (called from the resident while-loop body when armed)
def boundary(block, slot, *vals, tag: str | None = None):
    """Close one phase: fence ``vals`` (THE values the next phase
    consumes — pass them through and use the returned versions, or the
    barrier fences nothing), read the clock, charge ``now - TPREV`` to
    ``slot``, and advance ``TPREV``.

    ``slot`` is a static name or a traced int32 index (the push/overflow
    branch charges by predicate); a traced slot needs a static ``tag``
    for the callback identity. Returns ``(block, fenced_vals_tuple)``.
    """
    import jax.numpy as jnp
    from jax import lax

    fenced = lax.optimization_barrier((block[TPREV],) + tuple(vals))
    tprev, out = fenced[0], tuple(fenced[1:])
    t = read_clock(tprev, tag if tag is not None else slot)
    dt = t - tprev  # uint32 arithmetic: wrap-correct for segments < 2^32 ns
    idx = IDX[slot] if isinstance(slot, str) else slot
    block = block.at[idx].add(dt).at[TPREV].set(t)
    return block, out


# tts-lint: traced (called from the resident while-loop body when armed)
def close_total(block, t_start):
    """Charge the whole-cycle delta (last reading - ``t_start``, the
    reading ``boundary`` stored when the cycle began) to ``total``."""
    return block.at[IDX["total"]].add(block[TPREV] - t_start)


def merge_host(total: dict | None, block) -> dict:
    """Host-side accumulation of one harvested block (np array, possibly
    (D, NSLOTS+1) for the mesh tiers) into running per-phase nanosecond
    totals (Python ints — no wraparound across dispatches). Multi-shard
    blocks sum: the totals are aggregate device-time per phase, so the
    SHARES are D-invariant even though the sums exceed wall time."""
    arr = np.asarray(block, dtype=np.int64).reshape(-1, NSLOTS + 1)
    out = dict(total) if total else {name: 0 for name in SLOTS}
    for i, name in enumerate(SLOTS):
        out[name] = out.get(name, 0) + int(arr[:, i].sum())
    return out


def as_args(block) -> dict:
    """A harvested block as a {slot: ns} dict for counter events and
    metrics lines."""
    return merge_host(None, block)


def shares(totals: dict) -> dict:
    """Per-phase share of the measured cycle time: each CYCLE slot over
    ``total`` (0.0..1.0); ``balance``/``loop`` are reported relative to
    ``total`` too (they can exceed 1.0 — they are outside the cycle)."""
    t = max(1, int(totals.get("total", 0)))
    return {
        name: totals.get(name, 0) / t
        for name in SLOTS if name != "total"
    }


def dominant_phase(totals: dict | None) -> tuple[str, float] | None:
    """(name, share) of the largest in-cycle phase — the "next structural
    cost" line of ``tts report``/``tts profile``. None without data."""
    if not totals or not totals.get("total"):
        return None
    name = max(CYCLE_SLOTS, key=lambda s: totals.get(s, 0))
    return name, totals.get(name, 0) / max(1, int(totals["total"]))


def decomp(totals: dict) -> dict:
    """The decomposition record `tts report`/`tts profile` render:
    raw ns, cycle shares, and the dominant in-cycle phase."""
    dom = dominant_phase(totals)
    return {
        "ns": {k: int(v) for k, v in totals.items()},
        "shares": {k: round(v, 4) for k, v in shares(totals).items()},
        "dominant": dom[0] if dom else None,
        "dominant_share": round(dom[1], 4) if dom else None,
    }


# -- compiled-program contracts (`tts check`, analysis/contracts.py) --------

from ..analysis.contracts import contract


@contract(
    "phaseprof-off-identity",
    claim="TTS_PHASEPROF unset and =0 build byte-identical resident step "
          "jaxprs — the phase-clock block is compiled out when off, never "
          "branched (same contract as the obs counter block)",
    artifact="variants",
)
def _contract_phaseprof_off_identity(art, cell):
    if not art.has("off", "phase0"):
        return []
    out = []
    if art.text("off") != art.text("phase0"):
        out.append("TTS_PHASEPROF=0 build differs from the unset build "
                   "(clock reads leaked into the off path)")
    if art.outvars("phase0") != art.outvars("off"):
        out.append("TTS_PHASEPROF=0 build changed the carry width")
    return out


@contract(
    "phaseprof-block-leaf",
    claim="the armed phase profiler adds exactly ONE output leaf (the "
          "phase-clock block), two when device counters ride along "
          "(order: ..., ctr, ph) — and genuinely changes the program",
    artifact="variants",
)
def _contract_phaseprof_block(art, cell):
    if not art.has("off", "phase1", "phase1-obs1"):
        return []
    out = []
    base = art.outvars("off")
    if art.outvars("phase1") != base + 1:
        out.append(
            f"armed phase build carries {art.outvars('phase1')} output "
            f"leaves (expected {base + 1})"
        )
    if art.outvars("phase1-obs1") != base + 2:
        out.append(
            f"armed phase+obs build carries {art.outvars('phase1-obs1')} "
            f"output leaves (expected {base + 2})"
        )
    if art.text("phase1") == art.text("off"):
        out.append("armed phase build is byte-identical to off (the clock "
                   "block is silently gone)")
    return out
