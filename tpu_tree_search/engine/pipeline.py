"""Async pipelined dispatch + adaptive-K control for the host loops.

Every host loop in the repo used to be fully synchronous: dispatch the
compiled step, block reading its lagged scalars, decide, dispatch again —
so the device sat idle for a full host round trip between every K-cycle
block, exactly the
serialization the reference pays per chunk (`pfsp_gpu_chpl.chpl:373-396`).
JAX dispatch is asynchronous: enqueueing step k+1 *before* reading step
k's scalars keeps the device queue non-empty across the host round trip
(the transfer/compute-overlap playbook of arXiv 1904.06825 and the batch
host-loop pipelining of arXiv 2002.07062).

Speculation here is **exact**, not approximate: the compiled step's
while-cond (``size >= m``) makes a dispatch on a terminated or stalled
pool a zero-cycle no-op — the carry passes through untouched and every
counter increment is zero — so a speculatively enqueued step after
termination changes nothing (pinned by tests/test_pipeline.py's
no-op-dispatch invariant test).  The host reads only the small scalar
outputs of each dispatch (tree/sol/cycles/size/best and the obs counter
block); the donated pool carry is never forced — it flows device-side
from one dispatch's output into the next dispatch's input.

Knobs
-----

``TTS_PIPELINE``: dispatch queue depth. ``0``/``1`` = synchronous (one
dispatch in flight — the pre-pipeline behavior), ``2``/``3`` = that many
speculative dispatches in flight, ``auto`` (default) = 2.  Exactness does
not depend on the depth; bit-parity across depths is a test axis
(tests/test_cross_tier_fuzz.py).

``TTS_K``: K-cycles-per-dispatch schedule. An integer pins K; ``auto``
enables the :class:`AdaptiveK` controller — measure the host period per
dispatch from the obs-span clock and resize K along a **geometric
ladder** toward a target period, so the program cache (which keys on K)
sees at most ``len(ladder)`` distinct compilations and steady state stays
recompile-free (each rung's program compiles once, on a sanctioned warm
dispatch; re-selecting a rung is a cache hit).  The ladder cap is the
caller's K (the tier default when the CLI passes ``--K auto``); the
mesh/dist tiers hand the controller a tighter target band so K never
grows past their steal/exchange responsiveness.
"""

from __future__ import annotations

import os
from collections import deque

#: Hard cap on the in-flight dispatch queue: beyond 3 the lagged scalars
#: stop informing anything (termination detection lags `depth` dispatches,
#: each a no-op after the fact but still enqueue latency at shutdown).
MAX_DEPTH = 3

#: Default host-period target band (seconds) for ``TTS_K=auto`` on the
#: single-device resident tier: dispatches shorter than the band waste a
#: growing fraction of wall time on host round trips; longer ones delay
#: termination detection and checkpoint cadence.
RESIDENT_TARGET = (0.100, 0.250)

#: Tighter band for the mesh/dist tiers: incumbent folds, diffusion
#: balancing, and the inter-host exchange all happen at dispatch
#: boundaries, so K is bounded by steal/exchange responsiveness, not just
#: dispatch overhead.
MESH_TARGET = (0.050, 0.150)


def resolve_target_band(
    tier: str,
    default: tuple[float, float],
    problem=None,
    topology: str = "",
) -> tuple[tuple[float, float], str | None]:
    """The AdaptiveK target band for one run: ``(band, source)``.

    With ``TTS_COSTMODEL=<profile>`` set and a usable entry in it, the
    band derives from the profile's MEASURED per-dispatch latency fit
    (obs/costmodel.py — the arXiv:1904.06825 latency+bandwidth model);
    otherwise ``default`` (the documented fixed band) with source None.
    Because the mesh/dist tiers fold incumbents, run diffusion rounds,
    and exchange at dispatch boundaries, this band IS their steal and
    exchange period — resolving it from the profile paces those too.

    A band only moves K along the existing geometric ladder: search
    results stay bit-identical to the fixed-band fallback by construction
    (tests/test_costmodel.py pins it).
    """
    path_env = os.environ.get("TTS_COSTMODEL", "") or ""
    if path_env in ("", "0"):
        return default, None
    from ..obs import costmodel as cm

    profile = cm.load(path_env)
    if not profile:
        return default, None
    # Profiles are keyed by the platform of the default device.
    from ..ops.pallas_kernels import platform

    hit = cm.lookup(profile, platform(), topology, cm.shape_class(problem))
    if hit is None:
        return default, None
    key, entry = hit
    band = cm.resolve_band(entry, tier)
    if band is None:
        return default, None
    return band, key


def pipeline_mode() -> str:
    """The raw ``TTS_PIPELINE`` knob (``auto`` default)."""
    return os.environ.get("TTS_PIPELINE", "auto") or "auto"


def resolve_pipeline_depth(knob: str | int | None = None) -> int:
    """Dispatch queue depth: 1 = synchronous, >= 2 = pipelined.

    ``0`` and ``1`` both mean synchronous (``0`` is the natural "off"
    spelling; a queue always holds at least the dispatch being read).
    ``auto`` resolves to 2 — speculation is exact at any depth, and one
    speculative dispatch already hides a full host round trip.
    """
    if knob is None:
        knob = pipeline_mode()
    if knob == "auto":
        return 2
    try:
        depth = int(knob)
    except (TypeError, ValueError):
        raise ValueError(
            f"TTS_PIPELINE must be 'auto' or an integer 0..{MAX_DEPTH}, "
            f"got {knob!r}"
        ) from None
    if depth < 0 or depth > MAX_DEPTH:
        raise ValueError(
            f"TTS_PIPELINE must be in 0..{MAX_DEPTH} (got {depth}); "
            "0/1 = synchronous, 2/3 = speculative depth"
        )
    return max(1, depth)


def resolve_k(K: int | str, default_max: int) -> tuple[bool, int]:
    """Resolve the K schedule for one search: ``(auto, k)``.

    ``auto=True``: adaptive ladder capped at ``k``; ``auto=False``: fixed
    ``k``.  Precedence: the ``TTS_K`` env knob (``auto`` or an integer)
    overrides the engine parameter — so a test matrix can pin the whole
    suite without threading a kwarg through every tier — and a parameter
    of ``"auto"`` (the CLI's ``--K auto``) requests adaptation capped at
    the tier default.
    """
    knob = (os.environ.get("TTS_K") or "").strip()
    if knob:
        if knob == "auto":
            kmax = default_max if isinstance(K, str) else int(K)
            return True, max(1, kmax)
        try:
            return False, max(1, int(knob))
        except ValueError:
            raise ValueError(
                f"TTS_K must be 'auto' or a positive integer, got {knob!r}"
            ) from None
    if isinstance(K, str):
        if K != "auto":
            raise ValueError(f"K must be an integer or 'auto', got {K!r}")
        return True, max(1, default_max)
    return False, max(1, int(K))


class AdaptiveK:
    """Geometric-ladder K controller (``TTS_K=auto``).

    Rungs are ``k_max, k_max/4, k_max/16, ...`` down to 1 (ascending
    internally); the controller starts on the lowest rung (fast first
    feedback) and, fed one ``observe(period_s, cycles)`` per dispatch,
    climbs one rung when a full-K dispatch at the *next* rung is still
    predicted inside the target band, and drops rungs when the measured
    period overshoots the band.  Ladder-only K values mean the engines'
    program caches see a bounded set of compilations — the zero
    steady-state recompiles guarantee rides the caches' existing K key.
    """

    def __init__(self, k_max: int, target: tuple[float, float] | None = None,
                 factor: int = 4):
        k_max = max(1, int(k_max))
        rungs = [k_max]
        while rungs[-1] > 1 and len(rungs) < 8:
            rungs.append(max(1, rungs[-1] // factor))
        self.ladder: tuple[int, ...] = tuple(rungs[::-1])
        self.idx = 0
        self.lo, self.hi = target if target is not None else RESIDENT_TARGET
        self.factor = factor
        self.resizes = 0

    @property
    def K(self) -> int:
        return self.ladder[self.idx]

    def observe(self, period_s: float, cycles: int) -> bool:
        """Feed one dispatch's host period (scalars-ready to scalars-ready)
        and its device cycle count; returns True when K should change (the
        caller rebuilds its program from the cache at the new ``.K``).

        Dispatches can end early (pool drained below m mid-block), so the
        decision uses the *per-cycle* rate scaled to a full-K block, not
        the raw period.
        """
        if cycles <= 0 or period_s <= 0.0:
            return False
        per_cycle = period_s / cycles
        est = per_cycle * self.K
        if (self.idx + 1 < len(self.ladder)
                and est * self.factor <= self.hi):
            # The next rung's predicted full block still fits the band —
            # climbing can never overshoot, so no up/down oscillation.
            self.idx += 1
            self.resizes += 1
            return True
        if est > self.hi and self.idx > 0:
            while self.idx > 0 and per_cycle * self.ladder[self.idx] > self.hi:
                self.idx -= 1
            self.resizes += 1
            return True
        return False


class DispatchQueue:
    """Bounded FIFO of in-flight speculative dispatches.

    The engines own the dispatch call (it runs under their steady-state
    guard) and the scalar read; this class owns only the queue mechanics
    so the three resident host loops cannot drift on them.  Entries are
    ``(out, enqueue_us)`` — the dispatch's raw output tuple (whose pool
    leaves may already be donated into a later dispatch; only the scalar
    leaves may be read) and its enqueue timestamp for the obs span.
    """

    def __init__(self, depth: int):
        self.depth = max(1, int(depth))
        self._q: deque = deque()

    def __len__(self) -> int:
        return len(self._q)

    @property
    def full(self) -> bool:
        return len(self._q) >= self.depth

    def push(self, out, enqueue_us: float) -> None:
        if self.full:
            raise RuntimeError(
                f"dispatch queue overfull (depth {self.depth})"
            )
        self._q.append((out, enqueue_us))

    def pop(self):
        """Oldest in-flight dispatch ``(out, enqueue_us)``."""
        return self._q.popleft()

    def drain(self):
        """Yield every remaining entry, oldest first, emptying the queue.
        Engines drain (accumulating the scalar counts — zeros for no-op
        speculative dispatches, real work otherwise) before any action
        that must see coherent totals: termination, checkpoint cuts,
        K resizes, donation downloads, and the capacity-stall fallback."""
        while self._q:
            yield self._q.popleft()


# -- compiled-program contracts (`tts check`, analysis/contracts.py) --------

from ..analysis.contracts import contract


@contract(
    "pipeline-knob-inert",
    claim="TTS_PIPELINE never reaches the compiled program: depth-0 and "
          "depth-2 builds are byte-identical — speculation is host-side "
          "queueing only, and its exactness rests on the no-op-dispatch "
          "invariant of the while-cond, not on a program variant",
    artifact="variants",
)
def _contract_pipeline_inert(art, cell):
    if not art.has("off", "pipe0", "pipe2"):
        return []
    if art.text("off") == art.text("pipe0") == art.text("pipe2"):
        return []
    return ["TTS_PIPELINE leaked into the compiled step (depth-dependent "
            "program structure breaks the exact-speculation argument)"]
