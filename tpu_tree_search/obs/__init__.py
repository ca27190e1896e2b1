"""``tpu_tree_search.obs`` — guard-safe telemetry.

Three legs (see docs/OBSERVABILITY.md):

  * ``counters`` — on-device cycle counters: a fixed-shape int32 block in
    the resident loop carry, accumulated inside the jitted
    ``lax.while_loop`` and harvested at the existing K-cycle dispatch
    boundaries. Compiled out entirely (byte-identical jaxpr) when off.
  * ``events`` — host event tracing: thread-local buffers + merge, wired
    through every runtime (dispatches, steals, exchange rounds, incumbent
    improvements, checkpoint cuts, phase transitions).
  * ``export`` / ``report`` — Chrome-trace JSON for Perfetto, metrics
    JSON lines for scraping, and the ``tts report`` summarizer (steal
    efficiency, idle fraction per worker, cycle-rate timeline).

Closed-loop legs (same doc):

  * ``flightrec`` — crash-safe flight recorder: snapshot ring +
    last-dispatch registry, dumped as a valid trace on SIGTERM/SIGALRM/
    exception/watchdog stall (``TTS_FLIGHTREC``).
  * ``live`` — ``--obs-serve`` localhost HTTP/SSE snapshot streaming and
    the ``tts watch`` client.
  * ``costmodel`` — measured per-link latency+bandwidth profiles
    (``COSTMODEL.json``) that AdaptiveK and the mesh/dist periods resolve
    from (``TTS_COSTMODEL``).
  * ``phases`` — on-device per-phase cycle clocks (``TTS_PHASEPROF=1`` /
    ``tts profile``): a barrier-fenced clock block in the resident loop
    carry decomposing the chunk cycle into pop/eval/compact/push/
    overflow (+ mesh balance). A separate cache-keyed program variant —
    never the headline program.

Knobs: ``TTS_OBS=1`` (everything), ``TTS_OBS=host`` (host events only —
device programs untouched), off by default with zero hot-loop cost.
``--trace out.json`` / ``--metrics-file m.jsonl`` on every CLI tier.
"""

from __future__ import annotations

import os
from contextlib import contextmanager

from . import costmodel, counters, events, export, flightrec, live, phases, report

__all__ = [
    "capture",
    "costmodel",
    "counters",
    "events",
    "export",
    "flightrec",
    "live",
    "obs_enabled",
    "phases",
    "report",
]


def obs_enabled() -> bool:
    return events.enabled()


class Capture:
    """Result handle of a ``capture()`` block."""

    def __init__(self):
        self.events: list[dict] = []

    def explored_totals(self) -> tuple[int, int]:
        """(tree, sol) summed over the engines' per-phase ``explored``
        counter samples — the obs-side mirror of
        ``SearchResult.explored_tree/explored_sol`` (tests pin exact
        parity)."""
        tree = sol = 0
        for e in self.events:
            if e.get("name") == "explored":
                a = e.get("args") or {}
                tree += a.get("tree", 0)
                sol += a.get("sol", 0)
        return tree, sol

    def summary(self) -> dict:
        return report.summarize(self.events)


@contextmanager
def capture(trace_path: str | None = None, metrics_path: str | None = None,
            mode: str = "1"):
    """Run-scoped telemetry capture: pins ``TTS_OBS`` to ``mode``
    (``"1"`` full / ``"host"`` events-only), clears the recorder, and on
    exit drains the events into the yielded ``Capture`` (optionally
    writing the trace / metrics files). Restores the previous ``TTS_OBS``
    so a caller's explicit setting is never clobbered.

    Device-counter note: ``mode="1"`` takes effect for programs *built*
    inside the block — the engines key their program caches on the obs
    state, so a cached obs-off program is rebuilt, not reused stale.
    """
    prev = os.environ.get("TTS_OBS")
    os.environ["TTS_OBS"] = mode
    events.reset()
    cap = Capture()
    try:
        yield cap
    finally:
        cap.events = events.drain()
        if prev is None:
            os.environ.pop("TTS_OBS", None)
        else:
            os.environ["TTS_OBS"] = prev
        if trace_path is not None:
            export.write_chrome_trace(cap.events, trace_path)
        if metrics_path is not None:
            export.write_metrics_jsonl(cap.events, metrics_path)
