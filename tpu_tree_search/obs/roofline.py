"""Memory-roofline audit for the resident cycle (ROADMAP item 3).

Every phase of the chunk cycle (pop/eval/compact/push/overflow,
obs/phases.py) is memory-bound at the pool shapes this engine runs — the
bound math is a handful of small matmuls per node while every node's row
crosses HBM at least twice per cycle.  So the honest performance question
per phase is not FLOP/s but "what fraction of the memory-bound peak does
the measured time reach":

    pct_of_peak = (analytic byte FLOOR per cycle * cycles)
                  / (peak HBM bytes/s * measured phase seconds)

The three inputs come from machinery that already exists:

  * measured per-phase ns — the `tts profile` phase-clock splits
    (TTS_PHASEPROF=1, obs/phases.py), summed over the run;
  * cycles — the `dispatch` spans' ``cycles`` args (obs/events.py), or
    the host loop's own accumulation for the in-process SearchResult;
  * peak bytes/s — resolved in order from ``TTS_HBM_GBPS`` (explicit
    override), a measured COSTMODEL.json ``hbm`` link fit
    (``links.hbm.per_sec``, bytes/s), then the published per-chip peak
    of ``PEAKS`` below, keyed by ``device_kind`` — none for a device the
    table does not know, whose audit then has no percentage.

The byte counts are analytic FLOORS — the bytes the phase MUST move
(pool rows in, survivor rows out), not what XLA happens to materialize —
so ``pct_of_peak`` reads as "how close to unavoidable"; a low percentage
names a phase whose intermediates are round-tripping (the megakernel's
whole reason to exist), and the streamed megakernel's win shows up as the
fused ``eval`` row approaching its floor.  Percentages are per measured
run; the model never feeds back into routing.

Surfaces: ``tts report --roofline`` (table per trace, via the
``roofline_meta`` event the resident loop emits), ``SearchResult.
roofline`` (armed whenever the phase profiler ran), and the bench
megakernel A/B records (``roofline_mem`` — the bench's FLOP-based
``roofline`` MFU field is a different axis and keeps its name).
"""

from __future__ import annotations

import os

from . import phases as obs_phases

#: Published peaks of one chip, keyed by ``device_kind`` as JAX reports
#: it (``jax.devices()[0].device_kind``).  A kind this table does not know
#: has no peak: its audits and MFU fields say "not measured", never another
#: device's figure.
PEAKS = {
    "TPU v5 lite": {
        "hbm_gbps": 819.0,
        "bf16_tflops": 197.0,
        "source": "Google Cloud documentation, \"TPU v5e\" (per chip)",
    },
}

NOT_MEASURED = "not measured"


#: The cycle phases the audit rows cover (obs/phases.py CYCLE_SLOTS).
PHASES = obs_phases.CYCLE_SLOTS


def hbm_gbps_override() -> float | None:
    """The ``TTS_HBM_GBPS`` knob: explicit peak-bandwidth override for the
    roofline denominator (GB/s)."""
    raw = os.environ.get("TTS_HBM_GBPS")
    if raw is None or raw == "":
        return None
    v = float(raw)
    if v <= 0:
        raise ValueError(f"TTS_HBM_GBPS must be a positive GB/s figure, "
                         f"got {raw!r}")
    return v


def hbm_entry(profile: dict, backend: str) -> dict | None:
    """First profile entry (sorted for determinism) on ``backend`` that
    carries a measured ``hbm`` link fit — the bandwidth is a chip
    property, not a problem-shape one, so any entry qualifies."""
    for key in sorted(profile):
        e = profile[key]
        if not isinstance(e, dict) or e.get("backend") != backend:
            continue
        hbm = (e.get("links") or {}).get("hbm")
        if isinstance(hbm, dict) and hbm.get("per_sec"):
            return e
    return None


def peak_bytes_per_sec(device_kind: str | None, entry: dict | None = None
                       ) -> tuple[float | None, str]:
    """Resolve the roofline denominator: (bytes/s, source) — env override,
    then a measured COSTMODEL ``hbm`` link, then the published peak of
    ``device_kind``.  ``(None, "not measured")`` for a kind with none."""
    env = hbm_gbps_override()
    if env is not None:
        return env * 1e9, "env:TTS_HBM_GBPS"
    if entry is not None:
        hbm = (entry.get("links") or {}).get("hbm")
        if isinstance(hbm, dict) and hbm.get("per_sec"):
            return float(hbm["per_sec"]), "costmodel:hbm"
    peak = PEAKS.get(device_kind)
    if peak is None:
        return None, NOT_MEASURED
    return peak["hbm_gbps"] * 1e9, f"published:{device_kind}"


def phase_byte_floors(*, M: int, n: int, S: int, itemsize: int,
                      aux_itemsize: int = 4, megakernel: bool = False
                      ) -> dict[str, int]:
    """Analytic HBM byte floor per CYCLE for each phase — the bytes the
    phase must move at pool dtype, not what XLA materializes.

    Off path: ``pop`` slices the (M, node) chunk out of the pool; ``eval``
    reads the chunk and writes the (M*n) int32 bound/keep plane;
    ``compact`` reads the keep plane and writes the (S,) survivor ids;
    ``push`` gathers S survivor rows and writes them back (2x S rows at
    node width).  ``overflow`` is the fits==False branch — it moves the
    whole M*n reservation, but only on overflow cycles, which the floor
    model cannot apportion from totals alone; it is floored at 0 and its
    row reports measured time with no percentage.

    Megakernel path: the profiler charges the whole fused cycle into
    ``eval`` (engine/resident.py), whose floor is then the streamed pool
    tiles in + the compacted (M*n) int32 rows out of the kernel + the
    engine's pool-dtype write-back of the reserved headroom."""
    node = n * itemsize + aux_itemsize
    Mn = M * n
    if megakernel:
        return {
            "pop": M * node,
            "eval": M * node + Mn * (n + 1) * 4 + Mn * node,
            "compact": 0,
            "push": 0,
            "overflow": 0,
        }
    return {
        "pop": M * node,
        "eval": M * node + Mn * 4,
        "compact": Mn * 4 + S * 4,
        "push": 2 * S * node,
        "overflow": 0,
    }


def audit(phase_ns: dict, cycles: int, *, M: int, n: int, S: int,
          itemsize: int, aux_itemsize: int = 4, megakernel: bool = False,
          peak_bps: float | None, peak_source: str = "") -> dict:
    """The roofline document: per-phase measured ns, total byte floor,
    achieved GB/s, and %-of-memory-bound-peak.  Phases with no measured
    time or no byte floor report ns only, and no phase has a percentage
    when the device has no known peak (never divide by a missing
    measurement)."""
    floors = phase_byte_floors(M=M, n=n, S=S, itemsize=itemsize,
                               aux_itemsize=aux_itemsize,
                               megakernel=megakernel)
    rows = []
    for slot in PHASES:
        ns = int(phase_ns.get(slot, 0) or 0)
        nbytes = int(floors.get(slot, 0)) * int(cycles)
        row: dict = {"phase": slot, "ns": ns, "bytes": nbytes}
        if ns > 0 and nbytes > 0:
            sec = ns / 1e9
            gbps = nbytes / sec / 1e9
            row["gbps"] = round(gbps, 2)
            if peak_bps is not None:
                row["pct_of_peak"] = round(
                    100.0 * nbytes / (peak_bps * sec), 1)
        rows.append(row)
    return {
        "peak_gbps": (round(peak_bps / 1e9, 1) if peak_bps is not None
                      else NOT_MEASURED),
        "peak_source": peak_source,
        "cycles": int(cycles),
        "phases": rows,
    }


def table(doc: dict) -> list[str]:
    """Render an audit document as the `tts report --roofline` table."""
    peak = (f"peak {doc['peak_gbps']} GB/s, {doc['peak_source']}"
            if doc["peak_gbps"] != NOT_MEASURED else f"peak {NOT_MEASURED}")
    lines = [
        f"  roofline ({peak}; {doc['cycles']} cycles):",
        "    phase       time_ms     floor_MB    GB/s     % of peak",
    ]
    for row in doc["phases"]:
        ms = row["ns"] / 1e6
        mb = row["bytes"] / 2**20
        if "pct_of_peak" in row:
            tail = f"{row['gbps']:>8.2f}  {row['pct_of_peak']:>8.1f}%"
        elif "gbps" in row:
            tail = f"{row['gbps']:>8.2f}  {'-':>9}"
        else:
            tail = f"{'-':>8}  {'-':>9}"
        lines.append(
            f"    {row['phase']:<10}{ms:>10.2f}{mb:>13.2f}{tail}"
        )
    return lines


# -- engine/report adapters -------------------------------------------------


def meta_args(program) -> dict:
    """The ``roofline_meta`` event payload the resident loop emits — the
    static shape/routing facts `tts report --roofline` needs to rebuild
    the byte floors from a trace alone."""
    import numpy as np

    from ..ops.pallas_kernels import platform

    vals_dt = program.pool_fields[0][1]
    aux_dt = program.pool_fields[1][1]
    return {
        "M": int(program.M),
        "n": int(program.problem.child_slots),
        "S": int(program.S),
        "itemsize": int(np.dtype(vals_dt).itemsize),
        "aux_itemsize": int(np.dtype(aux_dt).itemsize),
        "megakernel": bool(program.megakernel.enabled),
        "megakernel_mt": int(program.megakernel.mt),
        "megakernel_grid": int(program.megakernel.grid),
        "backend": platform(program.device),
        "device_kind": program.device.device_kind,
    }


def from_meta(meta: dict, phase_ns: dict, cycles: int,
              costmodel: dict | None = None) -> dict | None:
    """Build the audit from a ``roofline_meta`` args dict + phase totals —
    the shared path of `tts report --roofline` and the in-process
    SearchResult field."""
    if not phase_ns or cycles <= 0:
        return None
    backend = meta.get("backend") or "cpu"
    entry = hbm_entry(costmodel, backend) if costmodel else None
    peak, src = peak_bytes_per_sec(meta.get("device_kind"), entry)
    return audit(
        phase_ns, cycles,
        M=int(meta["M"]), n=int(meta["n"]), S=int(meta["S"]),
        itemsize=int(meta.get("itemsize", 4)),
        aux_itemsize=int(meta.get("aux_itemsize", 4)),
        megakernel=bool(meta.get("megakernel")),
        peak_bps=peak, peak_source=src,
    )


def result_audit(program, phase_ns: dict | None, cycles: int) -> dict | None:
    """The SearchResult.roofline payload: audit the finished run's phase
    totals against the resolved peak (COSTMODEL profile when
    TTS_COSTMODEL points at one)."""
    if not phase_ns or cycles <= 0:
        return None
    from . import costmodel as CM

    prof = None
    path = CM.costmodel_path()
    if path:
        prof = CM.load(path)
    return from_meta(meta_args(program), phase_ns, cycles, costmodel=prof)
