"""Search result/report structures.

Reproduces the reference's self-reported metrics (SURVEY.md §6): exploredTree,
exploredSol, optimum, elapsed time, the 3-phase breakdown of the offload
tiers (`nqueens_gpu_chpl.chpl:178-245`), and offload diagnostics counters
(GpuDiagnostics equivalent, `pfsp_gpu_chpl.chpl:454-466`).
"""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class PhaseStats:
    """One phase's deltas (`res1/res2/res3`, `nqueens_gpu_chpl.chpl:178-245`)."""

    seconds: float = 0.0
    tree: int = 0
    sol: int = 0


@dataclass
class Diagnostics:
    """Offload counters (Chapel GpuDiagnostics: kernel_launch /
    host_to_device / device_to_host, `nqueens_gpu_chpl.chpl:278-283`).
    """

    kernel_launches: int = 0
    host_to_device: int = 0
    device_to_host: int = 0
    # Offload engine: dispatches whose H2D staging overlapped an
    # in-flight device evaluation (the double-buffer fast path,
    # `engine/device.py DeviceOffloader.stage`).
    double_buffered: int = 0


@dataclass
class SearchResult:
    explored_tree: int = 0
    explored_sol: int = 0
    best: int | None = None  # final incumbent (PFSP optimum)
    elapsed: float = 0.0
    phases: list[PhaseStats] = field(default_factory=list)
    diagnostics: Diagnostics = field(default_factory=Diagnostics)
    # multi-device extras (`pfsp_multigpu_chpl.chpl:518-522`)
    per_worker_tree: list[int] = field(default_factory=list)
    # False when the run stopped early (max_steps cutoff) and saved a
    # checkpoint instead of finishing; counters cover work done so far.
    complete: bool = True
    # Resident tiers: dispatch-boundary steps the RunController counted
    # this run (one per consumed K-cycle dispatch). The serve scheduler
    # accumulates this across preemption slices so a max_steps budget
    # spans resumes; 0 for tiers without a controller.
    steps: int = 0
    # multi/dist tiers: successful intra-host work steals (the reference
    # declares nSteal counters but never reports them,
    # `pfsp_multigpu_chpl.chpl:380`).
    steals: int = 0
    # dist tier: inter-host communicator totals (exchange rounds, stolen
    # blocks/nodes), summed across hosts.
    comm: dict | None = None
    # dist/dist_mesh tiers: the resolved steal policy (TTS_STEAL,
    # parallel/topology.py) — {"mode", "pods", "levels": {link: {level,
    # every, period_s, quantum, source}}} where source names the
    # COSTMODEL.json profile key the quantum/period resolved from (or
    # "fixed"). Identical on every host; None for tiers without an
    # inter-host communicator.
    steal_policy: dict | None = None
    # Resident tiers: the survivor-path compaction mode the compiled step
    # baked in (ops/compaction.py — "dense"/"scatter"/"sort"/"search"),
    # with compact_auto True when the TTS_COMPACT=auto policy chose it.
    # None for tiers that prune on host and never compact.
    compact: str | None = None
    compact_auto: bool = False
    # Resident tiers: the one-kernel cycle state the compiled step baked
    # in (TTS_MEGAKERNEL, ops/megakernel.py) — "on"/"off", with
    # megakernel_auto True when the auto policy decided and, when the
    # kernel refused to arm (or auto declined), the recorded reason.
    # None for tiers without a resident program.
    megakernel: str | None = None
    megakernel_auto: bool = False
    megakernel_reason: str | None = None
    # Resident tiers, armed builds: the resolved streamed pool-tile width
    # Mt and whether the pool axis actually tiled (grid > 1 — the
    # double-buffered HBM->VMEM streaming form; False is the single-tile
    # pool-resident form). None/False when the kernel is off.
    megakernel_mt: int | None = None
    megakernel_tiled: bool = False
    # Resident tiers: the route the build runs — "tpu" (the Pallas/Mosaic
    # kernels, `pallas_kernels.use_pallas` or an armed cycle) or "jnp" (no
    # Pallas kernel on the path) — and whether those kernels run in Pallas
    # interpret mode. None for tiers without a resident program.
    kernel_backend: str | None = None
    kernel_interpret: bool = False
    # Roofline audit (obs/roofline.py): per-phase %-of-memory-bound-peak
    # computed from the phase_profile ns splits, the analytic per-cycle
    # byte floors, and the resolved peak HBM bandwidth (COSTMODEL "hbm"
    # link / TTS_HBM_GBPS / nominal backend table) — {"peak_gbps",
    # "peak_source", "cycles", "phases": [{phase, ns, bytes, gbps,
    # pct_of_peak}, ...]}. None when the phase profiler is off.
    roofline: dict | None = None
    # Resident tiers: dispatch-pipeline depth the host loop ran with
    # (TTS_PIPELINE — 1 = synchronous, >= 2 = speculative), the K the
    # loop ended on, and whether TTS_K=auto resolved it (engine/pipeline.py).
    pipeline_depth: int = 1
    k_resolved: int | None = None
    k_auto: bool = False
    # Telemetry snapshot (TTS_OBS=1, docs/OBSERVABILITY.md): per-run totals
    # of the on-device counter block harvested at dispatch boundaries
    # ({"device_counters": {popped, pushed, leaves, pruned, overflow,
    # pool_hwm, surv_hwm}}). None when obs is off — the default-off path
    # carries no cost and no payload.
    obs: dict | None = None
    # Per-phase device-time totals in nanoseconds (TTS_PHASEPROF=1 /
    # `tts profile`, obs/phases.py): {pop, eval, compact, push, overflow,
    # balance, loop, total} harvested from the armed program variant's
    # phase-clock block. The in-cycle slots sum to `total` exactly; for
    # the mesh tiers the values aggregate across shards (shares stay
    # D-invariant). None when the profiler is off. (`phases` above is the
    # host-side 3-phase wall-clock breakdown — a different axis.)
    phase_profile: dict | None = None
    # Anytime quality telemetry (TTS_QUALITY=1 or a serve-bound recorder,
    # obs/quality.py): {"optimum": best-known reference or None, "points":
    # [{t_s, step, best, nodes}, ...]} — one point per incumbent
    # improvement, harvested host-side at dispatch boundaries. None when
    # the recorder is off (the default path records nothing and the
    # compiled step is byte-identical either way).
    quality: dict | None = None

    def workload_shares(self) -> list[float]:
        """Per-worker share of explored nodes (load-balance report,
        `nqueens_multigpu_chpl.chpl:337`)."""
        total = sum(self.per_worker_tree)
        if not total:
            return []
        return [100.0 * t / total for t in self.per_worker_tree]
