"""Command-line entry point — one program instead of the reference's eight.

``tts <problem> --tier seq|device|multi|dist [flags]`` replaces the
per-(problem, tier) Chapel mains (`README.md:47-88` of the reference). Flags
and defaults match the reference's config consts: ``--N --g`` for N-Queens
(`nqueens_chpl.chpl:15-16`), ``--inst --lb --ub`` for PFSP
(`pfsp_chpl.chpl:20-22`), ``--m --M`` chunk thresholds and ``--D`` device
count for the offload tiers (`nqueens_gpu_chpl.chpl:12-21`,
`README.md:47-58`). The banner/report format mirrors `print_settings` /
`print_results` (`pfsp_chpl.chpl:54-77`) plus the per-phase breakdown and
offload diagnostics of the device tiers (`nqueens_gpu_chpl.chpl:178-283`).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from .utils import compile_cache


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tts", description="TPU-native accelerated tree search"
    )
    sub = p.add_subparsers(dest="problem", required=True)

    common = argparse.ArgumentParser(add_help=False)
    common.add_argument(
        "--tier",
        choices=["seq", "device", "mesh", "multi", "dist", "dist_mesh"],
        default="seq",
        help=(
            "scaling tier: sequential / single-device / SPMD device mesh / "
            "multi-device host threads / multi-host (offload workers) / "
            "multi-host with per-host SPMD mesh engines (pod-scale)"
        ),
    )
    common.add_argument(
        "--engine",
        choices=["resident", "offload"],
        default="resident",
        help=(
            "device tier engine: resident = pool in HBM, chunk cycles inside "
            "one jitted loop (fast); offload = per-chunk host round trip "
            "(the reference's structure)"
        ),
    )
    common.add_argument("--m", type=int, default=25, help="minimum chunk size")
    common.add_argument(
        "--M", type=int, default=None,
        help="maximum chunk size (default: measured per problem/backend — "
        "1024 for PFSP device tiers on TPU, else the reference's 50000; "
        "see docs/HW_VALIDATION.md chunk-size tuning)",
    )
    common.add_argument("--K", type=str, default=None,
                        help="resident tiers: device chunk cycles per host "
                        "dispatch (default 4096 device / 16 mesh), or "
                        "'auto' — resize K along a geometric ladder toward "
                        "a target host period (also TTS_K=auto; "
                        "engine/pipeline.py)")
    common.add_argument(
        "--D", type=int, default=None,
        help="number of devices/shards (mesh, multi, dist tiers); "
        "default: all local devices. With --mp N this is the dp-axis "
        "size and the run consumes D*mp devices",
    )
    common.add_argument(
        "--mp", type=int, default=1,
        help="mesh tier, PFSP lb2 only: shard the Johnson machine-pair "
        "loop over a second mesh axis of this size (dp x mp devices)",
    )
    common.add_argument(
        "--compact",
        choices=["auto", "scatter", "sort", "search", "dense"],
        default=None,
        help="survivor-path compaction for the device tiers "
        "(default: TTS_COMPACT env or 'auto' — picks per problem shape "
        "from the measured table in ops/compaction.py; the explicit "
        "modes are bit-identical — pick by measurement, see bench.py's "
        "per-run A/B; 'dense' is the shift-based fast path, free of "
        "sort/scatter/searchsorted)",
    )
    common.add_argument("--stats-file", type=str, default=None,
                        help="append one result line to this .dat file")
    common.add_argument("--json", action="store_true", help="emit one JSON result line")
    common.add_argument("--checkpoint", type=str, default=None,
                        help="save the search frontier to this file periodically "
                        "(device/mesh tiers)")
    common.add_argument("--checkpoint-interval", type=float, default=60.0,
                        help="seconds between checkpoint snapshots")
    common.add_argument("--resume", type=str, default=None,
                        help="resume a search from a checkpoint file")
    common.add_argument("--max-steps", type=int, default=None,
                        help="stop after this many device dispatches "
                        "(checkpointing cutoff; result is marked incomplete)")
    common.add_argument("--perc", type=float, default=0.5,
                        help="multi/dist tiers: fraction of a victim's pool "
                        "front taken per steal (the CUDA baseline's --perc; "
                        "0.5 = the steal-half policy)")
    common.add_argument("--hosts", type=int, default=None,
                        help="dist tier: number of virtual hosts to run in "
                        "one process (testing mode; real pods use "
                        "jax.distributed and ignore this)")
    common.add_argument("--no-steal", action="store_true",
                        help="dist tier: disable inter-host stealing + "
                        "incumbent exchange (MPI-baseline join-point-only "
                        "semantics)")
    common.add_argument("--distributed", action="store_true",
                        help="dist tier, real pods: call "
                        "jax.distributed.initialize() before searching "
                        "(coordinator/process env supplied by the launcher, "
                        "e.g. GKE/TPU-VM metadata — the -nl/MPI launcher "
                        "analogue)")
    common.add_argument("--coordinator", type=str, default=None,
                        help="with --distributed: host:port of the rank-0 "
                        "coordination service (defaults to launcher env)")
    common.add_argument("--num-hosts", type=int, default=None,
                        help="with --distributed: process count in the "
                        "slice (defaults to launcher env)")
    common.add_argument("--host-id", type=int, default=None,
                        help="with --distributed: this process's rank "
                        "(defaults to launcher env, e.g. TPU_WORKER_ID)")
    common.add_argument("--steal-interval", type=float, default=None,
                        help="dist tier: communicator cadence floor in "
                        "seconds (default 0.02; backs off geometrically "
                        "while all hosts are busy)")
    common.add_argument("--profile", type=str, default=None,
                        metavar="DIR",
                        help="write a jax profiler trace of the search to "
                        "this directory (view with TensorBoard/XProf): "
                        "the search's host spans (tts.search, "
                        "tts.dispatch, tts.wait, ...) and its named device "
                        "kernels (tts_cycle_<family>, tts_eval_<family>) "
                        "on one clock (docs/OBSERVABILITY.md leg 2)")
    common.add_argument("--trace", type=str, default=None,
                        help="write a Chrome-trace-event JSON of the run's "
                        "telemetry to this file (open in Perfetto; "
                        "summarize with `tts report`); implies TTS_OBS=1 "
                        "unless TTS_OBS is already set "
                        "(docs/OBSERVABILITY.md)")
    common.add_argument("--metrics-file", type=str, default=None,
                        help="append one JSON line per telemetry counter "
                        "sample to this file (scrape-ready); implies "
                        "TTS_OBS=1 unless TTS_OBS is already set")
    common.add_argument("--obs-serve", type=int, default=None,
                        metavar="PORT",
                        help="serve live run snapshots (nodes/s, incumbent, "
                        "pool occupancy, pipeline depth/K) on "
                        "127.0.0.1:PORT over HTTP/SSE; watch with "
                        "`tts watch --port PORT`; implies TTS_OBS=1 "
                        "unless TTS_OBS is already set "
                        "(docs/OBSERVABILITY.md)")
    common.add_argument("--costmodel", type=str, default=None,
                        metavar="PATH",
                        help="after the run, fit per-link-class "
                        "latency+bandwidth profiles from the recorded "
                        "spans and merge them into this COSTMODEL.json "
                        "(keyed by backend/topology/problem shape); "
                        "TTS_COSTMODEL=PATH makes later runs resolve "
                        "their K bands from it; implies TTS_OBS=1 unless "
                        "TTS_OBS is already set")
    common.add_argument("--phase-profile", action="store_true",
                        help="resident tiers: arm the on-device per-phase "
                        "cycle clocks (pop/eval/compact/push/overflow + "
                        "mesh balance — obs/phases.py). Builds a separate "
                        "cache-keyed program variant (equivalent to "
                        "TTS_PHASEPROF=1); search results stay "
                        "bit-identical, the decomposition table prints "
                        "with the results. Never use for headline "
                        "measurements — see `tts profile` and "
                        "docs/OBSERVABILITY.md leg 7")
    common.add_argument("--guard", action="store_true",
                        help="resident tiers: assert every steady-state "
                        "device dispatch performs zero recompilations and "
                        "zero implicit host transfers (fail loudly instead "
                        "of silently paying ~360ms/cycle round trips; "
                        "equivalent to TTS_GUARD=1 — docs/ANALYSIS.md)")

    nq = sub.add_parser("nqueens", parents=[common], help="N-Queens backtracking")
    nq.add_argument("--N", type=int, default=14, help="number of queens")
    nq.add_argument("--g", type=int, default=1, help="safety checks per evaluation")

    pf = sub.add_parser("pfsp", parents=[common], help="PFSP Branch-and-Bound")
    pf.add_argument("--inst", type=int, default=14, help="Taillard instance (1..120)")
    pf.add_argument("--lb", type=str, default="lb1", choices=["lb1", "lb1_d", "lb2"])
    pf.add_argument("--ub", type=int, default=1, choices=[0, 1],
                    help="initial upper bound: 1=known optimum, 0=inf")
    pf.add_argument("--lb2-variant", type=str, default="full",
                    choices=["full", "nabeshima", "lageweg"],
                    help="lb2 Johnson machine-pair subset (the reference's "
                    "enum lb2_variant, Bound_johnson.chpl:6): full = all "
                    "m(m-1)/2 pairs (reference default); nabeshima = "
                    "adjacent pairs (i, i+1); lageweg = every machine "
                    "paired with the last — both m-1 pairs, weaker bounds "
                    "but ~m/2x fewer pair evaluations")
    pf.add_argument("--lb2-pairblock", type=str, default=None,
                    help="lb2 machine-pair block size: evaluate this many "
                    "Johnson pairs at once as an extra tensor axis "
                    "(default: TTS_LB2_PAIRBLOCK env or 'auto'; 1 = the "
                    "serial per-pair loop; clamped to the pair count)")

    lint = sub.add_parser(
        "lint",
        help="JAX-aware static analysis: host-sync-in-jit, tracer-branch, "
        "guarded-by, static-arg-hygiene (docs/ANALYSIS.md)",
    )
    from .analysis import add_lint_args

    add_lint_args(lint)

    chk = sub.add_parser(
        "check",
        help="compiled-program contract audit: trace every knob-matrix "
        "cell's resident program (no execution, CPU is enough) and "
        "verify the registered structural contracts + the op-fingerprint "
        "baseline + the lock-order audit (docs/ANALYSIS.md)",
    )
    from .analysis.program_audit import add_check_args

    add_check_args(chk)

    rep = sub.add_parser(
        "report",
        help="summarize a --trace file: steal efficiency, idle fraction "
        "per worker, cycle-rate timeline (docs/OBSERVABILITY.md)",
    )
    rep.add_argument("trace", nargs="+",
                     help="trace/metrics/flight-recorder files (merged "
                     "into one report; truncated or empty files are "
                     "summarized as far as they parse)")
    rep.add_argument("--json", action="store_true", dest="report_json",
                     help="emit the summary as one JSON object")
    rep.add_argument("--roofline", action="store_true",
                     dest="report_roofline",
                     help="require the memory-roofline section (per-phase "
                     "%%-of-peak, obs/roofline.py): exit 2 when the trace "
                     "was not phase-profiled (TTS_PHASEPROF=1)")
    rep.add_argument("--costmodel", type=str, default=None,
                     dest="report_costmodel", metavar="PATH",
                     help="COSTMODEL.json whose measured `hbm` link fit "
                     "supplies the roofline peak-bandwidth denominator "
                     "(else TTS_HBM_GBPS / the nominal backend table)")

    prof = sub.add_parser(
        "profile",
        help="run a search with the per-phase cycle clocks armed and "
        "print the decomposition table: `tts profile pfsp --inst 14 "
        "--tier device [--profile DIR]` — sugar for the same run command "
        "with --phase-profile forced on. On the one-kernel cycle the "
        "clocks charge the whole cycle to eval; --profile DIR traces the "
        "named kernels instead (docs/OBSERVABILITY.md leg 7)",
    )
    prof.add_argument("rest", nargs=argparse.REMAINDER,
                      help="a full run command (problem + flags)")

    watch = sub.add_parser(
        "watch",
        help="live view of a run started with --obs-serve PORT (or, with "
        "--job, of one serve-daemon job): one status line per snapshot "
        "(nodes/s, incumbent, pool occupancy, pipeline depth/K)",
    )
    watch.add_argument("--port", type=int, default=None,
                       help="the --obs-serve port (default 8642), or with "
                       "--job the serve daemon's port (default 8643)")
    watch.add_argument("--host", type=str, default="127.0.0.1")
    watch.add_argument("--interval", type=float, default=1.0,
                       help="polling fallback interval in seconds")
    watch.add_argument("--once", action="store_true",
                       help="print the current snapshot and exit")
    watch.add_argument("--json", action="store_true", dest="watch_json",
                       help="emit raw snapshot JSON lines")
    watch.add_argument("--job", type=str, default=None, metavar="ID",
                       help="follow one serve-daemon job's stream instead "
                       "of a --obs-serve run (docs/SERVING.md)")

    from .serve import DEFAULT_PORT as _SERVE_PORT

    srv = sub.add_parser(
        "serve",
        help="persistent multi-tenant search daemon: admit jobs over a "
        "localhost HTTP/JSON API, pool compiled programs per shape "
        "class (second same-class job admits with zero recompiles), "
        "preempt via bit-identical checkpoint cuts (docs/SERVING.md)",
    )
    srv.add_argument("--port", type=int, default=_SERVE_PORT,
                     help=f"listen port on 127.0.0.1 (default {_SERVE_PORT}; "
                     "0 = OS-assigned, printed at startup)")
    srv.add_argument("--host", type=str, default="127.0.0.1")
    srv.add_argument("--state-dir", type=str, default=None,
                     help="durable job records + checkpoints (default "
                     "TTS_SERVE_STATE or ~/.cache/tpu_tree_search/serve)")
    srv.add_argument("--workers", type=int, default=1,
                     help="concurrent job slices (default 1: one resident "
                     "loop owns the accelerator at a time)")
    srv.add_argument("--quantum", type=float, default=5.0,
                     help="seconds a job runs before it must yield to "
                     "waiting work (checkpoint cut + requeue; the cut "
                     "lands at the next dispatch boundary)")
    srv.add_argument("--max-queue", type=int, default=64,
                     help="admission control: reject submits (503) beyond "
                     "this queue depth")
    srv.add_argument("--warm", type=str, nargs="?", const="serve",
                     default=None, metavar="NAMES",
                     help="pre-warm the program pool at startup: 'serve' "
                     "(every serve-able config), 'all', or a "
                     "comma-separated config list (`tts warmup` names)")
    srv.add_argument("--batch-slots", type=int, default=None, metavar="B",
                     help="instance-axis batch slots per compiled program: "
                     "when >=2 same-shape-class jobs are queued, one "
                     "program advances up to B of them per dispatch, "
                     "splicing/retiring jobs at dispatch boundaries with "
                     "zero recompiles (default TTS_BATCH_SLOTS or 1 = "
                     "today's serial path; docs/SERVING.md)")
    srv.add_argument("--ckpt-every", type=float, default=None, metavar="S",
                     help="cut a recoverable checkpoint every S seconds "
                     "even with nothing waiting (default TTS_CKPT_EVERY "
                     "or off) — the fleet router pulls these to survive "
                     "a killed daemon (docs/SERVING.md)")
    srv.add_argument("--router", type=str, default=None, metavar="URL",
                     help="self-register with a `tts fleet` router at "
                     "startup (default TTS_ROUTER; failure is non-fatal)")

    from .fleet import DEFAULT_ROUTER_PORT as _ROUTER_PORT

    flt = sub.add_parser(
        "fleet",
        help="class-aware router over N serve daemons: one URL places "
        "each job where its compiled program is already warm, proxies "
        "the job lifecycle, and recovers in-flight jobs off dead or "
        "draining daemons via checkpoint resubmission (docs/SERVING.md)",
    )
    flt.add_argument("--port", type=int, default=_ROUTER_PORT,
                     help=f"router port on 127.0.0.1 (default "
                     f"{_ROUTER_PORT}; 0 = OS-assigned, printed at "
                     "startup)")
    flt.add_argument("--host", type=str, default="127.0.0.1")
    flt.add_argument("--state-dir", type=str, default=None,
                     help="durable fleet job map + pulled checkpoints "
                     "(default TTS_FLEET_STATE or "
                     "~/.cache/tpu_tree_search/fleet)")
    flt.add_argument("--daemon", action="append", default=None,
                     metavar="URL", dest="daemons",
                     help="register a serve daemon (repeatable; daemons "
                     "can also self-register via `tts serve --router` "
                     "or POST /register)")
    flt.add_argument("--scrape-interval", type=float, default=1.0,
                     help="seconds between keeper scrapes of each "
                     "daemon's /healthz,/classes,/metrics,/jobs")
    flt.add_argument("--health-misses", type=int, default=3,
                     help="consecutive failed probes before a daemon is "
                     "declared dead and its jobs recovered (default 3)")
    flt.add_argument("--pull-interval", type=float, default=2.0,
                     help="seconds between checkpoint pulls of in-flight "
                     "jobs (the SIGKILL-recovery fuel; default 2)")
    flt.add_argument("--no-rebalance", action="store_true",
                     help="disable hot->idle migration of long-runners")
    flt.add_argument("--rebalance-depth", type=int, default=2,
                     help="queue depth on the hot daemon before a "
                     "rebalance move is considered (default 2)")

    smt = sub.add_parser(
        "submit",
        help="submit a run to a serve daemon: `tts submit [--wait] -- "
        "pfsp --inst 14 --tier device` (the run args are the normal "
        "`tts` run command; --wait streams to completion)",
    )
    smt.add_argument("--port", type=int, default=_SERVE_PORT,
                     help=f"serve daemon port (default {_SERVE_PORT})")
    smt.add_argument("--host", type=str, default="127.0.0.1")
    smt.add_argument("--router", type=str, default=None, metavar="URL",
                     help="submit through a `tts fleet` router instead "
                     "of one daemon (default TTS_ROUTER): the job lands "
                     "on the daemon whose compiled programs are already "
                     "warm for its shape class")
    smt.add_argument("--wait", action="store_true",
                     help="follow the job's stream and print the final "
                     "result (exit 1 unless it completes)")
    smt.add_argument("--json", action="store_true", dest="submit_json",
                     help="emit the submit response (or with --wait the "
                     "final job record) as one JSON line")
    smt.add_argument("rest", nargs=argparse.REMAINDER,
                     help="a full run command (problem + flags)")

    top = sub.add_parser(
        "top",
        help="live per-job / per-class table for a serve daemon "
        "(assembled from /healthz + /jobs + /classes; the fleet "
        "operator console — docs/SERVING.md)",
    )
    top.add_argument("--port", type=int, default=_SERVE_PORT,
                     help=f"serve daemon port (default {_SERVE_PORT})")
    top.add_argument("--host", type=str, default="127.0.0.1")
    top.add_argument("--router", type=str, default=None, metavar="URL",
                     help="aggregate a whole fleet instead of one daemon "
                     "(default TTS_ROUTER): per-daemon rows + fleet "
                     "totals from the router's /fleet endpoint")
    top.add_argument("--interval", type=float, default=2.0,
                     help="refresh period in seconds (default 2)")
    top.add_argument("--once", action="store_true",
                     help="print one frame and exit (scripts, CI smoke)")
    top.add_argument("--json", action="store_true", dest="top_json",
                     help="emit the composed health/jobs/classes payload "
                     "as one JSON line per refresh")

    mig = sub.add_parser(
        "migrate",
        help="move a job between serve daemons over its portable "
        "checkpoint: cancel-with-cut on the source, fetch "
        "/job/<id>/checkpoint, resubmit spec+checkpoint to --to "
        "(counters stay cumulative, so the result is bit-identical "
        "to never having moved — docs/SERVING.md)",
    )
    mig.add_argument("job", type=str, help="job id on the source daemon")
    mig.add_argument("--to", type=str, required=True, metavar="URL",
                     help="destination daemon base URL "
                     "(host:port or http://host:port)")
    mig.add_argument("--port", type=int, default=_SERVE_PORT,
                     help=f"source daemon port (default {_SERVE_PORT})")
    mig.add_argument("--host", type=str, default="127.0.0.1",
                     help="source daemon host (default 127.0.0.1)")
    mig.add_argument("--json", action="store_true", dest="migrate_json",
                     help="emit the old->new id mapping as one JSON line")

    wrm = sub.add_parser(
        "warmup",
        help="AOT-compile the validation matrix into the persistent "
        "compile cache with per-config hit/miss reporting "
        "(scripts/warm_cache.py's engine; docs/SERVING.md)",
    )
    wrm.add_argument("--configs", type=str, default=None, metavar="NAMES",
                     help="'all' (default), 'serve', or a comma-separated "
                     "config name list")
    wrm.add_argument("--timeout", type=float, default=None,
                     help="per-config subprocess timeout in seconds "
                     "(default TTS_WARM_TIMEOUT or 420)")
    return p


def validate_args(parser: argparse.ArgumentParser, args) -> None:
    """Reject flag combinations that would otherwise be silently ignored."""
    if args.K is not None and args.K != "auto":
        try:
            args.K = int(args.K)
        except ValueError:
            parser.error("--K must be 'auto' or a positive integer")
        if args.K < 1:
            parser.error("--K must be >= 1 (or 'auto')")
    if args.guard and not (
        args.tier in ("mesh", "dist_mesh")
        or (args.tier == "device" and args.engine == "resident")
    ):
        parser.error(
            "--guard asserts steady-state purity of the resident device "
            "loops (--tier device with the resident engine, mesh, "
            "dist_mesh); the offload/multi/dist workers round-trip every "
            "chunk by design"
        )
    if args.tier in ("mesh", "dist_mesh") and args.engine == "offload":
        parser.error(
            "--engine offload is not available for this tier "
            "(mesh/dist_mesh are resident-only; use --tier multi for "
            "host-orchestrated offload across devices)"
        )
    if args.phase_profile and not uses_compaction(args):
        parser.error(
            "--phase-profile arms the resident loops' on-device phase "
            "clocks (--tier device with the resident engine, mesh, "
            "dist_mesh); the offload/multi/dist workers have no device "
            "cycle to decompose"
        )
    if args.compact is not None and not uses_compaction(args):
        parser.error(
            "--compact only applies to runs with device-side compaction "
            "(--tier device with the resident engine, mesh, dist_mesh); "
            "the offload/multi/dist workers prune on host"
        )
    if args.perc != 0.5 and args.tier not in ("multi", "dist"):
        parser.error(
            "--perc only applies to the work-stealing tiers (multi, dist)"
        )
    if not 0.0 < args.perc <= 1.0:
        # Semantics of the steal fraction: reference `Pool_ext.c:138-151`.
        parser.error(
            "--perc must be in (0, 1]: the fraction of the victim's front "
            "taken per steal"
        )
    if (
        args.hosts is not None or args.distributed
    ) and args.tier not in ("dist", "dist_mesh"):
        parser.error(
            "--hosts/--distributed only apply to --tier dist/dist_mesh"
        )
    if args.no_steal and args.tier != "dist":
        parser.error("--no-steal only applies to --tier dist")
    if args.distributed and args.hosts is not None:
        parser.error("--distributed (real pods) and --hosts (virtual "
                     "hosts) are mutually exclusive")
    if (
        args.coordinator is not None
        or args.num_hosts is not None
        or args.host_id is not None
    ) and not args.distributed:
        parser.error("--coordinator/--num-hosts/--host-id require "
                     "--distributed")
    if args.steal_interval is not None:
        if args.tier != "dist":
            parser.error("--steal-interval only applies to --tier dist")
        if args.steal_interval <= 0:
            parser.error("--steal-interval must be > 0")
    if args.hosts is not None and args.hosts < 1:
        parser.error("--hosts must be >= 1")
    if args.mp != 1:
        if args.tier not in ("mesh", "dist_mesh"):
            parser.error("--mp only applies to --tier mesh/dist_mesh")
        if args.mp < 1:
            parser.error("--mp must be >= 1")
        if args.problem != "pfsp" or args.lb != "lb2":
            parser.error("--mp shards the lb2 Johnson pair loop "
                         "(pfsp --lb lb2 only)")
    if args.problem == "pfsp":
        if args.lb2_variant != "full" and args.lb != "lb2":
            parser.error("--lb2-variant selects the lb2 Johnson pair "
                         "subset (--lb lb2 only)")
        if args.lb2_pairblock is not None:
            if args.lb != "lb2":
                parser.error("--lb2-pairblock batches the lb2 Johnson "
                             "pair axis (--lb lb2 only)")
            if args.lb2_pairblock != "auto":
                try:
                    v = int(args.lb2_pairblock)
                except ValueError:
                    parser.error("--lb2-pairblock must be 'auto' or a "
                                 "positive integer")
                else:
                    if v < 1:
                        parser.error("--lb2-pairblock must be >= 1 "
                                     "(1 = the serial per-pair loop)")


def make_problem(args):
    if args.problem == "nqueens":
        from .problems import NQueensProblem

        return NQueensProblem(N=args.N, g=args.g)
    from .problems import PFSPProblem

    return PFSPProblem(inst=args.inst, lb=args.lb, ub=args.ub,
                       lb2_variant=args.lb2_variant)


def resolve_chunk_size(M, problem_name: str, tier: str, engine: str,
                       backend: str | None = None) -> int:
    """Measured default for ``--M`` when the user does not pass one.

    On-chip tuning (scripts/headline_tune.py / lb2_tune.py, round 5 —
    docs/HW_VALIDATION.md) showed the RESIDENT loop's per-cycle cost is
    ~linear in M while PFSP frontiers rarely fill large chunks, so
    small-but-full chunks run ~1.3x (lb1) to ~3x (staged lb2) faster:
    PFSP device tier + resident engine on TPU defaults to 1024, and so
    do the sharded resident tiers (``mesh``/``dist_mesh``, where M is per
    shard and each shard runs the same cycle): at 50000 per shard ``auto``
    arms a 3125-tile lb2 megakernel grid whose D=4 step compiled to
    ~300 MB of code and did not finish within 540 s on four v5e chips.
    Everything else — explicit ``--M``, the offload engine (each chunk
    pays a ~360ms host round trip; small chunks would multiply them),
    non-TPU backends (unmeasured) and N-Queens (wide frontiers fill big
    chunks) — keeps the
    reference's 50000 (the per-program ``config const M = 50000`` of each
    GPU main, `pfsp_gpu_chpl.chpl:24` / `nqueens_gpu_chpl.chpl:21`; it is
    not defined in `util.chpl`). The candidate combination is
    checked BEFORE the backend so non-candidates (e.g. ``--tier seq``)
    never touch jax.  ``backend`` defaults to the platform of the process
    default device (`pallas_kernels.platform`)."""
    if M is not None:
        return M
    resident = (tier in ("mesh", "dist_mesh")
                or (tier == "device" and engine == "resident"))
    if not (problem_name == "pfsp" and resident):
        return 50000
    if backend is None:
        from .ops.pallas_kernels import platform

        backend = platform()
    return 1024 if backend == "tpu" else 50000


def uses_compaction(args) -> bool:
    """True for runs whose engine performs device-side stream compaction
    (`engine/resident.py _compact_ids`): the resident device engine and
    the mesh-resident tiers. The offload/multi/dist workers prune and
    branch on host and never consult TTS_COMPACT."""
    return (args.tier in ("mesh", "dist_mesh")
            or (args.tier == "device" and args.engine == "resident"))


def run_tier(problem, args):
    """One whole search of ``problem`` as ``args`` ask, under the
    ``tts.search`` span (docs/OBSERVABILITY.md leg 2)."""
    from .obs import events as obs_events

    with obs_events.search_span("tts.search"):
        return _run_tier(problem, args)


def _run_tier(problem, args):
    args.M = resolve_chunk_size(args.M, getattr(problem, "name", ""),
                                args.tier, args.engine)
    # Flag > env for THIS run only: restore on exit so a caller invoking
    # main() twice in one process does not inherit the pins (compaction
    # programs cache per mode via the routing token; the guard is read at
    # engine start).
    pins = {}
    if args.compact is not None:
        pins["TTS_COMPACT"] = args.compact
    if getattr(args, "lb2_pairblock", None) is not None:
        pins["TTS_LB2_PAIRBLOCK"] = args.lb2_pairblock
    if args.guard:
        pins["TTS_GUARD"] = "1"
    if args.phase_profile:
        pins["TTS_PHASEPROF"] = "1"
    if (
        (args.trace is not None or args.metrics_file is not None
         or args.obs_serve is not None or args.costmodel is not None)
        and "TTS_OBS" not in os.environ
    ):
        # --trace/--metrics-file/--obs-serve/--costmodel turn telemetry on
        # for the run; an explicit TTS_OBS (e.g. =host to keep device
        # programs untouched) wins.
        pins["TTS_OBS"] = "1"
    if not pins:
        return _dispatch_tier(problem, args)

    prev = {k: os.environ.get(k) for k in pins}
    os.environ.update(pins)
    try:
        return _dispatch_tier(problem, args)
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _dispatch_tier(problem, args):
    ckpt_kw = dict(
        max_steps=args.max_steps,
        checkpoint_path=args.checkpoint,
        checkpoint_interval_s=args.checkpoint_interval,
        resume_from=args.resume,
    )
    wants_resident = (
        args.checkpoint is not None
        or args.resume is not None
        or args.max_steps is not None
        or args.K is not None
    )
    if args.tier == "seq" and wants_resident:
        raise NotImplementedError(
            "--checkpoint/--resume/--max-steps/--K need a device tier"
        )
    if args.tier in ("multi", "dist") and (
        args.max_steps is not None or args.K is not None
    ):
        raise NotImplementedError(
            "--max-steps/--K need the device, mesh, or dist_mesh tier"
        )
    if args.tier == "dist_mesh":
        from .parallel.dist_mesh import dist_mesh_search

        kw = dict(m=args.m, M=args.M, D=args.D, mp=args.mp,
                  num_hosts=args.hosts, **ckpt_kw)
        if args.K is not None:
            kw["K"] = args.K
        return dist_mesh_search(problem, **kw)
    if args.tier == "seq":
        from .engine import sequential_search

        return sequential_search(problem)
    if args.tier == "device":
        if args.engine == "resident":
            from .engine.resident import resident_search

            if args.K is not None:
                ckpt_kw["K"] = args.K
            return resident_search(problem, m=args.m, M=args.M, **ckpt_kw)
        if wants_resident:
            raise NotImplementedError(
                "--checkpoint/--resume/--max-steps/--K need the resident engine"
            )
        from .engine.device import device_search

        return device_search(problem, m=args.m, M=args.M)
    if args.tier == "mesh":
        from .parallel.resident_mesh import mesh_resident_search

        if args.K is not None:
            ckpt_kw["K"] = args.K
        return mesh_resident_search(
            problem, m=args.m, M=args.M, D=args.D, mp=args.mp, **ckpt_kw
        )
    ckpt_pass = dict(
        checkpoint_path=args.checkpoint,
        checkpoint_interval_s=args.checkpoint_interval,
        resume_from=args.resume,
    )
    if args.tier == "multi":
        from .parallel.multidevice import multidevice_search

        return multidevice_search(
            problem, m=args.m, M=args.M, D=args.D, perc=args.perc,
            **ckpt_pass,
        )
    from .parallel.dist import dist_search

    if args.steal_interval is not None:
        # Only forward when explicitly set — dist_search owns the default.
        ckpt_pass["steal_interval_s"] = args.steal_interval
    return dist_search(
        problem, m=args.m, M=args.M, D=args.D, perc=args.perc,
        num_hosts=args.hosts, steal=not args.no_steal,
        **ckpt_pass,
    )


def print_settings(args) -> None:
    print("\n=================================================")
    tier_names = {
        "seq": "Sequential",
        "device": "Single-device",
        "mesh": "SPMD device-mesh",
        "multi": "Multi-device",
        "dist": "Distributed multi-device",
        "dist_mesh": "Distributed mesh-resident",
    }
    print(f"{tier_names[args.tier]} TPU tree search\n")
    if args.problem == "nqueens":
        print(f"Resolution of the {args.N}-Queens instance")
        print(f"  with {args.g} safety check(s) per evaluation")
    else:
        from .problems.pfsp import taillard

        print(
            f"Resolution of PFSP Taillard's instance: ta{args.inst:03d} "
            f"(m = {taillard.nb_machines(args.inst)}, n = {taillard.nb_jobs(args.inst)})"
        )
        print("Initial upper bound: " + ("opt" if args.ub == 1 else "inf"))
        print(f"Lower bound function: {args.lb}")
        if args.lb == "lb2" and args.lb2_variant != "full":
            print(f"lb2 machine-pair subset: {args.lb2_variant}")
        print("Branching rule: fwd")
    if uses_compaction(args):
        # The raw knob; the RESOLVED path (auto picks per problem shape)
        # is printed with the results and recorded in the stats line.
        import os

        knob = args.compact or os.environ.get("TTS_COMPACT", "auto")
        print(f"Survivor path (TTS_COMPACT): {knob}")
        # Raw one-kernel-cycle knob; the RESOLVED state (auto arms per
        # device/shape/VMEM fit, refusals record why) is printed with the
        # results and recorded in the stats line.
        mknob = os.environ.get("TTS_MEGAKERNEL", "auto") or "auto"
        print(f"One-kernel cycle (TTS_MEGAKERNEL): {mknob}")
        # Raw dispatch-pipeline knobs; the RESOLVED depth/K are printed
        # with the results (auto may resize K along the ladder mid-run).
        pknob = os.environ.get("TTS_PIPELINE", "auto") or "auto"
        kknob = os.environ.get("TTS_K") or (
            args.K if args.K is not None else "default"
        )
        print(f"Dispatch pipeline (TTS_PIPELINE): {pknob}; "
              f"K schedule (TTS_K): {kknob}")
        if args.phase_profile:
            print("Phase profiler (TTS_PHASEPROF): armed — separate "
                  "program variant, NOT a headline measurement")
    if args.tier in ("dist", "dist_mesh"):
        # Raw steal-hierarchy knob; the RESOLVED per-link-class periods
        # and quanta are printed with the results and recorded in the
        # stats line (parallel/topology.py).
        import os

        from .parallel.topology import steal_mode

        pods = os.environ.get("TTS_PODS")
        print(f"Inter-host stealing (TTS_STEAL): {steal_mode()}"
              + (f"; pod map (TTS_PODS): {pods}" if pods else ""))
    print("=================================================")


def print_results(args, problem, res) -> None:
    for i, ph in enumerate(res.phases[:3], 1):
        label = {1: "Initial search on CPU", 2: "Search on device", 3: "Final search on CPU"}.get(
            i, f"Phase {i}"
        )
        if len(res.phases) > 1:
            print(f"\n{label} completed")
            print(f"Size of the explored tree: {ph.tree}")
            print(f"Number of explored solutions: {ph.sol}")
            print(f"Elapsed time: {ph.seconds:.6f} [s]")
    if res.complete:
        print("\nExploration terminated.")
    elif args.checkpoint is not None:
        print("\nExploration interrupted (checkpointed; resume with --resume).")
    else:
        # max_steps cutoff without --checkpoint: no file exists — don't
        # claim one does.
        print("\nExploration interrupted (no checkpoint written).")
    print("\n=================================================")
    print(f"Size of the explored tree: {res.explored_tree}")
    print(f"Number of explored solutions: {res.explored_sol}")
    if args.problem == "pfsp":
        tag = " (improved)" if res.best < problem.initial_ub else " (not improved)"
        print(f"Optimal makespan: {res.best}{tag}")
    print(f"Elapsed time: {res.elapsed:.6f} [s]")
    if res.per_worker_tree:
        shares = ", ".join(f"{s:.2f}" for s in res.workload_shares())
        print(f"Workload per device (%): [{shares}]")
    if res.compact:
        tag = " (auto)" if res.compact_auto else ""
        print(f"Survivor path: {res.compact}{tag}")
    if res.kernel_backend:
        print(f"Kernel backend: {res.kernel_backend}")
    if res.megakernel:
        tag = " (auto)" if res.megakernel_auto else ""
        why = f" — {res.megakernel_reason}" if res.megakernel_reason else ""
        # Armed builds name the streamed pool-tile width and whether the
        # pool axis actually tiled (ops/megakernel.py Decision): "tiled
        # Mt=16 x4" is the double-buffered HBM->VMEM streaming form,
        # "resident Mt=M" the single-tile pool-resident form.
        tile = ""
        if res.megakernel == "on" and res.megakernel_mt:
            form = "tiled" if res.megakernel_tiled else "resident"
            tile = f", {form} Mt={res.megakernel_mt}"
        print(f"One-kernel cycle: {res.megakernel}{tag}{tile}{why}")
    if res.k_resolved is not None:
        tag = " (auto)" if res.k_auto else ""
        print(f"Dispatch pipeline: depth={res.pipeline_depth}, "
              f"K={res.k_resolved}{tag}")
    if res.phase_profile:
        # The `tts profile` deliverable: the measured on-device cycle
        # decomposition, closed by the dominant-phase call-out.
        from .obs import phases as obs_phases
        from .obs.report import phase_table

        for line in phase_table(obs_phases.decomp(res.phase_profile)):
            print(line)
    d = res.diagnostics
    if d.kernel_launches:
        dbuf = (
            f" double_buffered={d.double_buffered}"
            if d.double_buffered else ""
        )
        print(
            f"Device diagnostics: kernel_launch={d.kernel_launches} "
            f"host_to_device={d.host_to_device} "
            f"device_to_host={d.device_to_host}{dbuf}"
        )
    if res.quality and res.quality.get("points"):
        # TTS_QUALITY=1: the anytime curve (obs/quality.py) — one line per
        # incumbent improvement, closed with the primal gap when the
        # instance has a committed reference optimum.
        from .obs import quality as obs_quality

        q = res.quality
        opt = q.get("optimum")
        print(f"Quality trajectory ({len(q['points'])} incumbent(s)"
              + (f", optimum {opt}" if opt is not None else "") + "):")
        for p in q["points"]:
            g = obs_quality.primal_gap(p.get("best"), opt)
            print(f"  t={p['t_s']:.3f}s  step={p['step']}  "
                  f"best={p['best']}  nodes={p['nodes']}"
                  + (f"  gap={100.0 * g:.2f}%" if g is not None else ""))
        pi = obs_quality.primal_integral(q["points"], opt,
                                         max(res.elapsed, 1e-9))
        if pi is not None:
            print(f"  primal integral: {pi:.4f}")
    if res.steals:
        print(f"Work steals (intra-host): {res.steals}")
    if res.comm:
        c = res.comm
        print(
            f"Inter-host comm: exchange_rounds={c['rounds']} "
            f"stolen_blocks={c['blocks_received']} "
            f"stolen_nodes={c['nodes_received']}"
        )
    if res.steal_policy:
        # The RESOLVED steal hierarchy (parallel/topology.py): one line
        # per link class — level, match period, and donation quantum,
        # with the COSTMODEL.json key each resolved from (or "fixed").
        sp = res.steal_policy
        print(f"Steal policy: {sp['mode']} pods={sp['pods']}")
        for link, s in sp.get("levels", {}).items():
            print(f"  {link}: level={s['level']} every={s['every']} "
                  f"period={s['period_s']}s quantum={s['quantum']} "
                  f"({s['source']})")
    print("=================================================\n")


def result_record(args, res) -> dict:
    rec = {
        "problem": args.problem,
        "tier": args.tier,
        "explored_tree": res.explored_tree,
        "explored_sol": res.explored_sol,
        "elapsed_s": round(res.elapsed, 6),
    }
    if not res.complete:
        rec["complete"] = False
    if res.per_worker_tree:
        # Nodes each worker (device or dp shard) explored.
        rec["per_worker_tree"] = res.per_worker_tree
    if res.steals:
        rec["steals"] = res.steals
    if res.comm:
        rec["comm"] = res.comm
    if res.steal_policy:
        # The resolved steal hierarchy (TTS_STEAL, parallel/topology.py):
        # per-link-class periods/quanta + the profile key each came from.
        rec["steal_policy"] = res.steal_policy
    if res.obs:
        # On-device counter totals (TTS_OBS=1): the stats line carries the
        # run's telemetry snapshot like the reference's diagnostics counters
        # ride its .dat lines.
        rec["obs"] = res.obs
    if res.quality and res.quality.get("points"):
        # TTS_QUALITY=1: the incumbent trajectory (obs/quality.py).
        rec["quality"] = res.quality
    if args.problem == "pfsp":
        rec.update(inst=args.inst, lb=args.lb, ub=args.ub, optimum=res.best)
    else:
        rec.update(N=args.N, g=args.g)
    if args.tier != "seq":
        # Where the run ran: the device JAX placed it on.
        import jax

        devs = jax.devices()
        rec.update(platform=devs[0].platform, device_kind=devs[0].device_kind,
                   device_count=len(devs))
        # Whether a Pallas kernel is on the run's path. Resident engines
        # report what their compiled step routed; the other device tiers
        # re-derive it from the default backend.
        if res.kernel_backend is not None:
            rec["pallas"] = res.kernel_backend != "jnp"
        else:
            from .ops import pallas_kernels as PK

            rec["pallas"] = PK.use_pallas()
        if uses_compaction(args):
            # The RESOLVED survivor path the compiled step baked in (the
            # engine surfaces it on the result — under auto the knob alone
            # no longer names the mode). Runs whose engine never compacts
            # carry no "compact" key at all — a stats line must not claim
            # a mode the run did not use.  Fallback for engines that do
            # compact but predate the surfacing: args.compact first
            # (run_tier restores the env pin before this record is built).
            from .ops.pfsp_device import compact_mode

            rec["compact"] = (
                res.compact or args.compact or compact_mode()
            )
            if res.compact_auto:
                rec["compact_auto"] = True
            # Pipeline depth + the K the run ended on (auto may have
            # resized along the ladder) — the stats line must prove which
            # dispatch regime produced the number.
            rec["pipeline_depth"] = res.pipeline_depth
            if res.k_resolved is not None:
                rec["k"] = res.k_resolved
            if res.k_auto:
                rec["k_auto"] = True
            # Whether the Pallas kernels ran ("tpu") or the jnp oracles
            # ("jnp"), and whether the kernels ran in interpret mode.
            if res.kernel_backend is not None:
                rec["kernel_backend"] = res.kernel_backend
                rec["kernel_interpret"] = res.kernel_interpret
            # The RESOLVED one-kernel-cycle state (engine-surfaced, like
            # "compact") — a stats line must prove whether the fused cycle
            # or the op-chain produced the number, and a refusal must say
            # why it fell back.
            if res.megakernel is not None:
                rec["megakernel"] = res.megakernel
                if res.megakernel_auto:
                    rec["megakernel_auto"] = True
                if res.megakernel_reason:
                    rec["megakernel_reason"] = res.megakernel_reason
                # Armed builds record the streamed pool-tile width and
                # whether the pool axis tiled — the stats line must prove
                # WHICH megakernel form (single-tile resident vs streamed
                # grid) produced the number.
                if res.megakernel_mt:
                    rec["megakernel_mt"] = res.megakernel_mt
                    rec["megakernel_tiled"] = res.megakernel_tiled
            if res.roofline is not None:
                # Phase-profiled runs bank the memory-roofline audit
                # (obs/roofline.py) — per-phase %-of-memory-bound-peak.
                rec["roofline_mem"] = res.roofline
        if args.problem == "pfsp" and args.lb == "lb2":
            # Staging applies at every mp: under mp > 1 the compacted self
            # bound shards its pair loop with a pmax combine. The job count
            # matters: auto mode only stages at n <= 100.
            from .ops import pfsp_device as P
            from .problems.pfsp import bounds as PB
            from .problems.pfsp import taillard

            n_ = taillard.nb_jobs(args.inst)
            # Staging is an evaluator path: an armed one-kernel cycle
            # replaces the evaluator, so staging did not run.
            rec["lb2_staged"] = (P.lb2_staged_enabled(None, n_)
                                 and res.megakernel != "on")
            # Resolved pair-block size (the run's baked-in value): flag
            # first — run_tier restores the env pin before this record is
            # built (same convention as "compact" above).
            Pn = len(PB.machine_pairs(
                taillard.nb_machines(args.inst), args.lb2_variant
            ))
            knob = args.lb2_pairblock
            if knob is None or knob == "auto":
                rec["lb2_pairblock"] = (
                    P._auto_pairblock(Pn, n_) if knob == "auto"
                    else P.lb2_pairblock(Pn, n_)
                )
            else:
                rec["lb2_pairblock"] = min(int(knob), Pn)
            if args.lb2_variant != "full":
                rec["lb2_variant"] = args.lb2_variant
    return rec


def run_topology(args) -> str:
    """The profile-key topology string of this run (obs/costmodel.py):
    mirrors what the engines pass to ``resolve_target_band`` so a capture
    from tier X exactly matches a later run of tier X."""
    if args.tier in ("seq", "device"):
        return "device-D1"
    D = args.D if args.D is not None else 0  # 0 = "all local devices"
    if args.tier == "mesh":
        return f"mesh-D{D}" if D else "mesh-Dall"
    if args.tier == "dist_mesh":
        H = args.hosts or 1
        return f"dist_mesh-H{H}xD{D}" if D else f"dist_mesh-H{H}xDall"
    H = args.hosts or 1
    return f"{args.tier}-H{H}xD{D}" if D else f"{args.tier}-H{H}xDall"


def write_costmodel(args, problem, evts, path, cm) -> tuple[str, dict]:
    """Fit + merge this run's profile entry into ``path`` (the
    ``--costmodel`` capture). Returns the entry's (key, value)."""
    import jax

    backend = jax.default_backend()
    profile = cm.build_profile(
        evts, backend, run_topology(args), cm.shape_class(problem)
    )
    cm.save(path, profile)
    key = next(iter(profile))
    return key, profile[key]


def main(argv=None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.problem == "profile":
        # `tts profile <run-args>`: the same run command with the phase
        # clocks forced on; the decomposition table prints with the
        # results (add --profile DIR inside <run-args> to also write a
        # profiler trace of the search).
        rest = [a for a in args.rest if a != "--"]
        if not rest:
            parser.error(
                "profile: pass a full run command, e.g. "
                "`tts profile pfsp --inst 14 --tier device`"
            )
        args = parser.parse_args(rest)
        if args.problem in ("lint", "check", "report", "watch", "profile",
                            "serve", "submit", "warmup", "top", "migrate",
                            "fleet"):
            parser.error("profile wraps a search run, not another "
                         "subcommand")
        args.phase_profile = True
    if args.problem == "lint":
        # Pure static analysis: no jax import, no backend init.
        from .analysis import run_lint_cli

        return run_lint_cli(args)
    if args.problem == "check":
        # Tracing-only program audit (jax traces, nothing executes).
        from .analysis.program_audit import run_check_cli

        return run_check_cli(args)
    if args.problem == "report":
        # Pure trace summarization: no jax import, no backend init.
        from .obs.report import report_main

        return report_main(args.trace, as_json=args.report_json,
                           roofline=args.report_roofline,
                           costmodel=args.report_costmodel)
    if args.problem == "watch":
        if args.job is not None:
            # Pure HTTP client of a serve daemon: no jax import.
            from .serve import DEFAULT_PORT
            from .serve.client import watch_job_main

            return watch_job_main(
                args.job, port=args.port or DEFAULT_PORT, host=args.host,
                once=args.once, as_json=args.watch_json,
            )
        # Pure HTTP client of a --obs-serve run: no jax import.
        from .obs.live import watch_main

        return watch_main(args.port or 8642, host=args.host,
                          interval=args.interval, once=args.once,
                          as_json=args.watch_json)
    if args.problem == "top":
        # Pure HTTP client of a serve daemon (or fleet router): no jax.
        router = args.router or os.environ.get("TTS_ROUTER")
        if router:
            from .serve.client import fleet_top_main

            return fleet_top_main(router, interval=args.interval,
                                  once=args.once, as_json=args.top_json)
        from .serve.client import top_main

        return top_main(port=args.port, host=args.host,
                        interval=args.interval, once=args.once,
                        as_json=args.top_json)
    if args.problem == "migrate":
        # Pure HTTP client of two serve daemons: no jax import.
        from .serve.client import migrate_main

        return migrate_main(args.job, args.to, port=args.port,
                            host=args.host, as_json=args.migrate_json)
    if args.problem == "serve":
        # The daemon: jax stays out of the HTTP threads (scheduler
        # workers import the engines lazily on the first slice).
        from .serve.server import serve_main

        compile_cache.enable()
        return serve_main(port=args.port, host=args.host,
                          state_dir=args.state_dir, workers=args.workers,
                          quantum_s=args.quantum, max_queue=args.max_queue,
                          warm=args.warm, batch_slots=args.batch_slots,
                          ckpt_every_s=args.ckpt_every,
                          router=args.router or os.environ.get("TTS_ROUTER"))
    if args.problem == "fleet":
        # The router: host-only by construction (no jax anywhere in
        # fleet/ — placement reuses the daemons' own host-side class-key
        # computation), so no compile cache and no backend init.
        from .fleet.router import router_main

        return router_main(port=args.port, host=args.host,
                           state_dir=args.state_dir, daemons=args.daemons,
                           scrape_interval_s=args.scrape_interval,
                           max_misses=args.health_misses,
                           pull_interval_s=args.pull_interval,
                           rebalance=not args.no_rebalance,
                           rebalance_min_depth=args.rebalance_depth)
    if args.problem == "submit":
        # Thin client: re-parse the run command through THIS parser so
        # every CLI-side validation runs before the spec leaves the
        # process (same REMAINDER trick as `tts profile`); no jax import.
        rest = [a for a in args.rest if a != "--"]
        if not rest:
            parser.error(
                "submit: pass a full run command, e.g. "
                "`tts submit -- pfsp --inst 14 --tier device`"
            )
        run_args = parser.parse_args(rest)
        if run_args.problem not in ("nqueens", "pfsp"):
            parser.error("submit wraps a search run, not another "
                         "subcommand")
        validate_args(parser, run_args)
        from .serve.client import spec_from_args, submit_main

        return submit_main(spec_from_args(run_args), port=args.port,
                           host=args.host, wait=args.wait,
                           as_json=args.submit_json,
                           router=args.router or os.environ.get("TTS_ROUTER"))
    if args.problem == "warmup":
        # Subprocess orchestration: each config compiles in its own
        # process against the persistent cache; no jax import here.
        from .serve.warmup import warmup_main

        return warmup_main(args.configs, timeout_s=args.timeout)
    validate_args(parser, args)
    primary = True
    if args.distributed:
        # Must run before ANY jax call that initializes backends (including
        # the profiler's trace session). Coordinator/process ids come from
        # the launcher's environment (the -nl / mpirun analogue).
        import jax

        # Explicit flags override the launcher env (useful for manual
        # launches and the docs/POD_LAUNCH.md two-shell smoke test); None
        # falls back to GKE/TPU-VM metadata discovery.
        init_kw = {}
        if args.coordinator is not None:
            init_kw["coordinator_address"] = args.coordinator
        if args.num_hosts is not None:
            init_kw["num_processes"] = args.num_hosts
        if args.host_id is not None:
            init_kw["process_id"] = args.host_id
        try:
            jax.distributed.initialize(**init_kw)
        except Exception as e:
            print(
                f"Error: jax.distributed.initialize() failed: {e}\n"
                "(--distributed needs the launcher to supply coordinator/"
                "process environment, or pass --coordinator/--num-hosts/"
                "--host-id explicitly)",
                file=sys.stderr,
            )
            return 2
        primary = jax.process_index() == 0
    compile_cache.enable()
    try:
        problem = make_problem(args)
    except ValueError as e:
        print(f"Error: {e}", file=sys.stderr)
        return 2
    if primary:
        print_settings(args)
    from .obs import events as obs_events

    wants_obs = (args.trace or args.metrics_file or args.costmodel
                 or args.obs_serve is not None)
    if wants_obs or obs_events.enabled():
        # Run-scoped telemetry: a prior run's events in this process must
        # not leak into this run's trace.
        obs_events.reset()
        # Arm the flight recorder from the MAIN thread (signal handlers
        # only attach here; engines re-arm the watchdog per run). With
        # TTS_OBS off and TTS_FLIGHTREC unset this is a no-op.
        from .obs import flightrec

        flightrec.reset()
        flightrec.recorder().install()
    live_server = None
    if args.obs_serve is not None and primary:
        from .obs import live as obs_live

        live_server = obs_live.serve(args.obs_serve)
        print(f"Live monitor: {live_server.url} "
              f"(tts watch --port {live_server.port})")
    try:
        if args.profile:
            # Trace the whole search (phase timers remain the first-class
            # report, SURVEY.md §5 tracing; this adds the XLA-level view).
            import jax

            with jax.profiler.trace(args.profile):
                res = run_tier(problem, args)
        else:
            res = run_tier(problem, args)
    except (ModuleNotFoundError, NotImplementedError) as e:
        print(f"Error: tier '{args.tier}' unavailable: {e}", file=sys.stderr)
        return 2
    finally:
        if live_server is not None:
            live_server.close()
    # Multi-process pods: every host computed the same reduced result;
    # report from process 0 only (the MPI baseline's rank-0 stats line,
    # `pfsp_dist_multigpu_cuda.c:179-187`).
    if primary:
        print_results(args, problem, res)
        rec = result_record(args, res)
        if args.trace or args.metrics_file or args.costmodel:
            from .obs import export as obs_export

            evts = obs_events.drain()
            if args.trace:
                n = obs_export.write_chrome_trace(evts, args.trace)
                print(f"Trace written: {args.trace} ({n} events; "
                      "open in Perfetto or `tts report`)")
            if args.metrics_file:
                obs_export.write_metrics_jsonl(evts, args.metrics_file)
            if args.costmodel:
                from .obs import costmodel as obs_costmodel

                key, entry = write_costmodel(
                    args, problem, evts, args.costmodel, obs_costmodel
                )
                links = ", ".join(sorted(entry["links"])) or "none"
                print(f"Cost model written: {args.costmodel} [{key}] "
                      f"(links: {links}; arm with TTS_COSTMODEL="
                      f"{args.costmodel})")
        if args.json:
            print(json.dumps(rec))
        if args.stats_file:
            # Append-only stats line, like `stats_pfsp_gpu_cuda.dat`
            # (`pfsp_gpu_cuda.c:140-148`).
            with open(args.stats_file, "a") as f:
                f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
