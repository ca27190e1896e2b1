"""Distributed mesh-resident tier: per-host SPMD engines + DCN exchange.

The dist tier (`parallel/dist.py`) reproduces the reference's semantics with
per-device *offload* workers on every host — faithful, but each chunk pays a
host round trip. This tier is the pod-scale TPU-native composition instead:

  * **inside a host**: the mesh-resident engine (`parallel/resident_mesh.py`)
    owns all local chips with one `shard_map` program — HBM-resident pool
    shards, `lax.while_loop` chunk cycles, `pmin` incumbent folds and
    `ppermute` diffusion riding ICI;
  * **between hosts**: a bulk-synchronous exchange at step boundaries over
    the same `Collectives` interface the dist tier uses (threads for
    testing, `jax.distributed` / DCN on a real pod): incumbent all-reduce,
    deterministic donor->receiver matching with point-to-point node blocks
    through the KV channel, and two-round quiescence termination.

This is exactly SURVEY.md §2.5's prescription — "multi-chip = device mesh +
ICI collectives; multi-host pod = one process per host over DCN with
host-mediated work stealing" — with the reference's two-level hierarchy
(`pfsp_dist_multigpu_chpl.chpl:377-379`: locales over tasks) mapped to
hosts over mesh shards. Donations happen only when a receiver is starved
(its mesh cannot run a chunk), so the hot path stays pure ICI; a donation
costs the donor one frontier download + re-upload, amortized across the
many K-cycle blocks between exchanges.

Counting invariance: exchanges move nodes and tighten incumbents but never
create/destroy nodes, so with a fixed incumbent exploredTree/exploredSol
equal the sequential tier exactly (the same invariant every other tier
pins in tests).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..engine.device import drain, warmup
from ..engine.resident import _emit_device_explored
from ..engine.results import Diagnostics, PhaseStats, SearchResult
from ..obs import counters as obs_counters
from ..obs import events as ev
from ..obs import flightrec as fr
from ..obs import phases as obs_phases
from ..obs import quality as obs_quality
from ..pool import SoAPool
from ..problems.base import INF_BOUND, Problem, batch_length, index_batch
from .dist import (
    JaxCollectives,
    LocalCollectives,
    ThreadCollectives,
    secondary_error,
)
from .resident_mesh import get_mesh_program, make_dp_mp_mesh


def _stride_shards(batch: dict, D: int) -> list[dict]:
    return [{k: v[w::D] for k, v in batch.items()} for w in range(D)]


def _host_loop(
    problem: Problem,
    m: int,
    M: int,
    K: int,
    rounds: int,
    mesh,
    coll,
    initial_best: int | None,
    seed_tag: int = 0,
    exchange_sleep_s: float = 0.0,
    partition_fn=None,
    max_steps: int | None = None,
    checkpoint_path: str | None = None,
    checkpoint_interval_s: float = 60.0,
    resume_from: str | None = None,
) -> dict:
    import jax

    from ..engine import checkpoint as ckpt_mod

    H = coll.num_hosts
    me = coll.host_id
    D = int(mesh.shape[mesh.axis_names[0]])
    best = (
        initial_best
        if initial_best is not None
        else getattr(problem, "initial_ub", INF_BOUND)
    )
    suffix = f".h{me}" if H > 1 else ""
    eff_ckpt = None if checkpoint_path is None else checkpoint_path + suffix
    eff_resume = None if resume_from is None else resume_from + suffix

    diagnostics = Diagnostics()
    t0 = time.perf_counter()

    # -- phase 1: replicate-and-slice warm-up (dist.py's scheme: identical
    # deterministic warm-up everywhere, zero communication; host 0 owns the
    # counters so the cross-host sum counts them once) — or restore --------
    pool = SoAPool(problem.node_fields())
    if eff_resume is not None:
        loaded = ckpt_mod.load(eff_resume, problem, expect_hosts=H)
        if H > 1:
            # Lockstep-cut coherence across the per-host files (same check
            # as the dist tier's resume, multidevice.py): mixed cuts would
            # lose or double-explore nodes donated between rounds.
            tags = coll.allgather_obj(loaded.cut_tag)
            if len(set(tags)) != 1:
                raise ValueError(
                    "incoherent multi-host resume: per-host checkpoint "
                    f"files come from different cuts ({tags}); restore a "
                    "matching set before resuming"
                )
        pool.push_back_bulk(loaded.batch)
        tree1, sol1 = loaded.tree, loaded.sol
        best = min(best, loaded.best)
    else:
        pool.push_back(index_batch(problem.root(), 0))
        tree1, sol1, best = warmup(problem, pool, best, H * D * m)
        if H > 1:
            warm = pool.as_batch()
            pool = SoAPool(problem.node_fields())
            if partition_fn is None:
                pool.push_back_bulk({k: v[me::H] for k, v in warm.items()})
            else:
                pool.push_back_bulk(partition_fn(warm, me, H))
            if me != 0:
                tree1 = sol1 = 0
    t1 = time.perf_counter()
    ev.counter("explored", host=me, tree=tree1, sol=sol1, phase=1)

    # -- phase 2: per-host SPMD loop + step-boundary exchanges --------------
    from ..engine.pipeline import (
        AdaptiveK,
        DispatchQueue,
        MESH_TARGET,
        resolve_k,
        resolve_pipeline_depth,
        resolve_target_band,
    )
    from ..engine.resident import resolve_capacity

    capacity, M = resolve_capacity(problem, M, None)
    T = max(2 * m, min(M, 8192))
    # Per-host adaptive K (TTS_K=auto): each host resizes its own program
    # along the shared geometric ladder — hosts already run different
    # cycle counts per exchange round, so differing K across hosts changes
    # nothing the exchange protocol depends on. The mesh target band keeps
    # K bounded by exchange responsiveness — it IS this tier's exchange
    # period (exchanges ride dispatch boundaries); with TTS_COSTMODEL it
    # resolves from the measured dispatch-latency fit, and the idle-host
    # exchange back-off from the measured exchange-round latency.
    k_auto, k_value = resolve_k(K, default_max=16)
    band, band_src = resolve_target_band(
        "dist_mesh", MESH_TARGET, problem, topology=f"dist_mesh-H{H}xD{D}"
    )
    if band_src is not None and exchange_sleep_s == 0.0:
        from ..obs import costmodel as cm

        prof = cm.load(cm.costmodel_path() or "")
        hit = cm.lookup(prof or {}, *band_src.split("|")) if prof else None
        measured_sleep = cm.exchange_sleep_s(hit[1]) if hit else None
        if measured_sleep is not None:
            exchange_sleep_s = measured_sleep
    # Steal policy (TTS_STEAL, parallel/topology.py): flat keeps this
    # tier's single-level donor->needy matching byte-identical; hier
    # layers the near/far schedule over the same lockstep rounds. The
    # exchange period here IS the dispatch cadence, so the far-period
    # resolution uses the adaptive-K target band's midpoint as the base
    # interval (or the measured idle back-off when one resolved).
    policy = None
    if H > 1:
        from .topology import Topology, resolve_policy

        dev0 = next(iter(mesh.devices.flat), None)
        slice_idx = getattr(dev0, "slice_index", None)
        topo = Topology.detect(
            H, slice_index=slice_idx,
            allgather=coll.allgather_obj if slice_idx is not None else None,
        )
        from ..ops.pallas_kernels import platform

        policy = resolve_policy(
            problem, topo, m=m, cap=D * M,
            interval_s=exchange_sleep_s or (band[0] + band[1]) / 2.0,
            backend=platform(),
            topo_str=f"dist_mesh-H{H}xD{D}",
        )
    ctl = AdaptiveK(k_value, target=band) if k_auto else None
    depth = resolve_pipeline_depth()
    program = get_mesh_program(problem, mesh, m, M,
                               ctl.K if ctl else k_value, rounds, T, capacity)

    state = program.init_state(_stride_shards(pool.as_batch(), D), best)
    pool.clear()
    diagnostics.host_to_device += 1

    from ..analysis.guard import SteadyStateGuard, guard_enabled

    genabled = guard_enabled(None)
    guards: dict[int, SteadyStateGuard] = {}

    def guard_of(prog) -> SteadyStateGuard:
        g = guards.get(id(prog))
        if g is None:
            g = guards[id(prog)] = SteadyStateGuard(
                prog._step, "dist-mesh step", enabled=genabled
            )
        return g

    tree2 = 0
    sol2 = 0
    steps = 0
    completed = True  # flipped off on a max_steps cutoff
    quiescent_streak = 0
    blocks_sent = blocks_received = 0
    nodes_sent = nodes_received = 0
    exch_rounds = 0
    per_worker = np.zeros(D, dtype=np.int64)

    ctr_total: dict | None = None
    ph_total: dict | None = None  # per-phase ns totals (TTS_PHASEPROF=1)
    prev_best = best
    # Anytime quality (host-local trajectory, like the obs counters; an
    # exchange-adopted global incumbent lands at the next dispatch read).
    qt = obs_quality.tracker(problem)
    sizes = np.zeros(D, dtype=np.int32)
    n_disp = 0  # completed-dispatch sequence (flight-recorder registry)
    queue = DispatchQueue(depth)
    last_ready = time.monotonic()

    def enqueue() -> None:
        nonlocal state
        t_enq = ev.now_us()
        with guard_of(program).step():
            out = program.step(state)
        state = program.carry(out)
        queue.push(out, t_enq)

    def consume(out, t_enq) -> tuple[int, int, int]:
        nonlocal tree2, sol2, sizes, best, ctr_total, ph_total, prev_best
        nonlocal per_worker, n_disp
        t_wait = ev.now_us()
        ti, si, cy, sizes, best, tree_vec, ctr = program.read_scalars(out)
        phb = program.read_phase_block(out)
        tree2 += ti
        sol2 += si
        n_disp += 1
        per_worker += tree_vec.astype(np.int64)
        diagnostics.kernel_launches += cy
        if ctr is not None:
            ctr_total = obs_counters.merge_host(ctr_total, ctr)
        if phb is not None:
            ph_total = obs_phases.merge_host(ph_total, phb)
        fr.heartbeat("dist_mesh", host=me, seq=n_disp, cycles=cy,
                     size=int(sizes.sum()), best=int(best), tree=tree2,
                     sol=sol2, depth=depth, K=program.K,
                     inflight=len(queue), phases=ph_total)
        if qt is not None:
            qt.observe(best, n_disp, tree1 + tree2)
        if ev.enabled():
            now = ev.now_us()
            ev.emit("dispatch", ph="X", ts=t_enq, host=me,
                    dur=max(0.0, now - t_enq), args={
                        "cycles": cy, "tree": ti, "sol": si,
                        "size": int(sizes.sum()), "best": int(best),
                        "shard_sizes": sizes.tolist(),
                        "enqueue_us": t_enq, "read_wait_us": now - t_wait,
                        "pipeline_depth": depth,
                    })
            if ctr is not None:
                ev.counter("device_counters", host=me,
                           **obs_counters.as_args(ctr))
            if phb is not None:
                ev.counter("device_phases", host=me,
                           **obs_phases.as_args(phb))
            if best < prev_best:
                ev.emit("incumbent", host=me, args={"best": int(best)})
        prev_best = best
        return ti, si, cy

    def drain_queue() -> None:
        # Coherence barrier: any action that downloads/snapshots the pool
        # (donations, lockstep cuts) must first fold every in-flight
        # speculative dispatch's counts — the frontier includes their work.
        for out, t_enq in queue.drain():
            consume(out, t_enq)

    def download() -> SoAPool:
        nonlocal best
        drain_queue()
        batch = program.full_batch(state)
        diagnostics.device_to_host += 1
        p = SoAPool(problem.node_fields())
        p.push_back_bulk(batch)
        return p

    def upload(p: SoAPool):
        nonlocal state, last_ready
        state = program.init_state(_stride_shards(p.as_batch(), D), best)
        diagnostics.host_to_device += 1
        # Donation-round re-uploads are sanctioned host round trips: the
        # next dispatch is a fresh warm one for the steady-state guard.
        guard_of(program).rearm()
        last_ready = time.monotonic()

    import pickle
    import uuid as _uuid

    # Checkpointing: lockstep cuts at exchange boundaries. The cut point —
    # right after a round's allgather, before its donations — is provably
    # donation-coherent: the allgather is a barrier, so every prior round's
    # blocks are integrated on both ends and none are in flight. Host 0
    # proposes "<uuid>:<round>" in the control tuple; every host stamps
    # that exact tag (resume verifies coherence collectively).
    run_uuid = _uuid.uuid4().hex[:12]
    ckpt_last = time.monotonic()

    def do_lockstep_cut(tag) -> None:
        drain_queue()  # counters must cover the snapshot's in-flight work
        staging = eff_ckpt + ".staging"
        ok = True
        t_cut = ev.now_us()
        try:
            batch = program.full_batch(state)
            diagnostics.device_to_host += 1
            ckpt_mod.save(staging, problem, batch, best,
                          tree1 + tree2, sol1 + sol2, hosts=H, cut_tag=tag)
        except Exception:  # noqa: BLE001 — a failed host must veto commit
            ok = False
        ckpt_mod.lockstep_commit(
            ok, staging, eff_ckpt,
            vote=coll.allgather_obj if H > 1 else None,
        )
        ev.complete("checkpoint", t_cut, wid=ev.COMM_TID, host=me,
                    args={"tag": str(tag), "ok": ok})

    fr.arm("dist_mesh")
    ev.emit("pipeline", host=me, args={
        "depth": depth, "K": program.K, "k_auto": k_auto, "tier": "dist_mesh",
    })
    if band_src is not None:
        ev.emit("costmodel", host=me, args={
            "source": band_src, "lo_ms": round(1e3 * band[0], 1),
            "hi_ms": round(1e3 * band[1], 1), "tier": "dist_mesh",
        })

    while True:
        while not queue.full:
            enqueue()
        out, t_enq = queue.pop()
        ti, si, cy = consume(out, t_enq)
        now = time.monotonic()
        period, last_ready = now - last_ready, now
        steps += 1
        total = int(sizes.sum())
        if ctl is not None and cy > 0 and ctl.observe(period, cy):
            drain_queue()
            program = get_mesh_program(problem, mesh, m, M, ctl.K, rounds,
                                       T, capacity)
            ev.emit("k_resize", host=me, args={"K": program.K})
            last_ready = time.monotonic()
            total = int(sizes.sum())
        # Idle = this host's mesh cannot run another chunk cycle anywhere.
        idle = int(sizes.max()) < m
        if max_steps is not None and steps >= max_steps:
            completed = False  # budget cutoff, not quiescence
            if eff_ckpt is not None:
                # Final lockstep cut so the budgeted run is resumable; all
                # hosts reach this point in the same iteration, and host
                # 0's tag rides a dedicated allgather.
                tag = f"{run_uuid}:cutoff{steps}"
                if H > 1:
                    tag = coll.allgather_obj(tag)[0]
                do_lockstep_cut(tag)
            break
        if H == 1:
            if (eff_ckpt is not None
                    and time.monotonic() - ckpt_last
                    >= checkpoint_interval_s):
                do_lockstep_cut(f"{run_uuid}:{steps}")
                ckpt_last = time.monotonic()
            if idle:
                break
            continue
        # Bulk-synchronous exchange (the dist tier's control-round shape).
        exch_rounds += 1
        want_ckpt = (
            eff_ckpt is not None and me == 0
            and time.monotonic() - ckpt_last >= checkpoint_interval_s
        )
        cut_id = f"{run_uuid}:{exch_rounds}" if want_ckpt else None
        # The exchange is a SPAN (not an instant): its duration is the
        # measured DCN/KV control-round latency — the "exchange" link
        # class of the cost model (obs/costmodel.py).
        t_x = ev.now_us()
        rows = coll.allgather_obj(
            (total, bool(idle), int(best), want_ckpt, cut_id)
        )
        gbest = min(r[2] for r in rows)
        ev.complete("exchange", t_x, wid=ev.COMM_TID, host=me, args={
            "round": exch_rounds, "size": total, "best": int(gbest),
            "idle": bool(idle),
        })
        if gbest < best:
            # Inject the global incumbent into the sharded state: the best
            # vector is a tiny (D,) array — replace it in place with the
            # same sharding, no pool touch.
            pv, pa, sz, bst = state
            bst = jax.device_put(
                np.minimum(np.asarray(bst), gbest).astype(np.int32),
                program._sh_vec,
            )
            state = (pv, pa, sz, bst)
            best = gbest
        if eff_ckpt is not None and rows[0][3]:
            # Cut point: after incumbent adoption (the snapshot carries the
            # tightened best), before this round's donations.
            do_lockstep_cut(rows[0][4])
            ckpt_last = time.monotonic()
        totals = [r[0] for r in rows]
        idles = [r[1] for r in rows]
        donors = sorted(
            (h for h in range(H) if totals[h] >= 4 * D * m),
            key=lambda h: (-totals[h], h),
        )
        needy = sorted(
            (h for h in range(H) if idles[h]),
            key=lambda h: (totals[h], h),
        )
        if policy is not None and policy.hier:
            # Two-level matching (topology.py): near pairs every round,
            # far pairs on far rounds for near-unmatched needy only. Same
            # allgathered inputs + same round counter on every host ->
            # identical pairs, exactly like the flat zip.
            pairs = [(d, r)
                     for d, r in policy.match(donors, needy, exch_rounds,
                                              sizes=totals)
                     if d != r]
        else:
            pairs = [(d, r) for d, r in zip(donors, needy) if d != r]
        if all(idles) and not pairs:
            quiescent_streak += 1
            if quiescent_streak >= 2:
                ev.emit("terminate", wid=ev.COMM_TID, host=me,
                        args={"round": exch_rounds})
                break
            continue
        quiescent_streak = 0
        send_to = next((r for d, r in pairs if d == me), None)
        recv_from = next((d for d, r in pairs if r == me), None)
        if send_to is not None:
            # Donor: download the frontier, split off the FRONT (oldest,
            # shallowest — `Pool_par.chpl:180-191`) capped at D*M nodes,
            # re-upload the rest. One transfer each way, only on donation
            # rounds.
            link = policy.link(me, send_to)
            p = download()
            # Steal-half-from-front policy, capped (the dist tier's bounded
            # donation: a huge frontier never ships unbounded over DCN).
            # Flat cap is the legacy D*M; hier caps per link class so far
            # links ship their resolved bulk quantum.
            block = p.pop_front_bulk_half(m, 0.5, cap=policy.cap_for(link))
            blob = pickle.dumps(block)
            # Donation SPAN over the KV put alone (bytes + duration — the
            # "donate" bandwidth sample of the cost model); the frontier
            # download/re-upload around it is charged to the donor's own
            # dispatch gap, not the link. The simulated link latency
            # (TTS_SIM_LAT_*) sleeps INSIDE the span so injected latency
            # lands in the measured donate:{link} fit.
            t_d = ev.now_us()
            policy.sim.sleep(link)
            coll.kv_set(
                f"tts/dmesh/{exch_rounds}/{me}->{send_to}", blob
            )
            if block is not None:
                blocks_sent += 1
                nodes_sent += batch_length(block)
                ev.complete("donate_send", t_d, wid=ev.COMM_TID, host=me,
                            args={"peer": send_to,
                                  "nodes": batch_length(block),
                                  "bytes": len(blob),
                                  "round": exch_rounds,
                                  "link": link,
                                  "level": policy.level_of(link)})
            upload(p)
        if recv_from is not None:
            link = policy.link(recv_from, me)
            t_d = ev.now_us()
            raw = coll.kv_get(
                f"tts/dmesh/{exch_rounds}/{recv_from}->{me}",
                timeout_s=120.0,
            )
            block = pickle.loads(raw)
            if block is not None:
                # Span covers the KV wait (donor prep + transfer): the
                # measured cost of receiving a donation block.
                ev.complete("donate_recv", t_d, wid=ev.COMM_TID, host=me,
                            args={"peer": recv_from,
                                  "nodes": batch_length(block),
                                  "bytes": len(raw),
                                  "round": exch_rounds,
                                  "link": link,
                                  "level": policy.level_of(link)})
                p = download()
                p.push_back_bulk(block)
                upload(p)
                blocks_received += 1
                nodes_received += batch_length(block)
                fr.note_steal(me, link, policy.level_of(link))
        if idle and recv_from is None and exchange_sleep_s:
            time.sleep(exchange_sleep_s)

    # -- phase 3: local residual drain --------------------------------------
    drain_queue()  # remaining speculative dispatches are no-ops by now
    batch = program.residual_batch(state)
    diagnostics.device_to_host += 1
    pool.reset_from(batch)
    t2 = time.perf_counter()
    _emit_device_explored(ctr_total, tree2, sol2, 0, 0, host=me)
    tree3, sol3, best = drain(problem, pool, best)
    t3 = time.perf_counter()
    ev.counter("explored", host=me, tree=tree3, sol=sol3, phase=3)
    if qt is not None:
        # The host drain can improve the incumbent one last time.
        qt.observe(best, n_disp, tree1 + tree2 + tree3)

    return {
        "tree": tree1 + tree2 + tree3,
        "sol": sol1 + sol2 + sol3,
        "best": best,
        "steals": blocks_received,
        "elapsed": t3 - t0,
        "phases": [
            PhaseStats(t1 - t0, tree1, sol1),
            PhaseStats(t2 - t1, tree2, sol2),
            PhaseStats(t3 - t2, tree3, sol3),
        ],
        "diag": diagnostics,
        "per_worker_tree": per_worker.tolist(),
        "comm": {
            "rounds": exch_rounds,
            "blocks_sent": blocks_sent,
            "blocks_received": blocks_received,
            "nodes_sent": nodes_sent,
            "nodes_received": nodes_received,
        },
        # Resolved steal policy (identical on every host — env + profile
        # resolution only); None below the exchange threshold (H == 1).
        "steal_policy": policy.describe() if policy is not None else None,
        "complete": completed,
        # Survivor-path mode the per-host SPMD step baked in (identical on
        # every host: same knob, same problem shape, same device platform).
        "compact": program.inner.compact,
        "compact_auto": program.inner.compact_auto,
        # Pipeline/K the host loop ran with (host-local: adaptive K may
        # land hosts on different ladder rungs).
        "pipeline_depth": depth,
        "k_resolved": program.K,
        "k_auto": k_auto,
        # Host-local counter totals (not reduced — per-host telemetry).
        "obs": (
            {
                **({"device_counters": ctr_total}
                   if ctr_total is not None else {}),
                **({"device_phases": ph_total}
                   if ph_total is not None else {}),
            }
            if (ctr_total is not None or ph_total is not None) else None
        ),
        # Host-local per-phase ns totals (TTS_PHASEPROF=1, obs/phases.py).
        "phase_profile": ph_total,
        # Host-local incumbent trajectory (obs/quality.py; not reduced).
        "quality": qt.result() if qt is not None else None,
    }


def _reduce(local: dict, coll) -> SearchResult:
    comm = {k: coll.allreduce_sum(v) for k, v in local["comm"].items()}
    return SearchResult(
        explored_tree=coll.allreduce_sum(local["tree"]),
        explored_sol=coll.allreduce_sum(local["sol"]),
        best=coll.allreduce_min(local["best"]),
        elapsed=coll.allreduce_max(local["elapsed"]),
        phases=local["phases"],
        diagnostics=local["diag"],
        per_worker_tree=local["per_worker_tree"],
        steals=coll.allreduce_sum(local["steals"]),
        comm=comm,
        steal_policy=local.get("steal_policy"),
        complete=bool(coll.allreduce_min(int(local["complete"]))),
        compact=local.get("compact"),
        compact_auto=local.get("compact_auto", False),
        pipeline_depth=local.get("pipeline_depth", 1),
        k_resolved=local.get("k_resolved"),
        k_auto=local.get("k_auto", False),
        obs=local.get("obs"),
        phase_profile=local.get("phase_profile"),
        quality=local.get("quality"),
    )




def dist_mesh_search(
    problem: Problem,
    m: int = 25,
    M: int = 16384,
    K: int | str = 16,
    rounds: int = 2,
    D: int | None = None,
    mp: int = 1,
    num_hosts: int | None = None,
    devices=None,
    initial_best: int | None = None,
    partition_fn=None,
    max_steps: int | None = None,
    checkpoint_path: str | None = None,
    checkpoint_interval_s: float = 60.0,
    resume_from: str | None = None,
) -> SearchResult:
    """Pod-scale search: per-host mesh-resident SPMD engines, DCN exchange.

    * Under ``jax.distributed`` (process_count > 1): this process builds a
      dp (or dp x mp) mesh over its local devices and exchanges with peers
      over the coordination service.
    * Single process with ``num_hosts=H > 1``: H virtual hosts in threads
      over disjoint local-device groups (testing mode).
    * ``num_hosts`` unset/1: degenerates to ``mesh_resident_search``
      semantics (no exchange).
    * ``mp > 1`` (PFSP lb2 only): each host's mesh gains the machine-pair
      model-parallel axis; the staged evaluator composes per shard
      (`pfsp_device.lb2_self_bounds_mp`).
    """
    import jax

    if jax.process_count() > 1:
        coll = JaxCollectives()
        local_devices = jax.local_devices() if devices is None else devices
        if D is None:
            D = max(1, len(local_devices) // mp)
        local = _host_loop(
            problem, m, M, K, rounds, make_dp_mp_mesh(local_devices, D, mp),
            coll, initial_best,
            partition_fn=partition_fn, max_steps=max_steps,
            checkpoint_path=checkpoint_path,
            checkpoint_interval_s=checkpoint_interval_s,
            resume_from=resume_from,
        )
        return _reduce(local, coll)

    all_devices = jax.devices() if devices is None else devices
    H = num_hosts or 1
    if H == 1:
        if D is None:
            D = max(1, len(all_devices) // mp)
        local = _host_loop(
            problem, m, M, K, rounds, make_dp_mp_mesh(all_devices, D, mp),
            LocalCollectives(), initial_best, max_steps=max_steps,
            checkpoint_path=checkpoint_path,
            checkpoint_interval_s=checkpoint_interval_s,
            resume_from=resume_from,
        )
        return _reduce(local, LocalCollectives())

    if H > len(all_devices):
        raise ValueError(
            f"num_hosts={H} exceeds available devices ({len(all_devices)})"
        )
    groups = [all_devices[h::H] for h in range(H)]
    if D is None:
        D = max(1, min(len(g) for g in groups) // mp)
    coll = ThreadCollectives(H)
    results: list = [None] * H
    errors: list = [None] * H

    def host_main(h: int):
        try:
            local = _host_loop(
                problem, m, M, K, rounds, make_dp_mp_mesh(groups[h], D, mp),
                coll.bind(h), initial_best,
                partition_fn=partition_fn, max_steps=max_steps,
                checkpoint_path=checkpoint_path,
                checkpoint_interval_s=checkpoint_interval_s,
                resume_from=resume_from,
            )
            results[h] = _reduce(local, coll)
        except BaseException as e:
            errors[h] = e
            try:
                coll._barrier.abort()
            except Exception:
                pass

    threads = [
        threading.Thread(target=host_main, args=(h,), name=f"tts-dmesh-{h}")
        for h in range(H)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    real = [e for e in errors if e is not None and not secondary_error(e)]
    for e in real or errors:
        if e is not None:
            raise e
    global_res = results[0]
    global_res.per_worker_tree = [
        t for r in results for t in r.per_worker_tree
    ]
    return global_res
