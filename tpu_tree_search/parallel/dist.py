"""Distributed multi-host tier.

Reproduces the reference's distributed tier semantics
(`pfsp_dist_multigpu_chpl.chpl:313-647`; MPI baseline:
`pfsp_dist_multigpu_cuda.c:330-816`) on jax's multi-process model:

  * **warm-up**: the reference warms up on locale 0 and scatters
    (`pfsp_dist_multigpu_chpl.chpl:339-374`). TPU hosts share no memory, so
    instead every host runs the *identical deterministic* warm-up to
    ``H * D * m`` nodes and takes its stride-H slice — zero communication,
    byte-identical partitions (replicate-and-slice; the warm-up is pure
    host compute, seconds at most).
  * **per-host step 2**: the multi-device worker runtime (partition, work
    stealing, idle-scan termination) over the host's local devices — exactly
    the inner ``coforall gpuID`` tier (`pfsp_dist_multigpu_chpl.chpl:406-470`).
  * **no inter-host stealing in v1** — the semantics of the reference's MPI
    baseline, which only reconciles at the end
    (`pfsp_dist_multigpu_cuda.c:570-623`, SURVEY.md §2.5). (The Chapel tier's
    PGAS remote steals have no ICI analogue; host-RPC stealing is the
    planned extension.)
  * **step 3**: each host drains its own leftovers (the MPI baseline gathers
    them to rank 0 and drains there, `pfsp_dist_multigpu_cuda.c:741-790`;
    local drains produce the same totals without the gather).
  * **final reductions**: tree/sol summed, best min-reduced, time max-reduced
    across hosts — `MPI_Reduce` equivalents (`pfsp_dist_multigpu_cuda.c:680-694`)
    over jax collectives.

Communication is abstracted behind a tiny ``Collectives`` interface so the
same driver runs: single-process (``LocalCollectives``), N virtual hosts in
threads for testing (``ThreadCollectives``, the oversubscribed-locale
smoke-test mode of SURVEY.md §4.6), and real multi-host pods
(``JaxCollectives`` over jax.distributed / DCN).
"""

from __future__ import annotations

import threading

import numpy as np

from ..engine.results import SearchResult
from ..obs import events as ev
from ..obs import flightrec as fr
from ..pool import ParallelSoAPool
from ..problems.base import Problem
from .multidevice import host_pipeline


def secondary_error(e: BaseException) -> bool:
    """True for errors a virtual host raises only BECAUSE a peer aborted
    the shared barrier (BrokenBarrierError inside a collective, or kv_get's
    TimeoutError("... (peer aborted)")) — never the root cause."""
    return isinstance(e, threading.BrokenBarrierError) or (
        isinstance(e, TimeoutError) and "peer aborted" in str(e)
    )


class LocalCollectives:
    """H=1 degenerate collectives."""

    num_hosts = 1
    host_id = 0

    def allreduce_sum(self, value: int) -> int:
        return value

    def allreduce_min(self, value: int) -> int:
        return value

    def allreduce_max(self, value) -> float:
        return value

    def allgather_obj(self, value) -> list:
        return [value]

    def kv_set(self, key: str, value: bytes) -> None:
        self._kv = getattr(self, "_kv", {})
        self._kv[key] = value

    def kv_get(self, key: str, timeout_s: float) -> bytes:
        try:
            return getattr(self, "_kv", {}).pop(key)
        except KeyError:
            raise TimeoutError(f"kv_get({key!r}): no such key") from None


class ThreadCollectives:
    """In-process collectives for H virtual hosts running in threads (the
    multi-host smoke-test mode; cf. the reference's oversubscribed UDP
    locales, `g5k_dist_multigpu_nvidia.sh:33`)."""

    def __init__(self, num_hosts: int):
        self.num_hosts = num_hosts
        self._barrier = threading.Barrier(num_hosts)
        self._lock = threading.Lock()
        self._values: list = [None] * num_hosts
        self._local = threading.local()
        self._kv: dict = {}  # guarded-by: _kv_cond
        self._kv_cond = threading.Condition()

    def bind(self, host_id: int):
        """Each participating thread binds its host id once."""
        self._local.host_id = host_id
        return self

    @property
    def host_id(self) -> int:
        return self._local.host_id

    def _exchange(self, value):
        self._values[self.host_id] = value
        self._barrier.wait()
        vals = list(self._values)
        self._barrier.wait()
        return vals

    def allreduce_sum(self, value):
        return sum(self._exchange(value))

    def allreduce_min(self, value):
        return min(self._exchange(value))

    def allreduce_max(self, value):
        return max(self._exchange(value))

    def allgather_obj(self, value) -> list:
        return self._exchange(value)

    def kv_set(self, key: str, value: bytes) -> None:
        with self._kv_cond:
            self._kv[key] = value
            self._kv_cond.notify_all()

    def kv_get(self, key: str, timeout_s: float) -> bytes:
        import time as _time

        deadline = _time.monotonic() + timeout_s
        with self._kv_cond:
            while key not in self._kv:
                # Short wait slices so an aborted peer (broken barrier)
                # is noticed promptly even though aborts don't notify us.
                if self._barrier.broken or _time.monotonic() > deadline:
                    raise TimeoutError(
                        f"kv_get({key!r}) timed out"
                        + (" (peer aborted)" if self._barrier.broken else "")
                    )
                self._kv_cond.wait(timeout=0.05)
            return self._kv.pop(key)


class JaxCollectives:
    """Real multi-host collectives over jax.distributed (DCN). The launcher
    must have called ``jax.distributed.initialize``; every host participates
    in every call (the reductions happen only at start/end, mirroring the
    MPI baseline's join-point-only communication, SURVEY.md §2.5).

    The whole control plane rides the coordination service (the same
    DCN-backed KV store jax.distributed itself uses for barriers) rather
    than XLA array collectives: control tuples are a few hundred bytes at
    exchange boundaries, where a device dispatch per round would cost more
    than it moves — and a dead peer surfaces as a bounded-timeout error
    here (fail-stop with a root cause) instead of a hung collective."""

    #: Bounded wait for any single control-plane step (a peer's round blob,
    #: the cleanup barrier): seconds here mean a dead or wedged peer, so
    #: the exchange raises — fail-stop — instead of hanging the search.
    AG_TIMEOUT_S = 120.0

    def __init__(self):
        import jax

        self.num_hosts = jax.process_count()
        self.host_id = jax.process_index()
        self._round = 0  # per-call key uniqueness (all hosts count together)

    def allreduce_sum(self, value):
        return type(value)(sum(self.allgather_obj(value)))

    def allreduce_min(self, value):
        return type(value)(min(self.allgather_obj(value)))

    def allreduce_max(self, value):
        return type(value)(max(self.allgather_obj(value)))

    def allgather_obj(self, value) -> list:
        """RAGGED arbitrary-object allgather: each host posts its pickled
        blob once at a round-unique key and every peer reads exactly the
        bytes each sender wrote — the exchange payload scales with the
        actual sizes (sum of the blobs per receiver), not H x max-length
        as the old padded array-allgather did. Only small control tuples
        travel this way — node payloads go point-to-point via ``kv_set`` /
        ``kv_get``, never all-to-all. Cleanup: a blob has H-1 readers, so
        the sender may only delete its key after the round's barrier
        proves every peer has read it (kv_get's delete-after-first-read
        would lose it for the rest)."""
        import pickle

        if self.num_hosts == 1:
            return [value]
        client = self._client()
        r = self._round
        self._round += 1
        me = self.host_id
        tmo_ms = int(self.AG_TIMEOUT_S * 1000)
        client.key_value_set_bytes(f"tts/agobj/{r}/{me}", pickle.dumps(value))
        out = []
        for h in range(self.num_hosts):
            if h == me:
                out.append(value)
            else:
                out.append(pickle.loads(
                    client.blocking_key_value_get_bytes(
                        f"tts/agobj/{r}/{h}", tmo_ms
                    )
                ))
        client.wait_at_barrier(f"tts/agobj/{r}/done", tmo_ms)
        client.key_value_delete(f"tts/agobj/{r}/{me}")
        return out

    @staticmethod
    def _client():
        from jax._src import distributed

        client = distributed.global_state.client
        if client is None:
            raise RuntimeError(
                "jax.distributed KV store unavailable (initialize() not "
                "called?)"
            )
        return client

    def kv_set(self, key: str, value: bytes) -> None:
        """Point-to-point donation delivery over the jax.distributed
        coordination service's KV store — the DCN analogue of the CUDA
        baseline's point-to-point steal (`Pool_ext.c:138-151`); non-receivers
        never see the payload (vs. the broadcast allgather)."""
        self._client().key_value_set_bytes(key, value, allow_overwrite=True)

    def kv_get(self, key: str, timeout_s: float) -> bytes:
        client = self._client()
        data = client.blocking_key_value_get_bytes(
            key, int(timeout_s * 1000)
        )
        try:
            client.key_value_delete(key)
        except Exception:
            pass  # cleanup is best-effort; keys are round-unique
        return data


class _HostComm:
    """Per-host communicator: periodic cross-host incumbent exchange,
    host-mediated work stealing, and two-level termination.

    The Chapel reference steals across locales with remote CAS on the
    victim's pool lock (`pfsp_dist_multigpu_chpl.chpl:520-567`) — TPU hosts
    share no memory, so the steal is host-mediated (SURVEY.md §2.5): each
    host runs this loop in a thread next to its workers, and every
    ``interval_s`` all hosts meet in a bulk-synchronous exchange round:

      1. allgather ``(pool_size, best, all_workers_idle)``;
      2. every host adopts the global min incumbent (the periodic UB
         all-reduce the reference lacks, BASELINE.json north star);
      3. rich hosts (size >= 2m) are deterministically matched to starving
         idle hosts (same gathered data on every host -> same matching, no
         handshake); each donor locks its fullest local pool and pops half
         its *front* (`Pool_par.chpl:180-191` policy) **capped at M nodes**
         (the mesh tier's bounded-donation policy,
         `resident_mesh.py` diffusion cap), and delivers the block
         *point-to-point* through the collectives' KV channel — only the
         matched receiver ever sees the payload (the CUDA baseline's
         point-to-point steal semantics, `Pool_ext.c:138-151`, vs. a
         broadcast);
      4. two consecutive rounds with all hosts idle, no donations, and only
         drain-sized leftovers end the loop everywhere at once (two-level
         termination, `pfsp_dist_multigpu_chpl.chpl:569-587`; the second
         round re-samples pool sizes so a momentarily-between-polls worker
         can't divert poppable work to the serial host drain); local
         workers then exit via ``stop_event`` and the per-host drain picks
         up any sub-chunk remainder, so no work is ever lost.

    When every host is busy and none is needy, the exchange cadence backs
    off geometrically (up to 16x ``interval_s``) and resets the moment any
    host reports need — a balanced run pays almost no collective overhead.

    Under ``TTS_STEAL=hier`` (parallel/topology.py) the matching in step 3
    becomes two-level: near (intra-pod ICI) donor->needy pairs every
    round with the near quantum, far (inter-pod DCN) pairs only every
    ``far_every``-th round — and only for needy hosts the near level
    could not feed — with the bulk far quantum. The round counter
    advances in lockstep, so the level schedule is identical on every
    host and the flat policy's no-handshake property is preserved.
    ``TTS_STEAL=flat`` (default) keeps the single-level matching above
    byte/behavior-identical.
    """

    #: kv_get wait for a matched donation (donor is alive and popping from
    #: a local pool; seconds would indicate a dead peer -> fail-stop).
    KV_TIMEOUT_S = 120.0
    BACKOFF_MAX = 16  # cadence back-off cap (x interval_s)

    def __init__(self, collectives, m: int, perc: float = 0.5,
                 interval_s: float = 0.02, M: int = 50000,
                 ckpt_interval_s: float = 60.0, policy=None):
        from .topology import StealPolicy, Topology

        self.coll = collectives
        # Captured here (construction happens on the bound host thread):
        # ThreadCollectives.host_id is thread-local and the communicator
        # runs in its own thread, which re-binds with this value.
        self.me = collectives.host_id
        self.m = m
        self.M = M
        self.perc = perc
        self.interval_s = interval_s
        self.policy = policy or StealPolicy(
            mode="flat", topology=Topology(collectives.num_hosts), m=m,
            cap=M, interval_s=interval_s,
        )
        self.rounds = 0
        self.blocks_sent = 0
        self.blocks_received = 0
        self.nodes_sent = 0
        self.nodes_received = 0
        self.error: BaseException | None = None
        self._inflight = None  # popped-but-undelivered donation block
        # Checkpointing (set by run_workers when --checkpoint is active):
        # host 0's clock decides WHEN; the decision rides the round's
        # control tuple so every host snapshots in the same lockstep round
        # — donations complete within a round, so no node can straddle the
        # cut and the union of the per-host files is the exact frontier.
        self.ckpt_mgr = None
        self.ckpt_interval_s = ckpt_interval_s
        self._ckpt_last = None
        # Cut identity: host 0 proposes "<uuid>:<round>" in the round's
        # control tuple and every host stamps that exact string into its
        # per-host file, so resume can prove all files belong to the same
        # lockstep cut of the same run (stale files from a prior run with
        # the same host count, or files from two different cuts after a
        # mid-commit crash, must be refused — they describe incoherent
        # frontiers).
        import uuid as _uuid

        self._run_uuid = _uuid.uuid4().hex[:12]

    def _donate_from(self, pools: list[ParallelSoAPool], cap: int | None = None):
        """Locked front-steal from the fullest local pool (on behalf of a
        remote host); None when no pool can spare a block. Blocks are capped
        (M nodes flat; the link-class quantum under hier) so a huge pool
        never ships an unbounded payload over DCN (the reference steals
        perc-of-pool uncapped, `Pool_ext.c:138-151`; the mesh tier here
        caps donations — same policy)."""
        # (No waiver needed: guarded-by does not descend into lambda
        # bodies, so the advisory racy read in the key fn is out of its
        # scope — the pop below re-checks size under try_lock anyway.)
        victim = max(pools, key=lambda p: p.size)
        # tts-lint: waive guarded-by -- advisory racy size read; pop_front_bulk_half re-checks the 2m threshold under the lock
        if victim.size < 2 * self.m:
            return None
        if victim.try_lock():
            try:
                return victim.pop_front_bulk_half(
                    self.m, self.perc, cap=self.M if cap is None else cap
                )
            finally:
                victim.unlock()
        return None

    def run(self, pools: list[ParallelSoAPool], states, shared, stop_event):
        bind = getattr(self.coll, "bind", None)
        if bind is not None:
            bind(self.me)
        try:
            self._loop(pools, states, shared, stop_event)
        except BaseException as e:  # never leave workers polling forever
            self.error = e
            stop_event.set()
            # A block popped for donation but not delivered must not be
            # lost — requeue it locally (counts stay exact; the search
            # just keeps the work).
            if self._inflight is not None:
                pools[0].locked_push_back_bulk(self._inflight)
                self._inflight = None
            # ThreadCollectives: wake peers blocked in the barrier. Real
            # multi-host (JaxCollectives) has no abort — a dead host stalls
            # the collective, jax's fail-stop model (the reference behaves
            # identically: a crashed locale hangs allIdle, SURVEY.md §5).
            barrier = getattr(self.coll, "_barrier", None)
            if barrier is not None:
                try:
                    barrier.abort()
                except Exception:
                    pass

    def _loop(self, pools: list[ParallelSoAPool], states, shared, stop_event):
        import pickle
        import time as _time

        coll = self.coll
        H = coll.num_hosts
        me = self.me
        rrobin = 0
        backoff = 1  # cadence multiplier (adaptive back-off)
        quiescent_streak = 0
        from ..problems.base import batch_length

        while True:
            _time.sleep(self.interval_s * backoff)
            if states.flag.is_set():  # a worker died: abort everywhere
                stop_event.set()
                abort = getattr(coll, "_barrier", None)
                if abort is not None:
                    abort.abort()
                return
            self.rounds += 1
            # tts-lint: waive guarded-by -- advisory racy size sample for the control tuple; quiescence needs two consecutive all-idle rounds
            size = sum(p.size for p in pools)
            # Donations come from a single pool, so donor eligibility and
            # the quiescence test must use the *largest pool*, not the host
            # sum: D pools can each hold m-1 drain-leftover nodes — a host
            # sum >= 2m that no pool can ever donate would loop forever.
            # tts-lint: waive guarded-by -- advisory racy size sample; donor eligibility is re-checked under try_lock in _donate_from
            max_pool = max(p.size for p in pools)
            idle = states._all_idle()
            best = shared.read()
            # Host 0's wall clock decides checkpoint rounds (host clocks
            # need not agree; the flag in the control tuple synchronizes
            # the cut).
            want_ckpt = False
            if self.ckpt_mgr is not None and me == 0:
                if self._ckpt_last is None:
                    self._ckpt_last = _time.monotonic()
                elif (_time.monotonic() - self._ckpt_last
                      >= self.ckpt_interval_s):
                    want_ckpt = True
            cut_id = (
                f"{self._run_uuid}:{self.rounds}" if want_ckpt else None
            )
            # Timed SPAN: the allgather wall is the measured control-round
            # latency — the cost model's "exchange" link (obs/costmodel.py).
            t_x = ev.now_us()
            rows = coll.allgather_obj(
                (size, max_pool, best, bool(idle), want_ckpt, cut_id)
            )
            gbest = min(r[2] for r in rows)
            shared.publish(gbest)
            ev.complete("exchange", t_x, wid=ev.COMM_TID, host=me, args={
                "round": self.rounds, "size": size, "best": int(gbest),
                "idle": bool(idle), "backoff": backoff,
            })
            if gbest < best:
                ev.emit("incumbent", wid=ev.COMM_TID, host=me,
                        args={"best": int(gbest)})
            sizes = [r[0] for r in rows]
            maxes = [r[1] for r in rows]
            idles = [r[3] for r in rows]
            do_ckpt = self.ckpt_mgr is not None and rows[0][4]
            # Deterministic donor->receiver matching (identical on every
            # host): richest donors paired with hungriest idle receivers.
            donors = sorted(
                (h for h in range(H) if maxes[h] >= 2 * self.m),
                key=lambda h: (-maxes[h], h),
            )
            needy = sorted(
                (h for h in range(H) if idles[h] and sizes[h] < self.m),
                key=lambda h: (sizes[h], h),
            )
            if self.policy.hier:
                # Two-level topology-aware matching: near (ici) pairs
                # every round, far (dcn) pairs only on far rounds and
                # only for needy the near level missed — deterministic on
                # the lockstep round counter (parallel/topology.py).
                pairs = self.policy.match(donors, needy, self.rounds,
                                          sizes=maxes)
            else:
                pairs = list(zip(donors, needy))
            if not pairs:
                if all(idles) and max(maxes) < 2 * self.m:
                    # Global quiescence candidate: every host idle, no pool
                    # can donate. Confirm with a second consecutive round
                    # (sizes re-sampled after observing all-idle) so a
                    # worker that was momentarily between polls can't have
                    # its poppable work diverted to the serial host drain.
                    quiescent_streak += 1
                    if quiescent_streak >= 2:
                        ev.emit("terminate", wid=ev.COMM_TID, host=me,
                                args={"round": self.rounds})
                        stop_event.set()
                        return
                    backoff = 1  # confirm promptly
                else:
                    quiescent_streak = 0
                    if not needy:
                        # Everyone is busy and rich: back off geometrically
                        # so a balanced run pays ~no collective overhead;
                        # any needy report resets the cadence.
                        backoff = min(backoff * 2, self.BACKOFF_MAX)
                    else:
                        backoff = 1
            else:
                quiescent_streak = 0
                backoff = 1
                # Point-to-point delivery through the KV channel: only
                # matched hosts touch payloads; keys are round-unique (the
                # round counter advances in lockstep — one metadata
                # allgather per round).
                send_to = next((r for d, r in pairs if d == me), None)
                recv_from = next((d for d, r in pairs if r == me), None)
                if send_to is not None:
                    link = self.policy.link(me, send_to)
                    payload = self._donate_from(
                        pools, cap=self.policy.cap_for(link)
                    )
                    self._inflight = payload
                    blob = pickle.dumps(payload)
                    # Donation SPAN over the KV put (bytes + duration: the
                    # "donate"/"donate:<link>" bandwidth samples of the
                    # cost model). The simulated-latency harness sleeps
                    # INSIDE the span so injected link latencies land in
                    # the measured fit (zero sleeps unless TTS_SIM_LAT_*
                    # is armed).
                    t_d = ev.now_us()
                    self.policy.sim.sleep(link)
                    coll.kv_set(
                        f"tts/steal/{self.rounds}/{me}->{send_to}", blob
                    )
                    self._inflight = None
                    if payload is not None:
                        self.blocks_sent += 1
                        self.nodes_sent += batch_length(payload)
                        ev.complete("donate_send", t_d, wid=ev.COMM_TID,
                                    host=me,
                                    args={"peer": send_to,
                                          "nodes": batch_length(payload),
                                          "bytes": len(blob),
                                          "link": link,
                                          "level": self.policy.level_of(link),
                                          "round": self.rounds})
                if recv_from is not None:
                    link = self.policy.link(recv_from, me)
                    t_d = ev.now_us()
                    raw = coll.kv_get(
                        f"tts/steal/{self.rounds}/{recv_from}->{me}",
                        self.KV_TIMEOUT_S,
                    )
                    batch = pickle.loads(raw)
                    if batch is not None:
                        # Span covers the KV wait (donor prep + transfer).
                        ev.complete("donate_recv", t_d, wid=ev.COMM_TID,
                                    host=me,
                                    args={"peer": recv_from,
                                          "nodes": batch_length(batch),
                                          "bytes": len(raw),
                                          "link": link,
                                          "level": self.policy.level_of(link),
                                          "round": self.rounds})
                        # Whole block into one local pool (keeps it >= m so
                        # the receiving worker can pop; intra-host stealing
                        # spreads it from there).
                        pools[rrobin].locked_push_back_bulk(batch)
                        rrobin = (rrobin + 1) % len(pools)
                        self.blocks_received += 1
                        self.nodes_received += batch_length(batch)
                        fr.note_steal(me, link, self.policy.level_of(link))
            if do_ckpt:
                # Same round on every host (rows[0][4]): donations above
                # completed, workers pause at chunk boundaries, each host
                # stages its own share, and the set commits atomically only
                # if EVERY host staged successfully — a host whose worker
                # died keeps the whole set on the previous coherent cut
                # (donated nodes must never appear in files from different
                # rounds: they would be double-explored or lost on resume).
                from ..engine.checkpoint import lockstep_commit

                staging = self.ckpt_mgr.path + ".staging"
                ok = self.ckpt_mgr.do_checkpoint(
                    to_path=staging, cut_tag=rows[0][5]
                )
                lockstep_commit(ok, staging, self.ckpt_mgr.path,
                                vote=coll.allgather_obj)
                self._ckpt_last = _time.monotonic()


def _host_search(
    problem: Problem,
    m: int,
    M: int,
    D: int,
    devices,
    collectives,
    initial_best: int | None,
    share_bound: bool,
    seed_base: int = 0xD157,
    steal: bool = True,
    steal_interval_s: float = 0.02,
    perc: float = 0.5,
    partition_fn=None,
    checkpoint_path: str | None = None,
    checkpoint_interval_s: float = 60.0,
    resume_from: str | None = None,
    topology=None,
):
    """One host's full pipeline (warm-up + stride slice, local multi-device
    runtime with an inter-host communicator, local drain); returns its local
    stats for reduction. Delegates to the shared ``host_pipeline``
    (SURVEY.md §1: the reference duplicates this scaffolding between its
    multi and dist mains — we don't). Checkpoints are per-host files
    (``path.h<rank>``), cut in the same communicator round on every host —
    or on independent timers when ``steal=False`` (no inter-host traffic
    exists to straddle an unsynchronized cut)."""
    comm = None
    policy = None
    if steal and collectives.num_hosts > 1:
        import jax

        from .topology import Topology, resolve_policy

        topo = topology or Topology.detect(collectives.num_hosts)
        # Resolved from env + the (shared) profile file only — every host
        # lands on the identical policy without communication.
        from ..ops.pallas_kernels import platform

        policy = resolve_policy(
            problem, topo, m=m, cap=M, interval_s=steal_interval_s,
            backend=platform(),
            topo_str=f"dist-H{collectives.num_hosts}xD{D}",
        )
        comm = _HostComm(
            collectives, m, perc=perc, interval_s=steal_interval_s, M=M,
            ckpt_interval_s=checkpoint_interval_s, policy=policy,
        )
    local = host_pipeline(
        problem, m, M, D, devices,
        initial_best=initial_best, share_bound=share_bound,
        num_hosts=collectives.num_hosts, host_id=collectives.host_id,
        seed=seed_base + collectives.host_id, perc=perc, comm=comm,
        partition_fn=partition_fn,
        checkpoint_path=checkpoint_path,
        checkpoint_interval_s=checkpoint_interval_s,
        resume_from=resume_from,
    )
    if comm is not None:
        local["comm"] = {
            "rounds": comm.rounds,
            "blocks_sent": comm.blocks_sent,
            "blocks_received": comm.blocks_received,
            "nodes_sent": comm.nodes_sent,
            "nodes_received": comm.nodes_received,
        }
    if policy is not None:
        local["steal_policy"] = policy.describe()
    return local


def _reduce(local: dict, collectives) -> SearchResult:
    """`MPI_Reduce` equivalents: sum tree/sol, min best, max time
    (`pfsp_dist_multigpu_cuda.c:680-694`); communicator counters sum too."""
    tree = collectives.allreduce_sum(local["tree"])
    sol = collectives.allreduce_sum(local["sol"])
    best = collectives.allreduce_min(local["best"])
    elapsed = collectives.allreduce_max(local["elapsed"])
    steals = collectives.allreduce_sum(local["steals"])
    comm = None
    if "comm" in local:
        comm = {
            k: collectives.allreduce_sum(v) for k, v in local["comm"].items()
        }
    return SearchResult(
        explored_tree=tree,
        explored_sol=sol,
        best=best,
        elapsed=elapsed,
        phases=local["phases"],
        diagnostics=local["diag"],
        per_worker_tree=local["per_worker_tree"],
        steals=steals,
        comm=comm,
        steal_policy=local.get("steal_policy"),
    )


def dist_search(
    problem: Problem,
    m: int = 25,
    M: int = 50000,
    D: int | None = None,
    num_hosts: int | None = None,
    devices=None,
    initial_best: int | None = None,
    share_bound: bool = True,
    steal: bool = True,
    steal_interval_s: float = 0.02,
    perc: float = 0.5,
    partition_fn=None,
    checkpoint_path: str | None = None,
    checkpoint_interval_s: float = 60.0,
    resume_from: str | None = None,
) -> SearchResult:
    """Distributed search entry point.

    * Under ``jax.distributed`` (process_count > 1): this process runs its
      host's share; reductions go over DCN. Returns the global result.
    * Single process with ``num_hosts=H > 1``: runs H virtual hosts in
      threads over disjoint local-device groups (testing mode).
    * Single process, ``num_hosts`` unset/1: degenerates to one host.

    ``steal=True`` (default) runs the inter-host communicator: periodic
    incumbent all-reduce + host-mediated work stealing + two-level
    termination (see ``_HostComm``); ``steal=False`` keeps the MPI
    baseline's join-point-only semantics (`pfsp_dist_multigpu_cuda.c`).
    """
    import jax

    if jax.process_count() > 1:
        coll = JaxCollectives()
        local_devices = jax.local_devices() if devices is None else devices
        if D is None:
            D = len(local_devices)
        # Real pods: the pod map comes from each process's slice index,
        # allgathered once (multi-slice deployments put ICI inside a slice
        # and DCN between slices); TTS_PODS still wins inside detect().
        from .topology import Topology

        slice_idx = getattr(local_devices[0], "slice_index", None) \
            if (steal and local_devices) else None
        topo = Topology.detect(
            coll.num_hosts, slice_index=slice_idx,
            allgather=coll.allgather_obj if slice_idx is not None else None,
        )
        local = _host_search(
            problem, m, M, D, local_devices, coll, initial_best, share_bound,
            steal=steal, steal_interval_s=steal_interval_s, perc=perc,
            partition_fn=partition_fn,
            checkpoint_path=checkpoint_path,
            checkpoint_interval_s=checkpoint_interval_s,
            resume_from=resume_from,
            topology=topo,
        )
        return _reduce(local, coll)

    all_devices = jax.devices() if devices is None else devices
    H = num_hosts or 1
    if H == 1:
        coll = LocalCollectives()
        if D is None:
            D = len(all_devices)
        local = _host_search(
            problem, m, M, D, all_devices, coll, initial_best, share_bound,
            steal=False,
            checkpoint_path=checkpoint_path,
            checkpoint_interval_s=checkpoint_interval_s,
            resume_from=resume_from,
        )
        return _reduce(local, coll)

    # Virtual hosts: split local devices into H disjoint groups.
    if H > len(all_devices):
        raise ValueError(
            f"num_hosts={H} exceeds available devices ({len(all_devices)}); "
            "virtual hosts need at least one device each"
        )
    groups = [all_devices[h::H] for h in range(H)]
    if D is None:
        D = max(1, min(len(g) for g in groups))
    coll = ThreadCollectives(H)
    results: list = [None] * H
    errors: list = [None] * H

    locals_: list = [None] * H

    def host_main(h: int):
        try:
            locals_[h] = _host_search(
                problem, m, M, D, groups[h], coll.bind(h),
                initial_best, share_bound,
                steal=steal, steal_interval_s=steal_interval_s, perc=perc,
                partition_fn=partition_fn,
                checkpoint_path=checkpoint_path,
                checkpoint_interval_s=checkpoint_interval_s,
                resume_from=resume_from,
            )
            results[h] = _reduce(locals_[h], coll)
        except BaseException as e:  # propagate after join
            errors[h] = e
            try:
                coll._barrier.abort()
            except Exception:
                pass

    threads = [
        threading.Thread(target=host_main, args=(h,), name=f"tts-host-{h}")
        for h in range(H)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # An erroring host aborts the shared barrier, so its PEERS — possibly
    # including host 0 — die with secondary errors. Surface the root cause,
    # not whichever error sits at the lowest index.
    real = [e for e in errors if e is not None and not secondary_error(e)]
    for e in real or errors:
        if e is not None:
            raise e
    # All hosts computed identical global reductions; merge per-host extras.
    global_res = results[0]
    global_res.per_worker_tree = [
        t for r in results for t in r.per_worker_tree
    ]
    return global_res
