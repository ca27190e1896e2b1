"""Batched PFSP lower-bound kernels for TPU (vectorized XLA).

TPU-first reformulation of the reference's per-thread CUDA bound kernels
(`baselines/pfsp/lib/c_bounds_gpu.cu`, `baselines/pfsp/lib/evaluate.cu:25-91`;
Chapel: `pfsp_gpu_chpl.chpl:192-254`). Instead of one SIMT thread per
(parent, child) running scalar loops, each chunk is evaluated as dense
integer tensor algebra over a ``(B, J)`` lane grid (B parents x J child
slots), which XLA tiles onto the VPU:

  * Forward branching fixes ``limit2 == jobs`` (`pfsp_chpl.chpl:23-26`), so
    ``schedule_back`` is always the constant ``min_tails`` table — no tail
    scans at all.
  * A child's head schedule is one ``add_forward`` step from its parent's
    (`c_bound_simple.c:31-38` applied incrementally), so the kernel scans the
    parent prefix once (O(n) steps of (B, m) vector work) and then does a
    single unrolled O(m) update per child slot.
  * The Johnson two-machine recurrence
        tmp0_t = tmp0_{t-1} + p0_t
        tmp1_t = max(tmp1_{t-1}, tmp0_t + lag_t) + p1_t          (c_bound_johnson.c:190-209)
    is a max-plus scan whose closed form is
        tmp1_n = max( tmp1_0 + sum(p1),  max_t [ tmp0_t + lag_t + suffix_sum(p1)_t ] )
    i.e. prefix sums + suffix sums + a max reduction — log-depth parallel
    work instead of a sequential per-thread loop. The data-dependent early
    exit (`c_bound_johnson.c:231-234`) is dropped: on TPU a masked full
    reduction is cheaper than divergent control flow, and the host-side
    pruning decision `bound < best` is provably identical either way (an
    early-exited value exceeds best iff the full value does).

All arithmetic is int32 (bounds fit comfortably; max makespan < 2^31).
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = np.int32(-(2**30))  # a host scalar: importing starts no backend


def _add_forward_batched(front, pt_job):
    """One add_forward step over arbitrary leading axes.

    front: (..., m), pt_job: (..., m) processing times of the appended job.
    Returns the child front. Unrolled over machines (m is small & static).
    """
    m = front.shape[-1]
    cols = [front[..., 0] + pt_job[..., 0]]
    for j in range(1, m):
        cols.append(jnp.maximum(cols[-1], front[..., j]) + pt_job[..., j])
    return jnp.stack(cols, axis=-1)


def _machine_bound_from_parts(front, back, remain):
    """Vectorized `machine_bound_from_parts` (`c_bound_simple.c:126-141`).

    front/remain: (..., m); back: broadcastable (m,). Returns (...,).
    """
    m = front.shape[-1]
    tmp0 = front[..., 0] + remain[..., 0]
    lb = tmp0 + back[..., 0]
    for i in range(1, m):
        tmp1 = jnp.maximum(tmp0, front[..., i] + remain[..., i])
        lb = jnp.maximum(lb, tmp1 + back[..., i])
        tmp0 = tmp1
    return lb


def gather_ptimes(prmu, ptm_t, exact_bf16: bool = False):
    """Per-position processing times ``ptg[b, i, :] = ptm_t[prmu[b, i]]``.

    For small job counts this is a one-hot matmul instead of a gather: the
    MXU evaluates it far faster than TPU dynamic gathers, and it is exact
    (one-hot rows select a single int value). ``exact_bf16=True`` (set when
    every processing time < 256, i.e. all Taillard instances — times are
    1..99, `c_taillard.c:84`) runs it as a single-pass bf16 x bf16 -> f32
    matmul: 0/1 one-hot rows and ints < 2^8 are exactly representable in
    bf16 and the accumulation is f32, so the result is bit-identical to the
    f32 HIGHEST path at a third or less of the MXU cost. Larger instances
    fall back to the gather (the (B, n, n) one-hot would dominate memory:
    at n=50 and a 64k chunk it is already ~650 MB).
    """
    n = prmu.shape[-1]
    if n <= 32:
        dt = jnp.bfloat16 if exact_bf16 else jnp.float32
        # f32 needs HIGHEST (the TPU default single bf16 pass would round
        # ints > 256); the gated bf16 single pass is already exact.
        prec = None if exact_bf16 else jax.lax.Precision.HIGHEST
        oh = jax.nn.one_hot(prmu, n, dtype=dt)  # (B, n, n)
        return jnp.einsum(
            "bkj,jm->bkm", oh, ptm_t.astype(dt),
            preferred_element_type=jnp.float32,
            precision=prec,
        ).astype(jnp.int32)
    return ptm_t[prmu]


def _parent_state(prmu, limit1, ptm_t, min_heads, bf16: bool = False):
    """Shared per-parent precomputation for a chunk.

    prmu: (B, n) int32; limit1: (B,) int32; ptm_t: (n, m) int32 (transposed
    processing times); min_heads: (m,).

    Returns (front, remain, ptg, unsched) where
      front:   (B, m) = schedule_front(prmu, limit1)   (c_bound_simple.c:51-69)
      remain:  (B, m) = sum_unscheduled(prmu, limit1, n) (c_bound_simple.c:108-124)
      ptg:     (B, n, m) processing times gathered per position
      unsched: (B, n) 1.0 where position is free (pos >= limit1 + 1)
    """
    B, n = prmu.shape
    ptg = gather_ptimes(prmu, ptm_t, bf16)  # (B, n, m)
    pos = jnp.arange(n, dtype=jnp.int32)[None, :]
    unsched = (pos >= limit1[:, None] + 1).astype(jnp.int32)  # (B, n)

    def body(i, front):
        newf = _add_forward_batched(front, ptg[:, i, :])
        take = (i <= limit1)[:, None]
        return jnp.where(take, newf, front)

    # Derive the zero init from ptg (not jnp.zeros) so the carry inherits
    # ptg's varying-manual-axes type under shard_map (scan-vma rule).
    front0 = ptg[:, 0, :] * 0
    front = jax.lax.fori_loop(0, n, body, front0)
    # schedule_front(-1) returns min_heads (c_bound_simple.c:58-61); only the
    # root ever hits this, but keep parity.
    front = jnp.where((limit1 == -1)[:, None], min_heads[None, :], front)
    remain = jnp.sum(ptg * unsched[:, :, None], axis=1)  # (B, m)
    return front, remain, ptg, unsched


@partial(jax.jit, static_argnames=("bf16",))
def _lb1_chunk(prmu, limit1, ptm_t, min_heads, min_tails, bf16: bool = False):
    """Bounds of every child of every parent under lb1.

    Child slot (i, k), k >= limit1+1: full `lb1_bound` of the child whose
    prefix is the parent's plus the job at position k
    (`pfsp_gpu_chpl.chpl:192-208` / `evaluate.cu:25-49`). Returns (B, n)
    int32; slots k <= limit1 hold garbage (never read by the host, matching
    the reference's untouched-slot convention, SURVEY.md Appendix A).
    """
    front, remain, ptg, _ = _parent_state(prmu, limit1, ptm_t, min_heads, bf16)
    # Child k appends job prmu[:, k]: one add_forward step per slot.
    child_front = _add_forward_batched(front[:, None, :], ptg)  # (B, n, m)
    child_remain = remain[:, None, :] - ptg  # (B, n, m)
    return _machine_bound_from_parts(child_front, min_tails[None, None, :], child_remain)


@partial(jax.jit, static_argnames=("bf16",))
def _lb1_d_chunk(prmu, limit1, ptm_t, min_heads, min_tails, bf16: bool = False):
    """Bounds of every child under lb1_d (`add_front_and_bound`,
    `c_bound_simple.c:213-244`; device: `pfsp_gpu_chpl.chpl:216-235` /
    `evaluate.cu:51-71`): O(m) per child from the parent's front/remain,
    weaker than lb1's full chain but one pass for all children.
    """
    front, remain, ptg, _ = _parent_state(prmu, limit1, ptm_t, min_heads, bf16)
    m = front.shape[-1]
    back = min_tails
    f = front[:, None, :]  # (B, 1, m)
    r = remain[:, None, :]
    lb = f[..., 0] + r[..., 0] + back[0]  # (B, 1) -> broadcasts to (B, n)
    tmp0 = f[..., 0] + ptg[..., 0]  # (B, n)
    for i in range(1, m):
        tmp1 = jnp.maximum(tmp0, f[..., i])
        lb = jnp.maximum(lb, tmp1 + r[..., i] + back[i])
        tmp0 = tmp1 + ptg[..., i]
    return lb


def _pad_pair_tables(pairs, lags, scheds, Pb: int):
    """Pad the (P, ...) pair tables to a multiple of ``Pb`` with copies of
    pair 0 (max over pairs is idempotent, so duplicates only re-max the same
    value). Static shapes in, static shapes out — safe on traced arrays
    (the mp-sharded paths pass dynamic slices)."""
    P = pairs.shape[0]
    reps = -(-P // Pb) * Pb - P
    # tts-lint: waive tracer-branch -- reps is a Python int (static shape P and the static_argnames-bound Pb); the branch picks a padded vs unpadded program shape
    if reps:
        pairs = jnp.concatenate([pairs, jnp.repeat(pairs[:1], reps, 0)])
        lags = jnp.concatenate([lags, jnp.repeat(lags[:1], reps, 0)])
        scheds = jnp.concatenate([scheds, jnp.repeat(scheds[:1], reps, 0)])
    return pairs, lags, scheds


def _johnson_block_tables(pairs_b, lags_b, sched_b, ptm, min_tails):
    """Slot-ordered Johnson tables of one pair block, derived in-trace.

    pairs_b (Pb, 2), lags_b/sched_b (Pb, n), ptm (m, n) machine-major.
    Returns p0_o/p1_o/lag_o (Pb, n) — the value of the t-th job of each
    pair's Johnson schedule — plus tails0/tails1 (Pb,). The per-block gather
    is tiny (Pb x n) next to the (B, ..., Pb, n) batch tensors it feeds.
    """
    p0_o = jnp.take_along_axis(ptm[pairs_b[:, 0]], sched_b, axis=1)
    p1_o = jnp.take_along_axis(ptm[pairs_b[:, 1]], sched_b, axis=1)
    lag_o = jnp.take_along_axis(lags_b, sched_b, axis=1)
    return p0_o, p1_o, lag_o, min_tails[pairs_b[:, 0]], min_tails[pairs_b[:, 1]]


@partial(jax.jit, static_argnames=("bf16", "pairblock"))
def _lb2_chunk(
    prmu,
    limit1,
    ptm_t,
    min_heads,
    min_tails,
    pairs,
    lags,
    johnson_schedules,
    bf16: bool = False,
    pairblock: int = 1,
):
    """Bounds of every child under lb2 (`c_bound_johnson.c:239-254`; device:
    `pfsp_gpu_chpl.chpl:238-254` / `evaluate.cu:73-91`).

    Per child (i, k) and machine pair (ma0, ma1): the Johnson cmax of the
    free jobs with lags, via the closed-form max-plus scan (module
    docstring).

    ``pairblock`` (static) batches the machine-pair axis: ``Pb`` pairs are
    evaluated at once as an extra leading tensor axis over the slot-ordered
    tables and max-reduced within the block; the running max carries across
    the statically-unrolled blocks, so the compiled program contains NO
    serial per-pair loop (the reference serializes exactly this loop,
    `Bound_johnson.chpl:188-239`). ``pairblock=1`` keeps the original
    serial ``fori_loop`` (the degenerate old behavior, still used by the
    jaxpr-pin regression tests).

    Shapes: pairs (P, 2), lags/johnson_schedules (P, n).
    """
    B, n = prmu.shape
    front, remain_unused, ptg, unsched = _parent_state(
        prmu, limit1, ptm_t, min_heads, bf16
    )
    del remain_unused
    child_front = _add_forward_batched(front[:, None, :], ptg)  # (B, n, m)

    # Free-job indicator per child, by job id: parent's free jobs minus the
    # one the child schedules (set_flags, c_bound_johnson.c:180-188, inverted).
    u_parent = jnp.zeros((B, n), dtype=jnp.int32)
    bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
    u_parent = u_parent.at[bidx, prmu].set(unsched)  # by job id
    onehot_child = jax.nn.one_hot(prmu, n, dtype=jnp.int32)  # (B, n_slots, n_jobs)
    u_child = u_parent[:, None, :] * (1 - onehot_child)  # (B, k, job)

    P = pairs.shape[0]
    ptm = ptm_t.T  # (m, n)
    # Zero init derived from varying operands (not jnp.zeros) so the carry
    # type matches under shard_map along both dp (prmu) and mp (lags) axes.
    lb0 = prmu * 0 + 0 * jnp.min(lags).astype(jnp.int32)

    if pairblock > 1:
        Pb = min(pairblock, P)
        pairs, lags, johnson_schedules = _pad_pair_tables(
            pairs, lags, johnson_schedules, Pb
        )

        def block(lb, pairs_b, lags_b, sched_b):
            p0_o, p1_o, lag_o, tl0, tl1 = _johnson_block_tables(
                pairs_b, lags_b, sched_b, ptm, min_tails
            )
            u_o = u_child[:, :, sched_b]  # (B, k, Pb, n) ordered free flags
            mp0 = u_o * p0_o[None, None]
            mp1 = u_o * p1_o[None, None]
            f0 = jnp.take(child_front, pairs_b[:, 0], axis=2)  # (B, k, Pb)
            f1 = jnp.take(child_front, pairs_b[:, 1], axis=2)
            t0 = f0[..., None] + jnp.cumsum(mp0, axis=-1)
            suf1 = jnp.cumsum(mp1[..., ::-1], axis=-1)[..., ::-1]
            a = jnp.where(u_o > 0, t0 + lag_o[None, None] + suf1, NEG_INF)
            tmp1 = jnp.maximum(f1 + jnp.sum(mp1, axis=-1), jnp.max(a, axis=-1))
            tmp0 = f0 + jnp.sum(mp0, axis=-1)
            pair_lb = jnp.maximum(tmp1 + tl1[None, None], tmp0 + tl0[None, None])
            return jnp.maximum(lb, jnp.max(pair_lb, axis=-1))

        lb = lb0
        for b in range(pairs.shape[0] // Pb):
            sl = slice(b * Pb, (b + 1) * Pb)
            lb = block(lb, pairs[sl], lags[sl], johnson_schedules[sl])
        return lb

    def pair_body(q, lb):
        ma0 = pairs[q, 0]
        ma1 = pairs[q, 1]
        sched = johnson_schedules[q]  # (n,) job ids in Johnson order
        lag_o = lags[q][sched]  # (n,) lag per ordered slot
        p0_o = jnp.take(ptm, ma0, axis=0)[sched]  # (n,)
        p1_o = jnp.take(ptm, ma1, axis=0)[sched]
        u_o = jnp.take(u_child, sched, axis=2)  # (B, k, n) ordered free flags
        mp0 = u_o * p0_o[None, None, :]
        mp1 = u_o * p1_o[None, None, :]
        tmp0_0 = jnp.take_along_axis(child_front, jnp.broadcast_to(ma0, (B, n, 1)), axis=2)[..., 0]
        tmp1_0 = jnp.take_along_axis(child_front, jnp.broadcast_to(ma1, (B, n, 1)), axis=2)[..., 0]
        t0 = tmp0_0[:, :, None] + jnp.cumsum(mp0, axis=-1)  # running tmp0 at slot t
        suf1 = (
            jnp.cumsum(mp1[..., ::-1], axis=-1)[..., ::-1]
        )  # suffix sum of p1 from t inclusive
        a = jnp.where(u_o > 0, t0 + lag_o[None, None, :] + suf1, NEG_INF)
        tmp1 = jnp.maximum(
            tmp1_0 + jnp.sum(mp1, axis=-1), jnp.max(a, axis=-1)
        )
        tmp0 = tmp0_0 + jnp.sum(mp0, axis=-1)
        pair_lb = jnp.maximum(tmp1 + min_tails[ma1], tmp0 + min_tails[ma0])
        return jnp.maximum(lb, pair_lb)

    return jax.lax.fori_loop(0, P, pair_body, lb0)


class PFSPDeviceTables:
    """Instance tables placed on device once per search
    (`pfsp_gpu_chpl.chpl:362-371`: device-resident lbound1/lbound2 copies).

    ``johnson_ordered()`` additionally derives the Johnson data in *schedule
    order* per machine pair (`p0_o/p1_o/lag_o[q, t]` = value of the t-th job
    of pair q's Johnson schedule), the same as flat int32 scalar tables
    for the Pallas lb2 kernels (``slot_tab``/``pair_tab``, read from
    SMEM), and a per-pair permutation one-hot for the self-bound kernel,
    which reorders the free-job flags with one small matmul. Built lazily,
    only when a kernel runs.
    """

    def __init__(self, lb1_data, lb2_data):
        self.ptm_t = jnp.asarray(np.ascontiguousarray(lb1_data.p_times.T), dtype=jnp.int32)
        # Single-pass bf16 MXU gathers are exact iff every time < 2^8
        # (true for all Taillard instances: times are 1..99).
        self.exact_bf16 = bool(int(np.max(lb1_data.p_times)) < 256)
        self.min_heads = jnp.asarray(lb1_data.min_heads, dtype=jnp.int32)
        self.min_tails = jnp.asarray(lb1_data.min_tails, dtype=jnp.int32)
        self.pairs = jnp.asarray(lb2_data.pairs, dtype=jnp.int32)
        self.lags = jnp.asarray(lb2_data.lags, dtype=jnp.int32)
        self.johnson_schedules = jnp.asarray(lb2_data.johnson_schedules, dtype=jnp.int32)

    def mp_padded(self, mp_size: int):
        """(pairs, lags, johnson_schedules) padded to a multiple of
        ``mp_size`` with copies of pair 0 (max over pairs is idempotent, so
        duplicates only re-max the same value). Cached per mp_size.

        The cache holds NUMPY arrays, never jnp: this method is called
        inside shard_map traces (lb2_bounds_mp / lb2_self_bounds_mp), and
        a jnp constant created during trace A would be cached as a tracer
        that leaks into trace B — observed as an UnexpectedTracerError when
        two virtual hosts build their mesh programs from one shared tables
        object. Numpy re-lifts to a fresh constant in every trace."""
        cache = getattr(self, "_mp_padded", None)
        if cache is None:
            cache = self._mp_padded = {}
        if mp_size not in cache:
            pairs = np.asarray(self.pairs)
            lags = np.asarray(self.lags)
            scheds = np.asarray(self.johnson_schedules)
            P = pairs.shape[0]
            Pp = -(-P // mp_size) * mp_size
            if Pp != P:
                reps = Pp - P
                pairs = np.concatenate([pairs, np.repeat(pairs[:1], reps, 0)])
                lags = np.concatenate([lags, np.repeat(lags[:1], reps, 0)])
                scheds = np.concatenate(
                    [scheds, np.repeat(scheds[:1], reps, 0)]
                )
            cache[mp_size] = (pairs, lags, scheds)
        return cache[mp_size]

    def _build_ordered(self, pairs, lags, sched):
        # NUMPY fields only (same tracer-leak hazard as mp_padded: these
        # builders run inside shard_map traces, and caching a trace-created
        # jnp constant poisons every later trace).
        ptm = np.asarray(self.ptm_t).T  # (m, n)
        P, n = sched.shape
        rows = np.arange(P)[:, None]
        tails = np.asarray(self.min_tails)
        jorder = np.zeros((P, n, n), dtype=np.float32)
        jorder[rows, np.arange(n)[None, :], sched] = 1.0

        class _Ordered:
            pass

        o = _Ordered()
        o.p0_o = ptm[pairs[:, 0][:, None], sched].astype(np.int32)
        o.p1_o = ptm[pairs[:, 1][:, None], sched].astype(np.int32)
        o.lag_o = lags[rows, sched].astype(np.int32)
        o.tails0 = tails[pairs[:, 0]].astype(np.int32)
        o.tails1 = tails[pairs[:, 1]].astype(np.int32)
        o.jorder = jorder
        # (P, m) one-hot machine selectors: the Pallas kernel reads row q
        # and contracts it against the child fronts instead of dynamically
        # slicing a VMEM value along the machine (lane) axis.
        m = ptm.shape[0]
        eye = np.eye(m, dtype=np.float32)
        o.msel0 = eye[pairs[:, 0]]
        o.msel1 = eye[pairs[:, 1]]
        # The TPU kernels' SMEM scalar tables (`pallas_kernels._Lb2Lanes`):
        # per pair the Johnson order as job ids and its p0/p1/lag rows,
        # flat (P*4*n,); per pair its machines and tails, flat (P*4,).
        o.slot_tab = np.stack(
            [sched, o.p0_o, o.p1_o, o.lag_o], axis=1
        ).astype(np.int32).reshape(-1)
        o.pair_tab = np.stack(
            [pairs[:, 0], pairs[:, 1], o.tails0, o.tails1], axis=1
        ).astype(np.int32).reshape(-1)
        return o

    def johnson_ordered(self):
        if not hasattr(self, "_johnson_ordered"):
            self._johnson_ordered = self._build_ordered(
                np.asarray(self.pairs), np.asarray(self.lags),
                np.asarray(self.johnson_schedules),
            )
        return self._johnson_ordered

    def johnson_ordered_device(self, pad_to: int = 1):
        """Device-resident copy of the ordered tables for EAGER (un-jitted)
        kernel calls — without it every eager lb2 evaluation would pay a
        fresh host->device transfer of all eight arrays (the (P, n, n)
        jorder alone is MBs). ``pad_to``: pair axis padded to this multiple
        (the Pallas pair-group unroll), cached per multiple. Callers must
        only invoke this OUTSIDE a trace (`_eager_context()`), so the
        cache can never capture a tracer; traced callers keep the numpy
        tables, which bake into the executable as constants."""
        cache = getattr(self, "_johnson_ordered_dev", None)
        if cache is None:
            cache = self._johnson_ordered_dev = {}
        if pad_to not in cache:
            o = self.johnson_ordered_mp(pad_to)

            class _Dev:
                pass

            d = _Dev()
            for f in ("p0_o", "p1_o", "lag_o", "tails0", "tails1",
                      "msel0", "msel1", "jorder", "slot_tab", "pair_tab"):
                setattr(d, f, jnp.asarray(getattr(o, f)))
            cache[pad_to] = d
        return cache[pad_to]

    def johnson_ordered_mp(self, mp_size: int):
        """Ordered tables over the mp-padded pair set (P rounded up to a
        multiple of ``mp_size`` with copies of pair 0 — max over pairs is
        idempotent), so each mp shard can slice its contiguous P/mp block.
        Cached per mp_size."""
        if self.pairs.shape[0] % mp_size == 0:
            return self.johnson_ordered()  # no padding needed: share
        cache = getattr(self, "_johnson_ordered_mp", None)
        if cache is None:
            cache = self._johnson_ordered_mp = {}
        if mp_size not in cache:
            pairs, lags, scheds = self.mp_padded(mp_size)
            cache[mp_size] = self._build_ordered(
                np.asarray(pairs), np.asarray(lags), np.asarray(scheds)
            )
        return cache[mp_size]


def lb1_bounds(prmu, limit1, tables: "PFSPDeviceTables", device=None):
    """lb1 chunk bounds, routed per target device: Pallas kernel on TPU
    (VMEM-resident tile pass, `ops/pallas_kernels.py`), the jnp/XLA oracle
    elsewhere (cf. the reference's per-device dispatcher,
    `evaluate.cu:93-119`)."""
    from . import pallas_kernels as PK

    # The kernel covers every Taillard size (20-500 jobs): _auto_tile shrinks
    # the batch tile as n grows (`lb1_kernel_routed`). Demoted by default — the fused
    # jnp path measured ~7x faster in-kernel on chip (docs/HW_VALIDATION.md
    # decision record); TTS_PALLAS=force re-arms it for the A/B.
    n, m = prmu.shape[-1], tables.ptm_t.shape[1]
    if lb1_kernel_routed(n, m, device):
        return PK.pfsp_lb1_bounds(
            prmu, limit1, tables.ptm_t, tables.min_heads, tables.min_tails,
        )
    return _lb1_chunk(prmu, limit1, tables.ptm_t, tables.min_heads,
                      tables.min_tails, bf16=tables.exact_bf16)


def lb1_d_bounds(prmu, limit1, tables: "PFSPDeviceTables", device=None):
    """lb1_d chunk bounds, routed like ``lb1_bounds``
    (`evaluate.cu:51-71` is the per-parent CUDA counterpart)."""
    from . import pallas_kernels as PK

    n, m = prmu.shape[-1], tables.ptm_t.shape[1]
    if lb1_kernel_routed(n, m, device):
        return PK.pfsp_lb1_d_bounds(
            prmu, limit1, tables.ptm_t, tables.min_heads, tables.min_tails,
        )
    return _lb1_d_chunk(
        prmu, limit1, tables.ptm_t, tables.min_heads, tables.min_tails,
        bf16=tables.exact_bf16,
    )


def lb1_kernel_routed(n: int, m: int, device=None) -> bool:
    """True when lb1/lb1_d bounds of an (n jobs, m machines) chunk run
    the Pallas kernel on ``device``: the one routing predicate of
    ``lb1_bounds``/``lb1_d_bounds``, which the resident engines also read
    to report the route that ran. Shapes that cannot fit VMEM even at
    the smallest tile take the jnp oracle."""
    from . import pallas_kernels as PK

    return (PK.use_pallas(device) and PK.lb1_pallas_enabled() and n <= 512
            and PK.lb1_kernel_feasible(n, m))


def lb2_kernel_routed(n: int, m: int, P: int, device=None,
                      self_bound: bool = False) -> bool:
    """True when the lb2 child bounds (``self_bound``: the self bound of
    node rows, over ``P`` pairs — the shard's own block under mp) run the
    Pallas kernel on ``device``: the routing predicate of every lb2
    entry point, read by the resident engines to report the route. The
    (P, n, n) slot-order tables cap the kernels at ~100 jobs
    (ta031-ta090); beyond that, or where VMEM cannot hold them, the jnp
    path runs."""
    from . import pallas_kernels as PK

    feasible = (PK.lb2_self_kernel_feasible if self_bound
                else PK.lb2_kernel_feasible)
    return (PK.use_pallas(device) and _lb2_pallas_enabled() and n <= 100
            and feasible(n, m, P))


def _lb2_pallas_enabled() -> bool:
    """Per-family kill switch: TTS_PALLAS_LB2=0 routes ONLY the lb2-family
    kernels (child + self) to the jnp path while the hardware-proven
    lb1-family kernels stay on Pallas — so an lb2 compile failure costs the
    lb2 extras, never the headline lb1 number (bench.py probes the
    families in separate subprocesses and sets this on an lb2-only
    failure)."""
    import os

    return os.environ.get("TTS_PALLAS_LB2", "1") != "0"


def _auto_pairblock(P: int, n: int) -> int:
    """Auto pair-block policy: the largest power-of-two block whose
    per-(row, child) working set stays near ~2048 ordered-slot lanes
    (``Pb * n``), clamped to the pair count. At the published shapes this
    gives Pb = P at ta014 (n=20, P=45 — a single block, loop-free) and
    Pb = 64 at ta021 (P=190 — three unrolled blocks); 500-job instances
    fall to Pb = 4 so the (B, n, Pb, n) intermediates keep fitting."""
    per = max(4, 2048 // max(1, n))
    pb = 4
    # tts-lint: waive tracer-branch -- pure host policy on Python ints; P and n are static shapes at every call site (traced callers resolve the knob before tracing)
    while pb * 2 <= per:
        pb *= 2
    return max(1, min(P, pb))


def lb2_pairblock(P: int, n: int) -> int:
    """Resolved lb2 pair-block size for a (P pairs, n jobs) shape.

    ``TTS_LB2_PAIRBLOCK`` / ``--lb2-pairblock``: ``auto`` (default) applies
    `_auto_pairblock`; an explicit
    positive integer forces the block size (``1`` = the serial per-pair
    fori_loop, the pre-blocking behavior; values above P clamp to P).
    Baked into compiled programs at trace time, so `routing_cache_token`
    carries the resolved value."""
    import os

    knob = os.environ.get("TTS_LB2_PAIRBLOCK", "auto")
    if knob == "auto":
        return _auto_pairblock(P, n)
    try:
        v = int(knob)
    except ValueError:
        raise ValueError(
            "TTS_LB2_PAIRBLOCK must be 'auto' or a positive integer, got "
            f"{knob!r}"
        ) from None
    if v < 1:
        raise ValueError(
            f"TTS_LB2_PAIRBLOCK must be >= 1 (got {v}); 1 is the serial "
            "per-pair loop"
        )
    return min(v, P)


def lb2_kernel_pair_group(P: int, n: int) -> int:
    """Pair-group unroll of the Pallas lb2 kernels: the same knob, capped
    at 8 — the kernel VMEM model charges the per-pair live values once per
    unrolled group member (`pallas_kernels._model_bytes`), and 8 is the
    largest group whose modeled footprint keeps MXU-efficient batch tiles
    at the published shapes."""
    return min(lb2_pairblock(P, n), 8)


def lb2_bounds(prmu, limit1, tables: "PFSPDeviceTables", device=None):
    """lb2 chunk bounds, routed like ``lb1_bounds``. The Pallas kernel keeps
    the whole Johnson pair loop in VMEM — the jnp path's per-pair (B, n, n)
    intermediates round-trip HBM, which dominates its cost."""
    from . import pallas_kernels as PK

    n, m = prmu.shape[-1], tables.ptm_t.shape[1]
    P = tables.pairs.shape[0]
    if lb2_kernel_routed(n, m, P, device):
        return PK.pfsp_lb2_bounds(
            prmu, limit1, tables, pair_group=lb2_kernel_pair_group(P, n),
        )
    return _lb2_chunk(
        prmu, limit1, tables.ptm_t, tables.min_heads, tables.min_tails,
        tables.pairs, tables.lags, tables.johnson_schedules,
        bf16=tables.exact_bf16, pairblock=lb2_pairblock(P, n),
    )


@partial(jax.jit, static_argnames=("bf16", "pairblock"))
def _lb2_self_chunk(
    prmu,
    limit1,
    ptm_t,
    min_heads,
    min_tails,
    pairs,
    lags,
    johnson_schedules,
    bf16: bool = False,
    pairblock: int = 1,
):
    """lb2 of each ROW as a node (not of its children): the Johnson bound of
    the row's own partial schedule (`lb2_bound`, `c_bound_johnson.c:239-254`
    applied to the node itself). The staged evaluator feeds compacted child
    rows here — same closed-form max-plus scan as `_lb2_chunk` with the
    child-expansion axis dropped, and the same ``pairblock`` batching of
    the machine-pair axis. Returns (R,) int32."""
    R, n = prmu.shape
    front, _, ptg, unsched = _parent_state(prmu, limit1, ptm_t, min_heads, bf16)
    # Free flags by job id for the row itself.
    u = jnp.zeros((R, n), dtype=jnp.int32)
    ridx = jnp.arange(R, dtype=jnp.int32)[:, None]
    u = u.at[ridx, prmu].set(unsched)  # (R, job)

    P = pairs.shape[0]
    ptm = ptm_t.T  # (m, n)
    lb0 = prmu[:, 0] * 0 + 0 * jnp.min(lags).astype(jnp.int32)

    if pairblock > 1:
        Pb = min(pairblock, P)
        pairs, lags, johnson_schedules = _pad_pair_tables(
            pairs, lags, johnson_schedules, Pb
        )

        def block(lb, pairs_b, lags_b, sched_b):
            p0_o, p1_o, lag_o, tl0, tl1 = _johnson_block_tables(
                pairs_b, lags_b, sched_b, ptm, min_tails
            )
            u_o = u[:, sched_b]  # (R, Pb, n) ordered free flags
            mp0 = u_o * p0_o[None]
            mp1 = u_o * p1_o[None]
            f0 = jnp.take(front, pairs_b[:, 0], axis=1)  # (R, Pb)
            f1 = jnp.take(front, pairs_b[:, 1], axis=1)
            t0 = f0[..., None] + jnp.cumsum(mp0, axis=-1)
            suf1 = jnp.cumsum(mp1[..., ::-1], axis=-1)[..., ::-1]
            a = jnp.where(u_o > 0, t0 + lag_o[None] + suf1, NEG_INF)
            tmp1 = jnp.maximum(f1 + jnp.sum(mp1, axis=-1), jnp.max(a, axis=-1))
            tmp0 = f0 + jnp.sum(mp0, axis=-1)
            pair_lb = jnp.maximum(tmp1 + tl1[None], tmp0 + tl0[None])
            return jnp.maximum(lb, jnp.max(pair_lb, axis=-1))

        lb = lb0
        for b in range(pairs.shape[0] // Pb):
            sl = slice(b * Pb, (b + 1) * Pb)
            lb = block(lb, pairs[sl], lags[sl], johnson_schedules[sl])
        return lb

    def pair_body(q, lb):
        ma0 = pairs[q, 0]
        ma1 = pairs[q, 1]
        sched = johnson_schedules[q]
        lag_o = lags[q][sched]
        p0_o = jnp.take(ptm, ma0, axis=0)[sched]
        p1_o = jnp.take(ptm, ma1, axis=0)[sched]
        u_o = jnp.take(u, sched, axis=1)  # (R, n) ordered free flags
        mp0 = u_o * p0_o[None, :]
        mp1 = u_o * p1_o[None, :]
        tmp0_0 = jnp.take_along_axis(
            front, jnp.broadcast_to(ma0, (R, 1)), axis=1
        )  # (R, 1)
        tmp1_0 = jnp.take_along_axis(front, jnp.broadcast_to(ma1, (R, 1)), axis=1)
        t0 = tmp0_0 + jnp.cumsum(mp0, axis=-1)
        suf1 = jnp.cumsum(mp1[:, ::-1], axis=-1)[:, ::-1]
        a = jnp.where(u_o > 0, t0 + lag_o[None, :] + suf1, NEG_INF)
        tmp1 = jnp.maximum(
            tmp1_0[:, 0] + jnp.sum(mp1, axis=-1), jnp.max(a, axis=-1)
        )
        tmp0 = tmp0_0[:, 0] + jnp.sum(mp0, axis=-1)
        pair_lb = jnp.maximum(tmp1 + min_tails[ma1], tmp0 + min_tails[ma0])
        return jnp.maximum(lb, pair_lb)

    return jax.lax.fori_loop(0, P, pair_body, lb0)


def lb2_self_bounds(prmu, limit1, n_active, tables: "PFSPDeviceTables",
                    device=None):
    """Self lb2 of (R, n) node rows; rows >= ``n_active`` return garbage.
    On TPU the Pallas kernel skips whole inactive tiles (the
    incumbent-driven work reduction the reference gets from its per-thread
    early exit, `evaluate.cu:73-91`); the jnp oracle evaluates everything."""
    from . import pallas_kernels as PK

    n, m = prmu.shape[-1], tables.ptm_t.shape[1]
    P = tables.pairs.shape[0]
    if lb2_kernel_routed(n, m, P, device, self_bound=True):
        return PK.pfsp_lb2_self_bounds(
            prmu, limit1, n_active, tables,
            pair_group=lb2_kernel_pair_group(P, n),
        )
    return _lb2_self_chunk(
        prmu, limit1, tables.ptm_t, tables.min_heads, tables.min_tails,
        tables.pairs, tables.lags, tables.johnson_schedules,
        bf16=tables.exact_bf16, pairblock=lb2_pairblock(P, n),
    )


class _OrderedSlice:
    """Per-shard view of the Johnson-ordered tables: each field is a traced
    ``dynamic_slice`` of the mp-padded full table along the pair axis."""

    _FIELDS = ("p0_o", "p1_o", "lag_o", "tails0", "tails1", "msel0", "msel1",
               "jorder")

    def __init__(self, full, start, P_local: int):
        for f in self._FIELDS:
            arr = getattr(full, f)
            setattr(self, f, jax.lax.dynamic_slice_in_dim(
                arr, start, P_local, axis=0
            ))


def lb2_self_bounds_mp(prmu, limit1, n_active, tables: "PFSPDeviceTables",
                       mp_axis: str, mp_size: int, device=None):
    """Self lb2 with the Johnson pair loop sharded over ``mp_axis`` (the
    staged path's analogue of ``lb2_bounds_mp``): each shard bounds its own
    contiguous pair block — Pallas kernel on TPU (sliced ordered tables,
    inactive-tile skipping intact), jnp chunk elsewhere — and the shards
    combine with ``lax.pmax``. Must be called inside shard_map with
    ``mp_axis`` in scope. Exact: max over pairs is associative/idempotent
    and the padding pairs are copies of pair 0."""
    from . import pallas_kernels as PK

    n, m = prmu.shape[-1], tables.ptm_t.shape[1]
    idx = jax.lax.axis_index(mp_axis)
    # One source of truth for the slice geometry: the padded tables' own
    # pair axis (re-deriving the padding here could silently misalign with
    # mp_padded's policy).
    pairs, lags, scheds = tables.mp_padded(mp_size)
    P_local = pairs.shape[0] // mp_size
    start = idx * P_local
    if lb2_kernel_routed(n, m, P_local, device, self_bound=True):
        ordered = tables.johnson_ordered_mp(mp_size)
        assert ordered.lag_o.shape[0] == pairs.shape[0]
        sliced = _OrderedSlice(ordered, start, P_local)
        local = PK.pfsp_lb2_self_bounds_tables(
            prmu, limit1, n_active, tables.ptm_t, sliced,
            bf16=tables.exact_bf16,
            pair_group=lb2_kernel_pair_group(P_local, n),
        )
    else:
        prs = jax.lax.dynamic_slice_in_dim(pairs, start, P_local, axis=0)
        lgs = jax.lax.dynamic_slice_in_dim(lags, start, P_local, axis=0)
        sch = jax.lax.dynamic_slice_in_dim(scheds, start, P_local, axis=0)
        # Pair-blocking composes with the mp slicing: each shard blocks its
        # own P/mp pair subset (a smaller P just means fewer blocks).
        local = _lb2_self_chunk(
            prmu, limit1, tables.ptm_t, tables.min_heads, tables.min_tails,
            prs, lgs, sch, bf16=tables.exact_bf16,
            pairblock=lb2_pairblock(P_local, n),
        )
    return jax.lax.pmax(local, mp_axis)


def lb2_staged_enabled(device=None, n: int | None = None) -> bool:
    """Staged lb2 (lb1 prefilter -> compacted self-lb2) pays off only where
    inactive tiles are actually skipped — the Pallas path. TTS_LB2_STAGED=1
    forces it everywhere (tests exercise the compaction machinery on CPU);
    =0 disables."""
    import os

    from . import pallas_kernels as PK

    knob = os.environ.get("TTS_LB2_STAGED", "auto")
    if knob == "0":
        return False
    if knob == "1":
        return True
    return (PK.use_pallas(device) and _lb2_pallas_enabled()
            and (n is None or n <= 100))


def compact_mode() -> str:
    """The raw ``TTS_COMPACT`` knob (``auto`` default — see
    `ops/compaction.py` for the mode catalogue, the shift-based ``dense``
    fast path, and the measured ``auto`` table).  Re-exported here so the
    routing token below and its existing import sites keep one spelling;
    the survivor-path implementations live in `ops/compaction.py`."""
    from .compaction import compact_mode as _raw

    return _raw()


def routing_cache_token(problem, device=None) -> tuple:
    """Every env-dependent kernel-routing decision that gets baked into a
    compiled program at trace time (Pallas vs jnp, the lb2-family kill
    switch, the staged-lb2 choice). Program caches keyed per problem
    instance must carry this token so flipping TTS_PALLAS /
    TTS_PALLAS_LB2 / TTS_LB2_STAGED between searches rebuilds instead of
    silently reusing a stale program. One definition — used by both the
    resident and mesh-resident cache keys."""
    from ..problems.base import narrow_mode
    from . import pallas_kernels as PK
    from .megakernel import megakernel_mode

    tok: tuple = (PK.use_pallas(device), PK.pallas_interpret(),
                  # lb1-family demotion override (TTS_PALLAS=force) is a
                  # trace-time routing decision like the rest.
                  PK.pallas_forced(),
                  compact_mode(),
                  # One-kernel cycle knobs (ops/megakernel.py): the raw
                  # mode and the raw forced pool-tile width — the rest of
                  # the decision (M, device, family, mp) is already in
                  # every program cache key carrying this token.
                  megakernel_mode(),
                  os.environ.get("TTS_MEGAKERNEL_MT"),
                  # Narrow node storage (TTS_NARROW, problems/base.py):
                  # host staging dtypes and the megakernel auto window are
                  # trace-time decisions keyed on it.
                  narrow_mode())
    if getattr(problem, "name", None) == "pfsp" and problem.lb == "lb2":
        tok += (
            _lb2_pallas_enabled(),
            lb2_staged_enabled(device, problem.jobs),
            # The resolved pair-block size (TTS_LB2_PAIRBLOCK) is baked
            # into the evaluator at trace time; the kernel pair group is a
            # pure function of it, so one entry covers both paths.
            lb2_pairblock(problem.lb2_data.pairs.shape[0], problem.jobs),
        )
    return tok


def lb2_bounds_staged(prmu, limit1, cand, tables: "PFSPDeviceTables",
                      device=None, mp_axis: str | None = None,
                      mp_size: int = 1):
    """lb2 child bounds evaluated ONLY for candidate children.

    ``cand`` (B, n) marks open, non-leaf children whose lb1 is below the
    incumbent; since lb2 >= lb1 pointwise (every machine's lb1 term appears
    as the one-machine term of some Johnson pair), children outside ``cand``
    are pruned under lb2 too — skipping them is exact. Candidates are
    compacted to the front of an (R = B*n)-row buffer of materialized child
    nodes (parent permutation with slots (limit1+1, k) swapped), the self
    bound runs on ceil(count/tile) active tiles, and results scatter back.
    Non-candidate slots hold garbage (never read: the caller masks with
    ``cand``).

    ``mp_axis`` set (mesh dp x mp tier): the compaction is pure shard-local
    ops — every mp replica of a dp block computes the identical candidate
    set — and the self bound shards the pair loop over mp with a pmax
    combine (``lb2_self_bounds_mp``), so all replicas see full-pair bounds
    and stay in lockstep."""
    B, n = prmu.shape
    R = B * n
    flat = cand.reshape(R)
    pos = jnp.cumsum(flat.astype(jnp.int32)) - 1  # compacted row per cand
    count = jnp.sum(flat.astype(jnp.int32))
    # Scatter each candidate's flat source index into its compacted row
    # (R+1 buffer: non-candidates target the spill slot, then dropped).
    tgt = jnp.where(flat, pos, R)
    src = (
        jnp.zeros((R + 1,), jnp.int32)
        .at[tgt]
        .set(jnp.arange(R, dtype=jnp.int32))[:R]
    )
    b_idx = src // n
    k_idx = src % n
    parent = prmu[b_idx]  # (R, n)
    d = limit1[b_idx] + 1  # the child's limit1
    # Child permutation: swap slots d and k (k == d is a no-op swap).
    iota = jnp.arange(n, dtype=prmu.dtype)[None, :]
    vd = jnp.take_along_axis(parent, d[:, None], axis=1)[:, 0]
    vk = jnp.take_along_axis(parent, k_idx[:, None], axis=1)[:, 0]
    ohd = (iota == d[:, None]).astype(parent.dtype)
    ohk = (iota == k_idx[:, None]).astype(parent.dtype)
    child = parent + ohd * (vk - vd)[:, None] + ohk * (vd - vk)[:, None]
    if mp_axis is not None:
        out = lb2_self_bounds_mp(child, d, count, tables, mp_axis, mp_size,
                                 device)  # (R,)
    else:
        out = lb2_self_bounds(child, d, count, tables, device)  # (R,)
    vals = out[jnp.where(flat, pos, 0)]
    return vals.reshape(B, n)


def lb2_bounds_mp(prmu, limit1, tables: "PFSPDeviceTables", mp_axis: str,
                  mp_size: int, device=None):
    """lb2 chunk bounds with the Johnson machine-pair loop SHARDED over a
    mesh axis: each ``mp`` shard reduces its pair subset and the shards
    combine with ``lax.pmax`` (max over machine pairs is the bound; max is
    associative and idempotent, so padding with copies of pair 0 is safe).
    Must be called inside shard_map with ``mp_axis`` in scope. The SIMT
    design has no equivalent of this axis — it is the model-parallel
    analogue for bound evaluation (SURVEY.md §2.4 note).

    jnp path only: the Pallas kernel's per-pair ordered tables are built
    host-side for the full pair set; slicing them per shard inside the
    kernel would need a second staging pass (future work).
    """
    del device
    pairs, lags, scheds = tables.mp_padded(mp_size)
    P_local = pairs.shape[0] // mp_size
    idx = jax.lax.axis_index(mp_axis)
    start = idx * P_local
    prs = jax.lax.dynamic_slice_in_dim(pairs, start, P_local, axis=0)
    lgs = jax.lax.dynamic_slice_in_dim(lags, start, P_local, axis=0)
    sch = jax.lax.dynamic_slice_in_dim(scheds, start, P_local, axis=0)
    # Pair-blocking applies within each shard's P/mp subset (fewer blocks,
    # same math) — the pair axis composes with the mp slicing.
    local = _lb2_chunk(
        prmu, limit1, tables.ptm_t, tables.min_heads, tables.min_tails,
        prs, lgs, sch, bf16=tables.exact_bf16,
        pairblock=lb2_pairblock(P_local, prmu.shape[-1]),
    )
    return jax.lax.pmax(local, mp_axis)


def make_evaluator(tables: PFSPDeviceTables, lb: str, device=None):
    """Dispatcher over the three bounds (`pfsp_gpu_chpl.chpl:256-270`).

    Returns ``fn(parents: dict, count, best) -> (B, jobs) int32 bounds``;
    ``device`` selects the Pallas-vs-XLA path per target platform.

    The offload tiers may stage ``prmu``/``limit1`` at the narrow storage
    dtypes (TTS_NARROW, problems/base.py); bound arithmetic is exact at
    int32, so every entry point widens first — a no-op cast when storage
    is already wide (the resident tier pre-widens its popped chunks).
    """
    def _wide(parents):
        return (jnp.asarray(parents["prmu"]).astype(jnp.int32),
                jnp.asarray(parents["limit1"]).astype(jnp.int32))

    if lb == "lb1":
        def evaluate(parents, count, best):
            del count, best
            prmu, limit1 = _wide(parents)
            return lb1_bounds(prmu, limit1, tables, device)
    elif lb == "lb1_d":
        def evaluate(parents, count, best):
            del count, best
            prmu, limit1 = _wide(parents)
            return lb1_d_bounds(prmu, limit1, tables, device)
    elif lb == "lb2":
        if lb2_staged_enabled(device, tables.ptm_t.shape[0]):
            @jax.jit
            def _staged(prmu, limit1, count, best):
                prmu = prmu.astype(jnp.int32)
                limit1 = limit1.astype(jnp.int32)
                # Offload-path staging: children killed by the cheap lb1
                # pass report their lb1 value (>= the dispatch-time best,
                # so the host prunes them identically — lb2 >= lb1 and the
                # host's running best only tightens); candidates report
                # the compacted self lb2. Leaf slots report lb1 = exact
                # makespan, so the host's incumbent fold is unchanged.
                # ``count`` masks the bucket-padding clone rows out of the
                # candidate set (their result slots are never read, but
                # they would inflate the compaction and waste kernel
                # tiles).
                n = prmu.shape[-1]
                bounds1 = lb1_bounds(prmu, limit1, tables, device)
                kk = jnp.arange(n, dtype=jnp.int32)[None, :]
                valid = (
                    jnp.arange(prmu.shape[0], dtype=jnp.int32) < count
                )[:, None]
                open_ = (kk >= (limit1 + 1)[:, None]) & valid
                leaf = open_ & ((limit1[:, None] + 2) == n)
                # Fold this chunk's leaf makespans before selecting
                # candidates (as the resident staged path does): the host
                # folds leaves before its keep test anyway, so children a
                # leaf already dominates would be pruned regardless —
                # don't spend kernel tiles on them.
                from ..problems.base import INF_BOUND

                best = jnp.minimum(
                    best, jnp.min(jnp.where(leaf, bounds1, INF_BOUND))
                )
                cand = open_ & (~leaf) & (bounds1 < best)
                b2 = lb2_bounds_staged(prmu, limit1, cand, tables, device)
                return jnp.where(cand, b2, bounds1)

            def evaluate(parents, count, best):
                return _staged(parents["prmu"], parents["limit1"], count, best)
        else:
            def evaluate(parents, count, best):
                del count, best
                prmu, limit1 = _wide(parents)
                return lb2_bounds(prmu, limit1, tables, device)
    else:
        raise ValueError(f"Unsupported lower bound: {lb!r}")
    return evaluate


# -- compiled-program contracts (`tts check`, analysis/contracts.py) --------

from ..analysis.contracts import contract, loop_op_count  # noqa: E402


@contract(
    "lb2-pairblock-loop-free",
    claim="with pair-blocking on (Pb > 1) the compiled lb2 child/self "
          "evaluators contain NO loop whose trip count scales with P — "
          "only `_parent_state`'s O(n) prefix scan survives (1 loop op); "
          "the serial build (Pb=1) keeps its per-pair fori_loop (2 loop "
          "ops), so the pin is never trivially zero-by-construction",
    artifact="lb2-eval",
)
def _contract_pairblock_loop_free(art, cell):
    expect = 2 if art["pairblock"] == 1 else 1
    out = []
    for kind in ("child", "self"):
        got = loop_op_count(art[kind])
        if got != expect:
            out.append(
                f"lb2 {kind} evaluator at Pb={art['pairblock']}: {got} "
                f"serial loop ops (expected {expect})"
            )
    if art.get("auto") and art["pairblock"] <= 1:
        out.append(
            "auto pair-block policy resolved to the serial loop at a "
            "published blocked shape"
        )
    return out
