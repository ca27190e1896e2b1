"""Batched N-Queens safety kernel (vectorized XLA).

TPU-first reformulation of the reference's per-thread SIMT kernel
(`nqueens_gpu_chpl.chpl:97-123`, `baselines/nqueens/nqueens_gpu_cuda.cu:137-164`):
one (B, N, N) clash tensor — (parent, placed queen i, candidate slot k) —
reduced over i, instead of one scalar thread per (parent, k). All int32 lane
work; XLA tiles it onto the VPU.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp


def make_core(N: int, g: int = 1):
    """Returns ``fn(board: (B, N) uint8/int32, depth: (B,) int32) -> (B, N)
    uint8`` labels; labels[i, k] == 1 iff swapping slot k into position
    depth_i keeps all diagonals safe. Slots k < depth are 0 (the reference
    leaves them as garbage and never reads them; emitting 0 is strictly
    safer, SURVEY.md Appendix A).
    """

    def core(board, depth):
        board = board.astype(jnp.int32)  # (B, N)
        depth = depth.astype(jnp.int32)  # (B,)
        qk = board[:, None, :]  # candidate row for slot k: (B, 1, N)
        bi = board[:, :, None]  # placed queen rows:        (B, N, 1)
        i = jnp.arange(N, dtype=jnp.int32)
        d = depth[:, None] - i[None, :]  # (B, N): depth - i
        placed = i[None, :] < depth[:, None]  # (B, N) mask over i
        clash = (bi == qk - d[:, :, None]) | (bi == qk + d[:, :, None])
        safe = ~jnp.any(clash & placed[:, :, None], axis=1)  # (B, N)
        if g > 1:
            # Honor the g workload knob with a real loop op so XLA cannot
            # CSE the redundant rechecks away (the reference repeats the
            # comparisons g times, `nqueens_gpu_chpl.chpl:115-118`).
            def recheck(_, s):
                c = (bi == qk - d[:, :, None]) | (bi == qk + d[:, :, None])
                return s & ~jnp.any(c & placed[:, :, None], axis=1)

            safe = jax.lax.fori_loop(0, g - 1, recheck, safe)
        k = jnp.arange(N, dtype=jnp.int32)[None, :]
        valid = k >= depth[:, None]
        return (safe & valid).astype(jnp.uint8)

    return core


def make_labels(N: int, g: int = 1, device=None):
    """Routed safety evaluator: Pallas kernel when the target device is a
    TPU (`pallas_kernels.use_pallas`), the jnp/XLA core elsewhere. Same
    contract as ``make_core``."""
    from . import pallas_kernels as PK

    if PK.use_pallas(device):
        return lambda board, depth: PK.nqueens_labels(board, depth, N, g)
    return make_core(N, g)


@lru_cache(maxsize=None)
def _make_jitted_core(N: int, g: int, device, routing_key: tuple):
    del routing_key  # cache key only — the knobs it captures are baked in
    return jax.jit(make_labels(N, g, device))


def make_jitted_core(N: int, g: int = 1, device=None):
    """Module-level jit cache: every DeviceOffloader / worker thread shares
    one compiled kernel per bucket shape instead of re-tracing per closure
    (cf. the module-level jitted PFSP chunk kernels). The env-dependent
    routing decisions make_labels bakes in at trace time are part of the
    key — flipping TTS_PALLAS / TTS_PALLAS_INTERPRET between searches must
    rebuild, not reuse a stale core (same invariant as
    ``pfsp_device.routing_cache_token``)."""
    from . import pallas_kernels as PK

    return _make_jitted_core(
        N, g, device, (PK.use_pallas(device), PK.pallas_interpret()))
