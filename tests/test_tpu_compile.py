"""Compile the main path's kernels for a described TPU v5e, no chip needed.

Interpret-mode tests check the kernels' math on the CPU but never run the
Mosaic lowering, so a kernel the TPU compiler refuses (a block shape off
the (8, 128) tiling, a shape cast between sublanes and lanes, more VMEM
than the scope) would hide behind a green suite.  These tests compile each
kernel at the real widths for a ``v5e:2x2`` topology that JAX describes
from the installed libtpu, and assert that the program carries the kernel
(``tpu_custom_call``) under the name a profiler trace shows it by
(``%tts_cycle_<family>``, ``%tts_eval_<family>``).  Nothing runs, so
results and times come only from ``chip_smoke.py`` on the chip.

The topology is described inside a module fixture — never while a module
is imported — so every pytest-xdist worker collects the same tests and
only the worker given this file loads libtpu.
"""

from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip, with the persistent compile cache off (an
    entry compiled for a described chip cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu: nothing to compile
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile_text(fn, chip, *shapes) -> str:
    sh = SingleDeviceSharding(chip)
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_described_chip_is_a_v5e(chip):
    assert chip.platform == "tpu"
    assert chip.device_kind == "TPU v5 lite"


def test_nqueens_labels_compiles_n14(chip):
    from tpu_tree_search.ops import pallas_kernels as PK

    N, B = 14, 1024
    txt = _compile_text(
        lambda board, depth: PK.nqueens_labels(board, depth, N), chip,
        ((B, N), jnp.uint8), ((B,), jnp.int32),
    )
    assert "tpu_custom_call" in txt
    assert "%tts_eval_nqueens" in txt  # the name a profiler trace shows


def test_lb1_bounds_compiles_ta014(chip):
    from tpu_tree_search.ops import pallas_kernels as PK
    from tpu_tree_search.problems import PFSPProblem

    t = PFSPProblem(inst=14, lb="lb1", ub=1).device_tables()
    n, B = 20, 1024
    txt = _compile_text(
        lambda prmu, limit1: PK.pfsp_lb1_bounds(
            prmu, limit1, t.ptm_t, t.min_heads, t.min_tails),
        chip, ((B, n), jnp.int32), ((B,), jnp.int32),
    )
    assert "tpu_custom_call" in txt
    assert "%tts_eval_lb1" in txt


@pytest.mark.parametrize("fam", ["lb1", "nqueens", "lb2", "lb2-ta021"])
def test_megakernel_cycle_compiles_at_auto_tile(fam, chip):
    """The one-kernel cycle at the chunk size and pool tile ``auto``
    resolves on a TPU for ta014 lb1 and lb2 and ta021 lb2 (M=1024) and
    N=14 (M=50000), pushing into pools laid out as the program allocates
    them: the kernel takes both pools in place (donated, aliased to its
    outputs) and the program copies neither.  lb2 compiles the lane-dense
    pair loop, its tables streamed through SMEM a pair group at a time:
    at ta014 on 128-parent tiles (G=8), and no (P, n, n) one-hot order
    goes into the kernel."""
    from tpu_tree_search.cli import resolve_chunk_size
    from tpu_tree_search.engine.resident import resolve_capacity
    from tpu_tree_search.ops import megakernel as MK
    from tpu_tree_search.problems import NQueensProblem, PFSPProblem

    if fam != "nqueens":
        lb, _, inst = fam.partition("-ta")
        prob = PFSPProblem(inst=int(inst or 14), lb=lb, ub=1)
        M = resolve_chunk_size(None, "pfsp", "device", "resident",
                               backend="tpu")
        vdt = adt = jnp.int8
        fam = lb
    else:
        prob = NQueensProblem(N=14)
        M = resolve_chunk_size(None, "nqueens", "device", "resident",
                               backend="tpu")
        vdt, adt = jnp.uint8, jnp.int8
    dec = MK.resolve(prob, M, chip)
    assert dec.enabled and dec.auto and not dec.interpret, dec
    assert dec.grid > 1  # the streamed form is what auto picks here
    if fam == "lb2":
        assert dec.enabled, dec
        if prob.machines == 10:
            assert (M, dec.mt, dec.grid) == (1024, 128, 8)
    n = prob.child_slots
    capacity, M = resolve_capacity(prob, M, None)
    W, kq, rows = MK.push_layout(dec, n, capacity)
    # kq nodes to a 128-lane row, in slots of the next power of two
    assert (W, kq) == ((16, 8) if fam == "nqueens" else (32, 4))
    assert rows % 1024 == 0 and rows >= capacity + MK.PUSH_SLACK
    cycle = MK.make_cycle(prob, M, chip, dec)
    sh = SingleDeviceSharding(chip)
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in (
        ((rows // kq, 128), vdt), ((rows,), adt), ((), jnp.int32),
        ((M, n), jnp.int32), ((M,), jnp.int32), ((M,), jnp.bool_),
        ((), jnp.int32))]
    txt = jax.jit(cycle, donate_argnums=(0, 1)).lower(*args).compile() \
        .as_text()
    assert "tpu_custom_call" in txt
    assert f"%tts_cycle_{fam}" in txt
    assert "output_to_operand_aliasing" in txt
    if fam == "lb2":
        assert f",{n},{n}]" not in txt, "a (P, n, n) operand is in the step"
    pool = f"[{rows // kq},128]"
    assert not [ln for ln in txt.splitlines()
                if " copy(" in ln and pool in ln.split("=")[1][:40]], \
        "the program copies the value pool"
