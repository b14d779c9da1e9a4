"""Mosaic compile rehearsals of the main-path Pallas kernels at the sizes
``chip_smoke.py`` runs them, for a described (not attached) TPU v5e chip.

Nothing runs: each case lowers and compiles the kernel for the chip and
asserts the compiled program holds a ``tpu_custom_call`` (the Pallas kernel
itself, not an XLA fallback).  This catches what interpret mode cannot —
refused primitives, unaligned slices, VMEM overflow — at no chip time.

The topology is described inside a module fixture, never while a module is
imported: only one process may load the TPU library, and it keeps it until
it exits.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.spike import spike_supported
from repro.kernels import banded, ebv_lu, paged_attn, trsm


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip land in the persistent cache but cannot
    # be read back without one; keep the cache out of these tests
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32 = jnp.float32

# name -> (kernel call, operand (shape, dtype) list)
CASES = {
    # solve-dense: HBM-streaming fused LU at n=16384, VMEM variant at n=256
    "lu_fused_n16384": (lambda a: ebv_lu.lu_fused(a, interpret=False), [((16384, 16384), F32)]),
    "lu_fused_n256": (lambda a: ebv_lu.lu_fused(a, interpret=False), [((256, 256), F32)]),
    # the VMEM variant at a lane-unaligned block (n=384 -> B=192)
    "lu_fused_n384": (lambda a: ebv_lu.lu_fused(a, interpret=False), [((384, 384), F32)]),
    # the dense substitution the registry selects at n=16384 (pallas_tiled)
    "solve_tiled_n16384": (
        lambda lu, b: trsm.solve_tiled(lu, b, interpret=False),
        [((16384, 16384), F32), ((16384, 2), F32)],
    ),
    # solve-banded: 2-D Poisson nx=ny=256 (n=65536, bw=256)
    "banded_lu_tiled_n65536_bw256": (
        lambda a: banded.banded_lu_tiled(a, bw=256, interpret=False),
        [((65536, 513), F32)],
    ),
    "banded_solve_n65536_bw256": (
        lambda lu, b: banded.banded_solve_kernelized(lu, b, bw=256, interpret=False),
        [((65536, 513), F32), ((65536, 2), F32)],
    ),
    # SPIKE's per-device local work: the four-chip Poisson split (16384
    # rows, band past the VMEM cap -> tiled factor, (m, 2bw) spike solve) ...
    "spike_local_lu_n16384_bw256": (
        lambda a: banded.banded_lu_tiled(a, bw=256, interpret=False),
        [((16384, 513), F32)],
    ),
    "spike_local_solve_n16384_bw256": (
        lambda lu, b: banded.banded_solve_kernelized(lu, b, bw=256, interpret=False),
        [((16384, 513), F32), ((16384, 512), F32)],
    ),
    # ... and the paper-shape band (n=16384, bw=16) split over 4 devices,
    # whose local band fits VMEM -> the blocked kernel
    "spike_local_lu_blocked_n4096_bw16": (
        lambda a: banded.banded_lu_blocked(a, bw=16, interpret=False),
        [((4096, 33), F32)],
    ),
    # the paper's band on one device (n=16384, bw=16): the registry's
    # blocked factor and the vector solve, whose fill slab sits at lanes
    # 144:160 of the skewed row
    "banded_lu_blocked_n16384_bw16": (
        lambda a: banded.banded_lu_blocked(a, bw=16, interpret=False),
        [((16384, 33), F32)],
    ),
    "banded_solve_n16384_bw16": (
        lambda lu, b: banded.banded_solve_kernelized(lu, b, bw=16, interpret=False),
        [((16384, 33), F32), ((16384, 1), F32)],
    ),
    # paged decode attention at a small pool (P=64, page 16, KV 8, Dh 128)
    "paged_decode_attention_p64": (
        lambda q, kp, vp, pt, ln: paged_attn.paged_decode_attention(q, kp, vp, pt, ln, interpret=False),
        [((4, 16, 128), jnp.bfloat16), ((64, 16, 8, 128), jnp.bfloat16),
         ((64, 16, 8, 128), jnp.bfloat16), ((4, 8), jnp.int32), ((4,), jnp.int32)],
    ),
}


def test_spike_cases_match_the_split():
    """The SPIKE local cases are the partitions the four-chip smoke makes."""
    assert spike_supported(65536, 256, 4) and -(-65536 // 4) == 16384
    assert spike_supported(16384, 16, 4) and -(-16384 // 4) == 4096


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    hlo = _compile(fn, *shapes, sharding=one_chip)
    assert "tpu_custom_call" in hlo
