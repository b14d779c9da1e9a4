"""Backend registrations: every solver generation in one table.

Importing this module (done by ``repro.solvers``) populates the registry
with all existing implementations — the fused megakernel, the legacy
multi-launch blocked driver, VMEM/tiled substitution, the banded
blocked/tiled/scalar family, the batched VMEM grid kernels, the
multi-device shard_map LU, and the pure-jnp mirrors.  The static
``priority`` functions reproduce the pre-registry hardcoded dispatch
(fused-for-fp32, the 2048-order solve VMEM threshold, the 6 MB banded byte
cap) so a cache-less process is behaviour-identical to the historical
``kernels/ops.py`` tables.

Adding a backend is one :func:`repro.solvers.registry.register` call — see
``src/repro/solvers/README.md`` for the recipe.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import banded as _core_banded
from repro.core import blocked as _core_blocked
from repro.core import factorization as _fz
from repro.core import pivoted as _core_pivoted
from repro.core import randomized as _core_rand
from repro.core import refine as _core_refine
from repro.core import solve as _core_solve
from repro.core.factorization import packed_of as _packed
from repro.kernels import banded as _kbanded
from repro.kernels import batched_lu as _kbatched
from repro.kernels import ebv_lu as _k
from repro.kernels import trsm as _trsm

from .problem import Problem
from .registry import Backend, register

__all__ = [
    "SOLVE_VMEM_MAX_N",
    "BANDED_VMEM_MAX_BYTES",
    "BATCHED_VMEM_MAX_N",
    "BF16_IR_RESIDUAL_FLOOR",
    "RAND_LU_RESIDUAL_BOUND",
    "IR_MAX_ITERS",
    "banded_static_impl",
]

# Above this order the packed (n, n) LU no longer comfortably shares VMEM
# with an RHS tile, so the static solve choice switches to the tiled driver.
SOLVE_VMEM_MAX_N = 2048

# Above this many skewed-band bytes the static banded choice switches from
# the VMEM-resident blocked kernel to the HBM-streaming tiled kernel.
BANDED_VMEM_MAX_BYTES = _core_banded.BANDED_VMEM_MAX_BYTES

# Largest per-system order the batched grid kernels keep VMEM-resident
# ((n, n) matrix + (n, m) RHS per grid program).
BATCHED_VMEM_MAX_N = 1024

# ---------------------------------------------------------------------------
# accuracy tiers (the tolerance gate's residual guarantees)
# ---------------------------------------------------------------------------
# Tightest relative residual the bf16-factor + f32-refinement path commits
# to for diagonally-dominant f32 operands: refinement contracts by the bf16
# unit roundoff (~2^-8) per sweep and floors at f32 residual round-off;
# 1e-6 is reached in 2-3 sweeps at n ≤ 2048 (test_accuracy_tiers pins it).
BF16_IR_RESIDUAL_FLOOR = 1e-6

# Residual the randomized rank-k tier guarantees for its documented operand
# class (numerical rank ≤ k, range-consistent RHS) — see
# repro.core.randomized; measured each run by the ``rand_lu_n2048_k256``
# bench row and gated in scripts/check.sh (observed ~5e-7, bound 1e-3).
RAND_LU_RESIDUAL_BOUND = 1e-3

# Refinement-sweep cap: bounds serving-tier latency; the count actually
# taken surfaces through repro.core.refine.last_refinement().
IR_MAX_ITERS = _core_refine.DEFAULT_MAX_ITERS


def _itemsize(p: Problem) -> int:
    return jnp.dtype(p.dtype).itemsize


def _is_f32(p: Problem) -> bool:
    return p.dtype == "float32"


def _local(p: Problem) -> bool:
    return p.devices == 1


def _off_tpu(p: Problem) -> bool:
    """Capability clause of kernels Mosaic does not lower: they run only in
    interpret mode, so a TPU dispatch must never select them (a forced
    ``impl=`` of one raises from the kernel entry instead)."""
    return not p.tpu


def _banded_skew_bytes(p: Problem, block: int | None = None) -> int:
    c = _core_banded.band_block_size(p.n, p.bw, block)
    return _core_banded.skew_rows(p.n, p.bw, c) * (c + 2 * p.bw) * _itemsize(p)


def banded_static_impl(n: int, bw: int, block: int | None, itemsize: int) -> str:
    """The historical banded auto rule (kept callable for the shim/tests)."""
    takes = _core_banded.blocked_kernel_takes(n, bw, block, itemsize, compiled=False)
    return "pallas_blocked" if takes else "pallas_tiled"


# ---------------------------------------------------------------------------
# jitted wrappers for the pure-jnp mirrors (the Pallas entry points are
# already jitted at their definitions; the mirrors were relying on the old
# monolithic jit around ops.* and would otherwise run eagerly)
# ---------------------------------------------------------------------------
_fused_blocked_lu_j = jax.jit(_core_blocked.fused_blocked_lu, static_argnames=("block",))
_lu_solve_j = jax.jit(_core_solve.lu_solve)


# ---------------------------------------------------------------------------
# Factorization-artifact adapters (the inverted-diagonal solve fast path).
# Raw legacy operands are accepted through the one-release enrich-on-the-fly
# shim (dense_artifact / banded_artifact); enriched artifacts go straight to
# the kernels with zero layout work.
# ---------------------------------------------------------------------------
def _dense_inverted_call(lu, b, *, block, rhs_tile, interpret):
    art = _fz.dense_artifact(lu, block=block or 256)
    return _trsm.solve_inverted(
        art.packed, art.linv, art.uinv, b, rhs_tile=rhs_tile, interpret=interpret
    )


def _dense_inverted_mirror_call(lu, b, *, block):
    art = _fz.dense_artifact(lu, block=block or 256)
    return _fz.dense_inverted_solve(art.packed, art.linv, art.uinv, b, block=art.block)


def _banded_inverted_call(lub, b, *, bw, block, rhs_tile, interpret):
    art = _fz.banded_artifact(lub, bw=bw, block=block)
    return _kbanded.banded_solve_inverted(
        art.linv, art.uinv, art.tlo, art.tup, b,
        n=art.n, bw=art.bw, rhs_tile=rhs_tile, interpret=interpret,
    )


def _banded_inverted_mirror_call(lub, b, *, bw, block):
    art = _fz.banded_artifact(lub, bw=bw, block=block)
    return _fz.banded_inverted_solve(
        art.linv, art.uinv, art.tlo, art.tup, b, n=art.n, bw=art.bw
    )


@functools.partial(jax.jit, static_argnames=("block",))
def _batched_dense_inverted_solve(lu, linv, uinv, b, *, block):
    return jax.vmap(
        lambda l, li, ui, r: _fz.dense_inverted_solve(l, li, ui, r, block=block)
    )(lu, linv, uinv, b)


@functools.partial(jax.jit, static_argnames=("n", "bw"))
def _batched_banded_inverted_solve(linv, uinv, tlo, tup, b, *, n, bw):
    return jax.vmap(
        lambda li, ui, lo, up, r: _fz.banded_inverted_solve(li, ui, lo, up, r, n=n, bw=bw)
    )(linv, uinv, tlo, tup, b)


def _batched_dense_inverted_call(lu, b, *, block):
    art = _fz.dense_artifact(lu, block=block or 256)
    return _batched_dense_inverted_solve(art.packed, art.linv, art.uinv, b, block=art.block)


def _batched_banded_inverted_call(lub, b, *, bw, block):
    art = _fz.banded_artifact(lub, bw=bw, block=block)
    return _batched_banded_inverted_solve(
        art.linv, art.uinv, art.tlo, art.tup, b, n=art.n, bw=art.bw
    )


def _banded_inverted_vmem_bytes(p: Problem) -> int:
    # the (S, C, C) inverse stacks are VMEM-resident for the whole program,
    # plus the two (S, C, bw) transfer stacks and one equalized RHS tile
    c = _core_banded.band_block_size(p.n, p.bw, None)
    s = -(-p.n // c)
    rt = _fz.equalized_rhs_tile(max(p.rhs, 1), 512)
    return (2 * s * c * c + 2 * s * c * p.bw + 2 * s * c * rt) * _itemsize(p)


@functools.partial(jax.jit, static_argnames=("block", "col_tile", "interpret"))
def _pallas_blocked_lu(a, *, block: int, col_tile: int, interpret: bool | None):
    """Legacy multi-launch blocked driver: one panel kernel + one fused
    bi-vector step kernel per block column (kept as the forced-impl
    baseline; see kernels/README.md for the launch/traffic math)."""
    n = a.shape[-1]
    block = min(block, n)
    for k0 in range(0, n, block):
        b = min(block, n - k0)
        pan = _k.panel(a[k0:, k0 : k0 + b], interpret=interpret)
        a = a.at[k0:, k0 : k0 + b].set(pan)
        w = n - k0 - b
        if w > 0:
            ct = min(col_tile, w)
            if w % ct:
                # Pad the trailing width to the next tile multiple (tiles
                # capped at 128 lanes) instead of halving the tile — odd
                # widths used to degrade to 1-column tiles.  Zero columns are
                # inert through trsm and the rank-b update.
                ct = min(col_tile, 128)
                wp = -(-w // ct) * ct
                top = jnp.pad(a[k0 : k0 + b, k0 + b :], ((0, 0), (0, wp - w)))
                trail = jnp.pad(a[k0 + b :, k0 + b :], ((0, 0), (0, wp - w)))
                u12, new_trail = _k.fused_step(pan, top, trail, col_tile=ct, interpret=interpret)
                u12, new_trail = u12[:, :w], new_trail[:, :w]
            else:
                u12, new_trail = _k.fused_step(
                    pan, a[k0 : k0 + b, k0 + b :], a[k0 + b :, k0 + b :],
                    col_tile=ct, interpret=interpret,
                )
            a = a.at[k0 : k0 + b, k0 + b :].set(u12)
            a = a.at[k0 + b :, k0 + b :].set(new_trail)
    return a


@functools.partial(jax.jit, static_argnames=("block",))
def _batched_xla_lu(a, *, block: int = 256):
    return jax.vmap(lambda m: _core_blocked.fused_blocked_lu(m, block=block))(a)


_batched_xla_solve_j = jax.jit(jax.vmap(_core_solve.lu_solve))


@functools.partial(jax.jit, static_argnames=("bw", "block"))
def _batched_xla_banded_lu(arow, *, bw: int, block: int | None = None):
    return jax.vmap(lambda m: _core_banded.banded_lu_blocked(m, bw=bw, block=block))(arow)


@functools.partial(jax.jit, static_argnames=("bw", "block"))
def _batched_xla_banded_solve(lu_band, b, *, bw: int, block: int | None = None):
    return jax.vmap(lambda l, r: _core_banded.banded_solve_blocked(l, r, bw=bw, block=block))(lu_band, b)


def _distributed_lu(problem, a, *, mesh, axis="model", block=64, placement="ebv_folded", **_):
    from repro.core.distributed import distributed_blocked_lu

    return distributed_blocked_lu(a, mesh, axis=axis, block=block, placement=placement)


def _distributed_linear_solve(problem, a, b, *, mesh, axis="model", block=64, placement="ebv_folded", **_):
    from repro.core.distributed import distributed_lu_solve

    return distributed_lu_solve(a, b, mesh, axis=axis, block=block, placement=placement)


# ---------------------------------------------------------------------------
# dense factor
# ---------------------------------------------------------------------------
register(Backend(
    name="pallas_fused", op="factor", structure="dense",
    call=lambda p, a, *, block=256, interpret=None, **_: _k.lu_fused(a, block=block, interpret=interpret),
    supports=lambda p: _is_f32(p) and _local(p),
    priority=lambda p: 3.0,
    vmem_bytes=lambda p: 3 * p.n * 256 * _itemsize(p),  # three (N, B) scratch slabs
))
register(Backend(
    name="xla", op="factor", structure="dense",
    call=lambda p, a, *, block=256, interpret=None, **_: _fused_blocked_lu_j(a, block=block),
    supports=_local,
    priority=lambda p: 2.0,  # static winner for non-fp32 (fused is fp32-only)
))
register(Backend(
    name="pallas_vmem", op="factor", structure="dense",
    call=lambda p, a, *, interpret=None, **_: _k.lu_vmem(a, interpret=interpret),
    # off TPU: Mosaic refuses the value-level dynamic_slice in its body
    supports=lambda p: _is_f32(p) and _local(p) and p.n <= 4096 and _off_tpu(p),
    priority=lambda p: 1.0,
    autotune=False,  # not value-identical to the fused/xla twins
    vmem_bytes=lambda p: 2 * p.n * p.n * _itemsize(p),
))
register(Backend(
    name="pallas_blocked", op="factor", structure="dense",
    call=lambda p, a, *, block=256, col_tile=256, interpret=None, **_:
        _pallas_blocked_lu(a, block=block, col_tile=col_tile, interpret=interpret),
    # off TPU: Mosaic refuses the panel/fused_step kernels' value-level dynamic_slice
    supports=lambda p: _local(p) and _off_tpu(p),
    priority=lambda p: 0.0,
    autotune=False,  # dominated multi-launch legacy driver (forced-impl only)
))
register(Backend(
    name="distributed", op="factor", structure="dense",
    call=_distributed_lu,
    supports=lambda p: p.devices > 1,
    priority=lambda p: 10.0,
    autotune=False,  # needs a mesh; not shootable by the single-host harness
))
register(Backend(
    name="pivoted", op="factor", structure="dense",
    # last-resort fallback for operands outside the no-pivot class: the
    # escalation funnel reaches it after every no-pivot twin fails its
    # health screen.  Lowest priority so it can never win a default
    # selection; O(n) sequential rank-1 steps, so it must not.
    call=lambda p, a, **_: _core_pivoted.pivoted_lu(a),
    supports=_local,
    priority=lambda p: 0.05,
    autotune=False,  # different factor layout (PivotedFactors, not packed)
))

# ---------------------------------------------------------------------------
# dense solve
# ---------------------------------------------------------------------------
register(Backend(
    name="pallas_vmem", op="solve", structure="dense",
    call=lambda p, lu, b, *, rhs_tile=256, interpret=None, **_:
        _trsm.solve_vmem(_packed(lu), b, rhs_tile=rhs_tile, interpret=interpret),
    # off TPU: Mosaic refuses the value-level dynamic_slice in its body
    supports=lambda p: _local(p) and _off_tpu(p),
    priority=lambda p: 3.0 if p.n <= SOLVE_VMEM_MAX_N else 0.0,
    vmem_bytes=lambda p: (p.n * p.n + p.n * max(p.rhs, 1)) * _itemsize(p),
))
register(Backend(
    name="pallas_tiled", op="solve", structure="dense",
    call=lambda p, lu, b, *, block=256, rhs_tile=256, interpret=None, **_:
        _trsm.solve_tiled(_packed(lu), b, block=block, rhs_tile=rhs_tile, interpret=interpret),
    # on TPU the (B, B) factor tiles must be lane-aligned: B = min(256, n)
    supports=lambda p: _local(p) and (_off_tpu(p) or p.n >= 256 or p.n % 128 == 0),
    priority=lambda p: 1.0,
))
register(Backend(
    name="pallas_inverted", op="solve", structure="dense",
    # Factorization-artifact fast path: substitution against the factor-time
    # pre-inverted diagonal blocks (raw operands are enriched on the fly by
    # the one-release shim — the `enriched` capability keeps auto-selection
    # from ever steering a raw operand here).
    call=lambda p, lu, b, *, block=None, rhs_tile=512, interpret=None, **_:
        _dense_inverted_call(lu, b, block=block, rhs_tile=rhs_tile, interpret=interpret),
    # off TPU: Mosaic refuses the shared sweeps' value-level dynamic_slice
    supports=lambda p: _local(p) and p.enriched and _off_tpu(p),
    priority=lambda p: 0.75,  # below the defaults: reach it measured or forced
    autotune=False,  # not value-identical to the strip-recurrence twins
    vmem_bytes=lambda p: (2 * p.n * 256 + p.n * max(p.rhs, 1)) * _itemsize(p),
))
register(Backend(
    name="xla_inverted", op="solve", structure="dense",
    # pure-jnp bitwise mirror of pallas_inverted (twin contract)
    call=lambda p, lu, b, *, block=None, interpret=None, **_:
        _dense_inverted_mirror_call(lu, b, block=block),
    supports=lambda p: _local(p) and p.enriched,
    priority=lambda p: 0.1,
    autotune=False,
))
register(Backend(
    name="xla", op="solve", structure="dense",
    call=lambda p, lu, b, **_: _lu_solve_j(_packed(lu), b),
    supports=_local,
    priority=lambda p: 0.5,
))
register(Backend(
    name="pivoted", op="solve", structure="dense",
    # consumes PivotedFactors (row permutation applied to the RHS before
    # substitution) — never auto-selected; repro.kernels.ops.lu_solve
    # forces it when handed pivoted factors, like the rank-k pattern.
    call=lambda p, factors, b, **_: _core_pivoted.pivoted_solve(factors, b),
    supports=lambda p: False,
    priority=lambda p: 0.0,
    autotune=False,
))

# ---------------------------------------------------------------------------
# banded factor
# ---------------------------------------------------------------------------
register(Backend(
    name="pallas_blocked", op="factor", structure="banded",
    call=lambda p, arow, *, bw, block=None, interpret=None, **_:
        _kbanded.banded_lu_blocked(arow, bw=bw, block=block, interpret=interpret),
    supports=lambda p: _local(p) and (_off_tpu(p) or _core_banded.blocked_kernel_takes(
        p.n, p.bw, None, _itemsize(p), compiled=True)),
    priority=lambda p: 3.0 if _banded_skew_bytes(p) <= BANDED_VMEM_MAX_BYTES else 0.0,
    vmem_bytes=lambda p: 2 * _banded_skew_bytes(p),
))
register(Backend(
    name="pallas_tiled", op="factor", structure="banded",
    call=lambda p, arow, *, bw, block=None, interpret=None, **_:
        _kbanded.banded_lu_tiled(arow, bw=bw, block=block, interpret=interpret),
    supports=_local,
    priority=lambda p: 1.0,
))
register(Backend(
    name="xla", op="factor", structure="banded",
    call=lambda p, arow, *, bw, block=None, **_: _core_banded.banded_lu_blocked(arow, bw=bw, block=block),
    supports=_local,
    priority=lambda p: 0.5,
))
register(Backend(
    name="pallas_scalar", op="factor", structure="banded",
    call=lambda p, arow, *, bw, interpret=None, **_:
        _kbanded.banded_lu_kernelized(arow, bw=bw, interpret=interpret),
    # off TPU: Mosaic refuses the value-level dynamic_slice in its body
    supports=lambda p: _local(p) and _off_tpu(p),
    priority=lambda p: 0.2,
    autotune=False,  # legacy scalar-sequential kernel (forced-impl only)
))
register(Backend(
    name="xla_scalar", op="factor", structure="banded",
    call=lambda p, arow, *, bw, **_: _core_banded.banded_lu(arow, bw=bw),
    supports=_local,
    priority=lambda p: 0.1,
    autotune=False,  # not value-identical to the blocked twins
))

# ---------------------------------------------------------------------------
# banded solve
# ---------------------------------------------------------------------------
register(Backend(
    name="pallas", op="solve", structure="banded",
    call=lambda p, lub, b, *, bw, block=None, rhs_tile=256, interpret=None, **_:
        _kbanded.banded_solve_kernelized(_packed(lub), b, bw=bw, block=block, rhs_tile=rhs_tile, interpret=interpret),
    supports=_local,
    priority=lambda p: 2.0,
))
register(Backend(
    name="pallas_inverted", op="solve", structure="banded",
    # Factorization-artifact fast path: two-phase batched-GEMM substitution
    # against the factor-time inverted windows + pre-coupled transfer
    # blocks.  Statically below the blocked kernel (cache-less selection is
    # unchanged); the measured shootout rows (banded_solve_n16384_*) steer
    # enriched dispatches here where it wins.  The `enriched` capability
    # keeps raw-operand dispatches from paying the on-the-fly enrichment.
    call=lambda p, lub, b, *, bw, block=None, rhs_tile=512, interpret=None, **_:
        _banded_inverted_call(lub, b, bw=bw, block=block, rhs_tile=rhs_tile, interpret=interpret),
    # off TPU: Mosaic refuses the in-kernel lax.associative_scan
    supports=lambda p: _local(p) and p.enriched and _off_tpu(p),
    priority=lambda p: 1.5,
    vmem_bytes=_banded_inverted_vmem_bytes,
))
register(Backend(
    name="xla_inverted", op="solve", structure="banded",
    # pure-jnp bitwise mirror of pallas_inverted (twin contract)
    call=lambda p, lub, b, *, bw, block=None, **_:
        _banded_inverted_mirror_call(lub, b, bw=bw, block=block),
    supports=lambda p: _local(p) and p.enriched,
    priority=lambda p: 0.1,
    autotune=False,
))
register(Backend(
    name="xla", op="solve", structure="banded",
    call=lambda p, lub, b, *, bw, block=None, **_:
        _core_banded.banded_solve_blocked(_packed(lub), b, bw=bw, block=block),
    supports=_local,
    priority=lambda p: 1.0,
))
register(Backend(
    name="xla_scalar", op="solve", structure="banded",
    # multi-RHS capability slot: the scalar sweep is vector-only (its padded
    # carry is 1-D), so a coalesced stacked-RHS dispatch (serve.solve_service)
    # must never be steered here even when the measured cache (keyed without
    # rhs) says it wins for vector solves.
    # rhs <= 1 admits both a vector and a single-column coalesced stack
    # (serve dispatches (n, 1)); the sweep itself is strictly 1-D, so
    # squeeze/re-expand around it.
    call=lambda p, lub, b, *, bw, **_: (
        _core_banded.banded_solve(_packed(lub), b[:, 0], bw=bw)[:, None]
        if getattr(b, "ndim", 1) == 2
        else _core_banded.banded_solve(_packed(lub), b, bw=bw)),
    supports=lambda p: _local(p) and p.rhs <= 1,
    priority=lambda p: 0.5,  # statically dominated; wins via measurement on
                             # this container (BENCH_kernels.json, banded_solve_*)
))

# ---------------------------------------------------------------------------
# batched dense (optimizer path: many small independent systems)
# ---------------------------------------------------------------------------
register(Backend(
    name="pallas_vmem", op="factor", structure="batched_dense",
    call=lambda p, a, *, interpret=None, **_: _kbatched.batched_lu_vmem(a, interpret=interpret),
    # off TPU: Mosaic refuses the value-level dynamic_slice in its body
    supports=lambda p: _is_f32(p) and _local(p) and p.n <= BATCHED_VMEM_MAX_N and _off_tpu(p),
    priority=lambda p: 2.0,
    vmem_bytes=lambda p: 2 * p.n * p.n * _itemsize(p),  # per grid program
))
register(Backend(
    name="xla", op="factor", structure="batched_dense",
    call=lambda p, a, *, block=256, **_: _batched_xla_lu(a, block=block),
    supports=_local,
    priority=lambda p: 1.0,
))
register(Backend(
    name="pallas_vmem", op="solve", structure="batched_dense",
    # rhs-aware capability: each grid program holds its whole (n, rhs) RHS
    # in VMEM next to the (n, n) factors, so a wide coalesced stack must
    # overflow to the vmapped mirror rather than the kernel.
    call=lambda p, lu, b, *, interpret=None, **_: _kbatched.batched_lu_solve_vmem(_packed(lu), b, interpret=interpret),
    # off TPU: Mosaic refuses the value-level dynamic_slice in its body
    supports=lambda p: _is_f32(p) and _local(p) and p.n <= BATCHED_VMEM_MAX_N
        and max(p.rhs, 1) <= 4 * p.n and _off_tpu(p),
    priority=lambda p: 2.0,
    vmem_bytes=lambda p: (2 * p.n * p.n + 2 * p.n * max(p.rhs, 1)) * _itemsize(p),
))
register(Backend(
    name="xla", op="solve", structure="batched_dense",
    call=lambda p, lu, b, **_: _batched_xla_solve_j(_packed(lu), b),
    supports=_local,
    priority=lambda p: 1.0,
))
register(Backend(
    name="pallas_inverted", op="solve", structure="batched_dense",
    # batched analog of the dense inverted-diagonal path (the grouped
    # optimizer stacks): routes through the vmapped mirror — value-identical
    # to the unbatched twins, reached by name via ops._batched_impl.
    call=lambda p, lu, b, *, block=None, interpret=None, **_:
        _batched_dense_inverted_call(lu, b, block=block),
    supports=lambda p: _local(p) and p.enriched,
    priority=lambda p: 0.75,
    autotune=False,
))

# ---------------------------------------------------------------------------
# batched banded (optimizer / CFD ensemble path)
# ---------------------------------------------------------------------------
register(Backend(
    name="pallas_vmem", op="factor", structure="batched_banded",
    call=lambda p, arow, *, bw, block=None, interpret=None, **_:
        _kbanded.batched_banded_lu_vmem(arow, bw=bw, block=block, interpret=interpret),
    # off TPU: per-system row offsets are not provably sublane-aligned
    supports=lambda p: _is_f32(p) and _local(p) and _off_tpu(p)
        and _banded_skew_bytes(p) <= BANDED_VMEM_MAX_BYTES,
    priority=lambda p: 2.0,
    vmem_bytes=lambda p: 2 * _banded_skew_bytes(p),
))
register(Backend(
    name="xla", op="factor", structure="batched_banded",
    call=lambda p, arow, *, bw, block=None, **_: _batched_xla_banded_lu(arow, bw=bw, block=block),
    supports=_local,
    priority=lambda p: 1.0,
))
register(Backend(
    name="pallas_vmem", op="solve", structure="batched_banded",
    # rhs-aware: the per-program RHS ((n, rhs)) shares VMEM with the skewed
    # band, so both must fit under the banded byte cap.
    call=lambda p, lub, b, *, bw, block=None, interpret=None, **_:
        _kbanded.batched_banded_solve_vmem(_packed(lub), b, bw=bw, block=block, interpret=interpret),
    # off TPU: Mosaic refuses the sweeps' value-level dynamic_slice
    supports=lambda p: _is_f32(p) and _local(p) and _off_tpu(p)
        and _banded_skew_bytes(p) + 2 * p.n * max(p.rhs, 1) * _itemsize(p)
            <= BANDED_VMEM_MAX_BYTES,
    priority=lambda p: 2.0,
    vmem_bytes=lambda p: 2 * _banded_skew_bytes(p) + 2 * p.n * max(p.rhs, 1) * _itemsize(p),
))
register(Backend(
    name="xla", op="solve", structure="batched_banded",
    call=lambda p, lub, b, *, bw, block=None, **_: _batched_xla_banded_solve(_packed(lub), b, bw=bw, block=block),
    supports=_local,
    priority=lambda p: 1.0,
))
register(Backend(
    name="pallas_inverted", op="solve", structure="batched_banded",
    # batched analog of the two-phase inverted band solve (vmapped mirror)
    call=lambda p, lub, b, *, bw, block=None, interpret=None, **_:
        _batched_banded_inverted_call(lub, b, bw=bw, block=block),
    supports=lambda p: _local(p) and p.enriched,
    priority=lambda p: 1.5,
    autotune=False,
))

# ---------------------------------------------------------------------------
# fused linear_solve (factor + substitution in one backend) — multi-device
# only; single-device linear_solve composes a factor and a solve selection
# in repro.kernels.ops.
# ---------------------------------------------------------------------------
register(Backend(
    name="distributed", op="linear_solve", structure="dense",
    call=_distributed_linear_solve,
    supports=lambda p: p.devices > 1,
    priority=lambda p: 10.0,
    autotune=False,
))

# ---------------------------------------------------------------------------
# approximate tiers: admitted by the tolerance gate only (residual_bound
# set), so default-tolerance problems never see them.  Single-device
# linear_solve normally composes factor+solve in repro.kernels.ops; a
# tolerance-carrying call consults this slot first, which is where the
# mixed-precision path lives (it needs the full operand for refinement).
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("block", "tolerance", "max_iters", "interpret", "use_kernel"))
def _bf16_ir_solve(a, b, *, block, tolerance, max_iters, interpret, use_kernel):
    """Factor in bf16 (half the factor bytes, MXU-native), refine the
    solution in f32 against the full-precision operand."""
    # bf16 rounds the operand — that is the tier's accuracy class (half the
    # factor input precision) — while the factorization itself accumulates
    # in f32: the MXU contract for bf16 matmuls (bf16 operands, f32
    # accumulator), and ~6x faster than end-to-end bf16 emulation when the
    # kernel runs in interpret mode.
    a16 = a.astype(jnp.bfloat16).astype(jnp.float32)
    lu16 = (
        _k.lu_fused(a16, block=block, interpret=interpret)
        if use_kernel
        else _core_blocked.fused_blocked_lu(a16, block=block)
    )

    # The correction operator runs once per refinement sweep, so its cost
    # multiplies: pre-invert the diagonal blocks once and substitute via
    # the blocked inverted-diagonal sweeps (batched GEMMs) instead of the
    # 2n-step scalar recurrence of core.solve.lu_solve — same bf16-factor
    # accuracy class, the refinement loop still contracts to tolerance.
    linv, uinv = _fz.dense_block_inverses(lu16, block=block)

    def correct(r):
        return _fz.dense_inverted_solve(lu16, linv, uinv, r, block=block)

    x, _info = _core_refine.iterative_refinement(
        a, b, correct(b.astype(jnp.float32)), correct,
        tolerance=tolerance, max_iters=max_iters,
    )
    return x.astype(a.dtype)


@functools.partial(jax.jit, static_argnames=("block", "tolerance", "max_iters"))
def _bf16_ir_solve_batched(a, b, *, block, tolerance, max_iters):
    # same bf16-rounded-operand / f32-accumulation semantics as the
    # unbatched tier above
    lu16 = jax.vmap(lambda m: _core_blocked.fused_blocked_lu(m, block=block))(
        a.astype(jnp.bfloat16).astype(jnp.float32)
    )

    def one(ai, lui, bi):
        correct = lambda r: _core_solve.lu_solve(lui, r)
        x, _info = _core_refine.iterative_refinement(
            ai, bi, correct(bi.astype(jnp.float32)), correct,
            tolerance=tolerance, max_iters=max_iters,
        )
        return x

    return jax.vmap(one)(a, lu16, b).astype(a.dtype)


def _ir_tolerance(p: Problem) -> float:
    # refine to the caller's tolerance, never past the tier's floor (extra
    # sweeps below the floor only burn the iteration cap)
    return max(p.tolerance, BF16_IR_RESIDUAL_FLOOR)


register(Backend(
    name="bf16_ir", op="linear_solve", structure="dense",
    call=lambda p, a, b, *, block=256, interpret=None, **_: _bf16_ir_solve(
        a, b, block=block, tolerance=_ir_tolerance(p), max_iters=IR_MAX_ITERS,
        interpret=interpret, use_kernel=True),
    supports=lambda p: _is_f32(p) and _local(p),
    priority=lambda p: 5.0,  # the preferred approximate tier once admitted
    autotune=False,  # not value-identical to the exact tier
    residual_bound=lambda p: BF16_IR_RESIDUAL_FLOOR,
    vmem_bytes=lambda p: 3 * p.n * 256 * 2,  # bf16 megakernel scratch slabs
))
register(Backend(
    name="bf16_ir_xla", op="linear_solve", structure="dense",
    call=lambda p, a, b, *, block=256, interpret=None, **_: _bf16_ir_solve(
        a, b, block=block, tolerance=_ir_tolerance(p), max_iters=IR_MAX_ITERS,
        interpret=interpret, use_kernel=False),
    supports=lambda p: _is_f32(p) and _local(p),
    priority=lambda p: 4.0,
    autotune=False,
    residual_bound=lambda p: BF16_IR_RESIDUAL_FLOOR,
))
register(Backend(
    name="bf16_ir", op="linear_solve", structure="batched_dense",
    # the optimizer's grouped (B, n, n) preconditioner systems land here
    # when the run carries a solve tolerance
    call=lambda p, a, b, *, block=256, interpret=None, **_: _bf16_ir_solve_batched(
        a, b, block=block, tolerance=_ir_tolerance(p), max_iters=IR_MAX_ITERS),
    supports=lambda p: _is_f32(p) and _local(p),
    priority=lambda p: 5.0,
    autotune=False,
    residual_bound=lambda p: BF16_IR_RESIDUAL_FLOOR,
))


def _rand_rank(p: Problem, rank) -> int:
    # rank= comes through the public ops; an admitted auto-selection without
    # one sketches at n/8 (the class contract is the caller's to honour)
    return int(rank) if rank else max(1, p.n // 8)


register(Backend(
    name="rand_lu", op="factor", structure="dense",
    call=lambda p, a, *, rank=None, oversample=8, rng_key=None, interpret=None, **_:
        _core_rand.randomized_lu(
            a, rank=_rand_rank(p, rank), oversample=oversample, key=rng_key,
            lu_impl=lambda m: _k.lu_fused(m, interpret=interpret)),
    supports=lambda p: _is_f32(p) and _local(p),
    priority=lambda p: 0.1,  # statically dominated: reach it via rank=/impl=
    autotune=False,
    residual_bound=lambda p: RAND_LU_RESIDUAL_BOUND,
))
register(Backend(
    name="rand_lu", op="solve", structure="dense",
    # consumes RankKFactors, not a packed square factor — never
    # auto-selected; repro.kernels.ops.lu_solve forces it when handed
    # rank-k factors (the serve cache's low-rank tier)
    call=lambda p, factors, b, **_: _core_rand.randomized_solve(factors, b),
    supports=lambda p: False,
    priority=lambda p: 0.0,
    autotune=False,
    residual_bound=lambda p: RAND_LU_RESIDUAL_BOUND,
))
register(Backend(
    name="rand_lu", op="linear_solve", structure="dense",
    call=lambda p, a, b, *, rank=None, oversample=8, rng_key=None, interpret=None, **_:
        _core_rand.randomized_linear_solve(
            a, b, rank=_rand_rank(p, rank), oversample=oversample, key=rng_key,
            lu_impl=lambda m: _k.lu_fused(m, interpret=interpret),
            tolerance=(min(p.tolerance, RAND_LU_RESIDUAL_BOUND) if p.tolerance > 0
                       else RAND_LU_RESIDUAL_BOUND)),
    supports=lambda p: _is_f32(p) and _local(p),
    priority=lambda p: 0.5,  # below bf16_ir: admitted ≠ preferred
    autotune=False,
    residual_bound=lambda p: RAND_LU_RESIDUAL_BOUND,
))


# ---------------------------------------------------------------------------
# multi-device banded: SPIKE split solve vs replicated fallback.
#
# ``spike`` partitions the band into per-device diagonal blocks (see
# repro.core.spike / repro.kernels.spike), admitted only where the spike
# couplings cannot overlap (2·bw ≤ ceil(n/devices)).  ``replicated`` is the
# always-capable fallback: it re-dispatches the same operand as a devices=1
# problem through the ordinary local selection — correctness on one device,
# no scaling.  Both are ``autotune=True`` so the measured cache (keyed on
# ``devices``) weighs SPIKE against replication per (n, bw, devices); with
# no measurement the static priorities prefer SPIKE wherever it is admitted.
# A health-screened/residual-screened SPIKE dispatch demotes to replicated
# through the ordinary escalation funnel.
# ---------------------------------------------------------------------------
def _spike_ok(p: Problem) -> bool:
    from repro.core.spike import spike_supported

    return p.devices > 1 and spike_supported(p.n, p.bw, p.devices)


def _spike_lu(problem, arow, *, bw, mesh=None, axis="model", block=None,
              interpret=None, **_):
    if mesh is not None:
        from repro.kernels.spike import spike_lu_sharded

        return spike_lu_sharded(
            arow, bw=bw, mesh=mesh, axis=axis, block=block, interpret=interpret
        )
    from repro.core.spike import spike_lu

    return spike_lu(arow, bw=bw, devices=problem.devices, block=block)


def _spike_solve(problem, factors, b, *, bw=0, mesh=None, axis="model",
                 block=None, interpret=None, **_):
    if mesh is not None:
        from repro.kernels.spike import spike_solve_sharded

        return spike_solve_sharded(
            factors, b, mesh=mesh, axis=axis, block=block, interpret=interpret
        )
    from repro.core.spike import spike_solve

    return spike_solve(factors, b, block=block)


def _spike_linear_solve(problem, arow, b, *, bw, mesh=None, axis="model",
                        block=None, interpret=None, **_):
    if mesh is not None:
        from repro.kernels.spike import spike_linear_solve_sharded

        return spike_linear_solve_sharded(
            arow, b, bw=bw, mesh=mesh, axis=axis, block=block, interpret=interpret
        )
    from repro.core.spike import spike_linear_solve

    return spike_linear_solve(
        arow, b, bw=bw, devices=problem.devices, block=block
    )


def _replicated_banded_lu(problem, arow, *, bw, mesh=None, axis=None,
                          block=None, interpret=None, **_):
    from .registry import dispatch

    return dispatch(
        dataclasses.replace(problem, devices=1),
        arow, bw=bw, block=block, interpret=interpret,
    )


def _replicated_banded_linear_solve(problem, arow, b, *, bw, mesh=None,
                                    axis=None, block=None, interpret=None, **_):
    # single-device banded linear_solve has no fused backend (it composes in
    # repro.kernels.ops), so replication composes the local factor and solve
    # selections directly
    from .registry import dispatch

    local = dataclasses.replace(problem, devices=1)
    factors = dispatch(
        dataclasses.replace(local, op="factor"),
        arow, bw=bw, block=block, interpret=interpret,
    )
    return dispatch(
        dataclasses.replace(local, op="solve"),
        factors, b, bw=bw, block=block, interpret=interpret,
    )


register(Backend(
    name="spike", op="factor", structure="banded",
    call=_spike_lu,
    supports=_spike_ok,
    priority=lambda p: 10.0,
))
register(Backend(
    name="replicated", op="factor", structure="banded",
    call=_replicated_banded_lu,
    supports=lambda p: p.devices > 1,
    priority=lambda p: 1.0,
))
register(Backend(
    name="spike", op="solve", structure="banded",
    # consumes SpikeFactors, never auto-selected: repro.kernels.ops
    # .banded_solve forces it when handed a SPIKE artifact (the pivoted /
    # rank-k pattern)
    call=_spike_solve,
    supports=lambda p: False,
    priority=lambda p: 0.0,
    autotune=False,
))
register(Backend(
    name="spike", op="linear_solve", structure="banded",
    call=_spike_linear_solve,
    supports=_spike_ok,
    priority=lambda p: 10.0,
))
register(Backend(
    name="replicated", op="linear_solve", structure="banded",
    call=_replicated_banded_linear_solve,
    supports=lambda p: p.devices > 1,
    priority=lambda p: 1.0,
))
