"""Sharded SPIKE entry: per-partition Pallas local work under ``shard_map``.

The multi-device realization of :mod:`repro.core.spike`: the stacked
per-partition operands (leading ``devices`` axis) are laid over a mesh axis
with ``shard_map``, each device runs the existing single-dispatch Pallas
megakernels locally — :func:`repro.kernels.banded.banded_lu_blocked` (or
``banded_lu_tiled`` for bands past the VMEM cap) for the block factor,
:func:`repro.kernels.banded.banded_solve_kernelized` for the spike/``g``
solves — and everything *around* the local work (partitioning,
coupling extraction, reduced-system assembly and tip solve, recovery) is the
exact shared code from :mod:`repro.core.spike`.  Kernel-vs-mirror bitwise
equality therefore reduces to the established per-partition kernel/mirror
twin contract: same shapes, same blocked schedule, same window jaxprs.

Communication pattern per solve: the local ``g`` solves run embarrassingly
parallel, the ``2·d·bw``-row tips gather once for the reduced solve (the
only cross-device traffic — O(d·bw·k) floats), and the recovery GEMMs are
local again.
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import banded as core_banded
from repro.core import spike as core_spike
from repro.dist.sharding import shard_map

from . import banded as kbanded
from . import interpret_mode

__all__ = [
    "spike_lu_sharded",
    "spike_solve_sharded",
    "spike_linear_solve_sharded",
]


def _local_factor(p, *, bw: int, block: int | None, interpret: bool | None):
    """Per-partition band factor: the VMEM-resident blocked kernel where the
    registry's rule lets it take the band, the HBM-streaming tiled kernel
    otherwise (both bitwise twins of the same mirror)."""
    compiled = not interpret_mode(interpret)
    blocked = core_banded.blocked_kernel_takes(
        p.shape[0], bw, block, p.dtype.itemsize, compiled=compiled)
    kernel = kbanded.banded_lu_blocked if blocked else kbanded.banded_lu_tiled
    return kernel(p, bw=bw, block=block, interpret=interpret)


# The jitted shard_map entries are cached per (mesh, axis, kernel params):
# defining the local fn inside each public call would hand jax.jit a fresh
# function object every time, so every solve would re-trace and re-compile
# (~30x the actual substitution cost at the bench shape).  jax.jit still
# specializes per operand shape underneath each cached entry.
@functools.lru_cache(maxsize=None)
def _factor_entry(mesh, axis: str, bw: int, block: int | None,
                  interpret: bool | None):
    def local_fn(p, r):
        p = p[0] if p.ndim == 3 else p
        r = r[0] if r.ndim == 3 else r
        lu = _local_factor(p, bw=bw, block=block, interpret=interpret)
        wv = kbanded.banded_solve_kernelized(
            lu, r, bw=bw, block=block, interpret=interpret
        )
        return lu[None], wv[None]

    return jax.jit(
        shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(P(axis, None, None), P(axis, None, None)),
            out_specs=(P(axis, None, None), P(axis, None, None)),
            check_vma=False,
        )
    )


@functools.lru_cache(maxsize=None)
def _solve_entry(mesh, axis: str, bw: int, block: int | None,
                 interpret: bool | None):
    def local_fn(lu, fj):
        lu = lu[0] if lu.ndim == 3 else lu
        fj = fj[0] if fj.ndim == 3 else fj
        g = kbanded.banded_solve_kernelized(
            lu, fj, bw=bw, block=block, interpret=interpret
        )
        return g[None]

    return jax.jit(
        shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(P(axis, None, None), P(axis, None, None)),
            out_specs=P(axis, None, None),
            check_vma=False,
        )
    )


def spike_lu_sharded(
    arow: jax.Array,
    *,
    bw: int,
    mesh,
    axis: str = "model",
    block: int | None = None,
    interpret: bool | None = None,
) -> core_spike.SpikeFactors:
    """SPIKE factorization with the per-partition factor + spike solve
    sharded over ``mesh.shape[axis]`` devices.  Returns the same
    :class:`repro.core.spike.SpikeFactors` artifact as the mirror."""
    devices = mesh.shape[axis]
    parts, rhs, _m = core_spike.partition_band(arow, bw=bw, devices=devices)
    fn = _factor_entry(mesh, axis, bw, block, interpret)
    local_lu, wv = fn(parts, rhs)
    # canonicalize placement before the shared eager tail: the recovery and
    # assembly ops lower differently over mesh-sharded operands than over
    # single-device ones, which would break the kernel≡mirror bitwise
    # contract.  The solve entry re-shards ``local_lu`` through its own
    # in_specs, so nothing is lost.  (Without the gather the tail still
    # solves the system on an 8-device CPU mesh; no accelerator mesh has
    # run it yet, so the gather stays on every platform.)
    local_lu, wv = jax.device_put((local_lu, wv), jax.devices()[0])
    return core_spike.assemble_spike_factors(
        local_lu, wv, n=arow.shape[0], bw=bw, devices=devices
    )


def spike_solve_sharded(
    factors: core_spike.SpikeFactors,
    b: jax.Array,
    *,
    mesh,
    axis: str = "model",
    block: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """SPIKE substitution with the local ``g`` solves sharded over the mesh;
    the reduced tip solve and recovery run on the gathered result via the
    shared :mod:`repro.core.spike` tail."""
    f, squeeze = core_spike._solve_rhs_parts(factors, b)
    bw = factors.bw
    fn = _solve_entry(mesh, axis, bw, block, interpret)
    sharded = NamedSharding(mesh, P(axis, None, None))
    g = fn(
        jax.device_put(factors.local_lu, sharded), jax.device_put(f, sharded)
    )
    # same placement canonicalization as the factor entry: the shared
    # reduced-solve/recovery tail must see single-device operands to stay
    # bitwise with the mirror.
    g = jax.device_put(g, jax.devices()[0])
    return core_spike._finish_solve(factors, g, squeeze)


def spike_linear_solve_sharded(
    arow: jax.Array,
    b: jax.Array,
    *,
    bw: int,
    mesh,
    axis: str = "model",
    block: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Factor + solve through the sharded path."""
    factors = spike_lu_sharded(
        arow, bw=bw, mesh=mesh, axis=axis, block=block, interpret=interpret
    )
    return spike_solve_sharded(
        factors, b, mesh=mesh, axis=axis, block=block, interpret=interpret
    )
