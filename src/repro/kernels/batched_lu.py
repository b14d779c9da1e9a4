"""Pallas kernel: batched small-matrix EbV LU (+solve) — the optimizer path.

The EbV-preconditioned optimizer factors many independent (n, n) systems
(one per parameter factor / expert).  On TPU the natural mapping is one
grid program per matrix: each (n, n) system is VMEM-resident and the grid
runs the batch — equalized trivially (every work unit is one identical
factorization, the paper's invariant by construction).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import require_interpret
from .ebv_lu import _lu_body

# Both kernels run the rank-1 bodies on a VMEM value with traced
# dynamic_slice, which Mosaic refuses: interpret mode only.
_REFUSAL = "value-level dynamic_slice in the kernel body"

__all__ = ["batched_lu_vmem", "batched_lu_solve_vmem"]


def _batched_lu_kernel(a_ref, o_ref, *, steps: int):
    a = a_ref[0]
    o_ref[0] = jax.lax.fori_loop(0, steps, _lu_body(*a.shape), a)


@functools.partial(jax.jit, static_argnames=("interpret",))
def batched_lu_vmem(a: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """(B, n, n) → packed LU per matrix; grid over the batch."""
    interpret = require_interpret("batched_lu.batched_lu_vmem", _REFUSAL, interpret)
    bsz, n, _ = a.shape
    return pl.pallas_call(
        functools.partial(_batched_lu_kernel, steps=n - 1),
        grid=(bsz,),
        in_specs=[pl.BlockSpec((1, n, n), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, n, n), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret,
    )(a)


def _batched_solve_kernel(lu_ref, b_ref, x_ref, *, n: int):
    lu = lu_ref[0]
    y = b_ref[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)

    def fwd(k, y):
        lk = jnp.where(rows > k, jax.lax.dynamic_slice(lu, (0, k), (n, 1)), 0.0)
        return y - lk * jax.lax.dynamic_slice(y, (k, 0), (1, y.shape[1]))

    y = jax.lax.fori_loop(0, n - 1, fwd, y)

    def bwd(j, x):
        k = n - 1 - j
        pivot = jax.lax.dynamic_slice(lu, (k, k), (1, 1))
        xk = jax.lax.dynamic_slice(x, (k, 0), (1, x.shape[1])) / pivot
        x = jax.lax.dynamic_update_slice(x, xk, (k, 0))
        uk = jnp.where(rows < k, jax.lax.dynamic_slice(lu, (0, k), (n, 1)), 0.0)
        return x - uk * xk

    x_ref[0] = jax.lax.fori_loop(0, n, bwd, y)


@functools.partial(jax.jit, static_argnames=("interpret",))
def batched_lu_solve_vmem(lu: jax.Array, b: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """lu: (B, n, n) packed; b: (B, n, m) → x: (B, n, m)."""
    lu = getattr(lu, "packed", lu)  # accept Factorization artifacts
    interpret = require_interpret("batched_lu.batched_lu_solve_vmem", _REFUSAL, interpret)
    bsz, n, _ = lu.shape
    m = b.shape[-1]
    return pl.pallas_call(
        functools.partial(_batched_solve_kernel, n=n),
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, n, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n, m), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n, m), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        interpret=interpret,
    )(lu, b)
