"""Pallas kernels for the substitution (solve) phases.

Column-oriented vectorized substitution: once pivot ``k`` resolves, one
masked axpy retires its contribution from every remaining row — the solve
phase analogue of the bi-vectorized elimination step.

Two drivers:

* :func:`solve_vmem`  — the packed LU stays VMEM-resident per program and the
                        RHS block is tiled over the grid.  Simple and fast
                        while ``(n, n)`` fits in VMEM (n ≲ 4096 fp32).
* :func:`solve_tiled` — blocked substitution that never materializes the
                        whole LU on-chip: the factor stays in HBM (``ANY``
                        memory space) and only one ``(block, block)`` tile is
                        DMA'd to VMEM scratch at a time, so solves scale past
                        the VMEM wall.  Forward phase walks diagonal blocks
                        left→right (unit-lower tile solve, then one GEMM per
                        lower off-diagonal tile); backward phase mirrors it
                        right→left against U.  VMEM footprint per program:
                        ``N·rhs_tile + block²`` floats.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.blocked import pad_identity_tail as _pad_identity_tail
from repro.core.blocked import strip_trsm as _strip_trsm
from repro.core.blocked import strip_utrsm as _strip_utrsm
from repro.core.factorization import equalized_rhs_tile, inverted_dense_sweeps

from . import aligned, interpret_mode, lane_pad, require_interpret, vmem_limit

__all__ = ["solve_vmem", "solve_tiled", "solve_inverted"]


def _solve_kernel(lu_ref, b_ref, x_ref, *, n: int):
    lu = lu_ref[...]
    y = b_ref[...]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)

    def fwd(k, y):
        lk = jnp.where(rows > k, jax.lax.dynamic_slice(lu, (0, k), (n, 1)), 0.0)
        yk = jax.lax.dynamic_slice(y, (k, 0), (1, y.shape[1]))
        return y - lk * yk

    y = jax.lax.fori_loop(0, n - 1, fwd, y)

    def bwd(j, x):
        k = n - 1 - j
        pivot = jax.lax.dynamic_slice(lu, (k, k), (1, 1))
        xk = jax.lax.dynamic_slice(x, (k, 0), (1, x.shape[1])) / pivot
        x = jax.lax.dynamic_update_slice(x, xk, (k, 0))
        uk = jnp.where(rows < k, jax.lax.dynamic_slice(lu, (0, k), (n, 1)), 0.0)
        return x - uk * xk

    x_ref[...] = jax.lax.fori_loop(0, n, bwd, y)


@functools.partial(jax.jit, static_argnames=("rhs_tile", "interpret"))
def solve_vmem(
    lu: jax.Array, b: jax.Array, *, rhs_tile: int = 256, interpret: bool | None = None
) -> jax.Array:
    """Solve ``(LU) x = b`` for packed ``lu`` (n, n) and RHS ``b`` (n,) or
    (n, m); the RHS columns are tiled across the grid.  RHS widths that do
    not divide ``rhs_tile`` are zero-padded to the next tile multiple and
    sliced back (zero columns solve to zero, so padding is inert).
    Interpret mode only: the body slices its VMEM value with traced
    ``dynamic_slice``, which Mosaic refuses."""
    lu = getattr(lu, "packed", lu)  # accept Factorization artifacts
    interpret = require_interpret(
        "trsm.solve_vmem", "value-level dynamic_slice in the kernel body", interpret
    )
    squeeze = b.ndim == 1
    bm = b[:, None] if squeeze else b
    n, m = bm.shape
    rt = min(rhs_tile, m)
    m_pad = -(-m // rt) * rt
    if m_pad != m:
        bm = jnp.pad(bm, ((0, 0), (0, m_pad - m)))
    x = pl.pallas_call(
        functools.partial(_solve_kernel, n=n),
        grid=(m_pad // rt,),
        in_specs=[
            pl.BlockSpec((n, n), lambda j: (0, 0)),
            pl.BlockSpec((n, rt), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((n, rt), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((n, m_pad), bm.dtype),
        interpret=interpret,
    )(lu, bm)
    x = x[:, :m] if m_pad != m else x
    return x[:, 0] if squeeze else x


def _solve_tiled_kernel(lu_any, b_any, x_any, xbuf, ltile, sem, *, num_steps: int, block: int):
    """One RHS tile program: blocked forward then backward substitution with
    the LU factor streamed tile-by-tile from HBM.  The program's ``(N, rt)``
    RHS columns are DMA'd into VMEM scratch once, solved in place, and
    written back (``x_any`` aliases ``b_any``)."""
    del b_any  # aliased to x_any
    S, B = num_steps, block
    rt = xbuf.shape[1]  # a 128 multiple whenever there are several column tiles
    cols = x_any.at[:, pl.ds(pl.multiple_of(pl.program_id(0) * rt, 128), rt)]
    acc_dtype = jnp.promote_types(jnp.float32, xbuf.dtype)  # f32, or f64 under x64

    def rows(i):
        return pl.ds(aligned(i * B, B), B)

    def copy(src, dst):
        dma = pltpu.make_async_copy(src, dst, sem)
        dma.start()
        dma.wait()

    def load(i, j):
        copy(lu_any.at[pl.ds(i * B, B), pl.ds(j * B, B)], ltile)

    def retire(r, yi):
        load(r, yi[1])
        blk = xbuf[rows(r), :]
        xbuf[rows(r), :] = blk - jnp.dot(
            ltile[...], yi[0], precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=acc_dtype,
        ).astype(blk.dtype)
        return yi

    copy(cols, xbuf)

    def fwd_outer(i, _):
        load(i, i)
        yi = _strip_trsm(ltile[...], xbuf[rows(i), :])
        xbuf[rows(i), :] = yi
        jax.lax.fori_loop(i + 1, S, retire, (yi, i))
        return 0

    jax.lax.fori_loop(0, S, fwd_outer, 0)

    def bwd_outer(jj, _):
        i = (S - 1) - jj
        load(i, i)
        xi = _strip_utrsm(ltile[...], xbuf[rows(i), :])
        xbuf[rows(i), :] = xi
        jax.lax.fori_loop(0, i, retire, (xi, i))
        return 0

    jax.lax.fori_loop(0, S, bwd_outer, 0)
    copy(xbuf, cols)


# VMEM the tiled solve may spend on its resident (N, rt) RHS columns.
_SOLVE_TILED_X_BYTES = 16 * 2**20


@functools.partial(jax.jit, static_argnames=("block", "rhs_tile", "interpret"))
def solve_tiled(
    lu: jax.Array,
    b: jax.Array,
    *,
    block: int = 256,
    rhs_tile: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Blocked ``(LU) x = b`` solve with the factor HBM-resident.

    Pads ``n`` to a multiple of ``block`` with an identity tail (inert: unit
    diagonal, zero coupling) and the RHS with zero rows/columns, then runs one
    program per RHS column tile.  Only one ``(block, block)`` LU tile and the
    program's ``(N, rt)`` RHS columns are on-chip at a time, so the solve
    scales to matrices far past what :func:`solve_vmem` can hold (~4096²
    fp32); ``rt`` shrinks (in 128-lane steps) to keep the columns within
    16 MB of VMEM."""
    lu = getattr(lu, "packed", lu)  # accept Factorization artifacts
    interpret = interpret_mode(interpret)
    squeeze = b.ndim == 1
    bm = b[:, None] if squeeze else b
    out_dtype = bm.dtype
    # substitution runs at (at least) f32: lower-precision factors/RHS are
    # solved in f32 and cast back (more accurate than bf16 math); f64 inputs
    # keep f64 scratch and full accuracy
    compute_dtype = jnp.promote_types(jnp.float32, jnp.promote_types(lu.dtype, out_dtype))
    lu = lu.astype(compute_dtype)
    bm = bm.astype(compute_dtype)
    n, m = bm.shape
    B = min(block, n)
    S = -(-n // B)
    N = S * B
    itemsize = jnp.dtype(compute_dtype).itemsize
    mw = lane_pad(bm[:1]).shape[1]  # whole lane tiles
    lanes = max(128, _SOLVE_TILED_X_BYTES // (N * itemsize) // 128 * 128)
    rt = min(rhs_tile, mw, lanes)
    if rt < mw:  # several column tiles: each must start on a 128-lane boundary
        rt = max(128, rt // 128 * 128)
    M = -(-mw // rt) * rt
    lu = _pad_identity_tail(lu, N)
    if (N, M) != (n, m):
        bm = jnp.pad(bm, ((0, N - n), (0, M - m)))
    x = pl.pallas_call(
        functools.partial(_solve_tiled_kernel, num_steps=S, block=B),
        grid=(M // rt,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((N, M), bm.dtype),
        scratch_shapes=[
            pltpu.VMEM((N, rt), compute_dtype),
            pltpu.VMEM((B, B), compute_dtype),
            pltpu.SemaphoreType.DMA,
        ],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit((N * max(rt, 128) + B * B) * itemsize)
        ),
        interpret=interpret,
    )(lu, bm)
    x = x[:n, :m].astype(out_dtype)
    return x[:, 0] if squeeze else x


def _solve_inverted_kernel(
    lu_any, linv_any, uinv_any, b_ref, x_ref, ltile, ibuf, sem, isem,
    *, num_steps: int, block: int,
):
    """One RHS-tile program of the inverted-diagonal blocked solve: the
    factor and the ``(S, B, B)`` inverse stacks stay in HBM; per step one
    off-diagonal tile or one inverse block is DMA'd to VMEM and every
    diagonal step is pure GEMM
    (:func:`repro.core.factorization.inverted_dense_sweeps`)."""
    B = block

    def read_tile(r, i):
        dma = pltpu.make_async_copy(
            lu_any.at[pl.ds(r * B, B), pl.ds(i * B, B)], ltile, sem
        )
        dma.start()
        dma.wait()
        return ltile[...]

    def _read_inv(src, i):
        dma = pltpu.make_async_copy(src.at[pl.ds(i, 1)], ibuf, isem)
        dma.start()
        dma.wait()
        return ibuf[0]

    x_ref[...] = inverted_dense_sweeps(
        read_tile,
        functools.partial(_read_inv, linv_any),
        functools.partial(_read_inv, uinv_any),
        b_ref[...],
        num_steps=num_steps,
        block=B,
    )


@functools.partial(jax.jit, static_argnames=("rhs_tile", "interpret"))
def solve_inverted(
    lu: jax.Array,
    linv: jax.Array,
    uinv: jax.Array,
    b: jax.Array,
    *,
    rhs_tile: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Blocked ``(LU) x = b`` solve consuming a
    :class:`~repro.core.factorization.Factorization` artifact's pre-inverted
    ``(S, B, B)`` diagonal blocks: the per-diagonal-block ``strip_trsm``
    recurrence and the scalar backward loop of :func:`solve_tiled` are
    replaced by one GEMM against the stored inverse — the whole sweep is
    GEMM + rank-``B`` retirement.  RHS columns run in *equalized* tiles
    (:func:`repro.core.factorization.equalized_rhs_tile`), sized for the
    wide stacked-RHS dispatches the solve service coalesces.
    Bitwise-identical to
    :func:`repro.core.factorization.dense_inverted_solve`.  Interpret mode
    only: the shared sweeps slice the carried RHS value with traced
    ``dynamic_slice``, which Mosaic refuses."""
    interpret = require_interpret(
        "trsm.solve_inverted", "value-level dynamic_slice in the shared sweeps", interpret
    )
    squeeze = b.ndim == 1
    bm = b[:, None] if squeeze else b
    out_dtype = bm.dtype
    compute_dtype = jnp.promote_types(jnp.float32, jnp.promote_types(lu.dtype, out_dtype))
    n, m = bm.shape
    S, B = linv.shape[0], linv.shape[1]
    N = S * B
    rt = equalized_rhs_tile(m, rhs_tile)
    M = -(-m // rt) * rt
    lup = _pad_identity_tail(lu.astype(compute_dtype), N)
    bm = bm.astype(compute_dtype)
    if (N, M) != (n, m):
        bm = jnp.pad(bm, ((0, N - n), (0, M - m)))
    x = pl.pallas_call(
        functools.partial(_solve_inverted_kernel, num_steps=S, block=B),
        grid=(M // rt,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((N, rt), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((N, rt), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((N, M), bm.dtype),
        scratch_shapes=[
            pltpu.VMEM((B, B), compute_dtype),
            pltpu.VMEM((1, B, B), linv.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(lup, linv, uinv, bm)
    x = x[:n, :m].astype(out_dtype)
    return x[:, 0] if squeeze else x
