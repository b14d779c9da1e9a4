"""Pallas kernels for the banded ("sparse") EbV path.

The band is the paper's *naturally equalized* workload (DESIGN.md §4): every
elimination step touches exactly ``bw`` L and ``bw`` U elements.  Four
kernels, all single-dispatch (one ``pallas_call`` per factorization/solve):

* :func:`banded_lu_blocked`     — **blocked band LU megakernel**: the whole
                                  band VMEM-resident in the window-aligned
                                  skewed layout, one ``fori_loop`` step per
                                  ``C``-row block.  Each step assembles its
                                  dense ``(C+bw, C+bw)`` working window from
                                  two contiguous slices and retires ``C``
                                  pivots via ``(bw+1, bw+1)``-confined
                                  bi-vector updates
                                  (:func:`repro.core.banded.band_block_step`)
                                  — replacing the ``n−1`` scalar-sequential
                                  steps of the old kernel with ``⌈n/C⌉``
                                  equal-work block steps.
* :func:`banded_lu_tiled`       — HBM-streaming variant: the skewed band
                                  stays in HBM (``ANY`` memspace, carried in
                                  place via ``input_output_aliases``) and
                                  each grid step DMAs one ``(C+bw, C+2bw)``
                                  slab through a bounded VMEM buffer — ``n``
                                  is no longer capped by band-fits-VMEM.
* :func:`banded_solve_kernelized` — blocked forward/backward substitution on
                                  the packed band factors (factors and RHS
                                  HBM-resident; one ``(C, C+2bw)`` coupling
                                  strip and one RHS window DMA'd per
                                  block), mirroring ``trsm.py``'s
                                  strip-recurrence + rank-``C2`` retirement;
                                  RHS column tiles across the grid.
* :func:`batched_banded_lu_vmem` / :func:`batched_banded_solve_vmem` — the
                                  optimizer's many-small-systems path: one
                                  grid program per system (equalized
                                  trivially — every program factors one
                                  identical-shape band).

All blocked kernels trace the exact window-helper jaxprs of the pure-jnp
mirrors in :mod:`repro.core.banded`, so kernel and mirror produce
**bitwise-identical** packed band factors.  The legacy scalar kernel
(:func:`banded_lu_kernelized`) is kept as the measured baseline.  On TPU
the blocked, tiled and solve kernels lower to Mosaic (skewed-band rows and
narrow RHS are zero-padded to whole 128-lane tiles when compiled); the
scalar, inverted-solve and batched kernels run in interpret mode only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.banded import (
    band_block_size,
    band_block_step,
    band_to_skewed,
    pad_band_identity,
    skew_pad,
    skewed_to_band,
    unit_lower_window_solve,
    upper_window_solve,
)
from repro.core.blocked import dot_f32
from repro.core.factorization import equalized_rhs_tile, inverted_band_sweeps

from . import aligned, interpret_mode, lane_pad, require_interpret, vmem_limit

__all__ = [
    "banded_lu_kernelized",
    "banded_lu_blocked",
    "banded_lu_tiled",
    "banded_solve_kernelized",
    "banded_solve_inverted",
    "batched_banded_lu_vmem",
    "batched_banded_solve_vmem",
]


# ---------------------------------------------------------------------------
# legacy scalar-sequential kernel (kept as the measured baseline)
# ---------------------------------------------------------------------------
def _banded_kernel(ap_ref, out_ref, *, n: int, bw: int):
    w = 2 * bw + 1
    ap = ap_ref[...]  # (n + bw, w), zero-padded rows at the bottom
    s = jax.lax.broadcasted_iota(jnp.int32, (bw, w), 0) + 1  # row offset 1..bw
    c = jax.lax.broadcasted_iota(jnp.int32, (bw, w), 1)
    src = c - (bw + 1 - s)  # index into the pivot row's upper tail
    valid = (src >= 0) & (src < bw)
    anti_mask = c == (bw - s)  # where the L element sits in the window
    t = jax.lax.broadcasted_iota(jnp.int32, (bw, w, bw), 2)
    onehot = ((src[..., None] == t) & valid[..., None]).astype(ap.dtype)

    def body(k, ap):
        pivot = jax.lax.dynamic_slice(ap, (k, bw), (1, 1))
        window = jax.lax.dynamic_slice(ap, (k + 1, 0), (bw, w))
        u_tail = jax.lax.dynamic_slice(ap, (k, bw + 1), (1, bw))[0]  # (bw,)
        l = jnp.sum(jnp.where(anti_mask, window, 0.0), axis=1, keepdims=True) / pivot
        shifted = jnp.sum(onehot * u_tail[None, None, :], axis=2)  # (bw, w)
        window = window - l * shifted
        window = jnp.where(anti_mask, l, window)
        return jax.lax.dynamic_update_slice(ap, window, (k + 1, 0))

    out_ref[...] = jax.lax.fori_loop(0, n - 1, body, ap)


@functools.partial(jax.jit, static_argnames=("bw", "interpret"))
def banded_lu_kernelized(arow: jax.Array, *, bw: int, interpret: bool | None = None) -> jax.Array:
    """Row-aligned band (n, 2bw+1) → packed band LU, one scalar-sequential
    Pallas kernel (``n−1`` rank-1 ``fori_loop`` steps — the pre-blocked
    baseline; see :func:`banded_lu_blocked` for the fast path).
    Interpret mode only: the body slices its VMEM value with traced
    ``dynamic_slice``, which Mosaic refuses."""
    interpret = require_interpret(
        "banded.banded_lu_kernelized", "value-level dynamic_slice in the kernel body", interpret
    )
    n = arow.shape[0]
    ap = jnp.concatenate([arow, jnp.zeros((bw, arow.shape[1]), arow.dtype)], axis=0)
    out = pl.pallas_call(
        functools.partial(_banded_kernel, n=n, bw=bw),
        out_shape=jax.ShapeDtypeStruct(ap.shape, ap.dtype),
        interpret=interpret,
    )(ap)
    return out[:n]


# ---------------------------------------------------------------------------
# blocked band LU — VMEM-resident megakernel
# ---------------------------------------------------------------------------
def _banded_blocked_kernel(g_ref, out_ref, *, num_steps: int, block: int, bw: int):
    out_ref[...] = g_ref[...]  # output VMEM blocks start uninitialized on TPU

    def step(i, carry):
        band_block_step(out_ref, aligned(i * block, block), block=block, bw=bw)
        return carry

    jax.lax.fori_loop(0, num_steps, step, 0)


@functools.partial(jax.jit, static_argnames=("bw", "block", "interpret"))
def banded_lu_blocked(
    arow: jax.Array, *, bw: int, block: int | None = None, interpret: bool | None = None
) -> jax.Array:
    """Blocked band LU in ONE ``pallas_call``, whole band VMEM-resident.

    The identity-padded band is re-laid into the window-aligned skewed form
    (:func:`repro.core.banded.band_to_skewed`); each of the ``S``
    ``fori_loop`` steps assembles its dense ``(C+bw, C+bw)`` window from two
    static slices and retires ``C`` pivot rows.  Bitwise-identical to the
    :func:`repro.core.banded.banded_lu_blocked` mirror."""
    interpret = interpret_mode(interpret)
    n = arow.shape[0]
    c = band_block_size(n, bw, block)
    g0, s = skew_pad(arow, bw, c)
    g = lane_pad(g0)
    out = pl.pallas_call(
        functools.partial(_banded_blocked_kernel, num_steps=s, block=c, bw=bw),
        out_shape=jax.ShapeDtypeStruct(g.shape, g.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(2 * g.size * g.dtype.itemsize)
        ),
        interpret=interpret,
    )(g)
    return skewed_to_band(out[:, : g0.shape[1]], bw, c)[:n]


# ---------------------------------------------------------------------------
# blocked band LU — HBM-streaming variant
# ---------------------------------------------------------------------------
def _banded_tiled_kernel(g_any, o_any, slab_buf, sem, *, block: int, bw: int):
    """One grid step: DMA the ``(C+bw, C+2bw)`` skewed slab HBM→VMEM, factor
    its window, DMA it back.  TPU grid steps run sequentially, so step
    ``s+1`` observes the ``bw`` carry rows step ``s`` just wrote."""
    del g_any  # aliased to o_any; all traffic goes through the output ref
    s = pl.program_id(0)
    c = block
    hbm = o_any.at[pl.ds(s * c, c + bw), :]
    load = pltpu.make_async_copy(hbm, slab_buf, sem)
    load.start()
    load.wait()
    band_block_step(slab_buf, 0, block=c, bw=bw)
    store = pltpu.make_async_copy(slab_buf, hbm, sem)
    store.start()
    store.wait()


@functools.partial(jax.jit, static_argnames=("bw", "block", "interpret"))
def banded_lu_tiled(
    arow: jax.Array, *, bw: int, block: int | None = None, interpret: bool | None = None
) -> jax.Array:
    """Blocked band LU in ONE ``pallas_call`` with the band HBM-resident.

    The skewed band is carried in place through ``input_output_aliases``;
    VMEM holds only one ``(C+bw, C+2bw)`` slab regardless of ``n``, so the
    factorization scales past the band-fits-VMEM wall of
    :func:`banded_lu_blocked`.  Bitwise-identical to the blocked mirror
    (same window helpers)."""
    interpret = interpret_mode(interpret)
    n = arow.shape[0]
    c = band_block_size(n, bw, block)
    g0, s = skew_pad(arow, bw, c)
    g = lane_pad(g0)
    out = pl.pallas_call(
        functools.partial(_banded_tiled_kernel, block=c, bw=bw),
        grid=(s,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(g.shape, g.dtype),
        scratch_shapes=[
            pltpu.VMEM((c + bw, g.shape[1]), g.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        input_output_aliases={0: 0},
        interpret=interpret,
    )(g)
    return skewed_to_band(out[:, : g0.shape[1]], bw, c)[:n]


# ---------------------------------------------------------------------------
# blocked band solve
# ---------------------------------------------------------------------------
def _banded_solve_sweeps(read_strip, xp, *, num_steps: int, block: int, bw: int):
    """Blocked forward then backward band substitution on a carried RHS
    value (the batched grid kernel's VMEM-resident form).  ``read_strip(k)``
    yields the skewed factors' dense coupling strip ``F`` ``(C, C+2bw)`` of
    the block at row ``k``.  The carried RHS has ``bw`` zero margin rows at
    both ends so every block reads its above/below coupling window without
    branching."""
    c = block
    rt = xp.shape[1]

    def fwd(i, xp):
        k = i * c
        f = read_strip(k)
        yblk = jax.lax.dynamic_slice(xp, (bw + k, 0), (c, rt)) - dot_f32(
            f[:, :bw], jax.lax.dynamic_slice(xp, (k, 0), (bw, rt))
        ).astype(xp.dtype)
        yblk = unit_lower_window_solve(f[:, bw : bw + c], yblk, bw)
        return jax.lax.dynamic_update_slice(xp, yblk, (bw + k, 0))

    xp = jax.lax.fori_loop(0, num_steps, fwd, xp)

    def bwd(ii, xp):
        k = (num_steps - 1 - ii) * c
        f = read_strip(k)
        xblk = jax.lax.dynamic_slice(xp, (bw + k, 0), (c, rt)) - dot_f32(
            f[:, bw + c :], jax.lax.dynamic_slice(xp, (bw + k + c, 0), (bw, rt))
        ).astype(xp.dtype)
        xblk = upper_window_solve(f[:, bw : bw + c], xblk, bw)
        return jax.lax.dynamic_update_slice(xp, xblk, (bw + k, 0))

    return jax.lax.fori_loop(0, num_steps, bwd, xp)


def _banded_solve_kernel(g_any, b_any, x_any, fbuf, wbuf, sem, *, num_steps: int, block: int, bw: int):
    """One RHS-tile program.  The skewed factors and the padded RHS both
    stay in HBM (``ANY`` memspace; ``x_any`` aliases ``b_any``): per block
    one ``(C, C+2bw)`` coupling strip and one ``(C+2bw, rt)`` RHS window
    are DMA'd to VMEM, so VMEM holds ``C·(C+2bw) + (C+2bw)·rt`` floats
    whatever ``n`` — the band analogue of ``trsm.py:solve_tiled``.  Same
    op sequence as :func:`repro.core.banded.banded_solve_blocked`."""
    del b_any  # aliased to x_any
    c = block
    rt = wbuf.shape[1]  # a 128 multiple whenever there are several column tiles
    cols = x_any.at[:, pl.ds(pl.multiple_of(pl.program_id(0) * rt, 128), rt)]

    def copy(src, dst):
        dma = pltpu.make_async_copy(src, dst, sem)
        dma.start()
        dma.wait()

    def fwd(i, carry):
        k = i * c
        copy(g_any.at[pl.ds(k, c), :], fbuf)  # strip F (C, C+2bw), maybe lane-padded
        # rows [k, k+bw) hold the solved tail above, [bw+k, bw+k+c) the block
        copy(cols.at[pl.ds(k, bw + c), :], wbuf.at[pl.ds(0, bw + c), :])
        f = fbuf[...]
        yblk = wbuf[bw : bw + c, :] - dot_f32(f[:, :bw], wbuf[0:bw, :]).astype(wbuf.dtype)
        wbuf[bw : bw + c, :] = unit_lower_window_solve(f[:, bw : bw + c], yblk, bw)
        copy(wbuf.at[pl.ds(bw, c), :], cols.at[pl.ds(bw + k, c), :])
        return carry

    jax.lax.fori_loop(0, num_steps, fwd, 0)

    def bwd(ii, carry):
        k = (num_steps - 1 - ii) * c
        copy(g_any.at[pl.ds(k, c), :], fbuf)
        # rows [bw+k, bw+k+c) hold the block, [bw+k+c, bw+k+c+bw) the head below
        copy(cols.at[pl.ds(bw + k, c + bw), :], wbuf.at[pl.ds(0, c + bw), :])
        f = fbuf[...]
        xblk = wbuf[0:c, :] - dot_f32(f[:, bw + c : c + 2 * bw], wbuf[c : c + bw, :]).astype(wbuf.dtype)
        wbuf[0:c, :] = upper_window_solve(f[:, bw : bw + c], xblk, bw)
        copy(wbuf.at[pl.ds(0, c), :], cols.at[pl.ds(bw + k, c), :])
        return carry

    jax.lax.fori_loop(0, num_steps, bwd, 0)


@functools.partial(jax.jit, static_argnames=("bw", "block", "rhs_tile", "interpret"))
def banded_solve_kernelized(
    lu_band: jax.Array,
    b: jax.Array,
    *,
    bw: int,
    block: int | None = None,
    rhs_tile: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Solve ``(LU) x = b`` on packed band factors in ONE ``pallas_call``:
    blocked forward/backward sweeps (strip recurrence + rank-``C2``
    retirement per block, the band analogue of ``trsm.py``), RHS column
    tiles across the grid, factors and RHS HBM-resident and streamed
    block-by-block so the solve is not capped by VMEM.  Bitwise-identical to
    :func:`repro.core.banded.banded_solve_blocked`."""
    lu_band = getattr(lu_band, "packed", lu_band)  # accept artifacts
    interpret = interpret_mode(interpret)
    n = lu_band.shape[0]
    squeeze = b.ndim == 1
    bm = b[:, None] if squeeze else b
    m = bm.shape[1]
    c = band_block_size(n, bw, block)
    s = -(-n // c)
    np_rows = s * c
    g = lane_pad(band_to_skewed(pad_band_identity(lu_band, bw, np_rows), bw, c))
    # compiled: whole lane tiles.  Interpret mode keeps the RHS at its own
    # width: a wider RHS changes XLA:CPU's dot accumulation order, and the
    # kernel≡mirror tests compare bitwise (the padded band above is inert).
    mw = m if interpret else lane_pad(bm[:1]).shape[1]
    rt = min(rhs_tile, mw)
    if rt < mw:  # several column tiles: each must start on a 128-lane boundary
        rt = max(128, rt // 128 * 128)
    m_pad = -(-mw // rt) * rt
    p_rows = bw + np_rows + bw
    xp = jnp.zeros((p_rows, m_pad), bm.dtype).at[bw : bw + n, :m].set(bm)
    x = pl.pallas_call(
        functools.partial(_banded_solve_kernel, num_steps=s, block=c, bw=bw),
        grid=(m_pad // rt,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((p_rows, m_pad), bm.dtype),
        scratch_shapes=[
            pltpu.VMEM((c, g.shape[1]), g.dtype),
            pltpu.VMEM((c + 2 * bw, rt), bm.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        input_output_aliases={1: 0},
        interpret=interpret,
    )(g, xp)
    x = x[bw : bw + n, :m]
    return x[:, 0] if squeeze else x


# ---------------------------------------------------------------------------
# inverted-diagonal blocked band solve (Factorization artifact fast path)
# ---------------------------------------------------------------------------
def _banded_solve_inv_kernel(linv_ref, uinv_ref, tlo_ref, tup_ref, b_ref, x_ref, *, bw: int):
    """One RHS-tile program of the inverted-diagonal band solve: the
    VMEM-resident inverse / transfer stacks drive the two-phase batched-GEMM
    substitution (:func:`repro.core.factorization.inverted_band_sweeps`).
    The whole program is GEMM + one associative tail scan — equal
    contribution across all solve blocks, no per-block loop."""
    x_ref[...] = inverted_band_sweeps(
        linv_ref[...], uinv_ref[...], tlo_ref[...], tup_ref[...], b_ref[...], bw=bw
    )


@functools.partial(jax.jit, static_argnames=("n", "bw", "rhs_tile", "interpret"))
def banded_solve_inverted(
    linv: jax.Array,
    uinv: jax.Array,
    tlo: jax.Array,
    tup: jax.Array,
    b: jax.Array,
    *,
    n: int,
    bw: int,
    rhs_tile: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Solve ``(LU) x = b`` from a :class:`~repro.core.factorization
    .Factorization` artifact's enrichments: the pre-inverted in-window
    diagonal blocks and the pre-coupled transfer blocks, both derived ONCE
    at factor time — no per-solve re-skew, no sequential strip recurrence.
    Each sweep is two batched GEMMs over all ``S`` blocks plus an
    associative scan over the ``(bw, rt)`` tail states.  RHS columns run in
    equalized tiles (:func:`repro.core.factorization.equalized_rhs_tile`).
    Bitwise-identical to
    :func:`repro.core.factorization.banded_inverted_solve`.

    Like ``banded_lu_blocked``, this is the VMEM-resident variant: the
    ``(S, C, C)`` inverse stacks live in VMEM for the whole program (the
    artifact payload the registry's VMEM estimate accounts for); an
    HBM-streaming phase-split variant is the escape hatch past that wall.
    Interpret mode only: Mosaic does not lower the in-kernel
    ``lax.associative_scan`` of the tail recurrence."""
    interpret = require_interpret(
        "banded.banded_solve_inverted", "in-kernel lax.associative_scan", interpret
    )
    s, c = linv.shape[0], linv.shape[1]
    squeeze = b.ndim == 1
    bm = b[:, None] if squeeze else b
    out_dtype = bm.dtype
    compute = linv.dtype
    m = bm.shape[1]
    rt = equalized_rhs_tile(m, rhs_tile)
    m_pad = -(-m // rt) * rt
    xb = (
        jnp.zeros((s * c, m_pad), compute)
        .at[:n, :m]
        .set(bm.astype(compute))
        .reshape(s, c, m_pad)
    )
    x = pl.pallas_call(
        functools.partial(_banded_solve_inv_kernel, bw=bw),
        grid=(m_pad // rt,),
        in_specs=[
            pl.BlockSpec((s, c, c), lambda j: (0, 0, 0)),
            pl.BlockSpec((s, c, c), lambda j: (0, 0, 0)),
            pl.BlockSpec((s, c, bw), lambda j: (0, 0, 0)),
            pl.BlockSpec((s, c, bw), lambda j: (0, 0, 0)),
            pl.BlockSpec((s, c, rt), lambda j: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((s, c, rt), lambda j: (0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((s, c, m_pad), compute),
        interpret=interpret,
    )(linv, uinv, tlo, tup, xb)
    x = x.reshape(s * c, m_pad)[:n, :m].astype(out_dtype)
    return x[:, 0] if squeeze else x


# ---------------------------------------------------------------------------
# batched band grid path (optimizer: many small independent systems)
# ---------------------------------------------------------------------------
def _batched_banded_lu_kernel(g_ref, o_ref, *, num_steps: int, block: int, bw: int):
    o_ref[...] = g_ref[...]

    def step(i, carry):
        band_block_step(o_ref.at[0], i * block, block=block, bw=bw)
        return carry

    jax.lax.fori_loop(0, num_steps, step, 0)


@functools.partial(jax.jit, static_argnames=("bw", "block", "interpret"))
def batched_banded_lu_vmem(
    arow: jax.Array, *, bw: int, block: int | None = None, interpret: bool | None = None
) -> jax.Array:
    """(B, n, 2bw+1) → packed band LU per system; one grid program per
    system, each running the blocked window steps on its VMEM-resident band
    (equal work per program by construction — every system is one identical
    factorization).  Interpret mode only: the row offsets into the
    per-system block are not provably sublane-aligned for every band."""
    interpret = require_interpret(
        "banded.batched_banded_lu_vmem", "unaligned dynamic row slices", interpret
    )
    bsz, n, w = arow.shape
    c = band_block_size(n, bw, block)
    g = jax.vmap(lambda ap: skew_pad(ap, bw, c)[0])(arow)
    s = -(-n // c)
    rows, gw = g.shape[1], g.shape[2]
    out = pl.pallas_call(
        functools.partial(_batched_banded_lu_kernel, num_steps=s, block=c, bw=bw),
        grid=(bsz,),
        in_specs=[pl.BlockSpec((1, rows, gw), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, rows, gw), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(g.shape, g.dtype),
        interpret=interpret,
    )(g)
    return jax.vmap(lambda gi: skewed_to_band(gi, bw, c))(out)[:, :n]


def _batched_banded_solve_kernel(lu_ref, b_ref, x_ref, *, num_steps: int, block: int, bw: int):
    g = lu_ref[0]  # small per-system factors stay VMEM-resident

    def read_strip(k):
        return jax.lax.dynamic_slice(g, (k, 0), (block, g.shape[1]))

    x_ref[0] = _banded_solve_sweeps(
        read_strip, b_ref[0], num_steps=num_steps, block=block, bw=bw
    )


@functools.partial(jax.jit, static_argnames=("bw", "block", "interpret"))
def batched_banded_solve_vmem(
    lu_band: jax.Array, b: jax.Array, *, bw: int, block: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """lu_band: (B, n, 2bw+1) packed; b: (B, n) or (B, n, m) → x, same shape
    as ``b``; one grid program per system.  Interpret mode only: the
    sweeps slice the carried RHS value with traced ``dynamic_slice``."""
    lu_band = getattr(lu_band, "packed", lu_band)  # accept artifacts
    interpret = require_interpret(
        "banded.batched_banded_solve_vmem", "value-level dynamic_slice in the sweeps", interpret
    )
    bsz, n, w = lu_band.shape
    squeeze = b.ndim == 2
    bm = b[..., None] if squeeze else b
    m = bm.shape[-1]
    c = band_block_size(n, bw, block)
    s = -(-n // c)
    np_rows = s * c
    g = jax.vmap(
        lambda lb: band_to_skewed(pad_band_identity(lb, bw, np_rows), bw, c)
    )(lu_band)
    gw = g.shape[2]
    p_rows = bw + np_rows + bw
    xp = jnp.zeros((bsz, p_rows, m), bm.dtype).at[:, bw : bw + n].set(bm)
    x = pl.pallas_call(
        functools.partial(_batched_banded_solve_kernel, num_steps=s, block=c, bw=bw),
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, np_rows, gw), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, p_rows, m), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, p_rows, m), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, p_rows, m), bm.dtype),
        interpret=interpret,
    )(g, xp)
    x = x[:, bw : bw + n]
    return x[..., 0] if squeeze else x
