"""Pallas TPU kernels for EbV LU factorization.

Kernels, mirroring DESIGN.md §2's GPU→TPU adaptation:

* :func:`lu_fused`      — **single-dispatch blocked EbV LU megakernel**: one
                          ``pallas_call`` for the whole factorization.  The
                          packed matrix stays in HBM (``ANY`` memory space)
                          and is carried *in place* via
                          ``input_output_aliases``; the grid iterates
                          (block-step × equalized tile program) and each
                          program DMAs its panel/tiles through double-buffered
                          VMEM scratch, fusing panel factorization, unit-lower
                          trsm and the rank-b trailing update per step.
                          Tile→program assignment is the paper's eq. 7 fold
                          (:func:`repro.core.ebv.equalized_tile_schedule`):
                          program ``p`` owns trailing tiles ``p+1`` and
                          ``S-1-p`` whose lifetime work sums to the constant
                          ``S``.  See ``src/repro/kernels/README.md`` for the
                          launch-count / HBM-traffic math vs the legacy
                          multi-launch driver.
* :func:`lu_vmem`       — paper-faithful bi-vectorized LU with the whole
                          matrix VMEM-resident; every ``fori_loop`` step is a
                          fixed-shape masked rank-1 update (equal work/step).
* :func:`panel`         — tall (m, b) panel factorization (the unblocked
                          bi-vectorized steps confined to a VMEM panel).
* :func:`fused_step`    — the *fused bi-vector step*: unit-lower trsm
                          (U-row block) and the rank-b trailing update in a
                          single VMEM pass, grid over column tiles.
* :func:`update`        — standalone rank-k update GEMM (2-D tile grid) for
                          trailing blocks too tall for the fused kernel.

All kernels run under ``interpret=True`` on the CPU platform.  Only
:func:`lu_fused` lowers to Mosaic: the other four run the rank-1 body on a
VMEM *value* with traced ``dynamic_slice``, which Mosaic refuses, so on
TPU they raise (:func:`repro.kernels.require_interpret`) and the registry
never selects them there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.blocked import (
    FUSED_VMEM_MAX_N,
    dot_f32,
    factor_diag_strip,
    fused_block_size,
    fused_lu_steps,
    pad_identity_tail,
    solve_below_strip,
    strip_trsm,
    sub_block_width,
)

from . import aligned, interpret_mode, require_interpret, vmem_limit

__all__ = ["lu_fused", "lu_vmem", "panel", "fused_step", "update"]

# Padded orders at or below this run the fused LU on one VMEM-resident
# block (no HBM scratch streaming), tracing exactly the mirror's ref ops.
# 2·N²·4 bytes of VMEM at N=512 is 2 MB.
_FUSED_VMEM_MAX_N = FUSED_VMEM_MAX_N

_DYNAMIC_SLICE = "value-level dynamic_slice in the kernel body"


def _rows_cols(m: int, n: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    return rows, cols


def _lu_body(m: int, n: int):
    """Shared bi-vectorized elimination step on a VMEM-resident value."""
    rows, cols = _rows_cols(m, n)

    def body(k, a):
        pivot = jax.lax.dynamic_slice(a, (k, k), (1, 1))
        col = jax.lax.dynamic_slice(a, (0, k), (m, 1))
        row = jax.lax.dynamic_slice(a, (k, 0), (1, n))
        l_col = jnp.where(rows > k, col / pivot, 0.0)
        u_row = jnp.where(cols > k, row, 0.0)
        a = a - l_col * u_row  # rank-1 Schur update (masked to trailing block)
        new_col = jnp.where(rows > k, l_col, col)
        return jax.lax.dynamic_update_slice(a, new_col, (0, k))

    return body


def _lu_vmem_kernel(a_ref, o_ref, *, steps: int):
    a = a_ref[...]
    m, n = a.shape
    o_ref[...] = jax.lax.fori_loop(0, steps, _lu_body(m, n), a)


@functools.partial(jax.jit, static_argnames=("interpret",))
def lu_vmem(a: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Whole-matrix VMEM-resident EbV LU (paper-faithful kernel).

    Interpret mode only (see module docstring); larger inputs and TPU use
    :func:`lu_fused`.
    """
    interpret = require_interpret("ebv_lu.lu_vmem", _DYNAMIC_SLICE, interpret)
    n = a.shape[-1]
    return pl.pallas_call(
        functools.partial(_lu_vmem_kernel, steps=n - 1),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret,
    )(a)


def _panel_kernel(p_ref, o_ref, *, steps: int):
    p = p_ref[...]
    m, b = p.shape
    o_ref[...] = jax.lax.fori_loop(0, steps, _lu_body(m, b), p)


@functools.partial(jax.jit, static_argnames=("interpret",))
def panel(p: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Tall (m, b) panel factorization, pivots in the top b rows."""
    interpret = require_interpret("ebv_lu.panel", _DYNAMIC_SLICE, interpret)
    b = p.shape[-1]
    return pl.pallas_call(
        functools.partial(_panel_kernel, steps=b),
        out_shape=jax.ShapeDtypeStruct(p.shape, p.dtype),
        interpret=interpret,
    )(p)


def _fused_step_kernel(panel_ref, top_ref, trail_ref, u12_ref, new_trail_ref):
    """Per column tile: forward-substitute U12 against the unit-lower L11 of
    the packed panel, then immediately apply the rank-b update to the trailing
    rows — one VMEM round-trip for the whole bi-vector step."""
    pan = panel_ref[...]  # (m, b) packed panel (L11 top, L21 below)
    b = pan.shape[1]
    y = top_ref[...]  # (b, ct)
    rows, _ = _rows_cols(b, 1)

    def solve_body(k, y):
        lk = jnp.where(rows > k, jax.lax.dynamic_slice(pan, (0, k), (b, 1)), 0.0)
        yk = jax.lax.dynamic_slice(y, (k, 0), (1, y.shape[1]))
        return y - lk * yk

    y = jax.lax.fori_loop(0, b, solve_body, y)
    u12_ref[...] = y
    l21 = pan[b:, :]
    new_trail_ref[...] = trail_ref[...] - jnp.dot(
        l21, y, preferred_element_type=jnp.float32
    ).astype(trail_ref.dtype)


@functools.partial(jax.jit, static_argnames=("col_tile", "interpret"))
def fused_step(
    pan: jax.Array,
    a_top: jax.Array,
    a_trail: jax.Array,
    *,
    col_tile: int = 256,
    interpret: bool | None = None,
):
    """Fused bi-vector step.  ``pan``: (m, b) factored packed panel;
    ``a_top``: (b, W) A12 rows; ``a_trail``: (m-b, W) A22.
    Returns (U12, updated A22)."""
    interpret = require_interpret("ebv_lu.fused_step", _DYNAMIC_SLICE, interpret)
    m, b = pan.shape
    w = a_top.shape[1]
    ct = min(col_tile, w)
    assert w % ct == 0, (w, ct)
    grid = (w // ct,)
    return pl.pallas_call(
        _fused_step_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, b), lambda j: (0, 0)),
            pl.BlockSpec((b, ct), lambda j: (0, j)),
            pl.BlockSpec((m - b, ct), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((b, ct), lambda j: (0, j)),
            pl.BlockSpec((m - b, ct), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, w), a_top.dtype),
            jax.ShapeDtypeStruct((m - b, w), a_trail.dtype),
        ],
        interpret=interpret,
    )(pan, a_top, a_trail)


def _fused_lu_kernel(a_any, o_any, panel_buf, tile1_buf, tile2_buf, sems, *, num_steps: int, block: int):
    """One (step ``s``, program ``p``) grid point of the single-dispatch LU.

    Grid iteration on TPU is sequential with the last axis fastest, so within
    a step program 0 factorizes the panel first and every program of that step
    then consumes it from the persistent ``panel_buf`` scratch.  The matrix
    itself never moves through the pipeline: it stays in HBM (``o_any`` is
    aliased to the input) and only (N, B) column slabs are DMA'd to VMEM.

    Panel factorization and trsm are two-level blocked: sequential masked
    axpys are confined to ``C2``-wide strips and everything beyond the strip
    is retired by rank-``C2`` GEMMs — O(B/C2) instead of O(B) passes over the
    slab, which is what makes the megakernel decisively faster than the
    multi-launch driver even at equal FLOPs.
    """
    del a_any  # aliased to o_any; all traffic goes through the output ref
    s = pl.program_id(0)
    p = pl.program_id(1)
    S, B = num_steps, block
    C2 = sub_block_width(B)  # shared with the pure-jnp mirror (bitwise twin)
    base = aligned(s * B, B)

    def rows(off, size):
        return pl.ds(aligned(off, B), size)

    def copy_live_rows(buf, sem, src_cols, to_hbm):
        """DMA a column slab one (B, B) row block at a time, rows ``s*B``
        down only — rows above the current step hold final U values and
        never move."""

        def blk_copy(r, _):
            hbm = o_any.at[pl.ds(r * B, B), pl.ds(src_cols, B)]
            vmem = buf.at[rows(r * B, B), :]
            dma = pltpu.make_async_copy(*((vmem, hbm) if to_hbm else (hbm, vmem)), sem)
            dma.start()
            dma.wait()
            return 0

        jax.lax.fori_loop(s, S, blk_copy, 0)

    @pl.when(p == 0)
    def _factor_panel():
        copy_live_rows(panel_buf, sems.at[0], s * B, to_hbm=False)

        # All sequential recurrences run on small array carries through the
        # shared core.blocked strip helpers (the pure-jnp mirror traces the
        # same jaxprs — bitwise equality by construction) and write scratch
        # back once per strip.
        for j in range(0, B, C2):
            # (1) bi-vectorized factorization of the diagonal-block strip
            diag = factor_diag_strip(panel_buf[rows(base, B), pl.ds(j, C2)], j)
            panel_buf[rows(base, B), pl.ds(j, C2)] = diag

            # (2) unit-lower trsm: U rows of the strip vs the remaining cols
            w = B - j - C2
            if w:
                u = strip_trsm(diag[j : j + C2, :], panel_buf[rows(base + j, C2), pl.ds(j + C2, w)])
                panel_buf[rows(base + j, C2), pl.ds(j + C2, w)] = u
                lpart = diag[j + C2 :, :]
                blk = panel_buf[rows(base + j + C2, w), pl.ds(j + C2, w)]
                panel_buf[rows(base + j + C2, w), pl.ds(j + C2, w)] = (
                    blk - dot_f32(lpart, u)
                ).astype(blk.dtype)

            # (3) row blocks below: multipliers via right-solve against the
            # factored strip, then the rank-C2 GEMM retirement
            def rblk(r, _):
                off = r * B
                strip = solve_below_strip(diag, panel_buf[rows(off, B), pl.ds(j, C2)], j)
                panel_buf[rows(off, B), pl.ds(j, C2)] = strip
                if w:
                    blkr = panel_buf[rows(off, B), pl.ds(j + C2, w)]
                    panel_buf[rows(off, B), pl.ds(j + C2, w)] = (
                        blkr - dot_f32(strip, u)
                    ).astype(blkr.dtype)
                return 0

            jax.lax.fori_loop(s + 1, S, rblk, 0)
        copy_live_rows(panel_buf, sems.at[0], s * B, to_hbm=True)

    if S == 1:
        return  # no trailing tiles — the panel was the whole matrix

    # Equalized fold (paper eq. 7 at tile granularity): program p owns the
    # long-lived tile p+1 and the short-lived tile S-1-p; their lifetime work
    # sums to the constant S (see core.ebv.equalized_tile_schedule).
    t1 = p + 1
    t2 = (S - 1) - p
    act1 = t1 > s
    act2 = jnp.logical_and(t2 > s, t2 != t1)

    def tile_load(tbuf, sem, t):
        return pltpu.make_async_copy(o_any.at[:, pl.ds(t * B, B)], tbuf, sem)

    # Double buffering: both owned tiles start streaming in before the first
    # is consumed, so tile t2's HBM→VMEM load overlaps tile t1's update.
    @pl.when(act1)
    def _():
        tile_load(tile1_buf, sems.at[1], t1).start()

    @pl.when(act2)
    def _():
        tile_load(tile2_buf, sems.at[2], t2).start()

    def process(tbuf, sem, t):
        tile_load(tbuf, sem, t).wait()

        # Unit-lower trsm of the U12 tile, two-level: per C2-strip a short
        # sequential axpy solve, then one rank-C2 GEMM retires the strip.
        for j in range(0, B, C2):
            ldiag = panel_buf[rows(base + j, C2), pl.ds(j, C2)]
            strip = strip_trsm(ldiag, tbuf[rows(base + j, C2), :])
            tbuf[rows(base + j, C2), :] = strip
            w = B - j - C2
            if w:
                lpart = panel_buf[rows(base + j + C2, w), pl.ds(j, C2)]
                tail = tbuf[rows(base + j + C2, w), :]
                tbuf[rows(base + j + C2, w), :] = (tail - dot_f32(lpart, strip)).astype(tail.dtype)
        y = tbuf[rows(base, B), :]  # U12 tile

        def row_body(r, _):
            off = r * B
            blk = tbuf[rows(off, B), :]
            lblk = panel_buf[rows(off, B), :]  # L21 row block of this step
            tbuf[rows(off, B), :] = blk - dot_f32(lblk, y).astype(blk.dtype)
            return 0

        jax.lax.fori_loop(s + 1, S, row_body, 0)
        # Writeback moves live rows only — rows above s*B are final U values
        # the kernel never touched (the load stays one full-slab async copy
        # so the second owned tile's stream can overlap the first's update).
        copy_live_rows(tbuf, sem, t * B, to_hbm=True)

    @pl.when(act1)
    def _():
        process(tile1_buf, sems.at[1], t1)

    @pl.when(act2)
    def _():
        process(tile2_buf, sems.at[2], t2)


def _fused_vmem_lu_kernel(a_ref, o_ref, *, num_steps: int, block: int):
    """Small-n fused LU: the padded matrix is one VMEM block, factored in
    place by the mirror's exact ref-level step sequence — no DMA, still one
    ``pallas_call`` (and still bitwise-equal to the mirror by
    construction)."""
    o_ref[...] = a_ref[...]  # output VMEM blocks start uninitialized on TPU
    fused_lu_steps(o_ref, block=block, num_steps=num_steps)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def lu_fused(a: jax.Array, *, block: int = 256, interpret: bool | None = None) -> jax.Array:
    """Single-dispatch blocked EbV LU: the whole factorization in ONE
    ``pallas_call``.

    The matrix is padded to a multiple of ``block`` with an identity tail
    (inert under no-pivot elimination), kept in HBM for the whole kernel and
    mutated in place through ``input_output_aliases`` — no functional
    ``a.at[...].set`` copies and no per-block-column dispatches remain.
    VMEM footprint is 3·N·B floats (one panel slab + two double-buffered tile
    slabs), independent of the matrix being square-resident.

    Padded orders ≤ ``_FUSED_VMEM_MAX_N`` skip the HBM streaming entirely and
    run the same step sequence on a VMEM-resident value — the small-n fast
    path (see ``_fused_vmem_lu_kernel``).
    """
    interpret = interpret_mode(interpret)
    n = a.shape[-1]
    if a.dtype not in (jnp.float32, jnp.bfloat16):
        raise TypeError(f"lu_fused supports float32/bfloat16 only, got {a.dtype}")
    B = fused_block_size(n, block)  # padding- and VMEM-aware; mirror uses it too
    S = -(-n // B)
    N = S * B
    a = pad_identity_tail(a, N)
    if N <= _FUSED_VMEM_MAX_N:
        out = pl.pallas_call(
            functools.partial(_fused_vmem_lu_kernel, num_steps=S, block=B),
            out_shape=jax.ShapeDtypeStruct((N, N), a.dtype),
            input_output_aliases={0: 0},  # carried in place, like the HBM path
            interpret=interpret,
        )(a)
        return out[:n, :n] if N != n else out
    num_programs = max(1, S // 2)
    out = pl.pallas_call(
        functools.partial(_fused_lu_kernel, num_steps=S, block=B),
        grid=(S, num_programs),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((N, N), a.dtype),
        scratch_shapes=[
            pltpu.VMEM((N, B), a.dtype),
            pltpu.VMEM((N, B), a.dtype),
            pltpu.VMEM((N, B), a.dtype),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(3 * N * B * a.dtype.itemsize)
        ),
        interpret=interpret,
    )(a)
    return out[:n, :n] if N != n else out


def _update_kernel(l_ref, u_ref, c_ref, o_ref):
    o_ref[...] = c_ref[...] - dot_f32(l_ref[...], u_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("row_tile", "col_tile", "interpret"))
def update(
    l21: jax.Array,
    u12: jax.Array,
    a22: jax.Array,
    *,
    row_tile: int = 256,
    col_tile: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Rank-k trailing update ``A22 − L21 @ U12`` on a 2-D tile grid (for
    trailing blocks too tall for :func:`fused_step`)."""
    interpret = interpret_mode(interpret)
    m, b = l21.shape
    w = u12.shape[1]
    rt, ct = min(row_tile, m), min(col_tile, w)
    assert m % rt == 0 and w % ct == 0, (m, rt, w, ct)
    return pl.pallas_call(
        _update_kernel,
        grid=(m // rt, w // ct),
        in_specs=[
            pl.BlockSpec((rt, b), lambda i, j: (i, 0)),
            pl.BlockSpec((b, ct), lambda i, j: (0, j)),
            pl.BlockSpec((rt, ct), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((rt, ct), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, w), a22.dtype),
        interpret=interpret,
    )(l21, u12, a22)
