"""Banded ("sparse") EbV LU.

The paper's sparse matrices come from CFD stencils — banded systems.  For a
bandwidth-``bw`` matrix every elimination bi-vector has length exactly
``bw``: the vectors are *naturally equalized*, which is the EbV ideal case
(DESIGN.md §4).

Storage is row-aligned band form: ``arow[i, t] = A[i, i - bw + t]`` for
``t ∈ [0, 2bw]`` (zero outside the matrix).  Factorization costs
O(n·bw²) instead of O(n³).

Two realizations live here:

* the scalar-sequential reference (:func:`banded_lu` / :func:`banded_solve`):
  one ``fori_loop`` step per elimination row — the paper-faithful loop.
* the **blocked** path (:func:`banded_lu_blocked` /
  :func:`banded_solve_blocked`): ``C`` pivot rows retired per step through a
  dense ``(C+bw, C+bw)`` working *window*.  The band is first re-laid into a
  window-aligned skewed form (:func:`band_to_skewed`) in which every window
  assembles from two static slices — no per-step gather/shear — and each
  bi-vector elimination inside the window is confined to the ``(bw+1, bw+1)``
  sub-block the band can reach (the paper's naturally-equalized unit: every
  step identical shape and cost).  The window step collectively applies the
  rank-``C`` Schur update to the ``(bw, bw)`` carry corner that flows into
  the next step.  These pure-jnp drivers are the op-identical mirrors of the
  Pallas kernels in :mod:`repro.kernels.banded` — both sides trace the same
  window jaxprs, so their packed band factors are bitwise-identical (the
  dense path's PR-2 contract, extended to the band).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl

from .blocked import (
    ValueRef,
    col_at,
    dot_f32,
    row_at,
    set_rows,
    strip_trsm,
    strip_utrsm,
    sub_block_width,
)

__all__ = [
    "to_banded",
    "from_banded",
    "banded_lu",
    "banded_solve",
    "banded_lu_solve",
    "make_banded_dd",
    "band_block_size",
    "pad_band_identity",
    "band_to_skewed",
    "skewed_to_band",
    "skew_rows",
    "BANDED_VMEM_MAX_BYTES",
    "blocked_kernel_takes",
    "skew_pad",
    "band_window_from_slabs",
    "factor_band_window",
    "band_block_step",
    "unit_lower_window_solve",
    "upper_window_solve",
    "banded_lu_blocked",
    "banded_solve_blocked",
    "banded_linear_solve_blocked",
]


def to_banded(a: jax.Array, bw: int) -> jax.Array:
    """Dense (n, n) → row-aligned band (n, 2bw+1)."""
    n = a.shape[-1]
    i = jnp.arange(n)[:, None]
    t = jnp.arange(2 * bw + 1)[None, :]
    j = i - bw + t
    valid = (j >= 0) & (j < n)
    return jnp.where(valid, a[i, jnp.clip(j, 0, n - 1)], 0.0)


def from_banded(arow: jax.Array) -> jax.Array:
    """Row-aligned band (n, 2bw+1) → dense (n, n)."""
    n, w = arow.shape
    bw = (w - 1) // 2
    i = jnp.arange(n)[:, None]
    t = jnp.arange(w)[None, :]
    j = i - bw + t
    dense = jnp.zeros((n, n), arow.dtype)
    return dense.at[i, jnp.clip(j, 0, n - 1)].add(jnp.where((j >= 0) & (j < n), arow, 0.0))


def _update_indices(bw: int) -> tuple[np.ndarray, np.ndarray]:
    """Static gather map for the shifted-window rank-1 band update.

    For row offset ``s`` (1..bw) the touched columns of the row-band are
    ``t = bw+1-s .. 2bw-s`` and they consume ``u_tail[c - (bw+1-s)]``.
    """
    s = np.arange(1, bw + 1)[:, None]  # (bw, 1)
    c = np.arange(2 * bw + 1)[None, :]  # (1, 2bw+1)
    src = c - (bw + 1 - s)
    valid = (src >= 0) & (src < bw)
    return np.clip(src, 0, bw - 1), valid


@functools.partial(jax.jit, static_argnames=("bw",))
def banded_lu(arow: jax.Array, *, bw: int) -> jax.Array:
    """No-pivot LU on the row-aligned band; factors packed in place
    (``L`` strictly left of the centre diagonal, unit diagonal implicit)."""
    n = arow.shape[0]
    pad = jnp.zeros((bw, 2 * bw + 1), arow.dtype)
    ap = jnp.concatenate([arow, pad], axis=0)  # (n+bw, 2bw+1)
    src_idx, src_valid = _update_indices(bw)
    src_idx = jnp.asarray(src_idx)
    src_valid = jnp.asarray(src_valid)
    anti = (jnp.arange(bw), bw - 1 - jnp.arange(bw))  # L positions in the window

    def body(k, ap):
        pivot = ap[k, bw]
        window = jax.lax.dynamic_slice(ap, (k + 1, 0), (bw, 2 * bw + 1))
        # bi-vector: the L-column lives on the window's anti-diagonal …
        l = window[anti] / pivot
        # … and the U-row is the pivot row's upper tail.
        u_tail = jax.lax.dynamic_slice(ap, (k, bw + 1), (1, bw))[0]
        upd = l[:, None] * jnp.where(src_valid, u_tail[src_idx], 0.0)
        window = window - upd
        window = window.at[anti].set(l)
        return jax.lax.dynamic_update_slice(ap, window, (k + 1, 0))

    ap = jax.lax.fori_loop(0, n - 1, body, ap)
    return ap[:n]


@functools.partial(jax.jit, static_argnames=("bw",))
def banded_solve(lu_band: jax.Array, b: jax.Array, *, bw: int) -> jax.Array:
    """Forward+backward substitution on the packed band factors."""
    lu_band = getattr(lu_band, "packed", lu_band)
    n = lu_band.shape[0]

    # forward: y_i = b_i − Σ_t L[i, i-bw+t] · y_{i-bw+t}
    ypad = jnp.concatenate([jnp.zeros((bw,), b.dtype), b])

    def fwd(i, ypad):
        window = jax.lax.dynamic_slice(ypad, (i,), (bw,))  # y_{i-bw} … y_{i-1}
        yi = ypad[i + bw] - jnp.dot(lu_band[i, :bw], window)
        return ypad.at[i + bw].set(yi)

    ypad = jax.lax.fori_loop(0, n, fwd, ypad)

    # backward: x_i = (y_i − Σ_t U[i, i+t] · x_{i+t}) / U[i, i]
    xpad = jnp.concatenate([ypad[bw:], jnp.zeros((bw,), b.dtype)])

    def bwd(j, xpad):
        i = n - 1 - j
        window = jax.lax.dynamic_slice(xpad, (i + 1,), (bw,))  # x_{i+1} … x_{i+bw}
        xi = (xpad[i] - jnp.dot(lu_band[i, bw + 1 :], window)) / lu_band[i, bw]
        return xpad.at[i].set(xi)

    xpad = jax.lax.fori_loop(0, n, bwd, xpad)
    return xpad[:n]


def banded_lu_solve(arow: jax.Array, b: jax.Array, *, bw: int) -> jax.Array:
    return banded_solve(banded_lu(arow, bw=bw), b, bw=bw)


# ---------------------------------------------------------------------------
# blocked band path — shared helpers (kernel/mirror bitwise twins)
# ---------------------------------------------------------------------------
def make_banded_dd(key, n: int, bw: int, dtype=jnp.float32) -> jax.Array:
    """Diagonally-dominant row-aligned band factory, built directly in band
    form — no dense ``(n, n)`` detour, so it scales to the paper's n=16384
    (where the dense matrix alone would be 1 GB)."""
    w = 2 * bw + 1
    a = jax.random.uniform(key, (n, w), jnp.float32, minval=-1.0, maxval=1.0)
    i = jnp.arange(n)[:, None]
    t = jnp.arange(w)[None, :]
    j = i - bw + t
    a = jnp.where((j >= 0) & (j < n), a, 0.0)
    offsum = jnp.sum(jnp.abs(a), axis=1) - jnp.abs(a[:, bw])
    return a.at[:, bw].set(offsum + 1.0).astype(dtype)


def band_block_size(n: int, bw: int, block: int | None = None) -> int:
    """Pivot rows ``C`` retired per blocked band step.

    ``C ≈ 8·bw`` (clamped to [32, 256]) amortizes the per-step window
    assembly over many pivots while keeping the ``(C+bw)²`` dense window
    small.  ``C ≥ bw`` is enforced so a step's ``bw`` carry rows never span
    more than one following block (the skewed layout's contract); ``C ≤ n``
    caps the degenerate bw ≥ n case at one step.  Shared by the Pallas
    kernels and the pure-jnp mirrors so both sides block identically
    (bitwise contract)."""
    if block is None:
        block = max(32, min(256, 8 * bw))
    return min(max(block, bw), n)


def pad_band_identity(arow: jax.Array, bw: int, rows_to: int) -> jax.Array:
    """Pad the band with identity rows (centre diagonal 1, zero coupling) —
    inert under no-pivot elimination and substitution, the band analogue of
    :func:`repro.core.blocked.pad_identity_tail`."""
    n, w = arow.shape
    if rows_to == n:
        return arow
    pad = jnp.zeros((rows_to - n, w), arow.dtype).at[:, bw].set(1.0)
    return jnp.concatenate([arow, pad], axis=0)


def band_to_skewed(ap: jax.Array, bw: int, block: int) -> jax.Array:
    """Re-lay the row-aligned band ``(R, 2bw+1)`` (``R`` a multiple of
    ``block``) into the window-aligned skewed form ``G`` ``(R, C+2bw)``:
    ``G[i, c] = A[i, k(i) - bw + c]`` with ``k(i) = (i // C)·C``, i.e. row
    ``i`` of the band shifted right by ``i mod C`` (zero outside).

    In this layout the blocked drivers assemble every dense working window
    from two *contiguous static slices* of ``G`` — the per-step gather that
    a row-aligned shear would need never happens.  The shift is one
    ``take_along_axis`` (the flat-reshape form of the same shear took XLA's
    TPU compiler two minutes at some band heights).  Pure data movement
    (exact), so it never perturbs bitwise comparisons."""
    r, w = ap.shape
    gw = block + 2 * bw
    shift = jax.lax.broadcasted_iota(jnp.int32, (r, gw), 0) % block
    t = jax.lax.broadcasted_iota(jnp.int32, (r, gw), 1) - shift
    inside = (t >= 0) & (t < w)
    return jnp.where(inside, jnp.take_along_axis(ap, jnp.clip(t, 0, w - 1), axis=1), 0)


def skewed_to_band(g: jax.Array, bw: int, block: int) -> jax.Array:
    """Inverse of :func:`band_to_skewed`: skewed ``(R, C+2bw)`` → row-aligned
    band ``(R, 2bw+1)`` (``A[i, t] = G[i, t + i mod C]``)."""
    r = g.shape[0]
    w = 2 * bw + 1
    shift = jax.lax.broadcasted_iota(jnp.int32, (r, w), 0) % block
    t = jax.lax.broadcasted_iota(jnp.int32, (r, w), 1)
    return jnp.take_along_axis(g, t + shift, axis=1)


def band_window_from_slabs(own: jax.Array, carry: jax.Array, bw: int) -> jax.Array:
    """Assemble the dense ``(C+bw, C+bw)`` working window of one block step
    from its two skewed-layout slabs: ``own`` ``(C, C+2bw)`` (the step's own
    rows) and ``carry`` (the next block's first ``bw`` rows — ``(bw, 2bw)``
    when ``C ≥ bw``, ``(bw, C+bw)`` sliced at column ``bw-C`` otherwise)."""
    c = own.shape[0]
    top = own[:, bw : c + 2 * bw]  # window columns 0..C+bw-1 of the step's own rows
    if c > bw:
        bot = jnp.concatenate([jnp.zeros((bw, c - bw), own.dtype), carry], axis=1)
    else:
        bot = carry
    return jnp.concatenate([top, bot], axis=0)


def _band_pivot_step(size: int, bw: int):
    """One bi-vector elimination on a ``(size, size)`` window value,
    confined by masks to the ``(bw+1, bw+1)`` block the band can reach from
    pivot ``p``: scale the L column by the pivot, subtract the outer
    product.  Masks instead of a traced ``dynamic_slice`` of that block,
    which Mosaic does not lower; every masked-in value is computed by the
    same scalar ops as the sliced form, so the result is the same bits."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (size, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, size), 1)

    def piv(p, wnd):
        prow = row_at(wnd, p)
        pivot = col_at(prow, p)
        in_r = (rows > p) & (rows <= p + bw)
        in_c = (cols > p) & (cols <= p + bw)
        l_col = jnp.where(in_r, col_at(wnd, p) / pivot, 0.0)
        u_row = jnp.where(in_c, prow, 0.0)
        wnd = jnp.where(in_r & in_c, wnd - l_col * u_row, wnd)  # rank-1 Schur update
        return jnp.where(in_r & (cols == p), l_col, wnd)

    return piv


def _put_square(window: jax.Array, sub: jax.Array, q: int) -> jax.Array:
    """``window`` with its ``[q:e, q:e]`` square replaced by ``sub`` (static
    offsets, assembled by concatenation)."""
    e = q + sub.shape[0]
    mid = [window[q:e, :q], sub, window[q:e, e:]]
    mid = jnp.concatenate([m for m in mid if m.shape[1]], axis=1)
    return set_rows(window, mid, q)


def factor_band_window(window: jax.Array, npiv: int, bw: int) -> jax.Array:
    """No-pivot LU of the dense band window ``(npiv+bw, npiv+bw)``, retiring
    pivots ``0..npiv-1``.  Each bi-vector elimination is confined to the
    ``(bw+1, bw+1)`` sub-block the band can reach — the paper's naturally
    equalized unit: every step is one identical fixed-shape fused update
    (scale the L column by the pivot, subtract the outer product).
    Pivots run in static chunks of ``k``: chunk ``q`` only reaches the
    ``[q, q+k+bw)`` square, so each step's masked update covers
    ``(k+bw)²`` instead of the whole window.  Collectively the ``npiv``
    steps apply the block step's rank-``npiv`` Schur update to the
    ``(bw, bw)`` carry corner.  Shared verbatim by the Pallas kernels and
    the pure-jnp mirror (bitwise contract)."""
    k = min(npiv, max(8, min(64, bw // 2) // 8 * 8))
    for q in range(0, npiv, k):
        kq = min(k, npiv - q)
        e = q + kq + bw
        sub = window[q:e, q:e]
        sub = jax.lax.fori_loop(0, kq, _band_pivot_step(e - q, bw), sub)
        window = _put_square(window, sub, q)
    return window


def unit_lower_window_solve(lwin: jax.Array, y: jax.Array, bw: int) -> jax.Array:
    """Blocked forward substitution against the packed in-block window
    (unit-lower L read strictly below the diagonal): per ``C2`` strip a
    short masked-axpy recurrence (:func:`repro.core.blocked.strip_trsm`),
    then one rank-``C2`` GEMM retiring the ``bw`` rows the band couples."""
    c = lwin.shape[0]
    c2 = sub_block_width(c)
    for j in range(0, c, c2):
        strip = strip_trsm(lwin[j : j + c2, j : j + c2], y[j : j + c2, :])
        y = set_rows(y, strip, j)
        hr = min(bw, c - j - c2)
        if hr:
            lpart = lwin[j + c2 : j + c2 + hr, j : j + c2]
            tail = y[j + c2 : j + c2 + hr, :] - dot_f32(lpart, strip).astype(y.dtype)
            y = set_rows(y, tail, j + c2)
    return y


def upper_window_solve(uwin: jax.Array, x: jax.Array, bw: int) -> jax.Array:
    """Blocked backward substitution against the packed in-block window
    (U on and above the diagonal), mirroring :func:`unit_lower_window_solve`
    bottom-up with :func:`repro.core.blocked.strip_utrsm` strips."""
    c = uwin.shape[0]
    c2 = sub_block_width(c)
    for j in range(c - c2, -1, -c2):
        strip = strip_utrsm(uwin[j : j + c2, j : j + c2], x[j : j + c2, :])
        x = set_rows(x, strip, j)
        hr = min(bw, j)
        if hr:
            upart = uwin[j - hr : j, j : j + c2]
            head = x[j - hr : j, :] - dot_f32(upart, strip).astype(x.dtype)
            x = set_rows(x, head, j - hr)
    return x


# ---------------------------------------------------------------------------
# blocked band drivers — pure-jnp mirrors of the Pallas kernels
# ---------------------------------------------------------------------------
def skew_rows(n: int, bw: int, block: int) -> int:
    """Padded row count of the skewed band: a whole number of blocks plus
    enough carry blocks for the last step's ``bw`` overhang.  ONE formula
    shared by :func:`skew_pad` and the kernels' VMEM-budget estimate."""
    s = -(-n // block)
    return (s + max(1, -(-bw // block))) * block


# Above this many skewed-band bytes the VMEM-resident blocked kernel gives
# way to the HBM-streaming tiled kernel (the blocked kernel holds the skewed
# band twice — in and out — in VMEM).
BANDED_VMEM_MAX_BYTES = 6 * 2**20


def blocked_kernel_takes(n: int, bw: int, block: int | None, itemsize: int, *,
                         compiled: bool) -> bool:
    """Whether the VMEM-resident blocked band kernel takes this band rather
    than the tiled one: the skewed band within :data:`BANDED_VMEM_MAX_BYTES`
    and, when Mosaic compiles it, the traced block offsets sublane-aligned
    (``C % 8 == 0``).  One rule for the registry and SPIKE's local factor."""
    c = band_block_size(n, bw, block)
    fits = skew_rows(n, bw, c) * (c + 2 * bw) * itemsize <= BANDED_VMEM_MAX_BYTES
    return fits and (not compiled or c % 8 == 0)


def skew_pad(arow: jax.Array, bw: int, block: int) -> tuple[jax.Array, int]:
    """Identity-pad the band to :func:`skew_rows` rows and re-lay it into
    the skewed form the blocked drivers consume.  Returns ``(G, num_steps)``.
    Shared by the Pallas kernels and the pure-jnp mirrors — the bitwise
    kernel/mirror contract depends on both sides padding identically."""
    n = arow.shape[0]
    ap = pad_band_identity(arow, bw, skew_rows(n, bw, block))
    return band_to_skewed(ap, bw, block), -(-n // block)


def _carry_cols(block: int, bw: int) -> tuple[int, int]:
    """Column span ``(start, width)`` of a step's ``bw`` carry rows in the
    skewed band: ``(0, 2bw)`` when ``C ≥ bw``, ``(bw-C, C+bw)`` otherwise."""
    return (0, 2 * bw) if block >= bw else (bw - block, block + bw)


def band_block_step(g, k, *, block: int, bw: int) -> None:
    """One blocked band LU step on the skewed band *ref* ``g``, in place:
    read the step's own ``C`` rows and the next block's ``bw`` carry rows
    (row offset ``k``, traced or static), assemble the dense window, retire
    ``C`` pivots, write both slabs back — the own rows are final, the carry
    rows flow into the next step.  Shared verbatim by the Pallas kernels
    and the pure-jnp mirror (which runs it on a :class:`ValueRef`)."""
    c = block
    c0, cw = _carry_cols(c, bw)
    own = g[pl.ds(k, c), pl.ds(0, c + 2 * bw)]  # the ref may be lane-padded wider
    carry = g[pl.ds(k + c, bw), pl.ds(c0, cw)]
    window = factor_band_window(band_window_from_slabs(own, carry, bw), c, bw)
    g[pl.ds(k, c), pl.ds(bw, c + bw)] = window[:c, :]
    g[pl.ds(k + c, bw), pl.ds(c0, cw)] = window[c:, c + bw - cw :]


@functools.partial(jax.jit, static_argnames=("bw", "block"))
def banded_lu_blocked(arow: jax.Array, *, bw: int, block: int | None = None) -> jax.Array:
    """Blocked no-pivot band LU: ``C`` rows retired per step through the
    dense band window on the skewed layout.  Op-identical mirror of
    :func:`repro.kernels.banded.banded_lu_blocked` /
    :func:`repro.kernels.banded.banded_lu_tiled` — bitwise-equal packed band
    factors by construction."""
    n = arow.shape[0]
    c = band_block_size(n, bw, block)
    g, s = skew_pad(arow, bw, c)

    ref = ValueRef(g)
    for i in range(s):
        band_block_step(ref, i * c, block=c, bw=bw)
    return skewed_to_band(ref.value, bw, c)[:n]


@functools.partial(jax.jit, static_argnames=("bw", "block"))
def banded_solve_blocked(
    lu_band: jax.Array, b: jax.Array, *, bw: int, block: int | None = None
) -> jax.Array:
    """Blocked forward+backward substitution on the packed band factors —
    op-identical mirror of
    :func:`repro.kernels.banded.banded_solve_kernelized`."""
    lu_band = getattr(lu_band, "packed", lu_band)
    n = lu_band.shape[0]
    squeeze = b.ndim == 1
    bm = b[:, None] if squeeze else b
    m = bm.shape[1]
    c = band_block_size(n, bw, block)
    s = -(-n // c)
    np_rows = s * c
    # in the skewed layout each block's dense coupling strip F (C, C+2bw) —
    # columns k-bw .. k+C+bw-1 — is one contiguous row slice, no gather:
    # F[:, :bw] couples to rows above the block, F[:, bw:bw+C] is the
    # in-block packed L/U window, F[:, bw+C:] couples to rows below.
    g = band_to_skewed(pad_band_identity(lu_band, bw, np_rows), bw, c)
    # x carries `bw` zero margin rows on both ends so every block reads its
    # above/below coupling windows without branching (rows [bw, bw+n) real).
    xp = jnp.zeros((bw + np_rows + bw, m), bm.dtype).at[bw : bw + n].set(bm)
    for i in range(s):
        k = i * c
        f = g[k : k + c]
        yblk = xp[bw + k : bw + k + c] - dot_f32(f[:, :bw], xp[k : k + bw]).astype(xp.dtype)
        yblk = unit_lower_window_solve(f[:, bw : bw + c], yblk, bw)
        xp = jax.lax.dynamic_update_slice(xp, yblk, (bw + k, 0))
    for i in range(s - 1, -1, -1):
        k = i * c
        f = g[k : k + c]
        xblk = xp[bw + k : bw + k + c] - dot_f32(
            f[:, bw + c :], xp[bw + k + c : bw + k + c + bw]
        ).astype(xp.dtype)
        xblk = upper_window_solve(f[:, bw : bw + c], xblk, bw)
        xp = jax.lax.dynamic_update_slice(xp, xblk, (bw + k, 0))
    x = xp[bw : bw + n]
    return x[:, 0] if squeeze else x


def banded_linear_solve_blocked(
    arow: jax.Array, b: jax.Array, *, bw: int, block: int | None = None
) -> jax.Array:
    """Factor + solve through the blocked mirrors."""
    return banded_solve_blocked(banded_lu_blocked(arow, bw=bw, block=block), b, bw=bw, block=block)
