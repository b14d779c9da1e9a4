"""Plain reference for banded ``A x = b``, the band held row-aligned as
``(n, 2bw+1)``, ``a[i, t] = A[i, i - bw + t]``: band LU without pivoting,
then two band sweeps.

Both walk the band in steps of ``c = max(bw, BLOCK)`` rows through a dense
window gathered from the band.  A factor step takes the window of rows and
columns ``[s, s + c + bw)``: it factors the leading ``c x c`` block, solves
for the ``c x bw`` strip of U and the ``bw x c`` strip of L, and takes their
product from the trailing ``bw x bw`` block; no entry outside that window is
touched by those ``c`` pivots.  A sweep step solves ``c`` unknowns against
the ``bw`` solved before them.  The dense blocks go through
:mod:`bench.reference.dense_lu`, and every product between blocks through
:func:`precision.dot` at the stated precision.  Imports nothing of the
program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense_lu import lu, trsm
from bench.reference.precision import dot

BLOCK = 128


def _block(bw: int) -> int:
    return max(bw, BLOCK)


def _pad(arow, front: int, rows: int):
    """``front`` identity rows before the band and identity rows after it
    up to ``rows`` in all: the system is block diagonal with identities."""
    w = arow.shape[1]
    eye = jnp.zeros((1, w), arow.dtype).at[0, w // 2].set(1.0)
    back = rows - front - arow.shape[0]
    return jnp.concatenate([jnp.tile(eye, (front, 1)), arow, jnp.tile(eye, (back, 1))], axis=0)


def _window(shape, bw: int, shift: int):
    """Static map from a dense window to the band rows it starts at: window
    entry ``(r, s)`` is band entry ``(r, s - r + shift)``, where that lies
    inside the band."""
    r, s = np.indices(shape)
    t = s - r + shift
    inside = (t >= 0) & (t <= 2 * bw)
    return np.clip(t, 0, 2 * bw), inside


def _gather(slab, t, inside):
    return jnp.where(inside, jnp.take_along_axis(slab, jnp.asarray(t), axis=1), 0.0)


@functools.partial(jax.jit, static_argnames=("bw", "precision"))
def factor(a, *, bw: int, precision: str):
    """Packed band LU: unit L left of the centre column, U on and right of
    it, in the layout of ``a``."""
    n, w = a.shape
    c = _block(bw)
    steps = -(-n // c)
    ap = _pad(a.astype(jnp.float32), 0, steps * c + bw)
    size = c + bw
    t, inside = _window((size, size), bw, bw)
    # band entry (r, q) of a slab is window entry (r, r + q - bw)
    r_idx, q_idx = np.indices((size, w))
    s_idx = r_idx + q_idx - bw
    kept = (s_idx >= 0) & (s_idx < size)
    s_idx = np.clip(s_idx, 0, size - 1)

    def step(k, ap):
        slab = jax.lax.dynamic_slice(ap, (k * c, 0), (size, w))
        win = _gather(slab, t, inside)
        f11 = lu(win[:c, :c], precision)
        u12 = trsm(f11, win[:c, c:], lower=True, unit=True, precision=precision)
        l21 = trsm(f11.T, win[c:, :c].T, lower=True, unit=False, precision=precision).T
        f22 = win[c:, c:] - dot(l21, u12, precision)
        win = jnp.block([[f11, u12], [l21, f22]])
        back = jnp.take_along_axis(win, jnp.asarray(s_idx), axis=1)
        return jax.lax.dynamic_update_slice(ap, jnp.where(kept, back, slab), (k * c, 0))

    return jax.lax.fori_loop(0, steps, step, ap)[:n]


@functools.partial(jax.jit, static_argnames=("bw", "precision"))
def solve(f, b, *, bw: int, precision: str):
    """Forward sweep with unit L, backward sweep with U, ``c`` rows a step;
    ``b`` (n,) or (n, k)."""
    n, w = f.shape
    col = b.ndim == 1
    y = (b[:, None] if col else b).astype(jnp.float32)
    c = _block(bw)
    steps = -(-n // c)
    rows = bw + steps * c + bw  # bw identity rows on each side
    fp = _pad(f.astype(jnp.float32), bw, rows)
    y = jnp.concatenate([jnp.zeros((bw, y.shape[1]), y.dtype), y,
                         jnp.zeros((rows - bw - n, y.shape[1]), y.dtype)], axis=0)
    lt, lin = _window((c, bw + c), bw, 0)  # rows p..p+c, columns p-bw..p+c
    ut, uin = _window((c, c + bw), bw, bw)  # rows p..p+c, columns p..p+c+bw

    def forward(k, y):
        p = bw + k * c
        win = _gather(jax.lax.dynamic_slice(fp, (p, 0), (c, w)), lt, lin)
        rhs = jax.lax.dynamic_slice(y, (p, 0), (c, y.shape[1]))
        before = jax.lax.dynamic_slice(y, (p - bw, 0), (bw, y.shape[1]))
        yk = trsm(win[:, bw:], rhs - dot(win[:, :bw], before, precision),
                  lower=True, unit=True, precision=precision)
        return jax.lax.dynamic_update_slice(y, yk, (p, 0))

    def backward(j, x):
        p = bw + (steps - 1 - j) * c
        win = _gather(jax.lax.dynamic_slice(fp, (p, 0), (c, w)), ut, uin)
        rhs = jax.lax.dynamic_slice(x, (p, 0), (c, x.shape[1]))
        after = jax.lax.dynamic_slice(x, (p + c, 0), (bw, x.shape[1]))
        xk = trsm(win[:, :c], rhs - dot(win[:, c:], after, precision),
                  lower=False, unit=False, precision=precision)
        return jax.lax.dynamic_update_slice(x, xk, (p, 0))

    x = jax.lax.fori_loop(0, steps, backward, jax.lax.fori_loop(0, steps, forward, y))
    x = x[bw : bw + n]
    return x[:, 0] if col else x
