"""Plain reference for dense ``A x = b``: LU without pivoting, then two
triangular sweeps.

Recursive halving down to ``BASE`` rows; the base cases eliminate one row or
column at a time in elementwise f32, and every product between blocks goes
through :func:`precision.dot` at the stated precision.  Imports nothing of
the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference.precision import dot

BASE = 512


def _lu_base(a):
    m = a.shape[0]
    idx = jnp.arange(m)

    def body(k, a):
        below = idx > k
        col = jnp.where(below, a[:, k] / a[k, k], 0.0)
        row = jnp.where(below, a[k, :], 0.0)
        a = a - col[:, None] * row[None, :]
        return a.at[:, k].set(jnp.where(below, col, a[:, k]))

    return jax.lax.fori_loop(0, m, body, a)


def _trsm_base(t, b, lower: bool, unit: bool):
    """Solve ``T x = b`` for the lower (unit) or upper triangle of ``t``.
    Unsolved entries of ``x`` are zero, so a full row product only picks up
    the solved ones."""
    m = t.shape[0]

    def body(s, x):
        k = s if lower else m - 1 - s
        acc = jnp.sum(t[k][:, None] * x, axis=0)
        xk = b[k] - acc
        return x.at[k].set(xk if unit else xk / t[k, k])

    return jax.lax.fori_loop(0, m, body, jnp.zeros_like(b))


def trsm(t, b, *, lower: bool, unit: bool, precision: str):
    m = t.shape[0]
    if m <= BASE:
        return _trsm_base(t, b, lower, unit)
    h = m // 2
    rec = functools.partial(trsm, lower=lower, unit=unit, precision=precision)
    if lower:
        x1 = rec(t[:h, :h], b[:h])
        x2 = rec(t[h:, h:], b[h:] - dot(t[h:, :h], x1, precision))
    else:
        x2 = rec(t[h:, h:], b[h:])
        x1 = rec(t[:h, :h], b[:h] - dot(t[:h, h:], x2, precision))
    return jnp.concatenate([x1, x2], axis=0)


def lu(a, precision: str):
    """Packed LU (unit lower below the diagonal, upper on and above)."""
    m = a.shape[0]
    if m <= BASE:
        return _lu_base(a)
    h = m // 2
    f11 = lu(a[:h, :h], precision)
    u12 = trsm(f11, a[:h, h:], lower=True, unit=True, precision=precision)
    # L21 U11 = A21  <=>  U11^T L21^T = A21^T
    l21 = trsm(f11.T, a[h:, :h].T, lower=True, unit=False, precision=precision).T
    f22 = lu(a[h:, h:] - dot(l21, u12, precision), precision)
    return jnp.block([[f11, u12], [l21, f22]])


def lu_solve(f, b, precision: str):
    y = trsm(f, b, lower=True, unit=True, precision=precision)
    return trsm(f, y, lower=False, unit=False, precision=precision)


@functools.partial(jax.jit, static_argnames="precision")
def factor(a, *, precision: str):
    return lu(a.astype(jnp.float32), precision)


@functools.partial(jax.jit, static_argnames="precision")
def solve(f, b, *, precision: str):
    col = b.ndim == 1
    x = lu_solve(f, b[:, None] if col else b, precision)
    return x[:, 0] if col else x
