"""Post-factorization health screening for the no-pivot EbV contract.

Every factorization path in the repo is un-pivoted LU (the paper's
equalized scheme eliminates in fixed order), so a zero or tiny pivot
silently produces Inf/NaN factors — and un-pivoted elimination of an
off-class operand shows *element growth* (max|U| far above max|A|) long
before it overflows.  The randomized-LU work (arXiv 1310.7202) measured
exactly this signal: max|L| ~ 2e4 when a raw Gaussian panel is eliminated
pivot-free.  This module turns those observations into a cheap, on-device
screening record:

* **min |pivot|** — the smallest pivot magnitude actually divided by;
  compared *relative to max|A|* so the check is scale-invariant;
* **element growth** — ``max|U| / max|A|``, the classical stability ratio
  (bounded by 2^(n-1) for partial pivoting, unbounded without);
* **finiteness** — any Inf/NaN anywhere in the packed factors.

All three are plain ``jnp`` reductions over the packed factor layouts the
kernels already produce (dense ``(n, n)``, row-aligned band
``(n, 2bw+1)``, batched variants, rank-k and row-pivoted factor records),
so the Pallas kernels and their pure-jnp mirrors — whose packed factors
are bitwise-identical by the twin contract — produce bitwise-identical
:class:`FactorHealth` records too (asserted in ``tests/test_health.py``).

The record travels with the factors (``ops.lu(..., health=True)`` returns
``(factors, FactorHealth)``) and drives the registry's escalation funnel
and the solve service's cache-admission / quarantine decisions.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "HealthThresholds",
    "DEFAULT_THRESHOLDS",
    "FactorHealth",
    "factor_health",
    "relative_residual",
    "banded_matvec",
]

_TINY = 1e-30  # denominator floor: an all-zero operand is its own problem


@dataclasses.dataclass(frozen=True)
class HealthThresholds:
    """Configurable verdict bounds for a :class:`FactorHealth` record.

    ``min_pivot_ratio``  smallest acceptable ``min|pivot| / max|A|``.  The
                         default tolerates the benign pivot decay of
                         diagonally-dominant operands (pivots stay O(max|A|))
                         while catching exact/near-singularity.
    ``max_growth``       largest acceptable ``max|U| / max|A|``.  Healthy
                         no-pivot factorizations of the repo's operand class
                         stay O(1-10); runaway growth means the elimination
                         order was wrong for this operand.
    ``require_finite``   whether any Inf/NaN in the factors fails the verdict.
    """

    min_pivot_ratio: float = 1e-10
    max_growth: float = 1e6
    require_finite: bool = True


DEFAULT_THRESHOLDS = HealthThresholds()


class FactorHealth(NamedTuple):
    """On-device screening record for one factorization.

    All fields are scalars (``jnp`` on device, castable eagerly): batched
    factorizations reduce to the *worst member* — one bad system taints the
    batch record, which is the binding number for admission decisions.
    """

    min_pivot: jax.Array  # min |pivot| over every system in the dispatch
    growth: jax.Array     # max|U| / max|A|  (the element-growth ratio)
    finite: jax.Array     # bool: every packed factor entry finite
    ref_max: jax.Array    # max|A| of the operand (the screening reference)

    def ok(self, thresholds: HealthThresholds | None = None) -> jax.Array:
        """Device-side verdict (bool scalar).  NaN fields compare False, so
        a poisoned record can never pass."""
        t = thresholds or DEFAULT_THRESHOLDS
        good = self.min_pivot >= t.min_pivot_ratio * self.ref_max
        good = jnp.logical_and(good, self.growth <= t.max_growth)
        if t.require_finite:
            good = jnp.logical_and(good, self.finite)
        return good

    def verdict(self, thresholds: HealthThresholds | None = None) -> bool:
        """Eager verdict (host bool)."""
        return bool(self.ok(thresholds))

    def report(self, thresholds: HealthThresholds | None = None) -> str:
        """Eager one-line reason string for logs and failure records."""
        t = thresholds or DEFAULT_THRESHOLDS
        parts = []
        mp, gr, fin, rm = (
            float(self.min_pivot), float(self.growth),
            bool(self.finite), float(self.ref_max),
        )
        if t.require_finite and not fin:
            parts.append("non-finite factor entries")
        if not mp >= t.min_pivot_ratio * rm:  # NaN-safe: NaN comparisons are False
            parts.append(f"min|pivot|={mp:.3e} < {t.min_pivot_ratio:g}*max|A|={t.min_pivot_ratio * rm:.3e}")
        if not gr <= t.max_growth:
            parts.append(f"growth={gr:.3e} > {t.max_growth:g}")
        return "; ".join(parts) if parts else (
            f"healthy (min|pivot|={mp:.3e}, growth={gr:.3e})"
        )


def _dense_health(packed: jax.Array, ref_max: jax.Array) -> FactorHealth:
    diag = jnp.diagonal(packed, axis1=-2, axis2=-1)
    n = packed.shape[-1]
    umask = jnp.triu(jnp.ones((n, n), bool))
    umax = jnp.max(jnp.where(umask, jnp.abs(packed), 0.0))
    return FactorHealth(
        min_pivot=jnp.min(jnp.abs(diag)),
        growth=umax / jnp.maximum(ref_max, _TINY),
        finite=jnp.all(jnp.isfinite(packed)),
        ref_max=ref_max,
    )


def _banded_health(packed: jax.Array, ref_max: jax.Array, bw: int) -> FactorHealth:
    # row-aligned band: column bw is the diagonal (the pivots), columns
    # bw..2bw the U part; columns 0..bw-1 hold the L multipliers.
    pivots = packed[..., bw]
    umax = jnp.max(jnp.abs(packed[..., bw:]))
    return FactorHealth(
        min_pivot=jnp.min(jnp.abs(pivots)),
        growth=umax / jnp.maximum(ref_max, _TINY),
        finite=jnp.all(jnp.isfinite(packed)),
        ref_max=ref_max,
    )


def factor_health(factors, *, ref_max, bw: int = 0) -> FactorHealth:
    """Screening record for any factor object the repo produces.

    ``factors`` is a packed dense ``(..., n, n)`` array, a packed
    row-aligned band ``(..., n, 2bw+1)`` (``bw > 0``), a
    :class:`~repro.core.randomized.RankKFactors`, or a
    :class:`~repro.core.pivoted.PivotedFactors`.  Leading batch axes reduce
    to the worst member.  ``ref_max`` is ``max|A|`` of the operand that was
    factored (computed by the caller — the factors alone can't recover it).
    """
    from .pivoted import PivotedFactors
    from .randomized import RankKFactors

    # Factorization artifacts screen on their packed payload (attribute
    # access instead of an isinstance to keep this module import-cycle-free
    # with repro.core.factorization).
    factors = getattr(factors, "packed", factors)
    ref_max = jnp.asarray(ref_max, jnp.float32)
    if isinstance(factors, RankKFactors):
        # no square pivot sequence: the analogue of a vanished pivot is a
        # collapsed coefficient row of u (the basis column spans nothing)
        row_peak = jnp.max(jnp.abs(factors.u), axis=-1)
        amax = jnp.maximum(jnp.max(jnp.abs(factors.l)), jnp.max(jnp.abs(factors.u)))
        return FactorHealth(
            min_pivot=jnp.min(row_peak),
            growth=amax / jnp.maximum(ref_max, _TINY),
            finite=jnp.logical_and(
                jnp.all(jnp.isfinite(factors.l)), jnp.all(jnp.isfinite(factors.u))
            ),
            ref_max=ref_max,
        )
    if isinstance(factors, PivotedFactors):
        return _dense_health(factors.lu, ref_max)
    if bw:
        return _banded_health(factors, ref_max, bw)
    return _dense_health(factors, ref_max)


def banded_matvec(arow: jax.Array, x: jax.Array, *, bw: int) -> jax.Array:
    """``A @ x`` on the row-aligned band (``arow[i, t] = A[i, i-bw+t]``)
    without densifying: O(n·bw) work/memory.  ``x`` is ``(n,)`` or
    ``(n, m)``."""
    n = arow.shape[0]
    squeeze = x.ndim == 1
    xm = x[:, None] if squeeze else x
    pad = jnp.zeros((bw, xm.shape[1]), xm.dtype)
    xp = jnp.concatenate([pad, xm, pad], axis=0)  # (n + 2bw, m)
    y = jnp.zeros_like(xm)
    for t in range(2 * bw + 1):
        y = y + arow[:, t : t + 1] * jax.lax.dynamic_slice_in_dim(xp, t, n, 0)
    return y[:, 0] if squeeze else y


def relative_residual(a, b, x, *, bw: int = 0) -> jax.Array:
    """Frobenius relative residual ``|Ax - b| / |b|`` for a dense ``(n, n)``
    or row-aligned band operand — the same norm
    :func:`repro.core.refine.iterative_refinement` drives to tolerance, so
    verification and refinement agree on what "met" means."""
    a32 = jnp.asarray(a, jnp.float32)
    b32 = jnp.asarray(b, jnp.float32)
    x32 = jnp.asarray(x, jnp.float32)
    ax = (banded_matvec(a32, x32, bw=bw) if bw
          else jnp.matmul(a32, x32, precision=jax.lax.Precision.HIGHEST))
    return jnp.linalg.norm(b32 - ax) / jnp.maximum(jnp.linalg.norm(b32), _TINY)
