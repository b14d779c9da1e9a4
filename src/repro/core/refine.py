"""f32 iterative refinement over a lower-precision factorization.

The mixed-precision tier (Chen, Liu & Yang's GEMM-heavy solve restructuring,
arXiv 1606.00541, applied at the precision axis): factor once in bf16 — MXU
native throughput, half the factor bytes — then recover f32 accuracy by
refining the solution against the *full-precision* operand:

    r_i = b - A x_i            (f32 residual against the exact A)
    d_i = solve(LU_bf16, r_i)  (cheap correction through the bf16 factors)
    x_{i+1} = x_i + d_i

For the diagonally-dominant operands of the paper contract the iteration
contracts by roughly the bf16 unit roundoff (~2^-8) per pass, so a handful
of sweeps reach f32-level residuals.  The loop is a ``lax.while_loop``
capped at ``max_iters`` — the cap bounds serving-tier latency, and the
iteration/residual actually reached are surfaced through
:func:`last_refinement` (recorded via ``jax.debug.callback`` so the numbers
escape jit) for stats plumbing (``SolveServiceStats``, the accuracy bench).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["RefineInfo", "iterative_refinement", "last_refinement", "DEFAULT_MAX_ITERS"]

DEFAULT_MAX_ITERS = 12


class RefineInfo(NamedTuple):
    iterations: jax.Array  # int32: refinement sweeps taken (0 = x0 sufficed)
    residual: jax.Array    # float32: final relative residual |Ax-b|/|b|


# Last refinement executed in this process (updated from inside jit via
# debug callback — execution-ordered, so eager consumers reading after
# block_until_ready() see the run they just dispatched).
_LAST: dict = {"iterations": None, "residual": None}


def last_refinement() -> dict:
    """``{"iterations": int | None, "residual": float | None}`` of the most
    recently *executed* refinement (None before any ran)."""
    return dict(_LAST)


def _note(iterations, residual) -> None:
    import numpy as np

    # vmapped refinements may deliver per-batch arrays; report the worst
    # member (the binding number for a latency/accuracy budget)
    _LAST["iterations"] = int(np.max(np.asarray(iterations)))
    _LAST["residual"] = float(np.max(np.asarray(residual)))


def iterative_refinement(
    a: jax.Array,
    b: jax.Array,
    x0: jax.Array,
    solve_fn: Callable[[jax.Array], jax.Array],
    *,
    tolerance: float,
    max_iters: int = DEFAULT_MAX_ITERS,
) -> tuple[jax.Array, RefineInfo]:
    """Refine ``x0`` toward ``solve(a, b)`` until the relative residual
    drops to ``tolerance`` or ``max_iters`` sweeps elapse.

    ``solve_fn`` maps a residual to a correction through the approximate
    (e.g. bf16) factors; ``a``/``b`` are consumed in f32 so the residual is
    measured against the exact operand.  Works for vector and matrix RHS
    (the residual norm is Frobenius over all columns).
    """
    a32 = a.astype(jnp.float32)
    b32 = b.astype(jnp.float32)
    bnorm = jnp.maximum(jnp.linalg.norm(b32), jnp.float32(1e-30))

    def resid_norm(x):
        return jnp.linalg.norm(b32 - jnp.matmul(a32, x, precision=jax.lax.Precision.HIGHEST))

    def cond(carry):
        x, rn, it = carry
        return jnp.logical_and(rn > tolerance * bnorm, it < max_iters)

    def body(carry):
        x, _, it = carry
        r = b32 - jnp.matmul(a32, x, precision=jax.lax.Precision.HIGHEST)
        x = x + solve_fn(r).astype(jnp.float32)
        return (x, resid_norm(x), it + 1)

    x0 = x0.astype(jnp.float32)
    x, rn, iters = jax.lax.while_loop(
        cond, body, (x0, resid_norm(x0), jnp.int32(0))
    )
    rel = rn / bnorm
    jax.debug.callback(_note, iters, rel)
    return x, RefineInfo(iterations=iters, residual=rel)
