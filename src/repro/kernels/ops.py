"""Public solver ops — a thin compatibility shim over ``repro.solvers``.

Every call builds a :class:`repro.solvers.Problem` from its array arguments
and routes through the registry's selection engine
(:func:`repro.solvers.select`): capability filter → measured autotune cache
(``scripts/autotune.py`` / the smoke bench) → static heuristics that
reproduce the historical hardcoded dispatch.  The ``impl=`` kwarg is kept
as a **forced-backend override** — every historical name still routes to
the same implementation:

``lu``: ``"pallas_fused"`` (single-dispatch EbV megakernel, fp32; non-fp32
falls back to the op-identical ``"xla"`` mirror with a one-time warning),
``"pallas_blocked"`` (legacy multi-launch driver), ``"pallas_vmem"``,
``"xla"`` (bitwise mirror).  ``impl=None`` (the default) is the registry
auto path; with no cache it picks ``"pallas_fused"`` for fp32 — exactly the
old default.

``lu_solve``: ``"pallas_vmem"`` / ``"pallas_tiled"`` / ``"xla"`` forced;
``"pallas"`` = auto restricted to the Pallas drivers (the old meaning);
``None`` = full auto (old threshold: VMEM ≤ 2048, tiled beyond).

``banded_lu``: ``"pallas_blocked"`` / ``"pallas_tiled"`` / ``"pallas_scalar"``
/ ``"xla"`` / ``"xla_scalar"`` forced; ``"pallas"`` = Pallas-only auto (the
old 6 MB skewed-band VMEM rule); ``None`` = full auto.

``banded_solve``: ``"pallas"`` (blocked kernel) / ``"xla"`` (blocked mirror)
/ ``"xla_scalar"`` forced; ``None`` = auto — statically the blocked kernel,
but the smoke bench seeds the cache with the measured shootout
(``BENCH_kernels.json``), so on this container the auto path picks the
measured winner (``xla_scalar`` at n=16384) instead of losing 3.4x to it.

Batching: a leading batch axis on the matrix operand — or ``jax.vmap`` over
these ops — reroutes to the batched grid kernels
(:mod:`repro.kernels.batched_lu`, ``batched_banded_*_vmem``) instead of
unrolling per-sample kernels.

Multi-device: ``lu(a, mesh=mesh)`` / ``linear_solve(a, b, mesh=mesh)``
dispatch to the shard_map EbV LU (:mod:`repro.core.distributed`) via the
registry's ``devices > 1`` capability slot.

On CPU (this container) the Pallas paths run in interpret mode
automatically; on TPU they lower to Mosaic.
"""
from __future__ import annotations

import functools
import warnings

import importlib

import jax
import jax.numpy as jnp

from repro.core import health as _chealth
from repro.core.factorization import Factorization, factorize_banded, factorize_dense
from repro.core.pivoted import PivotedFactors
from repro.core.randomized import RankKFactors
from repro.core.spike import SpikeFactors
from repro.utils import spans

__all__ = [
    "lu",
    "lu_solve",
    "linear_solve",
    "banded_lu",
    "banded_solve",
    "banded_linear_solve",
]


def _sol():
    """Deferred import of the registry: ``repro.solvers.backends`` imports
    this module's siblings, so a module-level import here would cycle."""
    return importlib.import_module("repro.solvers")


def __getattr__(name: str):
    # Backward-compatible re-exports of the static thresholds, whose home is
    # now repro.solvers.backends (deferred for the same cycle reason).
    if name == "_SOLVE_VMEM_MAX_N":
        return _sol().backends.SOLVE_VMEM_MAX_N
    if name == "_BANDED_VMEM_MAX_BYTES":
        return _sol().backends.BANDED_VMEM_MAX_BYTES
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _spanned(name: str, attrs):
    """Record each call of the op as the program span ``name`` with
    ``attrs(*args, **kw)`` (:mod:`repro.utils.spans`).  The span ends when
    the op returns, once its kernels are enqueued: it is the host time
    before the device can start.  Off, it costs one check."""

    def wrap(fn):
        @functools.wraps(fn)
        def op(*args, **kw):
            if not spans.active():
                return fn(*args, **kw)
            with spans.span(name, **attrs(*args, **kw)):
                return fn(*args, **kw)

        return op

    return wrap


def _factor_attrs(a, *, bw: int = 0, **_) -> dict:
    n = a.shape[-2] if bw else a.shape[-1]
    return {"n": int(n), "bw": int(bw), "eager": not isinstance(a, jax.core.Tracer)}


def _solve_attrs(factors, b, *, bw: int = 0, **_) -> dict:
    vector = b.ndim == getattr(factors, "ndim", 2) - 1
    return {"n": int(b.shape[-1] if vector else b.shape[-2]), "bw": int(bw),
            "k": 1 if vector else int(b.shape[-1]),
            "eager": not isinstance(factors, jax.core.Tracer)
            and not isinstance(b, jax.core.Tracer)}


_FUSED_FALLBACK_WARNED: set[str] = set()


def _warn_fused_dtype_fallback(dtype) -> None:
    """One-time (per dtype) warning when the fp32-only fused kernel falls
    back to its op-identical pure-jnp mirror."""
    key = str(dtype)
    if key not in _FUSED_FALLBACK_WARNED:
        _FUSED_FALLBACK_WARNED.add(key)
        warnings.warn(
            f"lu(impl='pallas_fused') supports float32 only; got {key} — "
            "falling back to the op-identical 'xla' mirror "
            "(repro.core.blocked.fused_blocked_lu)",
            UserWarning,
            stacklevel=3,
        )


def _screen(health):
    """Normalize the ``health=`` kwarg: ``None``/``False`` → no screening,
    ``True`` → default thresholds, a :class:`HealthThresholds` → itself."""
    if health is None or health is False:
        return None
    return _chealth.DEFAULT_THRESHOLDS if health is True else health


def _health_validator(thresholds, ref_max, bw: int = 0):
    """Dispatch validator screening each candidate's factors — an unhealthy
    result rejects the backend and feeds the registry's escalation funnel."""

    def validate(problem, backend, result):
        rec = _chealth.factor_health(result, ref_max=ref_max, bw=bw)
        if not rec.verdict(thresholds):
            return (f"unhealthy factor from {backend.name}: "
                    f"{rec.report(thresholds)}", rec)
        return None

    return validate


def _banded_auto_impl(n: int, bw: int, block: int | None, itemsize: int) -> str:
    """Historical banded auto rule (kept for callers/tests; the registry's
    static priorities encode the same threshold)."""
    return _sol().backends.banded_static_impl(n, bw, block, itemsize)


def _batched_impl(op: str, structure: str, impl: str | None) -> str | None:
    """Map an unbatched impl name to its batched analog (Pallas names →
    the batched VMEM grid kernel, xla names → the vmapped mirror), after
    validating the name against the unbatched slot."""
    if impl is None:
        return None
    if impl != "pallas":  # legacy auto alias has no unbatched backend record
        _sol().get_backend(op, structure, impl)  # raises "unknown impl ..."
    if impl == "pallas_inverted":  # has a batched slot of its own (vmapped)
        return impl
    return "xla" if impl.startswith("xla") else "pallas_vmem"


def _as_artifact(packed, *, structure: str, bw: int = 0, block=None,
                 tier: float = 0.0, health_rec=None, enrich: bool = False):
    """Wrap an eager packed factor into the :class:`Factorization` artifact
    (the new factor→solve contract).  Special factor layouts (pivoted,
    rank-k), traced values (artifacts are a Python-level cache object) and
    already-wrapped results pass through unchanged."""
    if isinstance(packed, (Factorization, PivotedFactors, RankKFactors,
                           SpikeFactors, jax.core.Tracer)):
        return packed
    if packed.ndim > 3:  # deep-batched stacks stay raw (no batched enrichment)
        return packed
    if structure == "dense":
        return factorize_dense(packed, block=block or 256, tier=tier,
                               health=health_rec, enrich=enrich)
    return factorize_banded(packed, bw=bw, block=block, tier=tier,
                            health=health_rec, enrich=enrich)


def _with_batch_rule(unbatched_fn, batched_fn):
    """Wrap ``unbatched_fn`` so ``jax.vmap`` reroutes to ``batched_fn``
    (one batched grid kernel) instead of unrolling/lifting the unbatched
    kernels.  Unbatched operands are broadcast along the batch axis."""
    inner = jax.custom_batching.custom_vmap(unbatched_fn)

    @inner.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = tuple(
            a if batched else jnp.broadcast_to(a[None], (axis_size,) + a.shape)
            for a, batched in zip(args, in_batched)
        )
        return batched_fn(*args), True

    return inner


# ---------------------------------------------------------------------------
# dense LU
# ---------------------------------------------------------------------------
def _lu_2d(a: jax.Array, *, impl, block, col_tile, interpret, tolerance=0.0,
           rank=None, oversample=8, rng_key=None, validate=None) -> jax.Array:
    if impl in (None, "pallas_fused") and a.dtype != jnp.float32:
        # The fused kernel is fp32-only.  Fall back to its bitwise mirror
        # (as fast as fused at n=1024 per BENCH_kernels.json) rather than
        # the ~9x-slower multi-launch blocked driver.
        _warn_fused_dtype_fallback(a.dtype)
        impl = "xla"
    if rank is not None and impl is None:
        impl = "rand_lu"  # an explicit rank is a request for the rank-k tier
    problem = _sol().Problem.from_arrays("factor", a, tolerance=tolerance)
    return _sol().dispatch(
        problem, a, impl=impl, validate=validate,
        block=block, col_tile=col_tile, interpret=interpret,
        rank=rank, oversample=oversample, rng_key=rng_key,
    )


def _lu_batched(a: jax.Array, *, impl, block, interpret, tolerance=0.0,
                validate=None) -> jax.Array:
    problem = _sol().Problem.from_arrays("factor", a, tolerance=tolerance)
    return _sol().dispatch(
        problem, a, impl=_batched_impl("factor", "dense", impl),
        validate=validate, block=block, interpret=interpret,
    )


@_spanned("repro.ops.lu", _factor_attrs)
def lu(
    a: jax.Array,
    *,
    impl: str | None = None,
    block: int = 256,
    col_tile: int = 256,
    interpret: bool | None = None,
    mesh=None,
    mesh_axis: str = "model",
    placement: str = "ebv_folded",
    tolerance: float = 0.0,
    rank: int | None = None,
    oversample: int = 8,
    rng_key=None,
    health=None,
    enrich: bool = False,
) -> jax.Array:
    """Packed EbV LU factorization (no pivoting — paper contract).

    2-D input → dense backends; a leading batch axis (or ``jax.vmap``) →
    the batched grid kernels; ``mesh=`` → the multi-chip shard_map LU.

    ``tolerance`` (largest acceptable relative residual of downstream
    solves) keys the selection funnel and the autotune cache; 0.0 keeps the
    exact tier bitwise-identical to a tolerance-less call.  ``rank=`` routes
    to the randomized rank-k tier (``impl="rand_lu"``) and returns
    :class:`repro.core.randomized.RankKFactors` instead of a packed square
    factor (``lu_solve`` recognises them).

    ``health=`` turns on post-factor screening: ``True`` (default
    thresholds) or a :class:`repro.core.health.HealthThresholds` makes the
    op return ``(factors, FactorHealth)``.  On eager auto dispatches the
    screen also *validates*: a backend whose factors fail the verdict is
    demoted and the registry escalates down the capable candidates (ending
    at the partial-pivoting ``pivoted`` fallback for dense operands),
    raising :class:`repro.solvers.SolveFailure` only when every candidate
    fails.  ``health=None`` (the default) is bitwise-identical to the
    pre-screening op.

    Eager calls return a :class:`repro.core.factorization.Factorization`
    artifact wrapping the packed factors (it quacks like the packed array —
    the one-release shim); ``enrich=True`` additionally pre-inverts the
    solve blocks at factor time so downstream solves can take the
    inverted-diagonal GEMM path with zero layout work.  Traced calls and
    special factor layouts (pivoted, rank-k, distributed) return their
    legacy values unchanged."""
    thresholds = _screen(health)
    ref_max = jnp.max(jnp.abs(a)) if thresholds is not None else None

    def _record(factors, bw=0):
        return _chealth.factor_health(factors, ref_max=ref_max, bw=bw)

    if mesh is not None and mesh.shape[mesh_axis] > 1:
        if impl not in (None, "distributed"):
            raise ValueError(
                f"impl={impl!r} is a single-device backend and cannot honour "
                "mesh=; only 'distributed' spans devices (drop mesh= or impl=)"
            )
        problem = _sol().Problem.from_arrays(
            "factor", a, devices=mesh.shape[mesh_axis], tolerance=tolerance
        )
        packed = _sol().dispatch(
            problem, a, impl=impl, mesh=mesh, axis=mesh_axis,
            block=block, placement=placement, interpret=interpret,
        )
        return packed if thresholds is None else (packed, _record(packed))
    eager = not isinstance(a, jax.core.Tracer)
    validate = (
        _health_validator(thresholds, ref_max)
        if thresholds is not None and eager else None
    )
    if a.ndim >= 3:
        if rank is not None:
            raise ValueError("rank= (the randomized tier) supports 2-D operands only")
        lead, tail = a.shape[:-2], a.shape[-2:]
        out = _lu_batched(
            a.reshape((-1,) + tail), impl=impl, block=block, interpret=interpret,
            tolerance=tolerance, validate=validate,
        )
        out = out.reshape(lead + tail)
        rec = None if thresholds is None else _record(out)
        out = _as_artifact(out, structure="dense", block=block, tier=tolerance,
                           health_rec=rec, enrich=enrich)
        return out if thresholds is None else (out, rec)

    if validate is not None:
        # Screened eager call: go straight to the 2-D dispatch — the vmap
        # wrapper traces its wrapped function, which would blind the
        # validator (it only runs on concrete factors).
        out = _lu_2d(a, impl=impl, block=block, col_tile=col_tile, interpret=interpret,
                     tolerance=tolerance, rank=rank, oversample=oversample,
                     rng_key=rng_key, validate=validate)
        rec = _record(out)
        return _as_artifact(out, structure="dense", block=block, tier=tolerance,
                            health_rec=rec, enrich=enrich), rec
    out = _with_batch_rule(
        lambda x: _lu_2d(x, impl=impl, block=block, col_tile=col_tile, interpret=interpret,
                         tolerance=tolerance, rank=rank, oversample=oversample, rng_key=rng_key),
        lambda xs: _lu_batched(xs, impl=impl, block=block, interpret=interpret,
                               tolerance=tolerance),
    )(a)
    rec = None if thresholds is None else _record(out)
    out = _as_artifact(out, structure="dense", block=block, tier=tolerance,
                       health_rec=rec, enrich=enrich)
    return out if thresholds is None else (out, rec)


# ---------------------------------------------------------------------------
# substitution (solve) on packed factors
# ---------------------------------------------------------------------------
def _lu_solve_2d(lu_packed, b, *, impl, block, rhs_tile, interpret, tolerance=0.0):
    problem = _sol().Problem.from_arrays("solve", lu_packed, b, tolerance=tolerance)
    allow = None
    if impl == "pallas":  # old meaning: auto restricted to the Pallas drivers
        impl, allow = None, lambda be: be.name.startswith("pallas")
    return _sol().dispatch(
        problem, lu_packed, b, impl=impl, allow=allow,
        block=block, rhs_tile=rhs_tile, interpret=interpret,
    )


def _lu_solve_batched(lu_packed, b, *, impl, block, interpret, tolerance=0.0):
    squeeze = b.ndim == 2  # (B, n) vector RHS
    bm = b[..., None] if squeeze else b
    problem = _sol().Problem.from_arrays("solve", lu_packed, bm, tolerance=tolerance)
    x = _sol().dispatch(
        problem, lu_packed, bm, impl=_batched_impl("solve", "dense", impl),
        block=block, interpret=interpret,
    )
    return x[..., 0] if squeeze else x


@_spanned("repro.ops.lu_solve", _solve_attrs)
def lu_solve(
    lu_packed,
    b: jax.Array,
    *,
    impl: str | None = None,
    block: int = 256,
    rhs_tile: int = 256,
    interpret: bool | None = None,
    tolerance: float = 0.0,
) -> jax.Array:
    if isinstance(lu_packed, PivotedFactors):
        # row-permuted factors from the partial-pivoting last resort — only
        # the pivoted backend applies the permutation, so force it
        problem = _sol().Problem(
            op="solve", structure="dense", n=int(lu_packed.lu.shape[0]),
            dtype=jnp.dtype(lu_packed.lu.dtype).name,
            rhs=1 if b.ndim == 1 else int(b.shape[-1]),
            tolerance=float(tolerance),
        )
        return _sol().dispatch(problem, lu_packed, b, impl="pivoted")
    if isinstance(lu_packed, RankKFactors):
        # rank-k factors from lu(rank=...) — only the randomized backend
        # can consume them, so this is a forced dispatch by construction
        problem = _sol().Problem(
            op="solve", structure="dense", n=int(lu_packed.l.shape[0]),
            dtype=jnp.dtype(lu_packed.l.dtype).name,
            rhs=1 if b.ndim == 1 else int(b.shape[-1]),
            tolerance=float(tolerance),
        )
        return _sol().dispatch(problem, lu_packed, b, impl="rand_lu")
    if isinstance(lu_packed, Factorization):
        # The artifact is a Python-level pytree, not a jax array: it must
        # not flow through the custom_vmap wrapper (which traces even on
        # eager calls).  Dispatch directly — Problem.from_arrays reads its
        # duck-typed shape/dtype and the ``enriched`` capability flag, and
        # backends unwrap via ``packed_of`` (the one-release shim).
        if lu_packed.ndim >= 3:
            return _lu_solve_batched(
                lu_packed, b, impl=impl, block=block, interpret=interpret,
                tolerance=tolerance,
            )
        return _lu_solve_2d(
            lu_packed, b, impl=impl, block=block, rhs_tile=rhs_tile,
            interpret=interpret, tolerance=tolerance,
        )
    if lu_packed.ndim >= 3:
        if lu_packed.ndim > 3:  # fold extra leading batch dims, like lu()
            lead, tail = lu_packed.shape[:-2], lu_packed.shape[-2:]
            bf = b.reshape((-1,) + b.shape[len(lead):])
            x = _lu_solve_batched(
                lu_packed.reshape((-1,) + tail), bf,
                impl=impl, block=block, interpret=interpret, tolerance=tolerance,
            )
            return x.reshape(lead + x.shape[1:])
        return _lu_solve_batched(
            lu_packed, b, impl=impl, block=block, interpret=interpret, tolerance=tolerance
        )
    return _with_batch_rule(
        lambda l, r: _lu_solve_2d(l, r, impl=impl, block=block, rhs_tile=rhs_tile,
                                  interpret=interpret, tolerance=tolerance),
        lambda ls, rs: _lu_solve_batched(ls, rs, impl=impl, block=block,
                                         interpret=interpret, tolerance=tolerance),
    )(lu_packed, b)


# linear_solve slot backends that fuse factor+solve (the approximate tiers
# need the full operand — bf16_ir refines against it, rand_lu sketches it)
_FUSED_LINEAR_IMPLS = ("bf16_ir", "bf16_ir_xla", "rand_lu")


def linear_solve(
    a: jax.Array,
    b: jax.Array,
    *,
    solve_impl: str | None = None,
    mesh=None,
    mesh_axis: str = "model",
    placement: str = "ebv_folded",
    tolerance: float = 0.0,
    rank: int | None = None,
    oversample: int = 8,
    rng_key=None,
    verify_residual: bool = False,
    **kw,
) -> jax.Array:
    """Factor + solve.  ``impl`` routes BOTH phases: the factor phase gets it
    verbatim; the solve phase runs ``"xla"`` when the factor does and the
    Pallas auto driver otherwise (``impl="xla"`` used to silently solve with
    the default Pallas path).  Pass ``solve_impl`` to mix phases
    deliberately (any :func:`lu_solve` impl name).  With ``mesh=`` the whole
    factor+substitution pipeline runs distributed
    (:func:`repro.core.distributed.distributed_lu_solve`).

    ``tolerance`` (largest acceptable relative residual) opens the
    approximate tiers: the call first consults the fused ``linear_solve``
    slot, where the tolerance gate admits backends whose guaranteed
    residual bound it covers (``bf16_ir`` — bf16 factor + f32 iterative
    refinement — at ≥ 1e-6); with no admitted backend it composes the exact
    factor+solve as before.  ``rank=`` (or ``impl="rand_lu"``) forces the
    randomized rank-k tier.  ``tolerance=0.0`` (default) is
    bitwise-identical to the pre-tolerance call.

    ``verify_residual=True`` measures the relative residual ``|Ax-b|/|b|``
    of every eager dispatch against the declared bound (``tolerance`` when
    set, else ``repro.solvers.VERIFY_RESIDUAL_DEFAULT_BOUND``): fused-tier
    dispatches that miss the bound feed the registry's escalation funnel,
    and the composed exact path falls over to the partial-pivoting
    ``pivoted`` backend once before raising
    :class:`repro.solvers.SolveFailure`.  Off (the default) and under
    tracing, behaviour is unchanged."""
    if mesh is not None and mesh.shape[mesh_axis] > 1:
        if kw.get("impl") not in (None, "distributed"):
            raise ValueError(
                f"impl={kw['impl']!r} is a single-device backend and cannot "
                "honour mesh=; only 'distributed' spans devices"
            )
        problem = _sol().Problem.from_arrays(
            "linear_solve", a, b, devices=mesh.shape[mesh_axis], tolerance=tolerance
        )
        return _sol().dispatch(
            problem, a, b, impl=kw.get("impl"), mesh=mesh, axis=mesh_axis,
            block=kw.get("block", 64), placement=placement,
            interpret=kw.get("interpret"),
        )
    impl = kw.get("impl")
    if rank is not None and impl is None:
        impl = "rand_lu"
    if impl in _FUSED_LINEAR_IMPLS or (impl is None and tolerance > 0):
        bm = b[..., None] if b.ndim == a.ndim - 1 else b
        problem = _sol().Problem.from_arrays(
            "linear_solve", a, bm, tolerance=tolerance,
            verify_residual=verify_residual,
        )
        if impl is not None or _sol().candidates(problem):
            squeeze = bm is not b
            x = _sol().dispatch(
                problem, a, bm, impl=impl,
                block=kw.get("block", 256), interpret=kw.get("interpret"),
                rank=rank, oversample=oversample, rng_key=rng_key,
            )
            return x[..., 0] if squeeze else x
        # tolerance too tight for every approximate tier: compose the exact
        # factor+solve below (tolerance still keys their cache rows)
    lu_kw = {k: v for k, v in kw.items()
             if k in ("impl", "block", "col_tile", "interpret", "enrich")}
    solve_kw = {k: v for k, v in kw.items() if k in ("block", "rhs_tile", "interpret")}
    lu_kw["tolerance"] = solve_kw["tolerance"] = tolerance
    if solve_impl is None and kw.get("impl") is not None:
        solve_impl = "xla" if kw["impl"] == "xla" else "pallas"
    if solve_impl is not None:
        solve_kw["impl"] = solve_impl
    x = lu_solve(lu(a, **lu_kw), b, **solve_kw)
    if verify_residual and not isinstance(a, jax.core.Tracer) \
            and not isinstance(b, jax.core.Tracer):
        return _verify_composed(a, b, x, tolerance=tolerance)
    return x


def _verify_composed(a, b, x, *, tolerance: float, bw: int = 0):
    """Post-hoc residual gate for the composed factor+solve path (the
    check spans two dispatches, so the registry's in-dispatch validator
    can't host it).  A miss escalates once to the partial-pivoting last
    resort (dense only) before raising :class:`SolveFailure`."""
    sol = _sol()
    bound = tolerance if tolerance > 0 else sol.VERIFY_RESIDUAL_DEFAULT_BOUND
    rel = float(_chealth.relative_residual(a, b, x, bw=bw))
    if rel <= bound:  # NaN compares False and falls through to escalation
        return x
    problem = sol.Problem.from_arrays(
        "linear_solve", a, b, bw=bw, tolerance=tolerance, verify_residual=True
    )
    reason = f"residual {rel:.3e} > bound {bound:.1e} from composed exact solve"
    chain = [{"backend": "composed", "reason": reason}]
    if bw == 0:
        sol.registry._notify_escalation(problem, "composed", "pivoted", reason)
        xp = lu_solve(lu(a, impl="pivoted"), b)
        relp = float(_chealth.relative_residual(a, b, xp))
        if relp <= bound:
            return xp
        chain.append({
            "backend": "pivoted",
            "reason": f"residual {relp:.3e} > bound {bound:.1e}",
        })
        sol.registry._notify_escalation(problem, "pivoted", None, chain[-1]["reason"])
    else:
        sol.registry._notify_escalation(problem, "composed", None, reason)
    raise sol.SolveFailure(
        "verified linear solve failed for "
        f"{problem}: " + " -> ".join(f"{c['backend']} ({c['reason']})" for c in chain),
        problem=problem, chain=chain,
    )


# ---------------------------------------------------------------------------
# banded (row-aligned band, see repro.core.banded)
# ---------------------------------------------------------------------------
def _banded_lu_2d(arow, *, bw, impl, block, interpret, tolerance=0.0, validate=None):
    problem = _sol().Problem.from_arrays("factor", arow, bw=bw, tolerance=tolerance)
    allow = None
    if impl == "pallas":  # old meaning: Pallas-only auto (6 MB VMEM rule)
        impl, allow = None, lambda be: be.name in ("pallas_blocked", "pallas_tiled")
    return _sol().dispatch(
        problem, arow, impl=impl, allow=allow, validate=validate,
        bw=bw, block=block, interpret=interpret,
    )


def _banded_lu_batched(arow, *, bw, impl, block, interpret, tolerance=0.0,
                       validate=None):
    problem = _sol().Problem.from_arrays("factor", arow, bw=bw, tolerance=tolerance)
    return _sol().dispatch(
        problem, arow, impl=_batched_impl("factor", "banded", impl),
        validate=validate, bw=bw, block=block, interpret=interpret,
    )


@_spanned("repro.ops.banded_lu", _factor_attrs)
def banded_lu(
    arow: jax.Array,
    *,
    bw: int,
    impl: str | None = None,
    block: int | None = None,
    interpret: bool | None = None,
    tolerance: float = 0.0,
    health=None,
    enrich: bool = False,
    mesh=None,
    mesh_axis: str = "model",
) -> jax.Array:
    """Packed band LU on the row-aligned band (no pivoting).  ``tolerance``
    keys selection/cache like the dense ops (no approximate banded tier
    exists yet, so it only partitions cache rows).  ``health=`` (``True``
    or a :class:`HealthThresholds`) returns ``(factors, FactorHealth)`` and
    screens eager auto dispatches exactly like :func:`lu` — the band has no
    pivoted last resort, so an unhealthy band factor escalates through the
    remaining band backends and then fails structurally.

    Eager calls return a :class:`repro.core.factorization.Factorization`
    artifact (array-duck-typed shim over the packed band); ``enrich=True``
    pre-inverts the (C, C) diagonal blocks and pre-couples the off-band
    strips at factor time, unlocking the two-phase inverted-diagonal solve
    (``banded_solve`` impl ``"pallas_inverted"``).

    With ``mesh=`` the band spans ``mesh.shape[mesh_axis]`` devices: the
    registry's multi-device banded slot selects between the SPIKE split
    solver (:mod:`repro.core.spike` — returns a
    :class:`~repro.core.spike.SpikeFactors` artifact) and the replicated
    fallback, with ``health=`` screening feeding the escalation funnel so
    an operand outside SPIKE's class demotes to replication."""
    thresholds = _screen(health)
    ref_max = jnp.max(jnp.abs(arow)) if thresholds is not None else None

    def _record(factors):
        return _chealth.factor_health(factors, ref_max=ref_max, bw=bw)

    eager = not isinstance(arow, jax.core.Tracer)
    validate = (
        _health_validator(thresholds, ref_max, bw=bw)
        if thresholds is not None and eager else None
    )
    if mesh is not None and mesh.shape[mesh_axis] > 1:
        if impl not in (None, "spike", "replicated"):
            raise ValueError(
                f"impl={impl!r} is a single-device backend and cannot honour "
                "mesh=; only 'spike'/'replicated' span devices "
                "(drop mesh= or impl=)"
            )
        problem = _sol().Problem.from_arrays(
            "factor", arow, bw=bw, devices=mesh.shape[mesh_axis],
            tolerance=tolerance,
        )
        out = _sol().dispatch(
            problem, arow, impl=impl, validate=validate,
            bw=bw, block=block, interpret=interpret, mesh=mesh, axis=mesh_axis,
        )
        rec = None if thresholds is None else _record(out)
        # SpikeFactors pass _as_artifact unchanged; a replicated (local)
        # factor wraps into the ordinary Factorization artifact.
        out = _as_artifact(out, structure="banded", bw=bw, block=block,
                           tier=tolerance, health_rec=rec, enrich=enrich)
        return out if thresholds is None else (out, rec)
    if arow.ndim >= 3:
        lead, tail = arow.shape[:-2], arow.shape[-2:]
        out = _banded_lu_batched(
            arow.reshape((-1,) + tail), bw=bw, impl=impl, block=block,
            interpret=interpret, tolerance=tolerance, validate=validate,
        )
        out = out.reshape(lead + out.shape[1:])
        rec = None if thresholds is None else _record(out)
        out = _as_artifact(out, structure="banded", bw=bw, block=block,
                           tier=tolerance, health_rec=rec, enrich=enrich)
        return out if thresholds is None else (out, rec)
    if validate is not None:
        # screened eager call: skip the vmap wrapper (it traces, which
        # would blind the validator) and dispatch the 2-D band directly
        out = _banded_lu_2d(arow, bw=bw, impl=impl, block=block,
                            interpret=interpret, tolerance=tolerance,
                            validate=validate)
        rec = _record(out)
        return _as_artifact(out, structure="banded", bw=bw, block=block,
                            tier=tolerance, health_rec=rec, enrich=enrich), rec
    out = _with_batch_rule(
        lambda x: _banded_lu_2d(x, bw=bw, impl=impl, block=block, interpret=interpret,
                                tolerance=tolerance),
        lambda xs: _banded_lu_batched(xs, bw=bw, impl=impl, block=block,
                                      interpret=interpret, tolerance=tolerance),
    )(arow)
    rec = None if thresholds is None else _record(out)
    out = _as_artifact(out, structure="banded", bw=bw, block=block,
                       tier=tolerance, health_rec=rec, enrich=enrich)
    return out if thresholds is None else (out, rec)


def _banded_solve_2d(lu_band, b, *, bw, impl, block, rhs_tile, interpret, tolerance=0.0):
    problem = _sol().Problem.from_arrays("solve", lu_band, b, bw=bw, tolerance=tolerance)
    return _sol().dispatch(
        problem, lu_band, b, impl=impl,
        bw=bw, block=block, rhs_tile=rhs_tile, interpret=interpret,
    )


def _banded_solve_batched(lu_band, b, *, bw, impl, block, interpret, tolerance=0.0):
    problem = _sol().Problem.from_arrays("solve", lu_band, b, bw=bw, tolerance=tolerance)
    return _sol().dispatch(
        problem, lu_band, b, impl=_batched_impl("solve", "banded", impl),
        bw=bw, block=block, interpret=interpret,
    )


@_spanned("repro.ops.banded_solve", _solve_attrs)
def banded_solve(
    lu_band: jax.Array,
    b: jax.Array,
    *,
    bw: int,
    impl: str | None = None,
    block: int | None = None,
    rhs_tile: int = 256,
    interpret: bool | None = None,
    tolerance: float = 0.0,
    mesh=None,
    mesh_axis: str = "model",
) -> jax.Array:
    """Forward+backward substitution on packed band factors.

    ``impl=None`` consults the measured cache first: the smoke bench seeds
    it with the ``banded_solve_n16384_*`` shootout, so the auto path picks
    whatever actually won on this host (``xla_scalar`` beats the blocked
    kernel 2.4 ms vs 8.1 ms under interpret-mode DMA emulation on this CPU
    container; on a real TPU the measurement flips back).  An *enriched*
    :class:`Factorization` operand additionally admits the two-phase
    inverted-diagonal path (``"pallas_inverted"``), which wins the n=16384
    shootout outright on this container."""
    if isinstance(lu_band, SpikeFactors):
        # split-band factors from banded_lu(mesh=...) — only the spike
        # backend can consume them, so this is a forced dispatch by
        # construction (the pivoted / rank-k pattern).  ``mesh=`` runs the
        # local g-solves shard_map'd; without it the mirror loop runs.
        problem = _sol().Problem(
            op="solve", structure="banded", n=lu_band.n,
            dtype=jnp.dtype(lu_band.dtype).name, bw=lu_band.bw,
            rhs=1 if b.ndim == 1 else int(b.shape[-1]),
            devices=lu_band.devices, tolerance=float(tolerance),
        )
        return _sol().dispatch(
            problem, lu_band, b, impl="spike",
            bw=lu_band.bw, block=block, interpret=interpret,
            mesh=mesh, axis=mesh_axis,
        )
    if mesh is not None and mesh.shape[mesh_axis] > 1:
        raise ValueError(
            "banded_solve(mesh=...) expects SpikeFactors from "
            "banded_lu(mesh=...); local factors solve without a mesh"
        )
    if isinstance(lu_band, Factorization):
        # bypass the custom_vmap wrapper — see lu_solve
        if lu_band.ndim >= 3:
            return _banded_solve_batched(
                lu_band, b, bw=bw, impl=impl, block=block, interpret=interpret,
                tolerance=tolerance,
            )
        return _banded_solve_2d(
            lu_band, b, bw=bw, impl=impl, block=block, rhs_tile=rhs_tile,
            interpret=interpret, tolerance=tolerance,
        )
    if lu_band.ndim >= 3:
        if lu_band.ndim > 3:  # fold extra leading batch dims, like banded_lu()
            lead, tail = lu_band.shape[:-2], lu_band.shape[-2:]
            bf = b.reshape((-1,) + b.shape[len(lead):])
            x = _banded_solve_batched(
                lu_band.reshape((-1,) + tail), bf,
                bw=bw, impl=impl, block=block, interpret=interpret, tolerance=tolerance,
            )
            return x.reshape(lead + x.shape[1:])
        return _banded_solve_batched(
            lu_band, b, bw=bw, impl=impl, block=block, interpret=interpret,
            tolerance=tolerance,
        )
    return _with_batch_rule(
        lambda l, r: _banded_solve_2d(
            l, r, bw=bw, impl=impl, block=block, rhs_tile=rhs_tile,
            interpret=interpret, tolerance=tolerance,
        ),
        lambda ls, rs: _banded_solve_batched(
            ls, rs, bw=bw, impl=impl, block=block, interpret=interpret,
            tolerance=tolerance,
        ),
    )(lu_band, b)


def banded_linear_solve(
    arow: jax.Array,
    b: jax.Array,
    *,
    bw: int,
    impl: str | None = None,
    solve_impl: str | None = None,
    block: int | None = None,
    rhs_tile: int = 256,
    interpret: bool | None = None,
    tolerance: float = 0.0,
    verify_residual: bool = False,
    mesh=None,
    mesh_axis: str = "model",
) -> jax.Array:
    """Banded factor + solve with ``impl`` routed to BOTH phases (the same
    contract :func:`linear_solve` honours): ``"xla*"`` factor impls solve
    through the matching jnp path, Pallas factor impls solve through the
    blocked solve kernel.  ``solve_impl`` overrides the solve phase.
    ``verify_residual=True`` gates eager results on the relative residual
    like :func:`linear_solve` (there is no banded pivoted fallback, so a
    miss raises :class:`repro.solvers.SolveFailure` directly).

    With ``mesh=`` the fused multi-device banded slot selects SPIKE vs
    replication (measured cache keyed on ``devices``, static priorities
    otherwise); ``verify_residual=True`` then runs inside the registry
    funnel, so a SPIKE residual miss demotes to the replicated path."""
    if mesh is not None and mesh.shape[mesh_axis] > 1:
        if impl not in (None, "spike", "replicated"):
            raise ValueError(
                f"impl={impl!r} is a single-device backend and cannot honour "
                "mesh=; only 'spike'/'replicated' span devices "
                "(drop mesh= or impl=)"
            )
        problem = _sol().Problem.from_arrays(
            "linear_solve", arow, b[..., None] if b.ndim == 1 else b,
            bw=bw, devices=mesh.shape[mesh_axis], tolerance=tolerance,
            verify_residual=verify_residual,
        )
        return _sol().dispatch(
            problem, arow, b, impl=impl,
            bw=bw, block=block, interpret=interpret, mesh=mesh, axis=mesh_axis,
        )
    if solve_impl is None and impl is not None:
        solve_impl = impl if impl in ("xla", "xla_scalar") else "pallas"
    lub = banded_lu(arow, bw=bw, impl=impl, block=block, interpret=interpret,
                    tolerance=tolerance)
    x = banded_solve(
        lub, b, bw=bw, impl=solve_impl, block=block, rhs_tile=rhs_tile,
        interpret=interpret, tolerance=tolerance,
    )
    if verify_residual and not isinstance(arow, jax.core.Tracer) \
            and not isinstance(b, jax.core.Tracer):
        return _verify_composed(arow, b, x, tolerance=tolerance, bw=bw)
    return x
