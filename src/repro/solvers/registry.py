"""Backend registry + selection engine.

One table for the whole solver stack: every kernel generation (fused
megakernel, blocked drivers, banded blocked/tiled/scalar, batched VMEM grid
kernels, multi-device shard_map LU, pure-jnp mirrors) registers a
:class:`Backend` under its ``(op, structure)`` slot.  Selection is a
three-stage funnel:

1. **capability filter** — ``Backend.supports(problem)`` prunes backends
   that cannot run the problem at all (dtype, VMEM footprint, device count),
   and the **tolerance gate** prunes approximate backends (those declaring a
   ``residual_bound``) unless the problem carries a tolerance that bound
   meets — a default (``tolerance == 0``) problem only ever sees the exact
   tier, preserving pre-tolerance selection bitwise;
2. **measured selection** — the autotune cache
   (:mod:`repro.solvers.cache`) picks the fastest *measured* capable
   backend among those flagged ``autotune=True``;
3. **static fallback** — with no transferable measurement, the highest
   ``priority(problem)`` wins.  The registered priorities reproduce the
   pre-registry hardcoded heuristics exactly (``pallas_fused`` for fp32
   dense, the 2048-order VMEM solve threshold, the 6 MB banded byte cap),
   so a cache-less process behaves like the historical ``ops.py`` tables.

``impl=`` on the public ops is a *forced override*: it bypasses stages 2-3
(and the capability filter — forcing an unsupported backend is an explicit
request and fails with that backend's own error).

**Escalation funnel** (layer 2 of the failure-isolating pipeline): when a
dispatch carries a *validator* — a factor health screen from
``ops.lu(..., health=)``, the built-in relative-residual check armed by
``Problem.verify_residual``, or an injected fault plan
(:mod:`repro.solvers.faults`) — an auto-selected dispatch becomes a retry
loop over the capable candidates, best-first: a backend whose result fails
validation, or whose call raises an operand fault (an arithmetic or
linear-algebra error, a nested :class:`SolveFailure`, an injected fault),
is *demoted* for that problem shape
(skipped for the next ``DEMOTION_TTL`` same-shape dispatches), an
escalation event fires (``add_escalation_hook`` / ``record_escalations``),
and the next candidate runs.  The last resort for dense factors is the
partial-pivoting ``pivoted`` backend (:mod:`repro.core.pivoted`) registered
at the lowest priority.  When every candidate fails, the dispatch raises a
structured :class:`SolveFailure` carrying the problem, the per-backend
escalation chain, and the final health record — never NaN factors.  Any
other exception (a kernel the compiler refuses, a bad block shape, a VMEM
overflow, a bug) propagates: it is not the operand's fault, and serving the
next candidate would hide it.  A default dispatch (no validator, no active faults, no demotions) takes the
exact pre-funnel fast path, so default selection and results stay
bitwise-identical.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

from repro.utils import spans as _spans

from . import cache as _cache
from .problem import Problem

__all__ = [
    "Backend",
    "SolveFailure",
    "register",
    "backends_for",
    "get_backend",
    "candidates",
    "select",
    "dispatch",
    "add_dispatch_hook",
    "remove_dispatch_hook",
    "record_dispatches",
    "add_escalation_hook",
    "remove_escalation_hook",
    "record_escalations",
    "demotions",
    "clear_demotions",
    "DEMOTION_TTL",
    "VERIFY_RESIDUAL_DEFAULT_BOUND",
]


@dataclasses.dataclass(frozen=True)
class Backend:
    """One dispatchable implementation.

    ``call``      ``(problem, *arrays, **kw) -> result``; adapters accept
                  and ignore kwargs meant for other backends (``**_``) so
                  the public ops can pass their full kwarg set through.
    ``supports``  capability predicate; auto-selection only considers
                  backends whose predicate holds.
    ``priority``  static heuristic rank (higher wins) used when no
                  measurement transfers.
    ``autotune``  whether the backend competes in measured selection and is
                  swept by ``scripts/autotune.py``.  Kept False for
                  dominated legacy drivers and for backends whose output is
                  not value-identical to the default of their slot (a cache
                  flip must never change bitwise behaviour of twin-backed
                  slots).
    ``vmem_bytes`` optional footprint estimate (documentation + capability
                  predicates build on it).
    ``residual_bound`` relative residual ``|Ax-b|/|b|`` the backend
                  guarantees for its documented operand class, or None for
                  exact backends.  Approximate backends (non-None) only
                  enter auto-selection when ``problem.tolerance`` is set
                  and at least as loose as this bound.
    """

    name: str
    op: str
    structure: str
    call: Callable
    supports: Callable[[Problem], bool] = lambda p: True
    priority: Callable[[Problem], float] = lambda p: 0.0
    autotune: bool = True
    vmem_bytes: Callable[[Problem], int] | None = None
    residual_bound: Callable[[Problem], float] | None = None


_REGISTRY: dict[tuple[str, str], dict[str, Backend]] = {}


def register(backend: Backend, *, overwrite: bool = False) -> Backend:
    slot = _REGISTRY.setdefault((backend.op, backend.structure), {})
    if backend.name in slot and not overwrite:
        raise ValueError(
            f"backend {backend.name!r} already registered for "
            f"({backend.op}, {backend.structure})"
        )
    slot[backend.name] = backend
    return backend


def backends_for(op: str, structure: str) -> list[Backend]:
    return list(_REGISTRY.get((op, structure), {}).values())


def get_backend(op: str, structure: str, name: str) -> Backend:
    slot = _REGISTRY.get((op, structure), {})
    if name not in slot:
        raise ValueError(
            f"unknown impl {name!r} for ({op}, {structure}); "
            f"registered: {sorted(slot)}"
        )
    return slot[name]


def _tolerance_admits(backend: Backend, problem: Problem) -> bool:
    """The accuracy gate of the funnel: exact backends always pass;
    approximate backends pass only when the caller declared a tolerance at
    least as loose as the backend's guaranteed residual bound."""
    if backend.residual_bound is None:
        return True
    return problem.tolerance > 0 and backend.residual_bound(problem) <= problem.tolerance


def candidates(problem: Problem, *, allow: Callable[[Backend], bool] | None = None) -> list[Backend]:
    """Capability- and tolerance-filtered backends for ``problem``
    (optionally restricted by ``allow``, e.g. the legacy ``impl="pallas"``
    pallas-only auto)."""
    out = [
        b for b in backends_for(problem.op, problem.structure)
        if b.supports(problem) and _tolerance_admits(b, problem)
    ]
    if allow is not None:
        out = [b for b in out if allow(b)]
    return out


def select(
    problem: Problem,
    *,
    impl: str | None = None,
    cache: _cache.AutotuneCache | None = None,
    allow: Callable[[Backend], bool] | None = None,
) -> Backend:
    """Pick the backend for ``problem``: forced ``impl`` > measured winner >
    static priority."""
    if impl is not None:
        return get_backend(problem.op, problem.structure, impl)
    cands = candidates(problem, allow=allow)
    if not cands:
        raise ValueError(
            f"no capable backend for {problem} among "
            f"{[b.name for b in backends_for(problem.op, problem.structure)]}"
        )
    cache = _cache.get_cache() if cache is None else cache
    measured = cache.best(problem, [b.name for b in cands if b.autotune])
    if measured is not None:
        return get_backend(problem.op, problem.structure, measured)
    return max(cands, key=lambda b: b.priority(problem))


# ---------------------------------------------------------------------------
# dispatch observability — the hook layer higher-level caches build on.
# The serving layer's factorization cache (repro.serve.solve_service) counts
# factor vs solve dispatches through here to prove factor-once/solve-many;
# tests and benches use record_dispatches() for the same accounting.
# ---------------------------------------------------------------------------
_DISPATCH_HOOKS: list[Callable[[Problem, Backend], None]] = []


def add_dispatch_hook(fn: Callable[[Problem, Backend], None]) -> Callable:
    """Register ``fn(problem, backend)`` to observe every registry dispatch
    (called after selection, before the backend runs).  Returns ``fn`` so it
    can be handed straight to :func:`remove_dispatch_hook`."""
    _DISPATCH_HOOKS.append(fn)
    return fn


def remove_dispatch_hook(fn: Callable) -> None:
    try:
        _DISPATCH_HOOKS.remove(fn)
    except ValueError:
        pass


class record_dispatches:
    """Context manager collecting ``(problem, backend_name)`` for every
    dispatch inside the block::

        with record_dispatches() as log:
            ops.linear_solve(a, b)
        assert sum(p.op == "factor" for p, _ in log) == 1
    """

    def __enter__(self) -> list[tuple[Problem, str]]:
        self.log: list[tuple[Problem, str]] = []
        self._fn = add_dispatch_hook(lambda p, b: self.log.append((p, b.name)))
        return self.log

    def __exit__(self, *exc):
        remove_dispatch_hook(self._fn)
        return False


# ---------------------------------------------------------------------------
# failure structure + escalation state
# ---------------------------------------------------------------------------
class SolveFailure(RuntimeError):
    """Terminal dispatch failure: every capable backend raised or failed
    validation.  Structured — callers (the solve service) turn it into a
    per-ticket result value instead of NaN answers:

    ``problem``  the dispatched :class:`Problem`;
    ``chain``    the escalation chain, one ``{"backend", "reason"}`` dict
                 per failed attempt in the order tried;
    ``health``   the last :class:`repro.core.health.FactorHealth` record a
                 validator produced, or None (e.g. pure exception chains).
    """

    def __init__(self, message: str, *, problem: Problem | None = None,
                 chain: list | None = None, health=None):
        super().__init__(message)
        self.problem = problem
        self.chain = chain or []
        self.health = health


# Demotion: after a backend fails for a problem shape, skip it for the next
# DEMOTION_TTL *screened* dispatches of that shape (repeated hostile traffic
# goes straight to the survivor instead of re-failing every candidate; plain
# unscreened dispatches never consult the table).
# TTL-bounded so a transient fault can't permanently re-steer healthy
# traffic; faults.inject clears the table on exit for the same reason.
DEMOTION_TTL = 8

# Bound the built-in verify_residual check applies to exact-tier
# (tolerance == 0) linear solves; f32 no-pivot solves of in-class operands
# measure ~1e-7, so 1e-4 trips only on genuinely wrong answers.
VERIFY_RESIDUAL_DEFAULT_BOUND = 1e-4

_DEMOTIONS: dict[tuple, int] = {}  # (shape key, backend name) -> remaining TTL


def _shape_key(p: Problem) -> tuple:
    # ``devices`` is part of the shape: a SPIKE demotion on the 8-device
    # mesh must not suppress the (disjoint) single-device candidate set,
    # nor leak across mesh sizes.
    return (p.op, p.structure, p.dtype, p.n, p.bw, p.batch, p.devices)


def _demote(problem: Problem, name: str) -> None:
    _DEMOTIONS[(_shape_key(problem), name)] = DEMOTION_TTL


def _tick_demotions(key: tuple) -> None:
    """Age every demotion of this shape by one dispatch; drop the expired."""
    for k in [k for k in _DEMOTIONS if k[0] == key]:
        _DEMOTIONS[k] -= 1
        if _DEMOTIONS[k] <= 0:
            del _DEMOTIONS[k]


def demotions() -> dict[tuple, int]:
    """Snapshot of the active demotion table (tests/diagnostics)."""
    return dict(_DEMOTIONS)


def clear_demotions() -> None:
    _DEMOTIONS.clear()


_ESCALATION_HOOKS: list[Callable] = []


def add_escalation_hook(fn: Callable) -> Callable:
    """Register ``fn(problem, failed_backend_name, next_backend_name | None,
    reason)`` to observe every escalation event (``next`` is None on the
    terminal failure).  Returns ``fn`` for :func:`remove_escalation_hook`."""
    _ESCALATION_HOOKS.append(fn)
    return fn


def remove_escalation_hook(fn: Callable) -> None:
    try:
        _ESCALATION_HOOKS.remove(fn)
    except ValueError:
        pass


def _notify_escalation(problem, failed: str, nxt: str | None, reason: str) -> None:
    """Fire the escalation hooks.  Internal — dispatch calls it per funnel
    step, and the composed exact path in ``ops.linear_solve`` calls it when
    its post-hoc residual check (which spans two dispatches, so it cannot
    live inside either) fails over to the pivoted last resort."""
    for hook in _ESCALATION_HOOKS:
        hook(problem, failed, nxt, reason)


class record_escalations:
    """Context manager collecting ``(problem, failed, next, reason)`` for
    every escalation inside the block — the isolation tests' proof that a
    healthy rerun escalates zero times."""

    def __enter__(self) -> list[tuple]:
        self.log: list[tuple] = []
        self._fn = add_escalation_hook(
            lambda p, failed, nxt, reason: self.log.append((p, failed, nxt, reason))
        )
        return self.log

    def __exit__(self, *exc):
        remove_escalation_hook(self._fn)
        return False


def _eager(arrays) -> bool:
    import jax

    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


def _residual_validator(arrays, kw):
    """Built-in validator for ``Problem.verify_residual`` linear_solve
    dispatches: measure ``|Ax-b|/|b|`` of the eager result against the
    declared bound (``tolerance`` when set, else the exact-tier default)."""
    from repro.core import health as _health

    a, b = arrays[0], arrays[1]

    def validate(problem, backend, result):
        bound = problem.tolerance if problem.tolerance > 0 else VERIFY_RESIDUAL_DEFAULT_BOUND
        rel = float(_health.relative_residual(a, b, result, bw=problem.bw))
        if not rel <= bound:  # NaN-safe
            return (f"residual {rel:.3e} > bound {bound:.1e} from {backend.name}", None)
        return None

    return validate


def _run_attempt(plans, problem, backend, arrays, kw):
    """One dispatch attempt with fault plans applied around the call."""
    matched = [p for p in plans if p.matches(problem, backend.name)]
    for p in matched:
        p.before_call(problem, backend.name)
    result = backend.call(problem, *arrays, **kw)
    for p in matched:
        result = p.after_call(problem, backend.name, result)
    return result


def dispatch(
    problem: Problem,
    *arrays,
    impl: str | None = None,
    cache: _cache.AutotuneCache | None = None,
    allow: Callable[[Backend], bool] | None = None,
    validate: Callable | None = None,
    **kw,
):
    """Select and run in one step (the public ops' workhorse).

    ``validate(problem, backend, result)`` returns None to accept or a
    ``(reason, health_record | None)`` pair to reject — rejection feeds the
    escalation funnel on auto dispatches and raises :class:`SolveFailure`
    on forced ones.  Validation and the built-in residual check only run
    eagerly; under tracing (jit/vmap rules call dispatch at trace time)
    results pass through unscreened.

    Recorded as the program span ``repro.dispatch`` (:mod:`repro.utils.spans`),
    with children ``repro.dispatch.select``, ``repro.dispatch.call`` and
    ``repro.dispatch.validate``; ``traced`` says the operands were tracers.
    """
    with _spans.span("repro.dispatch", op=problem.op, structure=problem.structure,
                     n=problem.n, k=problem.rhs) as sp:
        if sp:
            sp.set(traced=not _eager(arrays))
        return _dispatch(sp, problem, arrays, impl, cache, allow, validate, kw)


def _dispatch(sp, problem, arrays, impl, cache, allow, validate, kw):
    from . import faults as _faults

    plans = _faults.active_plans()
    eager = (validate is not None or plans or problem.verify_residual) and _eager(arrays)
    if validate is None and eager and problem.verify_residual and problem.op == "linear_solve":
        validate = _residual_validator(arrays, kw)

    if impl is not None:
        # forced override: no escalation target exists, but faults still
        # apply and a failed validation still raises the structured failure
        # instead of returning a known-bad result.
        with _spans.span("repro.dispatch.select"):
            backend = get_backend(problem.op, problem.structure, impl)
        sp.set(backend=backend.name, attempts=1)
        for hook in _DISPATCH_HOOKS:
            hook(problem, backend)
        with _spans.span("repro.dispatch.call"):
            result = _run_attempt(plans, problem, backend, arrays, kw)
        if validate is not None and eager:
            with _spans.span("repro.dispatch.validate"):
                err = validate(problem, backend, result)
            if err is not None:
                reason, health = err
                raise SolveFailure(
                    f"forced impl {impl!r} failed validation for {problem}: {reason}",
                    problem=problem,
                    chain=[{"backend": backend.name, "reason": reason}],
                    health=health,
                )
        return result

    if not plans and validate is None:
        # The pre-funnel fast path: selection, hook order and the single
        # call are exactly the historical dispatch — bitwise-default.
        # Demotions are deliberately NOT consulted here: they only steer
        # *screened* dispatches (validator or fault plan present), so an
        # earlier hostile operand can never re-route plain default traffic.
        with _spans.span("repro.dispatch.select"):
            backend = select(problem, cache=cache, allow=allow)
        sp.set(backend=backend.name, attempts=1)
        for hook in _DISPATCH_HOOKS:
            hook(problem, backend)
        with _spans.span("repro.dispatch.call"):
            return backend.call(problem, *arrays, **kw)

    # --- escalation funnel -------------------------------------------------
    operand_faults = (ArithmeticError, np.linalg.LinAlgError, SolveFailure,
                      _faults.InjectedFault)
    with _spans.span("repro.dispatch.select"):
        winner = select(problem, cache=cache, allow=allow)
        rest = sorted(
            (b for b in candidates(problem, allow=allow) if b.name != winner.name),
            key=lambda b: b.priority(problem), reverse=True,
        )
        ordered = [winner] + rest
        key = _shape_key(problem)
        _tick_demotions(key)
        live = [b for b in ordered if (key, b.name) not in _DEMOTIONS] or ordered
    chain: list[dict] = []
    last_health = None
    for i, backend in enumerate(live):
        sp.set(backend=backend.name, attempts=i + 1)
        for hook in _DISPATCH_HOOKS:
            hook(problem, backend)
        health = None
        try:
            with _spans.span("repro.dispatch.call"):
                result = _run_attempt(plans, problem, backend, arrays, kw)
            err = None
            if validate and eager:
                with _spans.span("repro.dispatch.validate"):
                    err = validate(problem, backend, result)
            if err is None:
                return result
            reason, health = err
        except operand_faults as e:
            # only a fault of the operand escalates; any other exception (a
            # kernel Mosaic refuses, a block shape, a VMEM overflow, a bug)
            # propagates instead of quietly serving the next candidate
            reason = f"{type(e).__name__}: {e}"
        last_health = health if health is not None else last_health
        chain.append({"backend": backend.name, "reason": reason})
        _demote(problem, backend.name)
        nxt = live[i + 1].name if i + 1 < len(live) else None
        _notify_escalation(problem, backend.name, nxt, reason)
    raise SolveFailure(
        f"all {len(live)} capable backends failed for {problem}: "
        + " -> ".join(f"{c['backend']} ({c['reason']})" for c in chain),
        problem=problem, chain=chain, health=last_health,
    )
