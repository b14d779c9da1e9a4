"""Linear-system serving front end: factor once, solve many.

The dominant real traffic shape for a solver service is GLU3.0's
circuit-simulation pattern — the SAME matrix arrives over and over with
fresh right-hand sides (transient timesteps, Monte-Carlo sweeps, parameter
scans).  The service exploits it twice:

* **factorization cache** — an LRU keyed by matrix *fingerprint*
  (content hash of bytes + shape + dtype + bandwidth).  A hit skips the
  factorization dispatch entirely and jumps straight to substitution;
* **RHS coalescing** — pending requests against one fingerprint hstack
  their RHS columns into a single wide solve dispatch
  (:func:`repro.core.solve.stack_rhs`).  Substitution columns are
  independent, so the coalesced results are bitwise-identical to
  per-request solves while paying one kernel launch.

Everything routes through :class:`repro.solvers.Problem` descriptors and
the registry, so the autotuned backend selection (and its multi-RHS
capability filter — e.g. the vector-only scalar banded solve is pruned when
``rhs > 1``) decides *how* each coalesced dispatch runs.  Dispatch counts in
``stats`` come from the registry's dispatch hook, not from self-reporting.

**Accuracy tiers.**  Requests carry a ``tolerance`` (largest acceptable
relative residual; 0.0 = exact).  The factorization cache holds factors
*per accuracy tier* under each fingerprint — tier 0.0 for packed exact
factors, tier ``RAND_LU_RESIDUAL_BOUND`` for rank-k factors produced by a
``rank=`` request.  A request is served by any cached tier **at or below**
its tolerance (a tighter factor always satisfies a looser request); the
reverse — an approximate factor serving a tighter request — is structurally
impossible, because eligibility is ``tier <= tolerance``.  The tolerance
also threads into every factor/solve :class:`~repro.solvers.Problem`, so
the registry's tolerance gate and the autotune cache key see it.

**Coalescing-width cap.**  Stacked-RHS solves normally coalesce every
pending column into one dispatch.  When ``scripts/autotune.py`` has swept
dispatch widths for a transferable shape (``AutotuneCache.best_width``),
the stack is chunked at the measured most-µs-per-column-efficient width
instead — unmeasured shapes keep full coalescing.

**Mesh routing.**  A service built with ``mesh=`` routes banded groups
whose band fits the mesh partition (:func:`repro.core.spike.spike_supported`)
through the multi-device registry path: factorization dispatches as a
``devices > 1`` problem — SPIKE split factors vs replication, weighed per
``(n, bw, devices)`` by the measured autotune cache — and a SPIKE-factored
group's coalesced stacked-RHS substitution runs shard-local over the mesh
with one reduced spike solve for the whole stack.  Bands too wide for the
partition (and dense traffic) stay on the single-device path unchanged.

Admission/ordering rides the shared :class:`repro.serve.scheduler.Scheduler`
(buckets = ``(structure, n, bw, dtype, tolerance)``; deadline/FIFO order
decides which matrix group flushes first).

**Failure isolation.**  Factorizations are health-screened by default
(``ops.lu(..., health=)`` → the registry escalation funnel), so a hostile
operand escalates through the capable backends and — only when every one
fails — surfaces as a structured :class:`repro.solvers.SolveFailure`.  The
service degrades instead of dying: the failing coalesced group's tickets
resolve to the failure *value* (other groups in the same flush are
untouched), the unhealthy factors are never admitted to the LRU, and the
fingerprint enters a **negative cache** (quarantine) for the next
``quarantine_ttl`` flushes — repeat offenders short-circuit without
re-dispatching.  A ``clock=`` makes deadlines real: requests already past
deadline at drain are shed as :class:`DeadlineMiss` values rather than
burning a dispatch.  ``flush`` is transactional — an unexpected exception
mid-flush requeues every unprocessed entry with seq/deadline intact.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro import solvers
from repro.core import health as _health
from repro.core import refine as _refine
from repro.core.factorization import Factorization
from repro.core.pivoted import PivotedFactors
from repro.core.randomized import RankKFactors
from repro.core.solve import split_rhs, stack_rhs
from repro.core.spike import SpikeFactors, spike_supported
from repro.kernels import ops as kops
from repro.solvers.backends import RAND_LU_RESIDUAL_BOUND
from repro.utils import spans
from .scheduler import Scheduler

__all__ = [
    "FLUSH_COUNTERS",
    "SolveRequest",
    "SolveServiceStats",
    "SolveService",
    "fingerprint",
    "DeadlineMiss",
    "UnknownTicket",
    "NotFlushed",
]


class UnknownTicket(KeyError):
    """The ticket was never issued, or its result was already redeemed."""


class NotFlushed(KeyError):
    """The ticket is still queued — call :meth:`SolveService.flush` first."""


@dataclasses.dataclass(frozen=True)
class DeadlineMiss:
    """Result value for a request already past its deadline at drain time:
    the service sheds it instead of burning a dispatch on a stale answer."""

    ticket: int
    deadline: float
    now: float


def fingerprint(a, *, bw: int = 0) -> str:
    """Content hash identifying a matrix operand (dense or row-aligned
    band): sha1 over the raw bytes + shape + dtype + bandwidth."""
    with spans.span("repro.service.fingerprint") as sp:
        with spans.span("repro.service.fingerprint.to_host"):
            arr = np.asarray(a)
        sp.set(bytes=arr.nbytes)
        with spans.span("repro.service.fingerprint.hash"):
            h = hashlib.sha1()
            h.update(str((arr.shape, arr.dtype.str, int(bw))).encode())
            h.update(arr.tobytes())
            return h.hexdigest()


@dataclasses.dataclass
class SolveRequest:
    ticket: int
    fp: str
    a: object  # matrix operand (kept until its group's factor lands in cache)
    b: object  # RHS (n,) or (n, m)
    bw: int
    deadline: float | None = None
    tolerance: float = 0.0  # largest acceptable relative residual (0 = exact)
    rank: int | None = None  # request the randomized rank-k factor tier


@dataclasses.dataclass
class SolveServiceStats:
    requests: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    cache_evictions: int = 0
    factor_dispatches: int = 0
    solve_dispatches: int = 0
    coalesced_requests: int = 0  # requests that shared a solve dispatch
    solved_columns: int = 0
    approx_solves: int = 0  # dispatches served by a residual-bound (approximate) tier
    width_capped_dispatches: int = 0  # extra dispatches forced by the coalescing cap
    failed_requests: int = 0  # tickets resolved to a structured SolveFailure
    escalations: int = 0  # registry escalation events observed during flushes
    quarantined: int = 0  # tickets short-circuited by the negative cache
    shed_deadline: int = 0  # tickets shed as DeadlineMiss at drain
    last_refine_iterations: int | None = None  # refinement sweeps of the last
                                               # approximate solve (None = none ran)

    @property
    def hit_rate(self) -> float:
        tot = self.cache_hits + self.cache_misses
        return self.cache_hits / tot if tot else 0.0


# the stats counters whose change over one flush its span records
FLUSH_COUNTERS = ("cache_hits", "cache_misses", "factor_dispatches", "solve_dispatches",
                  "escalations", "solved_columns", "failed_requests")


class SolveService:
    """Batch front end over the solver registry.

    ``submit`` enqueues; ``flush`` drains the queue grouped by matrix
    fingerprint — one factorization dispatch per *cold* matrix, one
    coalesced stacked-RHS solve dispatch per (matrix, RHS-width-compatible)
    group — and returns ``{ticket: solution}``.  ``solve`` is the
    submit+flush convenience for a single request.
    """

    def __init__(
        self,
        *,
        cache_entries: int = 16,
        health=True,
        quarantine_ttl: int = 8,
        clock=None,
        verify_residual: bool = False,
        mesh=None,
        mesh_axis: str = "model",
    ):
        """``health=`` screens every factorization (``True`` = default
        thresholds, a :class:`repro.core.health.HealthThresholds` to tune,
        ``None``/``False`` to disable — restoring the unscreened ops).
        ``quarantine_ttl`` is how many subsequent flushes a
        terminally-failed fingerprint short-circuits for.  ``clock``
        (e.g. ``time.monotonic``) arms deadline shedding; without one,
        deadlines only order the flush (the historical behaviour).
        ``verify_residual=True`` additionally gates every coalesced solve
        on its measured relative residual.  ``mesh=`` (a ``jax.sharding``
        mesh spanning > 1 device along ``mesh_axis``) routes banded groups
        whose band fits the mesh partition (``spike_supported``) through
        the multi-device registry path — SPIKE split factors vs replication
        decided per ``(n, bw, devices)`` by the measured autotune cache,
        and the coalesced stacked-RHS substitution runs sharded."""
        self.cache_entries = cache_entries
        self.health = health
        self.quarantine_ttl = quarantine_ttl
        self.verify_residual = verify_residual
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self._clock = clock
        # fp -> {accuracy tier -> factors}; tier 0.0 = exact packed factors,
        # tier t > 0 = approximate factors guaranteeing relative residual t.
        # LRU order (and the entry budget) is per fingerprint.
        self._lru: OrderedDict[str, dict[float, object]] = OrderedDict()
        # negative cache: fp -> (expiry flush count, the SolveFailure)
        self._quarantine: dict[str, tuple[int, object]] = {}
        self._flush_count = 0
        self._sched = Scheduler()
        self._tickets = 0
        self._pending_tickets: set[int] = set()
        self._done: dict[int, object] = {}  # flushed, not yet redeemed
        self.stats = SolveServiceStats()

    # -- admission ----------------------------------------------------------
    def submit(
        self,
        a,
        b,
        *,
        bw: int = 0,
        deadline: float | None = None,
        tolerance: float = 0.0,
        rank: int | None = None,
    ) -> int:
        """Enqueue ``a x = b`` (``bw > 0`` = row-aligned band operand);
        returns a ticket redeemable at the next :meth:`flush`.

        ``tolerance`` is the largest acceptable relative residual — it keys
        the scheduler bucket and selects which cached factor tiers may serve
        the request (any tier ≤ tolerance).  ``rank=`` asks for the
        randomized rank-k tier (dense only; requires ``tolerance`` at least
        the tier's guaranteed bound)."""
        if tolerance < 0:
            raise ValueError(f"tolerance must be >= 0, got {tolerance}")
        if rank is not None:
            if bw:
                raise ValueError("rank= (randomized tier) is dense-only")
            if tolerance < RAND_LU_RESIDUAL_BOUND:
                raise ValueError(
                    f"rank= produces factors guaranteed to {RAND_LU_RESIDUAL_BOUND:g} "
                    f"relative residual; request tolerance {tolerance:g} is tighter"
                )
        a = jnp.asarray(a)
        b = jnp.asarray(b)
        n = int(a.shape[-2]) if bw else int(a.shape[-1])
        structure = "banded" if bw else "dense"
        cols = 1 if b.ndim == 1 else int(b.shape[-1])
        ticket = self._tickets
        self._tickets += 1
        with spans.span("repro.service.submit", request=ticket, n=n, bw=bw, cols=cols):
            req = SolveRequest(
                ticket=ticket, fp=fingerprint(a, bw=bw), a=a, b=b, bw=bw,
                deadline=deadline, tolerance=float(tolerance), rank=rank,
            )
            self._sched.submit(
                req, bucket=(structure, n, bw, str(a.dtype), float(tolerance)),
                cost=float(cols), deadline=deadline, real=cols,
            )
        self.stats.requests += 1
        self._pending_tickets.add(ticket)
        return ticket

    def pending(self) -> int:
        return len(self._sched)

    def quarantined_fingerprints(self) -> set[str]:
        """Fingerprints currently in the negative cache (diagnostics)."""
        return set(self._quarantine)

    # -- factorization cache ------------------------------------------------
    @staticmethod
    def _factor_tier(factors) -> float:
        """The accuracy tier a factor object belongs to: the residual its
        producing backend guarantees (rank-k factors), 0.0 for exact.
        Factorization artifacts carry their tier as metadata."""
        if isinstance(factors, Factorization):
            return factors.tier
        return RAND_LU_RESIDUAL_BOUND if isinstance(factors, RankKFactors) else 0.0

    def _band_spans_mesh(self, req: SolveRequest) -> bool:
        """True when this banded operand should take the multi-device
        path: a mesh is configured, it spans > 1 device, and the band is
        narrow enough for the SPIKE partition (``2·bw ≤ ceil(n/d)``)."""
        if self.mesh is None or not req.bw:
            return False
        devices = int(self.mesh.shape[self.mesh_axis])
        n = int(req.a.shape[-2])
        return devices > 1 and spike_supported(n, req.bw, devices)

    def _factors_for(self, req: SolveRequest, tolerance: float):
        tiers = self._lru.get(req.fp)
        if tiers is not None:
            # a cached tier serves the request iff it is at least as tight
            # as the request's tolerance — never the reverse.  Among the
            # eligible tiers the tightest wins (best answer, same price).
            eligible = [t for t in tiers if t <= tolerance]
            if eligible:
                self.stats.cache_hits += 1
                self._lru.move_to_end(req.fp)
                return tiers[min(eligible)]
        self.stats.cache_misses += 1
        # With health screening on, a SolveFailure propagates out of these
        # ops before anything reaches the LRU — unhealthy factors are never
        # admitted (success past the screen *is* the admission check).
        if req.bw:
            # enrich at factor time: the banded serve steady state is
            # many solves per factor, so the pre-inverted blocks pay for
            # themselves and every cache hit solves via the two-phase
            # inverted path with zero layout work.  When the band spans a
            # mesh, route through the multi-device registry path (SPIKE
            # split factors vs replication, measured per (n, bw, devices));
            # bands too wide for the partition stay on the local path.
            mesh = self.mesh if self._band_spans_mesh(req) else None
            factors = kops.banded_lu(
                req.a, bw=req.bw, tolerance=tolerance, health=self.health,
                enrich=True, mesh=mesh, mesh_axis=self.mesh_axis,
            )
        elif req.rank is not None:
            factors = kops.lu(
                req.a, rank=req.rank, tolerance=tolerance, health=self.health
            )
        else:
            factors = kops.lu(req.a, tolerance=tolerance, health=self.health)
        if self.health:
            factors, _record = factors  # screened ops return (factors, health)
        if isinstance(factors, Factorization):
            # stamp the cache identity on the artifact — a future consumer
            # (or a re-submitted artifact) carries its own fingerprint and
            # never needs the matrix bytes re-hashed or re-screened.
            factors = factors.with_meta(fingerprint=req.fp)
        self._lru.setdefault(req.fp, {})[self._factor_tier(factors)] = factors
        self._lru.move_to_end(req.fp)
        while len(self._lru) > self.cache_entries:
            self._lru.popitem(last=False)
            self.stats.cache_evictions += 1
        return factors

    # -- the flush ----------------------------------------------------------
    def flush(self) -> dict[int, object]:
        """Serve every pending request; returns ``{ticket: result}`` for the
        whole drained queue.  Results are also retained until redeemed via
        :meth:`result`, so a convenience :meth:`solve` draining the queue
        cannot lose earlier submissions' answers.

        A result is a solution array, a :class:`repro.solvers.SolveFailure`
        (the request's coalesced group exhausted the escalation funnel, or
        its fingerprint is quarantined), or a :class:`DeadlineMiss` (shed at
        drain — only when the service was built with a ``clock``).  One
        group failing never disturbs the other groups in the flush.

        While spans are recorded (:mod:`repro.utils.spans`) the flush's span
        carries the change of each of ``FLUSH_COUNTERS`` over the flush."""
        with spans.span("repro.service.flush", requests=len(self._sched)) as sp:
            if not sp:
                return self._flush(sp)
            before = [getattr(self.stats, f) for f in FLUSH_COUNTERS]
            try:
                return self._flush(sp)
            finally:
                sp.set(**{f: getattr(self.stats, f) - b for f, b in zip(FLUSH_COUNTERS, before)})

    def _flush(self, sp) -> dict[int, object]:
        counting = solvers.add_dispatch_hook(self._count_dispatch)
        escalating = solvers.add_escalation_hook(self._count_escalation)
        self._flush_count += 1
        for fp in [f for f, (exp, _) in self._quarantine.items()
                   if exp < self._flush_count]:
            del self._quarantine[fp]
        drained = self._sched.drain()
        processed: set[int] = set()  # seq of every entry whose group completed
        results: dict[int, object] = {}
        try:
            now = self._clock() if self._clock is not None else None
            live = []
            for entry in drained:
                r = entry.payload
                if now is not None and r.deadline is not None and r.deadline < now:
                    results[r.ticket] = DeadlineMiss(
                        ticket=r.ticket, deadline=r.deadline, now=now
                    )
                    self.stats.shed_deadline += 1
                    processed.add(entry.seq)
                else:
                    live.append(entry)
            groups: OrderedDict[tuple, list] = OrderedDict()
            for entry in live:
                p = entry.payload
                # rank-tier requests coalesce separately from exact requests
                # against the same matrix — they want different factors.
                groups.setdefault((p.fp, p.rank), []).append(entry)
            sp.set(groups=len(groups))
            for (fp, rank), entries in groups.items():
                reqs = [e.payload for e in entries]
                quarantined = self._quarantine.get(fp)
                if quarantined is not None:
                    # negative cache: this operand already exhausted the
                    # funnel recently — short-circuit without dispatching.
                    for r in reqs:
                        results[r.ticket] = quarantined[1]
                    self.stats.quarantined += len(reqs)
                    processed.update(e.seq for e in entries)
                    continue
                # tightest member tolerance governs the whole coalesced
                # dispatch: every member accepts its residual.
                group_tol = min(r.tolerance for r in reqs)
                try:
                    with spans.span("repro.service.factors", fp=fp[:12],
                                    tickets=[r.ticket for r in reqs]) as fsp:
                        hits = self.stats.cache_hits
                        factors = self._factors_for(reqs[0], group_tol)
                        fsp.set(hit=self.stats.cache_hits > hits)
                    # hit/miss accounting is per REQUEST: coalesced group
                    # members past the leader skip the factorization too
                    self.stats.cache_hits += len(reqs) - 1
                    stacked, widths, squeezes = stack_rhs([r.b for r in reqs])
                    self.stats.solved_columns += int(stacked.shape[-1])
                    if len(reqs) > 1:
                        self.stats.coalesced_requests += len(reqs)
                    x = self._dispatch_solve(reqs[0], factors, stacked, group_tol)
                    if self.verify_residual:
                        self._check_residual(reqs[0], stacked, x, group_tol)
                except solvers.SolveFailure as failure:
                    # graceful degradation: the whole group resolves to the
                    # structured failure VALUE (never NaN answers, never an
                    # exception that would abort the other groups), and the
                    # fingerprint enters the negative cache.
                    for r in reqs:
                        results[r.ticket] = failure
                    self.stats.failed_requests += len(reqs)
                    self._quarantine[fp] = (
                        self._flush_count + self.quarantine_ttl, failure
                    )
                    processed.update(e.seq for e in entries)
                    continue
                for r, xr in zip(reqs, split_rhs(x, widths, squeezes)):
                    results[r.ticket] = xr
                processed.update(e.seq for e in entries)
            return results
        finally:
            solvers.remove_dispatch_hook(counting)
            solvers.remove_escalation_hook(escalating)
            # commit every completed group's answers even when a later group
            # raised: callers redeem them via result().
            self._done.update(results)
            self._pending_tickets.difference_update(results)
            # transactional drain: an exception mid-flush must not lose the
            # rest of the batch — unprocessed entries go back to the queue
            # with their original seq/deadline intact.
            remaining = [e for e in drained if e.seq not in processed]
            if remaining:
                self._sched.restore(remaining)

    def _check_residual(self, req: SolveRequest, stacked, x, tolerance: float) -> None:
        """``verify_residual`` gate on the coalesced answer; a miss raises
        :class:`SolveFailure` into the group's failure handling."""
        bound = tolerance if tolerance > 0 else solvers.VERIFY_RESIDUAL_DEFAULT_BOUND
        rel = float(_health.relative_residual(req.a, stacked, x, bw=req.bw))
        if not rel <= bound:  # NaN-safe
            problem = solvers.Problem.from_arrays(
                "linear_solve", req.a, stacked, bw=req.bw,
                tolerance=tolerance, verify_residual=True,
            )
            raise solvers.SolveFailure(
                f"coalesced solve residual {rel:.3e} > bound {bound:.1e} "
                f"for {problem}",
                problem=problem,
                chain=[{"backend": "serve", "reason": f"residual {rel:.3e}"}],
            )

    def _dispatch_solve(self, req: SolveRequest, factors, stacked, tolerance: float):
        """One coalesced substitution — chunked at the autotuned coalescing
        width when the registry has measured one for this shape."""
        def run(cols):
            if isinstance(factors, SpikeFactors):
                # split factors substitute shard-locally over the mesh; the
                # coalesced stack is one wide multi-RHS spike solve.
                return kops.banded_solve(
                    factors, cols, bw=req.bw, tolerance=tolerance,
                    mesh=self.mesh, mesh_axis=self.mesh_axis,
                )
            if req.bw:
                return kops.banded_solve(factors, cols, bw=req.bw, tolerance=tolerance)
            return kops.lu_solve(factors, cols, tolerance=tolerance)

        width = int(stacked.shape[-1])
        with spans.span("repro.service.solve", fp=req.fp[:12], width=width) as sp:
            cap = None
            if not isinstance(factors, (RankKFactors, PivotedFactors, SpikeFactors)):
                # width measurements only exist for packed-factor substitution;
                # rank-k solves are GEMM-shaped and always coalesce fully,
                # pivoted factors (the escalation last resort) are too rare to
                # have measured widths, and SPIKE split factors coalesce fully
                # so the reduced spike system is solved exactly once.
                problem = solvers.Problem.from_arrays(
                    "solve", factors, stacked, bw=req.bw, tolerance=tolerance
                )
                cap = solvers.get_cache().best_width(problem)
            if cap and width > cap:
                pieces = [
                    run(stacked[..., i : i + cap]) for i in range(0, width, cap)
                ]
                self.stats.width_capped_dispatches += len(pieces) - 1
                x = jnp.concatenate(pieces, axis=-1)
            else:
                x = run(stacked)
            if isinstance(factors, RankKFactors) and tolerance > 0.0:
                # polish the approximate-tier answer to the group tolerance
                # against the full operand; the sweep count lands in stats.
                x, info = _refine.iterative_refinement(
                    req.a, stacked, x, run, tolerance=tolerance
                )
                jax.block_until_ready(x)
                self.stats.last_refine_iterations = int(info.iterations)
                sp.set(refine_iterations=int(info.iterations))
            return x

    def result(self, ticket: int):
        """Redeem (pop) a flushed ticket.  Raises :class:`NotFlushed` when
        the ticket is still queued and :class:`UnknownTicket` when it was
        never issued or was already redeemed (both subclass ``KeyError``)."""
        try:
            return self._done.pop(ticket)
        except KeyError:
            pass
        if ticket in self._pending_tickets:
            raise NotFlushed(
                f"ticket {ticket} has not been flushed yet (call flush())"
            )
        raise UnknownTicket(f"ticket {ticket} was never issued or already redeemed")

    def solve(self, a, b, *, bw: int = 0, tolerance: float = 0.0, rank: int | None = None):
        """submit + flush for one request (still hits/extends the cache).
        Other pending requests flushed alongside stay redeemable via
        :meth:`result`.  A request that terminally failed raises its
        :class:`SolveFailure` (batch callers using submit/flush/result get
        it as a value instead)."""
        ticket = self.submit(a, b, bw=bw, tolerance=tolerance, rank=rank)
        self.flush()
        out = self.result(ticket)
        if isinstance(out, solvers.SolveFailure):
            raise out
        return out

    def _count_dispatch(self, problem, backend) -> None:
        if problem.op == "factor":
            self.stats.factor_dispatches += 1
        elif problem.op in ("solve", "linear_solve"):
            self.stats.solve_dispatches += 1
            if getattr(backend, "residual_bound", None) is not None:
                self.stats.approx_solves += 1

    def _count_escalation(self, problem, failed, nxt, reason) -> None:
        self.stats.escalations += 1
