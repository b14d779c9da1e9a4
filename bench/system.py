"""The solver a run drives: the program under test, or the plain reference
put in its place (the control).

Both offer ``factor(a, bw=, mesh=)``, ``solve(factors, b, bw=, mesh=)`` and
``service(cache_entries)``, the last an object with ``submit(a, b[, bw=])``
and ``flush() -> {ticket: x}``.  ``bw`` 0 is a dense ``(n, n)`` operand;
otherwise ``a`` is the row-aligned band ``(n, 2bw+1)``, ``a[i, t] =
A[i, i - bw + t]``.  ``mesh`` is a one-axis mesh over the cell's chips, or
``None`` on one chip.
"""
from __future__ import annotations

import importlib


def _spanning(mesh) -> dict:
    return {} if mesh is None else {"mesh": mesh, "mesh_axis": mesh.axis_names[0]}


class Program:
    """``repro.kernels.ops`` for fresh systems and ``SolveService`` for
    service traffic, each with its default selection."""

    name = "program"

    def __init__(self):
        self.ops = importlib.import_module("repro.kernels.ops")

    def factor(self, a, *, bw: int = 0, mesh=None):
        if not bw:
            return self.ops.lu(a)
        return self.ops.banded_lu(a, bw=bw, **_spanning(mesh))

    def solve(self, factors, b, *, bw: int = 0, mesh=None):
        if not bw:
            return self.ops.lu_solve(factors, b)
        return self.ops.banded_solve(factors, b, bw=bw, **_spanning(mesh))

    def service(self, cache_entries: int):
        from repro.serve.solve_service import SolveService

        return SolveService(cache_entries=cache_entries)


class Reference:
    """The configuration's plain reference at a stated precision.  It runs
    wherever JAX places its operands; ``mesh`` is not its business."""

    def __init__(self, module, precision: str):
        self.mod, self.precision = module, precision
        self.name = f"reference@{precision}"

    def factor(self, a, *, bw: int = 0, mesh=None):
        if not bw:
            return self.mod.factor(a, precision=self.precision)
        return self.mod.factor(a, bw=bw, precision=self.precision)

    def solve(self, factors, b, *, bw: int = 0, mesh=None):
        if not bw:
            return self.mod.solve(factors, b, precision=self.precision)
        return self.mod.solve(factors, b, bw=bw, precision=self.precision)

    def service(self, cache_entries: int):
        return _ReferenceService(self)


class _ReferenceService:
    """Factor once per operator, solve each flush's requests against one
    operator as one stacked right-hand side."""

    def __init__(self, ref: Reference):
        self.ref = ref
        self.factors: dict[int, object] = {}
        self.pending: list[tuple[int, object, object, int]] = []
        self.tickets = 0

    def submit(self, a, b, bw: int = 0) -> int:
        self.tickets += 1
        self.pending.append((self.tickets, a, b, bw))
        return self.tickets

    def flush(self) -> dict:
        import jax.numpy as jnp

        groups: dict[int, list] = {}
        for entry in self.pending:
            groups.setdefault(id(entry[1]), []).append(entry)
        out = {}
        for entries in groups.values():
            a, bw = entries[0][1], entries[0][3]
            if id(a) not in self.factors:
                self.factors[id(a)] = self.ref.factor(a, bw=bw)
            x = self.ref.solve(self.factors[id(a)], jnp.stack([e[2] for e in entries], axis=1), bw=bw)
            for j, (ticket, *_) in enumerate(entries):
                out[ticket] = x[:, j]
        self.pending = []
        return out
