"""The solver a run drives: the program under test, or the plain reference
put in its place (the control).

Both offer ``factor(a)``, ``solve(factors, b)`` and ``service(cache_entries)``,
the last an object with ``submit(a, b, bw=)`` and ``flush() -> {ticket: x}``.
"""
from __future__ import annotations

import importlib


class Program:
    """``repro.kernels.ops`` for fresh systems and ``SolveService`` for
    service traffic, each with its default selection."""

    name = "program"

    def __init__(self):
        self.ops = importlib.import_module("repro.kernels.ops")

    def factor(self, a):
        return self.ops.lu(a)

    def solve(self, factors, b):
        return self.ops.lu_solve(factors, b)

    def service(self, cache_entries: int):
        from repro.serve.solve_service import SolveService

        return SolveService(cache_entries=cache_entries)


class Reference:
    """The configuration's plain reference at a stated precision."""

    def __init__(self, module, precision: str):
        self.mod, self.precision = module, precision
        self.name = f"reference@{precision}"

    def factor(self, a):
        return self.mod.factor(a, precision=self.precision)

    def solve(self, factors, b):
        return self.mod.solve(factors, b, precision=self.precision)

    def service(self, cache_entries: int):
        return _ReferenceService(self)


class _ReferenceService:
    """Factor once per operator, solve each flush's requests against one
    operator as one stacked right-hand side."""

    def __init__(self, ref: Reference):
        self.ref = ref
        self.factors: dict[int, object] = {}
        self.pending: list[tuple[int, object, object]] = []
        self.tickets = 0

    def submit(self, a, b) -> int:
        self.tickets += 1
        self.pending.append((self.tickets, a, b))
        return self.tickets

    def flush(self) -> dict:
        import jax.numpy as jnp

        groups: dict[int, list] = {}
        for entry in self.pending:
            groups.setdefault(id(entry[1]), []).append(entry)
        out = {}
        for entries in groups.values():
            a = entries[0][1]
            if id(a) not in self.factors:
                self.factors[id(a)] = self.ref.factor(a)
            x = self.ref.solve(self.factors[id(a)], jnp.stack([e[2] for e in entries], axis=1))
            for j, (ticket, _, _) in enumerate(entries):
                out[ticket] = x[:, j]
        self.pending = []
        return out
