"""Fresh systems, closed loop with one caller: each system is a new seeded
operator and one right-hand side, made on the device in one call, then
factored and solved.  The window runs whole systems until ``--seconds`` have
passed.  Spans: ``generate``, ``factor``, ``solve``.

Traffic parameters: ``check_sample`` (how many of the window's systems the
check regenerates and compares).
"""
from __future__ import annotations

import jax

from bench import check
from bench.core import OPERATOR, RHS, WARM, fold


def setup(run) -> dict:
    gen, cfg = run.generator, run.config
    system = jax.jit(lambda key, i: (gen.operator(fold(key, OPERATOR, i), cfg),
                                     gen.rhs(fold(key, RHS, i), cfg)))
    warm_key = fold(run.key, WARM)
    a, b = system(warm_key, 0)
    x = run.solver.solve(run.solver.factor(a), b)
    jax.block_until_ready(x)
    return {"system": system}


def window(run, state: dict):
    system, solver = state["system"], run.solver
    run.start_window()
    i = 0
    while True:
        with run.span("generate"):
            a, b = jax.block_until_ready(system(run.key, i))
        with run.span("factor"):
            factors = jax.block_until_ready(solver.factor(a))
        with run.span("solve"):
            x = jax.block_until_ready(solver.solve(factors, b))
        del a, b, factors
        run.answers.append((i, x))
        run.attempted += 1
        i += 1
        if run.past_window():
            break
    run.end_window()


def release(run, state: dict):
    pass


def check_answers(run, state: dict) -> dict:
    rel = []
    for idx in run.sample(len(run.answers)):
        i, x = run.answers[idx]
        a, b = state["system"](run.key, i)
        rel.extend(check.residuals(a, b, x))
    return {"relative_residual": max(rel, default=float("nan"))}

