"""Factor once, solve many: a closed loop of ticks through the solve
service.  Set-up makes ``operators`` seeded operators on the device in one
call.  Each tick makes ``requests_per_tick`` single-column right-hand sides
in one call, submits them, calls one ``flush`` and waits for every answer.

Each tick sends the same widths: the operators' request counts are the
Zipf(``zipf_s``) expectation over ``operators`` ranks, rounded by largest
remainder (``zipf_s`` 0: every operator alike).  The order of the ranks' requests in tick ``t`` is drawn from
``(pattern_seed, t)``, the same for every seed, so that every seed sends the
same arrivals; the seed draws the operands and, per tick, which operator
holds which rank.  The first tick runs in set-up: it factors every
operator that the ticks reach and compiles every width they send.  The
window runs whole ticks until ``--seconds`` have passed.  A request's latency
runs from its ``submit`` to its answer being ready; a request that returns no
answer counts as failed.  Spans: ``submit``, ``flush``.

Traffic parameters: ``operators``, ``requests_per_tick``, ``zipf_s``,
``cache_entries``, ``pattern_seed``, ``check_sample``.
"""
from __future__ import annotations

import time
from collections import defaultdict

import jax
import numpy as np

from bench import check
from bench.core import OPERATOR, RHS, WARM, fold


def zipf_counts(ranks: int, requests: int, s: float) -> list[int]:
    w = np.arange(1, ranks + 1, dtype=np.float64) ** -s
    exp = requests * w / w.sum()
    counts = np.floor(exp).astype(int)
    for r in np.argsort(-(exp - counts), kind="stable")[: requests - counts.sum()]:
        counts[r] += 1
    return [int(c) for c in counts]


def setup(run) -> dict:
    gen, cfg, t = run.generator, run.config, run.traffic
    m, k = t["operators"], t["requests_per_tick"]
    operators = jax.jit(lambda key: tuple(gen.operator(fold(key, OPERATOR, j), cfg) for j in range(m)))
    rhs_tick = jax.jit(lambda key, tick: tuple(gen.rhs(fold(key, RHS, tick, j), cfg) for j in range(k)))
    state = {
        "ops": jax.block_until_ready(operators(run.key)),
        "rhs_tick": rhs_tick,
        "counts": zipf_counts(m, k, t["zipf_s"]),
        "rng": np.random.default_rng([int(run.seed) % 2**63, 11]),
        "svc": run.solver.service(t["cache_entries"]),
    }
    _tick(run, state, fold(run.key, WARM), 0, record=False)
    return state


def _tick(run, state, key, tick: int, record: bool = True):
    counts = state["counts"]
    holder = state["rng"].permutation(len(counts))  # operator holding each rank
    ranks = [r for r, c in enumerate(counts) for _ in range(c)]
    pattern = np.random.default_rng([run.traffic["pattern_seed"], tick])
    order = [int(holder[ranks[j]]) for j in pattern.permutation(len(ranks))]
    bs = jax.block_until_ready(state["rhs_tick"](key, tick))
    svc, ops = state["svc"], state["ops"]
    submitted, tickets = [], []
    for j, op in enumerate(order):
        submitted.append(time.perf_counter())
        with run.span("submit"):
            tickets.append(svc.submit(ops[op], bs[j]))
    with run.span("flush"):
        out = svc.flush()
        ready = []
        for t in tickets:
            jax.block_until_ready(out[t])
            ready.append(time.perf_counter())
    if not record:
        return
    for j, (op, t) in enumerate(zip(order, tickets)):
        run.attempted += 1
        run.latencies_s.append(ready[j] - submitted[j])
        if hasattr(out[t], "shape"):
            run.answers.append((op, tick, j, out[t]))
        else:
            run.failed += 1
            run.note(f"request failed: tick={tick} j={j} operator={op}: {out[t]!r}"[:300])


def window(run, state: dict):
    run.start_window()
    tick = 0
    while True:
        _tick(run, state, run.key, tick)
        tick += 1
        if run.past_window():
            break
    run.end_window()


def release(run, state: dict):
    del state["svc"]


def check_answers(run, state: dict) -> dict:
    by_op = defaultdict(list)
    for idx in run.sample(len(run.answers)):
        op, tick, j, x = run.answers[idx]
        by_op[op].append((tick, j, x))
    rel = []
    for op, items in sorted(by_op.items()):
        a = np.asarray(state["ops"][op])
        b = np.stack([np.asarray(state["rhs_tick"](run.key, tick)[j]) for tick, j, _ in items], axis=1)
        x = np.stack([np.asarray(x) for _, _, x in items], axis=1)
        rel.extend(check.residuals(a, b, x))
        del a
    return {"relative_residual": max(rel, default=float("nan"))}
