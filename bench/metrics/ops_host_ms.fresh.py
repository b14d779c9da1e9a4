"""Ops entry and dispatch: host time per system of the window's
``repro.ops.lu`` and ``repro.ops.lu_solve`` program spans, from the call
until the op returns with its kernels enqueued (traced runs only)."""

from bench.program_spans import durations


def value(run):
    d = durations(run, ("repro.ops.lu", "repro.ops.lu_solve"))
    return 1000.0 * sum(d) / run.attempted if d and run.attempted else None
