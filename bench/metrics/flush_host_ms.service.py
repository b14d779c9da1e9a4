"""Front end: mean host time of the window's ``repro.service.flush``
program spans, from the call until ``flush`` returns with the solves
enqueued (traced runs only)."""

from bench.program_spans import mean_ms


def value(run):
    return mean_ms(run, "repro.service.flush")
