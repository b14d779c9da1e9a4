"""Front end: mean host time of the window's ``submit`` spans
(``SolveService.submit``: operand fingerprint and enqueue)."""


def value(run):
    spans = run.spans_named("submit")
    return 1000.0 * sum(spans) / len(spans) if spans else None
