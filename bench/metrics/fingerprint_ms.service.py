"""Front end: mean host time of the window's ``repro.service.fingerprint``
program spans (the operand's device-to-host copy and sha1, inside
``SolveService.submit``; traced runs only)."""

from bench.program_spans import mean_ms


def value(run):
    return mean_ms(run, "repro.service.fingerprint")
