"""Set-up time: process start to the start of the first measured unit
(loading, operand generation, warm-up and, on a cold cache, compilation)."""


def value(run):
    return run.setup_s
