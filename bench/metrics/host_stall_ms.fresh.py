"""Host: total time of the window's compilations (``repro.compile``) and
Python garbage collections (``repro.gc``), as the program records them
(traced runs only)."""

from bench.program_spans import stall_ms


def value(run):
    return stall_ms(run)
