"""What the per-layer metrics read of the program's own spans
(``repro.utils.spans``).  The program records them only while a profiler
session is active, so they have values in traced runs alone."""
from __future__ import annotations


def in_window(run):
    """The program's spans inside the run's window, or ``None``: where the
    program has no recorder, where it recorded nothing (it was off), or
    where its buffer let go of spans of the window."""
    try:
        from repro.utils import spans
    except ImportError:
        return None
    return spans.recorded(*run.window) or None


def durations(run, names) -> list[float] | None:
    """Seconds of each window span named in ``names``; ``None`` as
    :func:`in_window`."""
    found = in_window(run)
    if found is None:
        return None
    return [s.duration for s in found if s.name in names]


def mean_ms(run, name: str):
    d = durations(run, (name,))
    return 1000.0 * sum(d) / len(d) if d else None


def stall_ms(run):
    """Total time of the window's compilations and garbage collections."""
    d = durations(run, ("repro.compile", "repro.gc"))
    return None if d is None else 1000.0 * sum(d)
