"""Factor cache: 100 x hits / (hits + misses), summed over the window's
``repro.service.flush`` program spans, which carry each flush's change of
``SolveService.stats`` (traced runs only)."""

from bench.program_spans import in_window


def value(run):
    found = in_window(run)
    flushes = [s.attrs for s in found or () if s.name == "repro.service.flush"]
    hits = sum(f["cache_hits"] for f in flushes)
    lookups = hits + sum(f["cache_misses"] for f in flushes)
    return 100.0 * hits / lookups if lookups else None
