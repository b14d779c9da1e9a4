"""Front end: 100 x the share of the window's ``repro.service.fingerprint``
program spans served by the operand's memoized digest (``memo`` true: no
host copy and no sha1; traced runs only).  ``None`` where the spans carry no
``memo``, as on a program without the memo."""

from bench.program_spans import in_window


def value(run):
    found = [s.attrs for s in in_window(run) or () if s.name == "repro.service.fingerprint"]
    if not found or not all("memo" in f for f in found):
        return None
    return 100.0 * sum(bool(f["memo"]) for f in found) / len(found)
