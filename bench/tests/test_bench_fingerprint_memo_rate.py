"""``fingerprint_memo_rate.service``: the share of the window's operand
fingerprints that the program's memo served, read from its spans."""
import time
import types

import pytest

from bench import core
from bench.tests.conftest import run_tiny
from repro.utils import spans

CELL = "hpl_dense_n16384.implicit_steps"
NAME = "fingerprint_memo_rate.service"


def _read(root, attrs: list[dict]):
    """The reader's value over a window holding one fingerprint span per
    entry of ``attrs``."""
    t0 = time.perf_counter()
    with spans.recording():
        for a in attrs:
            with spans.span("repro.service.fingerprint", **a):
                pass
    run = types.SimpleNamespace(window=(t0, time.perf_counter()))
    return core.module(root, "metrics", NAME).value(run)


def test_listed_for_the_service_cell_only():
    bm = core.load_benchmark()
    (m,) = [m for m in bm["per_layer"] if m["name"] == NAME]
    assert m["workloads"] == [CELL] and m["moves"] == "solves_per_s"
    assert m["layer"] == "front end" and m["source"] == "program_span"


@pytest.mark.parametrize("attrs, expected", [
    ([{"memo": True}] * 3, 100.0),
    ([{"memo": False}, {"memo": True}, {"memo": True}, {"memo": False}], 50.0),
    ([{"memo": False}], 0.0),
    ([{"bytes": 64}] * 2, None),  # a program without the memo
    ([], None),  # no fingerprint in the window
], ids=["all_memo", "half", "none_memo", "no_memo_attr", "no_spans"])
def test_reads_the_memo_attribute(attrs, expected):
    assert _read(core.ROOT, attrs) == expected


def test_traced_service_run_reads_100(tiny_root):
    """Set-up's warm tick hashes each operator once; every request of the
    window resubmits the same array."""
    metrics = run_tiny(tiny_root, CELL, trace=True)["result"]["metrics"]
    assert metrics[NAME]["value"] == 100.0
