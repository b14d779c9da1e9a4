"""The per-layer metrics read from the program's own spans: a traced run of
each cell reports them, an untraced run records no span at all."""
import time

import pytest

from bench import core
from bench.tests.conftest import run_tiny
from repro.utils import spans

PROGRAM = {"hpl_dense_n16384.fresh": ("ops_host_ms.fresh", "host_stall_ms.fresh"),
           "hpl_dense_n16384.implicit_steps": ("fingerprint_ms.service", "flush_host_ms.service",
                                               "cache_hit_rate.service", "host_stall_ms.service")}


def test_every_program_metric_is_listed_for_its_cell():
    bm = core.load_benchmark()
    for cell, names in PROGRAM.items():
        assert set(names) <= {m["name"] for m in core.metrics_of(bm, cell, "per_layer")}


@pytest.mark.parametrize("cell", sorted(PROGRAM))
def test_traced_run_reports_the_program_metrics(tiny_root, cell):
    metrics = run_tiny(tiny_root, cell, trace=True)["result"]["metrics"]
    values = {name: metrics[name]["value"] for name in PROGRAM[cell]}
    assert all(v >= 0 for v in values.values()), values
    for name in ("ops_host_ms.fresh", "fingerprint_ms.service", "flush_host_ms.service"):
        if name in values:
            assert values[name] > 0
    if "cache_hit_rate.service" in values:
        assert values["cache_hit_rate.service"] == 100.0


@pytest.mark.parametrize("cell", sorted(PROGRAM))
def test_untraced_run_records_no_span(tiny_root, cell):
    t0 = time.perf_counter()
    out = run_tiny(tiny_root, cell, trace=False)
    assert out["result"]["correct"]
    assert spans.recorded(t0, time.perf_counter()) == []
