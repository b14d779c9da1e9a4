"""A configuration, a traffic mix and a per-layer metric added as new files
are found by name, with no edit to a file the benchmark already has; and a
cell runs end to end on the CPU."""
import hashlib
import json
import os

import pytest

from bench import core
from bench.tests.conftest import run_tiny


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for name in files:
            path = os.path.join(d, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha1(f.read()).hexdigest()
    return out


def test_new_files_are_picked_up_without_edits(tiny_root):
    before = _digests(tiny_root)
    bench = os.path.join(tiny_root, "bench")
    with open(os.path.join(bench, "configs", "hpl_dense_n16384.json")) as f:
        cfg = json.load(f)
    cfg.update(name="dense_small", n=128)
    with open(os.path.join(bench, "configs", "dense_small.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "service_two.json"), "w") as f:
        json.dump({"loop": "service", "operators": 2, "requests_per_tick": 3, "zipf_s": 1.0,
                   "cache_entries": 2, "pattern_seed": 0, "check_sample": 6}, f)
    with open(os.path.join(bench, "metrics", "ticks_per_window.py"), "w") as f:
        f.write("def value(run):\n    return len(run.spans_named('flush')) / run.window_s\n")
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "dense_small", "source": "https://www.netlib.org/benchmark/hpl/",
                          "file": "bench/configs/dense_small.json", "reduced": ["n"], "why": "test"})
    bm["workloads"].append({"name": "dense_small.service_two", "config": "dense_small",
                            "traffic": "service_two", "chips": 1, "why": "test"})
    bm["end_to_end"][1]["workloads"].append("dense_small.service_two")
    bm["end_to_end"][2]["workloads"].append("dense_small.service_two")
    bm["per_layer"].append({"name": "ticks_per_window", "unit": "1/s", "better": "higher",
                            "source": "host_clock", "layer": "front end", "moves": "solves_per_s",
                            "workloads": ["dense_small.service_two"]})
    with open(os.path.join(tiny_root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)

    out = run_tiny(tiny_root, "dense_small.service_two", trace=True)
    assert out["result"]["correct"], out["check_lines"]
    assert out["result"]["metrics"]["ticks_per_window"]["value"] > 0
    assert out["result"]["attempted"] % 3 == 0
    after = _digests(tiny_root)
    assert {k: v for k, v in after.items() if k in before} == before


@pytest.mark.parametrize("cell", [w["name"] for w in core.load_benchmark()["workloads"]])
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_on_the_cpu(tiny_root, cell, trace):
    out = run_tiny(tiny_root, cell, trace=trace)
    res = out["result"]
    assert res["correct"], out["check_lines"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    assert "compiles_in_window: jaxpr_to_mlir_module_duration=0 backend_compile_duration=0" in out["notes"]
    bm = core.load_benchmark(tiny_root)
    section = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in core.metrics_of(bm, cell, section)}
    assert set(res["metrics"]) == want
    for m in res["metrics"].values():
        assert m["value"] == m["value"] and m["value"] >= 0
    if trace:
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]
