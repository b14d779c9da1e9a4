"""A configuration, a traffic mix and a per-layer metric added as new files
are found by name, with no edit to a file the benchmark already has; and a
cell runs end to end on the CPU.  A banded configuration with a cell on one
chip and a cell on four is added the same way, by new files and new list
entries alone."""
import hashlib
import json
import os

import pytest

from bench import core
from bench.tests.conftest import ROOT, cut_to_cpu, make_copy, run_tiny
from bench.tests.test_bench_control import separates
from bench.tests.test_bench_faults import FAULTS, applies


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


def runs_on_the_cpu(root, cell, trace) -> dict:
    """Runs ``cell`` of the benchmark at ``root`` once and checks the line:
    correct, every metric of the cell there and nothing compiled in the
    window."""
    out = run_tiny(root, cell, trace=trace)
    res = out["result"]
    assert res["correct"], out["check_lines"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    assert "compiles_in_window: jaxpr_to_mlir_module_duration=0 backend_compile_duration=0" in out["notes"]
    bm = core.load_benchmark(root)
    assert res["device"]["count"] == core.workload(bm, cell)["chips"]
    section = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in core.metrics_of(bm, cell, section)}
    assert set(res["metrics"]) == want
    for m in res["metrics"].values():
        assert m["value"] == m["value"] and m["value"] >= 0
    if trace:
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]
    return out


@pytest.mark.parametrize("cell", [w["name"] for w in core.load_benchmark()["workloads"]])
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_on_the_cpu(tiny_root, cell, trace):
    runs_on_the_cpu(tiny_root, cell, trace)


def test_a_configuration_without_cpu_sizes_fails_by_name(tiny_root):
    """Cut to the CPU as every copy's configurations are, a configuration
    that gives no ``cpu_sizes`` fails its cell by name and is never run."""
    with open(os.path.join(ROOT, "bench", "configs", "hpl_dense_n16384.json")) as f:
        cfg = json.load(f)
    del cfg["cpu_sizes"]
    cfg["name"] = "dense_unsized"
    path = os.path.join(tiny_root, "bench", "configs", "dense_unsized.json")
    with open(path, "w") as f:
        json.dump(cfg, f)
    cut_to_cpu(path)
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["workloads"].append({"name": "dense_unsized.fresh", "config": "dense_unsized", "traffic": "fresh",
                            "chips": 1, "why": "test"})
    with open(os.path.join(tiny_root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    with pytest.raises(pytest.fail.Exception, match="configuration dense_unsized has no cpu_sizes"):
        run_tiny(tiny_root, "dense_unsized.fresh")


BAND_GENERATOR = '''"""Dominant band operators, held row-aligned: entries uniform in [-1, 1]
inside the band, each diagonal entry its row's absolute off-diagonal sum
plus one.  Right-hand sides are standard normal."""
import jax
import jax.numpy as jnp

STRUCTURE = "banded"


def bw(config):
    return config["bw"]


def operator(key, config):
    n, b = config["n"], config["bw"]
    a = jax.random.uniform(key, (n, 2 * b + 1), jnp.float32, minval=-1.0, maxval=1.0)
    cols = jnp.arange(n)[:, None] - b + jnp.arange(2 * b + 1)[None, :]
    a = jnp.where((cols >= 0) & (cols < n), a, 0.0)
    return a.at[:, b].set(jnp.sum(jnp.abs(a), axis=1) - jnp.abs(a[:, b]) + 1.0)


def rhs(key, config):
    return jax.random.normal(key, (config["n"],), jnp.float32)
'''
BAND_READER = ("from bench.core import roofline\n\n\n"
               "def value(run):\n    return roofline(run, {span!r}, {op!r}, 'banded', {name!r})\n")
BAND_CELLS = {"band_small.band_fresh": 1, "band_small.spike4_fresh": 4}
BAND_ROOFLINES = {"banded_factor_roofline": ("factor", "factor"), "banded_solve_roofline": ("solve", "solve")}


def add_band_cells(root: str):
    """A banded configuration with a one-chip and a four-chip cell, under
    two traffic names, added by new files and new list entries alone."""
    bench = os.path.join(root, "bench")
    files = {
        "generators/dominant_band.py": BAND_GENERATOR,
        "configs/band_small.json": json.dumps({
            "name": "band_small", "source": "test", "generator": "dominant_band", "reference": "banded_lu",
            "n": 256, "bw": 8, "dtype": "float32", "control": "bfloat16", "reduced": {},
            "limits": {"relative_residual": 5e-6}, "cpu_sizes": {}}),
        "traffic/band_fresh.json": json.dumps({"loop": "fresh", "check_sample": 2}),
        "traffic/spike4_fresh.json": json.dumps({"loop": "fresh", "check_sample": 2}),
    }
    for name, (span, op) in BAND_ROOFLINES.items():
        files[f"metrics/{name}.py"] = BAND_READER.format(span=span, op=op, name=name)
    for rel, text in files.items():
        assert not os.path.exists(os.path.join(bench, rel)), rel
        with open(os.path.join(bench, rel), "w") as f:
            f.write(text)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "band_small", "source": "test", "file": "bench/configs/band_small.json",
                          "reduced": [], "why": "test"})
    for cell, chips in BAND_CELLS.items():
        bm["workloads"].append({"name": cell, "config": "band_small", "traffic": cell.split(".")[1],
                                "chips": chips, "why": "test"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if m["name"] in ("time_to_solution_s", "solution_mfu.fresh", "device_idle.fresh"):
            m["workloads"] += list(BAND_CELLS)
    for name, (span, _) in BAND_ROOFLINES.items():
        bm["per_layer"].append({"name": name, "unit": "%", "better": "higher", "source": "device_trace",
                                "layer": f"{span} kernels", "moves": "time_to_solution_s",
                                "workloads": list(BAND_CELLS)})
    with open(path, "w") as f:
        json.dump(bm, f)


@pytest.fixture(scope="module")
def band_root(tmp_path_factory):
    root = make_copy(str(tmp_path_factory.mktemp("band")))
    before = _digests(root)
    add_band_cells(root)
    assert {k: v for k, v in _digests(root).items() if k in before} == before
    yield root
    assert {k: v for k, v in _digests(root).items() if k in before} == before


@pytest.mark.parametrize("cell", sorted(BAND_CELLS))
@pytest.mark.parametrize("trace", [False, True])
def test_band_cell_added_by_new_files_runs_on_the_cpu(band_root, cell, trace):
    out = runs_on_the_cpu(band_root, cell, trace)
    backend = "spike" if BAND_CELLS[cell] > 1 else ""
    served = next(line for line in out["notes"] if line.startswith("served"))
    assert "factor:banded=" + backend in served and "solve:banded=" + backend in served, served
    if trace:
        for name in BAND_ROOFLINES:
            assert 0 < out["result"]["metrics"][name]["value"] <= 100


def test_four_chip_band_cell_runs_with_the_control(band_root):
    separates(band_root, "band_small.spike4_fresh")


@pytest.mark.parametrize("cell,fault", [(c, f) for c in sorted(BAND_CELLS) for f in FAULTS
                                        if f != "half_batch" and (f != "exchange_left_out" or BAND_CELLS[c] > 1)])
def test_band_cell_with_a_fault_is_not_correct(band_root, monkeypatch, cell, fault):
    from repro.kernels import ops

    assert applies(core.load_benchmark(band_root), band_root, cell, fault)
    FAULTS[fault](ops, monkeypatch)
    out = run_tiny(band_root, cell)
    assert out["result"]["correct"] is False, out["check_lines"]
