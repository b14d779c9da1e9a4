"""Every name in BENCHMARK.json resolves to its files, and the file keeps
the benchmark contract's shape."""
import json
import os
import re

import pytest

from bench import core

BM = core.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
CELLS = [w["name"] for w in BM["workloads"]]


def test_top_level_shape():
    assert set(BM) == KEYS
    assert BM["command"] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench"]
    assert isinstance(BM["run_seconds"], int) and 1 <= BM["run_seconds"] <= 51
    assert len(json.dumps(BM)) <= 64 * 1024


def test_names_units_and_keys():
    seen = set()
    for section, keys in (("configs", {"name", "source", "file", "reduced", "why"}),
                          ("workloads", {"name", "config", "traffic", "chips", "why"}),
                          ("end_to_end", {"name", "unit", "better", "bound", "source"}),
                          ("per_layer", {"name", "unit", "better", "source", "layer", "moves"})):
        for entry in BM[section]:
            assert set(entry) - {"workloads"} == keys, entry
            assert NAME.match(entry["name"]), entry["name"]
            assert (section, entry["name"]) not in seen
            seen.add((section, entry["name"]))
            if "unit" in entry:
                assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
            for text in ("why", "layer", "source"):
                if text in entry:
                    assert 1 <= len(entry[text]) <= 200 and "\n" not in entry[text]
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BM["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    w = core.workload(BM, cell)
    assert w["chips"] in (1, 4) and NAME.match(w["traffic"]) and NAME.match(w["config"])
    config = core.data(core.ROOT, "configs", w["config"])
    traffic = core.data(core.ROOT, "traffic", w["traffic"])
    gen = core.module(core.ROOT, "generators", config["generator"])
    assert gen.STRUCTURE in ("dense", "banded")
    if gen.STRUCTURE == "banded":
        assert gen.bw(config) >= 1
    loop = core.module(core.ROOT, "loops", traffic["loop"])
    for fn in ("setup", "window", "release", "check_answers"):
        assert callable(getattr(loop, fn))
    ref = core.module(core.ROOT, "reference", config["reference"])
    assert callable(ref.factor) and callable(ref.solve)
    assert config["limits"] and all(v > 0 for v in config["limits"].values())
    for structure_op in ("lu", "solve"):
        assert os.path.isfile(core.part_path(core.ROOT, "work", f"{gen.STRUCTURE}_{structure_op}", ".py"))
        core.module(core.ROOT, "work", f"{gen.STRUCTURE}_{structure_op}")


def test_each_pair_of_config_and_traffic_is_one_cell():
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs)), pairs


def test_four_chip_cells_within_quota():
    """At most half of the cells, rounded down, take four chips, or one."""
    four = [w["name"] for w in BM["workloads"] if w["chips"] == 4]
    assert len(four) <= len(CELLS) // 2 or len(four) == 1, four


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_the_metrics_it_must(cell):
    e2e = [m["name"] for m in core.metrics_of(BM, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = core.metrics_of(BM, cell, "per_layer")
    assert layer
    for m in layer:
        assert m["moves"] in e2e


def test_every_metric_has_a_reader():
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert callable(core.module(core.ROOT, "metrics", m["name"]).value)
        for cell in m.get("workloads", []):
            core.workload(BM, cell)


def test_every_config_file_lies_under_paths():
    files = set()
    for c in BM["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert os.path.isfile(os.path.join(core.ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(core.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) == set(cfg["reduced"]) and c["source"] == cfg["source"]
        files.add(c["file"])
        assert any(w["config"] == c["name"] for w in BM["workloads"])
    assert len(files) == len(BM["configs"])


def test_peaks_are_keyed_by_device_kind():
    assert core.peaks(core.ROOT, "TPU v5 lite") == {"flops_bf16": 197e12, "hbm_bytes_s": 819e9}
    with pytest.raises(KeyError):
        core.peaks(core.ROOT, "no such chip")
