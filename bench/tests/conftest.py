"""Fixtures for the benchmark's own tests: a copy of the benchmark with its
configurations cut to sizes the CPU runs in a second or two."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY = {"hpl_dense_n16384": {"n": 256}}
# made-up peaks so that per-layer readers run on the CPU; no CPU number is a
# device metric
CPU_PEAKS = {"flops_bf16": 1e11, "hbm_bytes_s": 1e10}


def make_copy(dest: str, sizes: dict = TINY) -> str:
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    for name, changes in sizes.items():
        path = os.path.join(dest, "bench", "configs", name + ".json")
        with open(path) as f:
            cfg = json.load(f)
        cfg.update(changes)
        with open(path, "w") as f:
            json.dump(cfg, f)
    path = os.path.join(dest, "bench", "peaks.json")
    with open(path) as f:
        table = json.load(f)
    table["peaks"]["cpu"] = CPU_PEAKS
    with open(path, "w") as f:
        json.dump(table, f)
    return dest


@pytest.fixture
def tiny_root(tmp_path):
    return make_copy(str(tmp_path))


def run_tiny(root: str, cell: str, *, seed: int = 2**31 + 17, seconds: float = 0.3,
             trace: bool = False, solver=None) -> dict:
    from bench.run import run_cell

    return run_cell(cell, seed, seconds, trace, root=root, solver=solver, require_chip=False)
