"""Fixtures for the benchmark's own tests: a copy of the benchmark with each
configuration cut to the sizes its own ``cpu_sizes`` gives, at which the CPU
runs a cell in a second or two.  The chip's runs never read ``cpu_sizes``.
The CPU backend is given 8 devices before JAX starts, as the repository's
``tests/conftest.py`` gives it, so that cells on four chips run here."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
    ).strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# made-up peaks so that per-layer readers run on the CPU; no CPU number is a
# device metric
CPU_PEAKS = {"flops_bf16": 1e11, "hbm_bytes_s": 1e10}


def cut_to_cpu(path: str, sizes: dict | None = None):
    """Cut the configuration at ``path`` to ``sizes``, or else to its own
    ``cpu_sizes``; one with neither stays as it is, and ``run_tiny`` runs
    none of its cells."""
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(sizes if sizes is not None else cfg.get("cpu_sizes", {}))
    with open(path, "w") as f:
        json.dump(cfg, f)


def make_copy(dest: str, sizes: dict | None = None) -> str:
    """A copy of the benchmark under ``dest``, every configuration cut to the
    CPU; ``sizes`` (by configuration name) overrides ``cpu_sizes``."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    configs = os.path.join(dest, "bench", "configs")
    for name in os.listdir(configs):
        if name.endswith(".json"):
            cut_to_cpu(os.path.join(configs, name), (sizes or {}).get(name[: -len(".json")]))
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
    from bench import core
    from bench.run import run_cell

    name = core.workload(core.load_benchmark(root), cell)["config"]
    if "cpu_sizes" not in core.data(root, "configs", name):
        pytest.fail(f"configuration {name} has no cpu_sizes: its cells would run here at full size")
    return run_cell(cell, seed, seconds, trace, root=root, solver=solver, require_chip=False)
