"""The control: the configuration's plain reference put in the program's
place.  At its stated precision (``highest``) it is sound; at the
configuration's control precision its run reads ``correct`` false under the
configuration's own limits, while the program's run reads true.  Sizes are
the smallest at which the references take their blocked paths: the dense
configuration's here, any other's its ``cpu_control_sizes``, else its
``cpu_sizes``."""
import pytest

from bench import core, system
from bench.tests.conftest import ROOT, make_copy, run_tiny

BM = core.load_benchmark()
CELLS = [w["name"] for w in BM["workloads"]]
SIZES = {"hpl_dense_n16384": {"n": 1024}}


def control_sizes() -> dict:
    out = {}
    for c in BM["configs"]:
        cfg = core.data(ROOT, "configs", c["name"])
        out[c["name"]] = SIZES.get(c["name"], cfg.get("cpu_control_sizes", cfg.get("cpu_sizes")))
    return out


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_copy(str(tmp_path_factory.mktemp("control")), control_sizes())


def reference(root, cell, precision):
    w = core.workload(core.load_benchmark(root), cell)
    config = core.data(root, "configs", w["config"])
    ref = core.module(root, "reference", config["reference"])
    return config, system.Reference(ref, precision)


def separates(root, cell):
    """The program and the sound reference read correct, the control not,
    each with room to its limit."""
    config, sound = reference(root, cell, "highest")
    _, control = reference(root, cell, config["control"])
    program = run_tiny(root, cell, seed=3)
    assert program["result"]["correct"], program["check_lines"]
    out = run_tiny(root, cell, seed=3, solver=sound)
    assert out["result"]["correct"], out["check_lines"]
    out = run_tiny(root, cell, seed=3, solver=control)
    assert out["result"]["correct"] is False, out["check_lines"]
    limit = config["limits"]["relative_residual"]
    assert out["readings"]["relative_residual"] > 1.5 * limit
    assert program["readings"]["relative_residual"] < limit / 1.5


@pytest.mark.parametrize("cell", CELLS)
def test_program_and_reference_pass_and_the_control_fails(root, cell):
    separates(root, cell)
