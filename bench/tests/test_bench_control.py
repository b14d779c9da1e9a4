"""The control: the configuration's plain reference put in the program's
place.  At its stated precision (``highest``) it is sound; at the
configuration's control precision its run reads ``correct`` false under the
configuration's own limits, while the program's run reads true.  Sizes are
the smallest at which the references take their blocked paths."""
import pytest

from bench import core, system
from bench.tests.conftest import make_copy, run_tiny

SIZES = {"hpl_dense_n16384": {"n": 1024}}
CELLS = [w["name"] for w in core.load_benchmark()["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_copy(str(tmp_path_factory.mktemp("control")), SIZES)


def _reference(root, cell, precision):
    w = core.workload(core.load_benchmark(root), cell)
    config = core.data(root, "configs", w["config"])
    ref = core.module(root, "reference", config["reference"])
    return config, system.Reference(ref, precision)


@pytest.mark.parametrize("cell", CELLS)
def test_program_and_reference_pass_and_the_control_fails(root, cell):
    config, sound = _reference(root, cell, "highest")
    _, control = _reference(root, cell, config["control"])
    program = run_tiny(root, cell, seed=3)
    assert program["result"]["correct"], program["check_lines"]
    reference = run_tiny(root, cell, seed=3, solver=sound)
    assert reference["result"]["correct"], reference["check_lines"]
    out = run_tiny(root, cell, seed=3, solver=control)
    assert out["result"]["correct"] is False, out["check_lines"]
    limit = config["limits"]["relative_residual"]
    assert out["readings"]["relative_residual"] > 1.5 * limit
    assert program["readings"]["relative_residual"] < limit / 1.5
