"""The least-work counts against hand-computed values."""
import pytest

from bench import core

CASES = [
    # op, n, bw, k, flops, bytes (f32)
    ("dense_lu", 6, 0, 0, 2 * 216 / 3, 2 * 4 * 36),
    ("dense_solve", 6, 0, 3, 2 * 36 * 3, 4 * 36 + 2 * 4 * 6 * 3),
    ("dense_solve", 10, 0, 1, 200, 4 * 100 + 2 * 4 * 10),
    ("dense_lu", 9, 0, 2, 2 * 729 / 3, 2 * 4 * 81),
]


@pytest.mark.parametrize("op,n,bw,k,flops,nbytes", CASES)
def test_work_counts(op, n, bw, k, flops, nbytes):
    got = core.module(core.ROOT, "work", op).count(n=n, bw=bw, k=k, itemsize=4)
    assert got == pytest.approx((flops, nbytes), rel=1e-12)


def test_run_work_names_the_op_from_the_dispatch():
    run = core.Run.__new__(core.Run)
    run.root = core.ROOT
    call = {"op": "solve", "structure": "dense", "n": 12, "bw": 0, "k": 2, "dtype": "float32"}
    assert run.work(call) == pytest.approx((2 * 144 * 2, 4 * 144 + 2 * 4 * 12 * 2))
    call = {"op": "factor", "structure": "dense", "n": 6, "bw": 0, "k": 0, "dtype": "float32"}
    assert run.work(call) == pytest.approx((144.0, 288.0))
