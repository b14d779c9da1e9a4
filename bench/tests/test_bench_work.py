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


def _brute_band(n: int, bw: int, k: int) -> tuple[float, float]:
    """FLOPs of band LU and of the two sweeps, counted pivot by pivot and
    row by row."""
    factor = solve = 0
    for p in range(n):
        r = min(bw, n - 1 - p)  # rows below the pivot, and columns right of it
        factor += r + 2 * r * r
    for i in range(n):
        solve += 2 * k * (min(bw, i) + min(bw, n - 1 - i))
    return float(factor), float(solve)


@pytest.mark.parametrize("n,bw,k", [(1, 1, 1), (7, 3, 3), (9, 1, 2), (50, 7, 1), (5, 9, 4), (64, 63, 2)])
def test_band_counts_against_brute_force(n, bw, k):
    factor, solve = _brute_band(n, bw, k)
    lu = core.module(core.ROOT, "work", "banded_lu").count(n=n, bw=bw, k=0, itemsize=4)
    sv = core.module(core.ROOT, "work", "banded_solve").count(n=n, bw=bw, k=k, itemsize=4)
    assert lu == pytest.approx((factor, 2 * 4 * n * (2 * bw + 1)), rel=1e-12)
    assert sv == pytest.approx((solve, 4 * (n * (2 * bw + 1) + 2 * n * k)), rel=1e-12)


def _run(devices: int, call_devices: int):
    """A synthetic run of ``devices`` chips whose window of 2 s made one
    dense factor call spanning ``call_devices`` chips, with 0.5 s of busy
    time inside its ``factor`` span."""
    run = core.Run.__new__(core.Run)
    run.root, run.device_kind, run.notes = core.ROOT, "TPU v5 lite", []
    run.devices = [object()] * devices
    run.window = (10.0, 12.0)
    run.reduced = {"busy_in_s": {"factor": 0.5}}
    run.calls = [{"span": "factor", "op": "factor", "structure": "dense", "n": 4096, "bw": 0, "k": 0,
                  "dtype": "float32", "devices": call_devices, "backend": "x"}]
    return run


def test_one_chip_roofline_and_mfu_keep_their_formula():
    run = _run(1, 1)
    flops, nbytes = 2.0 * 4096**3 / 3.0, 2.0 * 4 * 4096 * 4096
    least = max(flops / 197e12, nbytes / 819e9)
    assert core.roofline(run, "factor", "factor", "dense", "r") == 100.0 * least / 0.5
    assert core.mfu(run, ("factor",), "m") == 100.0 * flops / 2.0 / 197e12


def test_a_call_over_four_chips_reads_a_quarter():
    one, four = _run(1, 1), _run(4, 4)
    assert core.roofline(four, "factor", "factor", "dense", "r") == pytest.approx(
        core.roofline(one, "factor", "factor", "dense", "r") / 4, rel=1e-12)
    assert core.mfu(four, ("factor",), "m") == pytest.approx(core.mfu(one, ("factor",), "m") / 4, rel=1e-12)
