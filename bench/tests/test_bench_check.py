"""The comparison that decides ``correct``, and the widths a service mix
sends."""
import numpy as np
import pytest

from bench import check, core


def test_residuals_of_an_exact_answer_are_rounding():
    rng = np.random.default_rng(0)
    a = rng.uniform(-1, 1, (40, 40)).astype(np.float32) + 40 * np.eye(40, dtype=np.float32)
    x = rng.normal(size=(40, 3))
    b = a.astype(np.float64) @ x
    got = check.residuals(a, b, x, rows=16)
    assert got.shape == (3,) and np.all(got < 1e-14)


def test_residuals_of_a_zero_or_nan_answer_fail():
    a = np.eye(8, dtype=np.float32)
    b = np.ones(8)
    assert check.residuals(a, b, np.zeros(8))[0] == pytest.approx(1.0)
    ok, lines = check.judge({"relative_residual": float(check.residuals(a, b, np.full(8, np.nan))[0])},
                            {"relative_residual": 1e-6})
    assert not ok and lines == ["relative_residual=nan limit=1e-06 FAIL"]


def test_judge_names_every_limit_and_a_missing_reading_fails():
    ok, lines = check.judge({"relative_residual": 1e-7}, {"relative_residual": 5e-6})
    assert ok and lines == ["relative_residual=1e-07 limit=5e-06 ok"]
    ok, _ = check.judge({}, {"relative_residual": 5e-6})
    assert not ok


@pytest.mark.parametrize("ranks,requests,s,want", [
    (4, 4, 0.0, [1, 1, 1, 1]),
    (4, 8, 1.1, [4, 2, 1, 1]),
    (2, 5, 1.0, [3, 2]),
])
def test_service_widths(ranks, requests, s, want):
    loop = core.module(core.ROOT, "loops", "service")
    assert loop.zipf_counts(ranks, requests, s) == want


def test_implicit_steps_sends_one_request_per_operator():
    t = core.data(core.ROOT, "traffic", "implicit_steps")
    loop = core.module(core.ROOT, "loops", t["loop"])
    assert loop.zipf_counts(t["operators"], t["requests_per_tick"], t["zipf_s"]) == [1] * t["operators"]
