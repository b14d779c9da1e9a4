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


def _band_system(n: int, bw: int, k: int, seed: int = 0):
    """A dominant band matrix, dense and row-aligned, and right-hand sides."""
    rng = np.random.default_rng(seed)
    i, j = np.indices((n, n))
    dense = np.where(np.abs(i - j) <= bw, rng.uniform(-1, 1, (n, n)), 0.0).astype(np.float32)
    dense[np.arange(n), np.arange(n)] = np.abs(dense).sum(axis=1) + 1
    t = np.arange(2 * bw + 1)
    cols = np.arange(n)[:, None] - bw + t[None, :]
    inside = (cols >= 0) & (cols < n)
    band = np.where(inside, dense[np.arange(n)[:, None], np.clip(cols, 0, n - 1)], 0.0).astype(np.float32)
    return dense, band, rng.normal(size=(n, k))


@pytest.mark.parametrize("n,bw,k,rows", [(40, 3, 2, 16), (33, 1, 1, 7), (12, 11, 3, 5), (64, 8, 1, 2048)])
def test_band_residuals_equal_the_dense_ones(n, bw, k, rows):
    dense, band, x = _band_system(n, bw, k)
    b = dense.astype(np.float64) @ x + np.random.default_rng(1).normal(scale=1e-3, size=x.shape)
    want = check.residuals(dense, b, x)
    got = check.residuals(band, b, x, bw=bw, rows=rows)
    assert got.shape == (k,) and np.all(want > 1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-10)
    vec = check.residuals(band, b[:, 0], x[:, 0], bw=bw, rows=rows)
    np.testing.assert_allclose(vec, want[:1], rtol=1e-10)


def test_band_residuals_read_a_row_sharded_operand_shard_by_shard():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    dense, band, x = _band_system(64, 4, 2, seed=2)
    b = dense.astype(np.float64) @ x + 1e-3
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("chips",))
    sharded = jax.device_put(band, NamedSharding(mesh, P("chips", None)))
    assert len(sharded.addressable_shards) == 4
    np.testing.assert_allclose(check.residuals(sharded, b, x, bw=4, rows=5),
                               check.residuals(dense, b, x), rtol=1e-10)
