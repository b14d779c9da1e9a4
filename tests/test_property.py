"""Hypothesis property tests on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import (
    banded_lu_solve,
    blocked_lu,
    ebv_lu,
    equalized_pairing,
    fold_index,
    linear_solve,
    lu_solve,
    pair_lengths,
    reconstruct,
    to_banded,
)
from repro.core.blocked import ebv_folded_owners

SETTINGS = dict(max_examples=25, deadline=None)


def _dd_matrix(draw, n):
    """Diagonally dominant matrix from sampled entries (paper contract)."""
    elems = draw(
        st.lists(
            st.floats(-1, 1, allow_nan=False, width=32),
            min_size=n * n, max_size=n * n,
        )
    )
    a = np.array(elems, np.float32).reshape(n, n)
    np.fill_diagonal(a, np.abs(a).sum(1) + 1.0)
    return jnp.asarray(a)


@settings(**SETTINGS)
@given(st.data(), st.integers(2, 24))
def test_lu_reconstructs_input(data, n):
    a = _dd_matrix(data.draw, n)
    rel = float(jnp.abs(reconstruct(ebv_lu(a)) - a).max()) / max(float(jnp.abs(a).max()), 1e-6)
    assert rel < 1e-4


@settings(**SETTINGS)
@given(st.data(), st.integers(2, 24), st.integers(1, 24))
def test_solve_residual_bounded(data, n, block):
    a = _dd_matrix(data.draw, n)
    b = jnp.asarray(
        np.array(data.draw(st.lists(st.floats(-1, 1, width=32), min_size=n, max_size=n)), np.float32)
    )
    x = linear_solve(a, b, method="ebv_blocked", block=min(block, n))
    denom = max(float(jnp.linalg.norm(b)), 1e-6)
    assert float(jnp.linalg.norm(a @ x - b)) / denom < 1e-4


@settings(**SETTINGS)
@given(st.integers(2, 4096))
def test_equalization_invariants(n):
    units = equalized_pairing(n)
    covered = sorted(r for u in units for r in u)
    assert covered == list(range(n - 1))
    for u, l in zip(units, pair_lengths(n)):
        if len(u) == 2:
            assert l == n


@settings(**SETTINGS)
@given(st.integers(1, 2048))
def test_fold_index_bijection(count):
    seen = {int(fold_index(i, count)) for i in range(count)}
    assert seen == set(range(count))


@settings(**SETTINGS)
@given(st.integers(1, 32), st.integers(1, 8))
def test_folded_owner_work_equalized(pairs_per_dev, p):
    nb = 2 * pairs_per_dev * p
    owners = ebv_folded_owners(nb, p)
    work = [0.0] * p
    for k, o in enumerate(owners):
        work[o] += nb - k
    assert max(work) == min(work)


@settings(**SETTINGS)
@given(st.data(), st.integers(4, 24), st.integers(1, 3))
def test_banded_equals_dense_solve(data, n, bw):
    a = np.array(_dd_matrix(data.draw, n))
    i, j = np.indices(a.shape)
    a[np.abs(i - j) > bw] = 0.0
    np.fill_diagonal(a, np.abs(a).sum(1) + 1.0)
    a = jnp.asarray(a)
    b = jnp.asarray(
        np.array(data.draw(st.lists(st.floats(-1, 1, width=32), min_size=n, max_size=n)), np.float32)
    )
    xd = lu_solve(blocked_lu(a, block=min(8, n)), b)
    xb = banded_lu_solve(to_banded(a, bw), b, bw=bw)
    np.testing.assert_allclose(np.asarray(xb), np.asarray(xd), atol=1e-3, rtol=1e-3)
