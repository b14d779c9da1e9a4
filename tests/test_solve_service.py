"""Solve service tests: factorization cache hit/miss/evict, coalesced
multi-RHS parity, factor-once/solve-many dispatch accounting, and the
fingerprint memo of live ``jax.Array`` operands."""
import gc
import hashlib
import types
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_diagonally_dominant
from repro.core.banded import make_banded_dd
from repro.kernels import ops as kops
from repro.serve import solve_service
from repro.serve.solve_service import SolveService, fingerprint


@pytest.fixture()
def dense_system():
    n = 96
    a = make_diagonally_dominant(jax.random.PRNGKey(0), n)
    bs = [jax.random.normal(jax.random.PRNGKey(100 + i), (n,)) for i in range(8)]
    return a, bs


def test_factor_once_solve_many_coalesced(dense_system):
    """Acceptance: 1 matrix x 64 RHS arriving as separate requests triggers
    exactly one factorization dispatch plus ONE coalesced solve dispatch,
    bitwise-identical per request to per-request solves."""
    a, _ = dense_system
    n = a.shape[0]
    bs = [jax.random.normal(jax.random.PRNGKey(i), (n,)) for i in range(64)]
    svc = SolveService()
    tickets = [svc.submit(a, b) for b in bs]
    assert svc.pending() == 64
    results = svc.flush()
    st = svc.stats
    assert st.factor_dispatches == 1
    assert st.solve_dispatches == 1  # all 64 RHS in one stacked dispatch
    assert st.cache_misses == 1 and st.cache_hits == 63
    assert st.coalesced_requests == 64
    assert st.solved_columns == 64

    factors = kops.lu(a)
    for t, b in zip(tickets, bs):
        ref = kops.lu_solve(factors, b)
        np.testing.assert_array_equal(np.asarray(results[t]), np.asarray(ref))


def test_cache_hit_miss_evict(dense_system):
    a, bs = dense_system
    n = a.shape[0]
    a2 = make_diagonally_dominant(jax.random.PRNGKey(1), n)
    a3 = make_diagonally_dominant(jax.random.PRNGKey(2), n)
    svc = SolveService(cache_entries=2)
    svc.solve(a, bs[0])
    assert (svc.stats.cache_misses, svc.stats.cache_hits) == (1, 0)
    svc.solve(a, bs[1])  # hit
    assert (svc.stats.cache_misses, svc.stats.cache_hits) == (1, 1)
    svc.solve(a2, bs[2])  # miss, cache = {a, a2}
    svc.solve(a3, bs[3])  # miss, evicts a (LRU)
    assert svc.stats.cache_evictions == 1
    svc.solve(a, bs[4])  # miss again: a was evicted
    assert svc.stats.cache_misses == 4
    assert svc.stats.factor_dispatches == 4
    assert svc.stats.hit_rate == pytest.approx(1 / 5)


def test_mixed_matrices_grouped(dense_system):
    """Interleaved requests against two matrices coalesce into one solve
    dispatch per matrix, not per request."""
    a, bs = dense_system
    a2 = make_diagonally_dominant(jax.random.PRNGKey(7), a.shape[0])
    svc = SolveService()
    tickets = [
        svc.submit(a, bs[0]), svc.submit(a2, bs[1]),
        svc.submit(a, bs[2]), svc.submit(a2, bs[3]),
        svc.submit(a, bs[4]),
    ]
    results = svc.flush()
    assert svc.stats.factor_dispatches == 2
    assert svc.stats.solve_dispatches == 2
    f1, f2 = kops.lu(a), kops.lu(a2)
    for t, (m, b) in zip(tickets, [(f1, bs[0]), (f2, bs[1]), (f1, bs[2]), (f2, bs[3]), (f1, bs[4])]):
        np.testing.assert_array_equal(
            np.asarray(results[t]), np.asarray(kops.lu_solve(m, b))
        )


def test_matrix_rhs_requests_coalesce(dense_system):
    """(n, m) block RHS and (n,) vector RHS against one matrix stack into a
    single wide dispatch and split back with original shapes."""
    a, bs = dense_system
    n = a.shape[0]
    blk = jax.random.normal(jax.random.PRNGKey(50), (n, 5))
    svc = SolveService()
    t1 = svc.submit(a, bs[0])
    t2 = svc.submit(a, blk)
    out = svc.flush()
    assert out[t1].shape == (n,)
    assert out[t2].shape == (n, 5)
    assert svc.stats.solve_dispatches == 1
    assert svc.stats.solved_columns == 6
    factors = kops.lu(a)
    np.testing.assert_array_equal(np.asarray(out[t2]), np.asarray(kops.lu_solve(factors, blk)))


def test_banded_service_parity():
    n, bw = 128, 3
    arow = make_banded_dd(jax.random.PRNGKey(3), n, bw)
    bs = [jax.random.normal(jax.random.PRNGKey(200 + i), (n,)) for i in range(6)]
    svc = SolveService()
    tickets = [svc.submit(arow, b, bw=bw) for b in bs]
    results = svc.flush()
    assert svc.stats.factor_dispatches == 1
    assert svc.stats.solve_dispatches == 1
    lub = kops.banded_lu(arow, bw=bw)
    # per-request reference through the SAME multi-RHS-capable backend the
    # coalesced dispatch used (the scalar backend is vector-only and is
    # capability-filtered out of stacked dispatches)
    for t, b in zip(tickets, bs):
        ref = kops.banded_solve(lub, b[:, None], bw=bw)[:, 0]
        np.testing.assert_array_equal(np.asarray(results[t]), np.asarray(ref))


def test_fingerprint_sensitivity():
    a = np.eye(8, dtype=np.float32)
    assert fingerprint(a) == fingerprint(a.copy())
    b = a.copy()
    b[3, 4] = 1e-7
    assert fingerprint(a) != fingerprint(b)
    assert fingerprint(a) != fingerprint(a.astype(np.float64))
    assert fingerprint(a, bw=0) != fingerprint(a, bw=2)


def _sha1(a, bw: int = 0) -> str:
    """The fingerprint's digest, computed here from its definition."""
    arr = np.asarray(a)
    h = hashlib.sha1(str((arr.shape, arr.dtype.str, bw)).encode())
    h.update(arr.tobytes())
    return h.hexdigest()


@pytest.fixture()
def sha1_calls(monkeypatch):
    """Counts the service's sha1 hashers (one per hashed operand)."""
    calls = []

    def sha1(*args):
        calls.append(args)
        return hashlib.sha1(*args)

    monkeypatch.setattr(solve_service, "hashlib", types.SimpleNamespace(sha1=sha1))
    return calls


def _same(a):
    return a


def _copy(a):
    return jnp.array(a, copy=True)


def _mutated(a):
    a[0, 1] += 0.5  # in place: still diagonally dominant
    return a


@pytest.mark.parametrize(
    "as_operand, second, same_digest, hashes, memo_hits",
    [(jnp.asarray, _same, True, 1, 1),
     (jnp.asarray, _copy, True, 2, 0),
     (np.array, _mutated, False, 2, 0)],
    ids=["same_array", "equal_content_copy", "numpy_mutated_in_place"],
)
def test_fingerprint_memo_on_resubmit(dense_system, sha1_calls, as_operand, second,
                                      same_digest, hashes, memo_hits):
    """Only a resubmitted live jax.Array skips the host copy and sha1; an
    equal-content copy is hashed to the same digest (and hits the factor
    cache), a numpy operand written in place is hashed to a new one."""
    a0, bs = dense_system
    a = as_operand(np.asarray(a0))
    svc = SolveService()
    t1 = svc.submit(a, bs[0])
    digest1 = _sha1(a)
    a2 = second(a)
    t2 = svc.submit(a2, bs[1])
    assert len(sha1_calls) == hashes
    assert svc.stats.fingerprint_memo_hits == memo_hits
    out = svc.flush()
    assert set(out) == {t1, t2}
    assert list(svc._lru) == ([digest1] if same_digest else [digest1, _sha1(a2)])
    assert (svc.stats.cache_hits, svc.stats.cache_misses) == ((1, 1) if same_digest else (0, 2))


def test_fingerprint_memo_keys_on_bw(sha1_calls):
    """``bw`` is part of the digest and of the memo's key."""
    a = make_banded_dd(jax.random.PRNGKey(4), 32, 2)
    d0, d2 = fingerprint(a), fingerprint(a, bw=2)
    assert (d0, d2) == (_sha1(a, 0), _sha1(a, 2)) and d0 != d2
    assert (fingerprint(a), fingerprint(a, bw=2)) == (d0, d2)
    assert len(sha1_calls) == 2  # the repeats were memo hits


def test_fingerprint_memo_holds_its_array_weakly():
    a = make_diagonally_dominant(jax.random.PRNGKey(5), 16)
    key, digest = (id(a), 0), fingerprint(a)
    assert solve_service._memo[key][1] == digest == _sha1(a)
    alive = weakref.ref(a)
    del a
    gc.collect()
    assert alive() is None  # the memo kept no strong reference
    assert key not in solve_service._memo


@pytest.mark.parametrize("fingerprinted", [False, True], ids=["cold", "memoized"])
@pytest.mark.parametrize("how", ["delete", "donate"])
def test_deleted_operand_raises_at_submit(dense_system, how, fingerprinted):
    """A deleted or donated array fails at submit even when its digest is
    memoized: the memo is never consulted for it."""
    a0, bs = dense_system
    a = jnp.array(a0, copy=True)
    if fingerprinted:
        fingerprint(a)
    if how == "delete":
        a.delete()
    else:
        jax.jit(lambda x: x + 1.0, donate_argnums=0)(a)
    assert a.is_deleted()
    svc = SolveService()
    with pytest.raises(RuntimeError, match="deleted"):
        svc.submit(a, bs[0])
    assert svc.stats.fingerprint_memo_hits == 0


def test_deadline_orders_flush_groups(dense_system):
    """The deadline-bearing matrix group flushes first (EDF over the shared
    scheduler), regardless of submission order."""
    a, bs = dense_system
    a2 = make_diagonally_dominant(jax.random.PRNGKey(9), a.shape[0])
    svc = SolveService()
    svc.submit(a, bs[0])
    svc.submit(a2, bs[1], deadline=1.0)
    order = []
    import repro.solvers as solvers

    hook = solvers.add_dispatch_hook(
        lambda p, be: order.append(p.op) if p.op == "factor" else None
    )
    try:
        fps = []
        orig = svc._factors_for

        def spy(req, tolerance):
            fps.append(req.fp)
            return orig(req, tolerance)

        svc._factors_for = spy
        svc.flush()
    finally:
        solvers.remove_dispatch_hook(hook)
    assert fps[0] == fingerprint(a2)  # deadline group factored first


def test_flush_requeues_unprocessed_on_error(dense_system):
    """An exception while serving one group must not drop the rest of the
    drained batch: unprocessed requests return to the queue and a later
    flush serves them."""
    a, bs = dense_system
    a2 = make_diagonally_dominant(jax.random.PRNGKey(21), a.shape[0])
    a3 = make_diagonally_dominant(jax.random.PRNGKey(22), a.shape[0])
    svc = SolveService()
    t1 = svc.submit(a, bs[0])
    t2 = svc.submit(a2, bs[1])
    t3 = svc.submit(a3, bs[2])
    bad_fp = fingerprint(a2)
    orig = svc._factors_for

    def boom(req, tolerance):
        if req.fp == bad_fp:
            raise RuntimeError("injected factor failure")
        return orig(req, tolerance)

    svc._factors_for = boom
    with pytest.raises(RuntimeError, match="injected factor failure"):
        svc.flush()
    # the failing group AND everything drained after it went back to the queue
    assert svc.pending() == 2
    # the group that completed before the failure stays redeemable
    np.testing.assert_array_equal(
        np.asarray(svc.result(t1)),
        np.asarray(kops.lu_solve(kops.lu(a), bs[0])),
    )
    svc._factors_for = orig
    results = svc.flush()
    assert set(results) == {t2, t3}
    np.testing.assert_array_equal(
        np.asarray(results[t3]), np.asarray(kops.lu_solve(kops.lu(a3), bs[2]))
    )


def test_solve_convenience_retains_other_results(dense_system):
    """solve() drains the whole queue; earlier submissions' answers stay
    redeemable via result() instead of being silently discarded."""
    a, bs = dense_system
    a2 = make_diagonally_dominant(jax.random.PRNGKey(11), a.shape[0])
    svc = SolveService()
    t_early = svc.submit(a, bs[0])
    x2 = svc.solve(a2, bs[1])
    np.testing.assert_array_equal(
        np.asarray(x2), np.asarray(kops.lu_solve(kops.lu(a2), bs[1]))
    )
    x_early = svc.result(t_early)
    np.testing.assert_array_equal(
        np.asarray(x_early), np.asarray(kops.lu_solve(kops.lu(a), bs[0]))
    )
    with pytest.raises(KeyError):
        svc.result(t_early)  # single redemption
