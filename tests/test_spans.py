"""Program spans (``repro.utils.spans``): off unless a profiler session or
``recording()`` is active; under a profiler the solver path records its
span tree, with the same names on the trace's host plane."""
import gc
import glob
import os
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.serve.solve_service import FLUSH_COUNTERS, SolveService
from repro.utils import spans

N = 256


def _dominant(seed: int):
    rng = np.random.default_rng(seed)
    a = rng.uniform(-1.0, 1.0, (N, N)).astype(np.float32)
    a[np.arange(N), np.arange(N)] = np.abs(a).sum(axis=1) + 1.0
    return jnp.asarray(a), jnp.asarray(rng.normal(size=N).astype(np.float32))


def _submit_and_flush(svc, a, b):
    tickets = [svc.submit(a, b), svc.submit(a, 2.0 * b)]
    before = {f: getattr(svc.stats, f) for f in FLUSH_COUNTERS}
    out = svc.flush()
    jax.block_until_ready([out[t] for t in tickets])
    return tickets, {f: getattr(svc.stats, f) - before[f] for f in FLUSH_COUNTERS}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One submit + flush of two requests on a cached operator and one fresh
    ``ops.lu`` → ``ops.lu_solve``, under ``jax.profiler.start_trace``."""
    a, b = _dominant(0)
    svc = SolveService()
    svc.solve(a, b)  # factors the operator: the traced flush hits
    log_dir = str(tmp_path_factory.mktemp("trace"))
    t0 = time.perf_counter()
    jax.profiler.start_trace(log_dir)
    try:
        with jax.profiler.TraceAnnotation("enclosing"):
            tickets, deltas = _submit_and_flush(svc, a, b)
            jax.block_until_ready(ops.lu_solve(ops.lu(a), b))
    finally:
        jax.profiler.stop_trace()
    found = spans.recorded(t0, time.perf_counter())
    trace = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return {"spans": found, "tickets": tickets, "deltas": deltas, "trace": trace}


def test_off_by_default_records_nothing_and_allocates_nothing():
    assert not spans.active()
    # one shared, falsy no-op context: no span object is made
    assert spans.span("repro.test", n=1) is spans.span("repro.other") is spans._OFF
    assert not spans.span("repro.test")
    a, b = _dominant(1)
    t0 = time.perf_counter()
    _submit_and_flush(SolveService(), a, b)
    jax.block_until_ready(ops.lu_solve(ops.lu(a), b))
    assert spans.recorded(t0, time.perf_counter()) == []


def test_service_records_its_span_tree(traced):
    found = traced["spans"]
    by_id = {s.id: s for s in found}
    names = [s.name for s in found]

    def parent(s):
        return by_id[s.parent].name if s.parent in by_id else None

    submits = [s for s in found if s.name == "repro.service.submit"]
    assert [s.request for s in submits] == traced["tickets"]
    assert all(s.attrs == {"n": N, "bw": 0, "cols": 1} for s in submits)
    fps = [s for s in found if s.name == "repro.service.fingerprint"]
    assert [parent(s) for s in fps] == ["repro.service.submit"] * 2
    assert [s.request for s in fps] == traced["tickets"]
    # svc.solve fingerprinted the operator before the trace: both digests
    # come from the memo, with no host copy and no hash
    assert all(s.attrs == {"memo": True, "bytes": N * N * 4} for s in fps)
    assert not {"repro.service.fingerprint.to_host", "repro.service.fingerprint.hash"} & set(names)

    (flush,) = [s for s in found if s.name == "repro.service.flush"]
    assert flush.parent is None
    assert {f: flush.attrs[f] for f in FLUSH_COUNTERS} == traced["deltas"]
    assert flush.attrs["cache_hits"] == 2 and flush.attrs["cache_misses"] == 0
    assert flush.attrs["requests"] == 2 and flush.attrs["groups"] == 1
    (factors,) = [s for s in found if s.name == "repro.service.factors"]
    assert parent(factors) == "repro.service.flush"
    assert factors.attrs["hit"] is True and factors.attrs["tickets"] == traced["tickets"]
    (solve,) = [s for s in found if s.name == "repro.service.solve"]
    assert parent(solve) == "repro.service.flush" and solve.attrs["width"] == 2
    assert solve.attrs["fp"] == factors.attrs["fp"]

    solves = [s for s in found if s.name == "repro.ops.lu_solve"]
    assert [parent(s) for s in solves] == ["repro.service.solve", None]
    assert solves[0].attrs == {"n": N, "bw": 0, "k": 2, "eager": True}
    (lu,) = [s for s in found if s.name == "repro.ops.lu"]
    assert lu.parent is None and lu.attrs == {"n": N, "bw": 0, "eager": True}
    dispatches = [s for s in found if s.name == "repro.dispatch"]
    assert {parent(s) for s in dispatches} == {"repro.ops.lu", "repro.ops.lu_solve"}
    for d in dispatches:
        assert d.attrs["attempts"] == 1 and d.attrs["backend"]
        kids = [s.name for s in found if s.parent == d.id]
        assert kids.count("repro.dispatch.select") == kids.count("repro.dispatch.call") == 1
    # the eager ops.lu traces its body under custom_vmap: its dispatch is traced
    (lu_dispatch,) = [d for d in dispatches if d.parent == lu.id]
    assert lu_dispatch.attrs["traced"] is True and lu_dispatch.attrs["op"] == "factor"
    assert names.index("repro.service.flush") < names.index("repro.ops.lu")


def test_first_fingerprint_of_a_fresh_array_records_its_hash():
    a, b = _dominant(2)
    svc = SolveService()
    t0 = time.perf_counter()
    with spans.recording():
        tickets = [svc.submit(a, b), svc.submit(a, 2.0 * b)]
    found = spans.recorded(t0, time.perf_counter())
    fps = [s for s in found if s.name == "repro.service.fingerprint"]
    assert [s.request for s in fps] == tickets
    assert [s.attrs for s in fps] == [{"memo": False, "bytes": N * N * 4},
                                      {"memo": True, "bytes": N * N * 4}]
    for child in ("repro.service.fingerprint.to_host", "repro.service.fingerprint.hash"):
        assert [s.parent for s in found if s.name == child] == [fps[0].id]
    assert svc.stats.fingerprint_memo_hits == 1
    svc.flush()


def test_span_names_are_on_the_trace_host_plane(traced):
    from jax.profiler import ProfileData

    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(traced["trace"]).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]
    (outer,) = [(s, e) for name, s, e in host if name == "enclosing"]
    inside = {name for name, s, e in host if outer[0] <= s and e <= outer[1]}
    recorded = {s.name for s in traced["spans"] if s.name not in ("repro.compile", "repro.gc")}
    assert recorded <= inside


def test_no_program_span_is_a_benchmark_span(traced):
    from bench.run import TRACED_SPANS
    from bench.trace_reduce import WINDOW

    names = {s.name for s in traced["spans"]}
    assert all(name.startswith("repro.") for name in names)
    assert not names & (set(TRACED_SPANS) | {WINDOW})


def test_compile_and_gc_inside_a_span_are_its_children():
    x = jnp.arange(8.0)
    c = float(np.random.default_rng().integers(1, 2**30))  # a new program: no cache hit
    t0 = time.perf_counter()
    with spans.recording():
        with spans.span("repro.test.outer", request=7) as outer:
            jax.block_until_ready(jax.jit(lambda v: v * c + 1.0)(x))
            gc.collect()
    found = spans.recorded(t0, time.perf_counter())
    compiles = [s for s in found if s.name == "repro.compile"]
    assert compiles and all(s.parent == outer.id and s.request == 7 for s in compiles)
    assert all(outer.t0 <= s.t0 <= s.t1 <= outer.t1 for s in compiles)
    assert all(s.duration == pytest.approx(s.attrs["duration_s"]) for s in compiles)
    collections = [s for s in found if s.name == "repro.gc" and s.attrs["generation"] == 2]
    assert collections and all(s.parent == outer.id for s in collections)
    assert not spans.active()


def test_a_full_buffer_reports_the_drop(monkeypatch):
    monkeypatch.setattr(spans, "_buffer", deque(maxlen=2))
    try:
        with spans.recording():
            t0 = time.perf_counter()
            for i in range(3):
                with spans.span("repro.test", i=i):
                    pass
                if i == 0:
                    t_mid = time.perf_counter()
        assert spans.dropped() == 1
        assert spans.recorded(t0) is None
        assert [s.attrs["i"] for s in spans.recorded(t_mid)] == [1, 2]
    finally:
        spans.clear()
    assert spans.dropped() == 0
