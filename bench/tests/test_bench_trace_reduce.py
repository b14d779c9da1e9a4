"""The trace reduction on hand-made intervals and on a small trace recorded
with the CPU profiler."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench import trace_reduce


def test_union_merges_overlaps_and_drops_empty():
    got = trace_reduce.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9), (6, 8)])
    assert got == [(0, 4), (5, 8)]


def test_covered_clips_to_the_interval():
    merged = [(0, 4), (5, 8)]
    assert trace_reduce.covered(merged, 0, 10) == 7
    assert trace_reduce.covered(merged, 3, 6) == 2
    assert trace_reduce.covered(merged, 8, 9) == 0


def test_label_names_the_innermost_span():
    spans = [(0, 10, "flush"), (2, 4, "solve")]
    assert trace_reduce._label(spans, 3) == "solve"
    assert trace_reduce._label(spans, 6) == "flush"
    assert trace_reduce._label(spans, 11) == "between spans"


def test_reduce_a_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((192, 192), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("factor"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("solve"):
                f(x + 1.0).block_until_ready()
    jax.profiler.stop_trace()

    path = trace_reduce.latest_trace(str(tmp_path))
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce(path, ("factor",), planes=["/device:CPU:0"])  # a chip run: no device plane is an error
    r = trace_reduce.reduce(path, ("factor", "solve", "flush"), planes=["/device:CPU:0"], host_ops_allowed=True)
    assert 0.0 < r["busy_s"] <= r["window_s"]
    assert r["idle_share"] == pytest.approx(1.0 - r["busy_s"] / r["window_s"])
    assert r["busy_in_s"]["factor"] > 0.0 and r["busy_in_s"]["solve"] > 0.0
    assert r["busy_in_s"]["flush"] == 0.0
    assert sum(r["busy_in_s"].values()) <= r["busy_s"] * (1 + 1e-9)
    for key in ("device_ops", "idle_gaps"):
        assert 1 <= len(r[key]) <= trace_reduce.TOP
        for name, seconds in r[key]:
            assert isinstance(name, str) and seconds > 0.0
    assert any("dot" in name for name, _ in r["device_ops"])


def _event(name, start, duration):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=duration, stats=())


def _plane(name, line, events):
    return SimpleNamespace(name=name, lines=[SimpleNamespace(name=line, events=events)])


@pytest.fixture
def three_chips(monkeypatch):
    """A trace of a window [0, 100) ns with one ``factor`` span [0, 50):
    chip 0 busy 40 ns, chip 1 busy 80 ns, chip 2 traced but idle."""
    planes = [
        _plane("/host:CPU", "host", [_event(trace_reduce.WINDOW, 0, 100), _event("factor", 0, 50)]),
        _plane("/device:TPU:0", "XLA Ops", [_event("lu", 10, 40)]),
        _plane("/device:TPU:1", "XLA Ops", [_event("lu", 0, 50), _event("solve", 60, 30)]),
        _plane("/device:TPU:2", "XLA Ops", []),
    ]
    monkeypatch.setattr(jax.profiler, "ProfileData",
                        SimpleNamespace(from_file=lambda path: SimpleNamespace(planes=planes)))


@pytest.mark.parametrize("planes,busy,in_factor", [
    (["/device:TPU:0"], 40, 40),  # a one-chip cell: the other planes are ignored
    (["/device:TPU:0", "/device:TPU:1"], 60, 45),
    (["/device:TPU:0", "/device:TPU:2"], 20, 20),  # a chip of the cell that ran nothing is busy 0
    (["/device:TPU:0", "/device:TPU:1", "/device:TPU:2", "/device:TPU:3"], 30, 22.5),
])
def test_only_the_cells_planes_count(three_chips, planes, busy, in_factor):
    r = trace_reduce.reduce("trace.xplane.pb", ("factor",), planes=planes)
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert r["busy_in_s"]["factor"] == pytest.approx(in_factor * 1e-9)
    assert r["idle_share"] == pytest.approx(1 - busy / 100)


def test_a_cell_whose_planes_ran_nothing_is_an_error(three_chips):
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce("trace.xplane.pb", ("factor",), planes=["/device:TPU:2", "/device:TPU:5"])
