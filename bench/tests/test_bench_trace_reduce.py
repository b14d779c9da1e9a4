"""The trace reduction on hand-made intervals and on a small trace recorded
with the CPU profiler."""
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
        trace_reduce.reduce(path, ("factor",))  # a chip run: no device plane is an error
    r = trace_reduce.reduce(path, ("factor", "solve", "flush"), host_ops_allowed=True)
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
