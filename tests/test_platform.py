"""Platform-facing behaviour: the interpret helper, kernels Mosaic cannot
lower, device-kind keyed dispatch and autotune rows, the escalation funnel's
handling of lowering errors, and the persistent compile cache location."""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro import kernels
from repro.kernels import banded, batched_lu, ebv_lu, trsm
from repro.solvers import AutotuneCache, Problem, candidates, dispatch, registry
from repro.solvers import cache as scache
from repro.utils import compile_cache

TPU = "TPU v5 lite"


@pytest.fixture
def no_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_SOLVERS_CACHE", str(tmp_path / "absent.json"))
    scache.invalidate()
    yield
    scache.invalidate()


def test_interpret_mode_only_on_cpu():
    assert kernels.interpret_mode() == (jax.default_backend() == "cpu")
    assert kernels.interpret_mode(False) is False
    assert kernels.interpret_mode(True) is True


@pytest.mark.parametrize("call", [
    lambda: ebv_lu.lu_vmem(jnp.eye(8), interpret=False),
    lambda: ebv_lu.panel(jnp.eye(8), interpret=False),
    lambda: ebv_lu.fused_step(jnp.eye(16)[:, :8], jnp.eye(8), jnp.eye(8), interpret=False),
    lambda: trsm.solve_vmem(jnp.eye(8), jnp.ones(8), interpret=False),
    lambda: banded.banded_lu_kernelized(jnp.ones((8, 3)), bw=1, interpret=False),
    lambda: batched_lu.batched_lu_vmem(jnp.ones((2, 8, 8)), interpret=False),
    lambda: batched_lu.batched_lu_solve_vmem(jnp.ones((2, 8, 8)), jnp.ones((2, 8, 1)), interpret=False),
    lambda: banded.batched_banded_lu_vmem(jnp.ones((2, 8, 3)), bw=1, interpret=False),
], ids=["lu_vmem", "panel", "fused_step", "solve_vmem", "banded_scalar",
        "batched_lu", "batched_solve", "batched_banded_lu"])
def test_unlowerable_kernels_refuse_to_compile(call):
    with pytest.raises(NotImplementedError, match="does not lower on Mosaic"):
        call()


def test_problem_carries_device_kind():
    p = Problem.from_arrays("factor", jnp.ones((4, 4)))
    assert p.device_kind == jax.devices()[0].device_kind
    assert not Problem(op="factor", structure="dense", n=8).tpu
    assert Problem(op="factor", structure="dense", n=8, device_kind=TPU).tpu


@pytest.mark.parametrize("op,structure,n,bw,rhs,forbidden", [
    ("factor", "dense", 1024, 0, 0, {"pallas_vmem", "pallas_blocked"}),
    ("solve", "dense", 1024, 0, 1, {"pallas_vmem", "pallas_inverted"}),
    ("factor", "banded", 1024, 4, 0, {"pallas_scalar"}),
    ("solve", "banded", 1024, 4, 1, {"pallas_inverted"}),
    ("factor", "batched_dense", 64, 0, 0, {"pallas_vmem"}),
    ("solve", "batched_dense", 64, 0, 1, {"pallas_vmem"}),
    ("factor", "batched_banded", 64, 2, 0, {"pallas_vmem"}),
    ("solve", "batched_banded", 64, 2, 1, {"pallas_vmem"}),
])
def test_tpu_candidates_exclude_unlowerable_kernels(no_cache, op, structure, n, bw, rhs, forbidden):
    batch = 4 if structure.startswith("batched_") else 1
    kw = dict(op=op, structure=structure, n=n, bw=bw, rhs=rhs, batch=batch)
    on_cpu = {b.name for b in candidates(Problem(**kw, device_kind="cpu"))}
    on_tpu = {b.name for b in candidates(Problem(**kw, device_kind=TPU))}
    assert forbidden <= on_cpu  # interpret mode keeps them usable on CPU
    assert not forbidden & on_tpu
    assert on_tpu  # something lowerable (kernel or XLA) is always left


def test_tpu_static_selection_is_the_ported_kernels(no_cache):
    sel = lambda **kw: registry.select(Problem(device_kind=TPU, **kw)).name  # noqa: E731
    assert sel(op="factor", structure="dense", n=16384) == "pallas_fused"
    assert sel(op="solve", structure="dense", n=16384, rhs=2, enriched=False) == "pallas_tiled"
    assert sel(op="factor", structure="banded", n=65536, bw=256) == "pallas_tiled"
    assert sel(op="solve", structure="banded", n=65536, bw=256, rhs=2) == "pallas"
    # the VMEM-resident band kernel only where its band fits the byte cap
    assert sel(op="factor", structure="banded", n=4096, bw=16) == "pallas_blocked"


def test_cache_device_kind_exact_key_field(tmp_path):
    """A row measured on one device kind never steers another, and rows
    persisted before the field existed load as CPU rows."""
    row = {"op": "factor", "structure": "dense", "dtype": "float32", "bw": 0,
           "n": 1024, "times_us": {"xla": 1.0, "pallas_fused": 9.0}}
    path = tmp_path / "c.json"
    path.write_text(json.dumps({"version": 1, "entries": [row]}))
    cache = AutotuneCache.load(str(path))
    cpu = Problem(op="factor", structure="dense", n=1024, device_kind="cpu")
    tpu = Problem(op="factor", structure="dense", n=1024, device_kind=TPU)
    assert cache.best(cpu, ["xla", "pallas_fused"]) == "xla"
    assert cache.best(tpu, ["xla", "pallas_fused"]) is None
    cache.record(tpu, {"pallas_fused": 2.0})
    assert len(cache.entries) == 2
    assert cache.best(tpu, ["xla", "pallas_fused"]) == "pallas_fused"


def test_funnel_raises_lowering_errors_instead_of_escalating(no_cache):
    """A backend that cannot run here is a dispatch bug, not a hostile
    operand: a screened dispatch must raise, not demote it silently."""
    p = Problem(op="factor", structure="dense", n=8)

    def boom(problem, a, **_):
        raise NotImplementedError("kernel does not lower")

    backend = registry.Backend(name="boom", op="factor", structure="dense", call=boom,
                               priority=lambda q: 1e9)
    registry.register(backend)
    try:
        with registry.record_escalations() as log:
            with pytest.raises(NotImplementedError):
                dispatch(p, jnp.eye(8), validate=lambda *a: None)
        assert log == []
    finally:
        registry._REGISTRY[("factor", "dense")].pop("boom")
        registry.clear_demotions()


def _raising_backend(exc):
    def boom(problem, a, **_):
        raise exc

    return registry.Backend(name="boom", op="factor", structure="dense", call=boom,
                            priority=lambda q: 1e9)


def _lowering_exception(msg):
    from jax._src.pallas.mosaic.lowering import LoweringException

    return LoweringException(msg)


@pytest.mark.parametrize("make_exc", [
    lambda: ValueError("block shape (8, 144) is not lane-aligned"),
    lambda: jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: scoped vmem limit exceeded"),
    lambda: _lowering_exception("Unimplemented primitive: dynamic_slice"),
    lambda: TypeError("a bug in the backend"),
], ids=["ValueError", "JaxRuntimeError", "LoweringException", "TypeError"])
def test_funnel_propagates_backend_faults_without_demoting(no_cache, make_exc):
    """Only an operand fault escalates: a compile refusal, a block shape, a
    VMEM overflow or a bug propagates from a screened dispatch, and the
    backend is not demoted for the next same-shape dispatch."""
    p = Problem(op="factor", structure="dense", n=8)
    exc = make_exc()
    registry.register(_raising_backend(exc))
    try:
        with registry.record_escalations() as log:
            with pytest.raises(type(exc)):
                dispatch(p, jnp.eye(8), validate=lambda *a: None)
        assert log == []
        assert not any(name == "boom" for _, name in registry._DEMOTIONS)
    finally:
        registry._REGISTRY[("factor", "dense")].pop("boom")
        registry.clear_demotions()


@pytest.mark.parametrize("make_exc", [
    lambda: FloatingPointError("zero pivot"),
    lambda: __import__("numpy").linalg.LinAlgError("singular block"),
], ids=["FloatingPointError", "LinAlgError"])
def test_funnel_escalates_operand_faults(no_cache, make_exc):
    """A numeric fault of the operand demotes the backend and the next
    candidate serves the dispatch."""
    p = Problem(op="factor", structure="dense", n=8)
    registry.register(_raising_backend(make_exc()))
    try:
        with registry.record_escalations() as log:
            out = dispatch(p, jnp.eye(8), validate=lambda *a: None)
        assert [e[1] for e in log] == ["boom"]
        assert out is not None
    finally:
        registry._REGISTRY[("factor", "dense")].pop("boom")
        registry.clear_demotions()


@pytest.mark.parametrize("env", [None, "set"])
def test_compile_cache_directory(monkeypatch, tmp_path, env):
    """With $JAX_COMPILATION_CACHE_DIR set, compiled programs land there;
    without it, in <repo>/.jax_cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in names}
    if env:
        want = str(tmp_path / "cc")
        monkeypatch.setenv(compile_cache.ENV_VAR, want)
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")
    cc.reset_cache()
    try:
        assert os.path.abspath(compile_cache.enable_compilation_cache()) == os.path.abspath(want)
        seen = set(os.listdir(want)) if os.path.isdir(want) else set()
        # a constant no earlier run compiled, so the program is a cache miss
        salt = float(int(hashlib.sha1(str(tmp_path).encode()).hexdigest()[:6], 16))
        jax.jit(lambda x: x * salt + 3.0)(jnp.arange(7.0)).block_until_ready()
        assert set(os.listdir(want)) - seen
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()
