"""A run with the timed path broken underneath reads ``correct`` false, for
each fault a cell can have; the same run unbroken reads true.  The chip
check is skipped; everything else is the harness's own run."""
import json
import os

import jax.numpy as jnp
import pytest

from bench import core
from bench.tests.conftest import run_tiny

BM = core.load_benchmark()
CELLS = [w["name"] for w in BM["workloads"]]


def _coalesces(cell: str) -> bool:
    """Whether the cell's traffic sends one operator two requests in a tick."""
    t = core.data(core.ROOT, "traffic", core.workload(BM, cell)["traffic"])
    return t["loop"] == "service" and t["requests_per_tick"] > t["operators"]


def _unchanged_state(ops, mp):
    """The factorization returns its operand as the factors."""
    orig = ops.lu

    def fake(a, *args, **kw):
        out = orig(a, *args, **kw)
        f, rec = out if isinstance(out, tuple) else (out, None)
        f = f.with_meta(packed=a, linv=None, uinv=None, tlo=None, tup=None)
        return f if rec is None else (f, rec)

    mp.setattr(ops, "lu", fake)


def _wrap_solves(ops, mp, change):
    orig = ops.lu_solve

    def fake(f, b, *args, **kw):
        return change(orig, f, b, args, kw)

    mp.setattr(ops, "lu_solve", fake)


def _answer_altered(ops, mp):
    """One entry of each solution is altered where it is produced."""
    def change(orig, f, b, args, kw):
        x = orig(f, b, *args, **kw)
        return x.at[0].add(1.0)

    _wrap_solves(ops, mp, change)


def _half_batch(ops, mp):
    """A coalesced solve answers half its columns and leaves the rest out."""
    def change(orig, f, b, args, kw):
        if b.ndim == 1 or b.shape[1] < 2:
            return orig(f, b, *args, **kw)
        half = b.shape[1] // 2
        x = orig(f, b[:, :half], *args, **kw)
        return jnp.concatenate([x, jnp.zeros_like(b[:, half:])], axis=1)

    _wrap_solves(ops, mp, change)


FAULTS = {"unchanged_state": _unchanged_state, "answer_altered": _answer_altered,
          "half_batch": _half_batch}
CASES = [(cell, fault) for cell in CELLS for fault in FAULTS
         if fault != "half_batch" or _coalesces(cell)]


@pytest.mark.parametrize("cell", CELLS)
def test_unbroken_run_is_correct(tiny_root, cell):
    out = run_tiny(tiny_root, cell)
    assert out["result"]["correct"], out["check_lines"]


@pytest.mark.parametrize("cell,fault", CASES)
def test_broken_run_is_not_correct(tiny_root, monkeypatch, cell, fault):
    from repro.kernels import ops

    FAULTS[fault](ops, monkeypatch)
    out = run_tiny(tiny_root, cell)
    assert out["result"]["correct"] is False, out["check_lines"]


def test_half_batch_is_caught_where_requests_coalesce(tiny_root, monkeypatch):
    """A mix that sends one operator several requests a tick, added as a new
    file, catches a coalesced solve that answers half its columns."""
    from repro.kernels import ops

    with open(os.path.join(tiny_root, "bench", "traffic", "coalesced.json"), "w") as f:
        json.dump({"loop": "service", "operators": 2, "requests_per_tick": 5, "zipf_s": 1.0,
                   "cache_entries": 2, "pattern_seed": 0, "check_sample": 10}, f)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["workloads"].append({"name": "hpl_dense_n16384.coalesced", "config": "hpl_dense_n16384",
                            "traffic": "coalesced", "chips": 1, "why": "test"})
    with open(path, "w") as f:
        json.dump(bm, f)
    assert run_tiny(tiny_root, "hpl_dense_n16384.coalesced")["result"]["correct"]
    _half_batch(ops, monkeypatch)
    out = run_tiny(tiny_root, "hpl_dense_n16384.coalesced")
    assert out["result"]["correct"] is False, out["check_lines"]
