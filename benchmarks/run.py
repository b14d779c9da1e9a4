"""Benchmark driver — one module per paper table + the framework step bench.

Prints ``name,us_per_call,derived`` CSV (brief contract).  ``--full`` runs
the paper's full matrix sizes (up to 16000); default sizes keep the suite
CPU-friendly.  ``--smoke`` runs a fast CI subset (table2 at n=256, the LU
kernel-impl shootout at n∈{256, 1024}, the banded kernel shootout at the
paper's n=16384 / bw=16, the optimizer trajectory, and the serving rows —
decode host-sync before/after, ragged continuous batching, solve-service
cache speedup, plus the SPIKE substitution row: 8 forced host devices in
a CPU subprocess under ``JAX_PLATFORMS=cpu``, else this process's devices)
and writes ``BENCH_kernels.json`` (name → us_per_call) at
the repo root, seeding the perf trajectory across PRs.  ``--smoke --full``
additionally runs the slow ``rand_lu_n2048_k256`` accuracy-tier rows.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys

SMOKE_LU_SIZES = (256, 1024)
SMOKE_LU_IMPLS = ("pallas_fused", "pallas_blocked", "xla")
SMOKE_BANDED_N = 16384
SMOKE_BANDED_BW = 16
SMOKE_BANDED_IMPLS = ("pallas_blocked", "pallas_tiled", "pallas_scalar")


def _spike_subprocess_row(n: int, bw: int, devices: int) -> float | None:
    """Time the multi-device SPIKE substitution at the paper shape on
    ``devices`` emulated host devices.

    Runs in a CPU-only child process with its own ``XLA_FLAGS`` because the
    host platform's device count is locked at backend init — forcing
    ``devices`` host devices in *this* process would change the timing
    environment of every single-device row.  :func:`main` starts it before
    this process imports JAX, so the child never competes with a parent
    that holds an accelerator.  Returns seconds per call, or ``None`` when
    the child fails (row is then omitted and scripts/check.sh skips its
    gate with a note)."""
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = f"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count={devices} "
    + os.environ.get("XLA_FLAGS", "")
)
import sys
sys.path.insert(0, {os.path.join(root, "src")!r})
sys.path.insert(0, {root!r})
import jax
from benchmarks.common import time_call
from repro.core.banded import make_banded_dd
from repro.kernels.spike import spike_lu_sharded, spike_solve_sharded
from repro.launch.mesh import make_mesh

mesh = make_mesh(({devices},), ("model",))
arow = make_banded_dd(jax.random.PRNGKey(0), {n}, {bw})
b = jax.random.normal(jax.random.PRNGKey(1), ({n},))
factors = spike_lu_sharded(arow, bw={bw}, mesh=mesh)  # untimed, factor-once
t = time_call(lambda: spike_solve_sharded(factors, b, mesh=mesh), iters=5)
print(f"SPIKE_US={{t * 1e6:.1f}}")
"""
    try:
        out = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True, text=True, timeout=900, check=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
    except (subprocess.SubprocessError, OSError) as e:
        detail = getattr(e, "stderr", "") or ""
        print(f"banded_solve_n{n}_spike_d{devices}_FAILED,0,"
              f"{type(e).__name__}:{detail.strip().splitlines()[-1:] or ''}",
              file=sys.stderr)
        return None
    for line in out.stdout.splitlines():
        if line.startswith("SPIKE_US="):
            return float(line.split("=", 1)[1]) / 1e6
    print(f"banded_solve_n{n}_spike_d{devices}_FAILED,0,no_marker_in_output",
          file=sys.stderr)
    return None


def _cpu_only() -> bool:
    """Whether ``$JAX_PLATFORMS`` holds this process to the CPU — known
    before JAX is imported, so :func:`main` can start the CPU SPIKE child
    first only where no accelerator is in play."""
    platforms = [p for p in os.environ.get("JAX_PLATFORMS", "").split(",") if p]
    return platforms == ["cpu"]


def _capable(problem, impls) -> list[str]:
    """The impls of a shootout this device can run: each backend's registry
    capability predicate for ``problem`` (kernels Mosaic cannot lower are
    rejected on TPU).  ``"pallas"`` is the Pallas-only auto alias."""
    from repro.solvers import registry

    return [i for i in impls if i == "pallas" or
            registry.get_backend(problem.op, problem.structure, i).supports(problem)]


def _spike_mesh_row(n: int, bw: int) -> tuple[int, float] | None:
    """In-process SPIKE substitution on a mesh of every local device (the
    real-mesh counterpart of :func:`_spike_subprocess_row`); returns
    ``(devices, seconds per call)``, or None with fewer than two devices."""
    import jax

    from repro.core.banded import make_banded_dd
    from repro.kernels.spike import spike_lu_sharded, spike_solve_sharded
    from repro.launch.mesh import make_mesh
    from .common import time_call

    devices = jax.device_count()
    if devices < 2:
        return None
    mesh = make_mesh((devices,), ("model",))
    arow = make_banded_dd(jax.random.PRNGKey(0), n, bw)
    b = jax.random.normal(jax.random.PRNGKey(1), (n,))
    factors = spike_lu_sharded(arow, bw=bw, mesh=mesh)  # untimed, factor-once
    return devices, time_call(lambda: spike_solve_sharded(factors, b, mesh=mesh), iters=5)


def smoke(out_path: str | None = None, full: bool = False,
          spike_cpu_s: float | None = None) -> dict[str, float]:
    """Fast perf smoke: table2 at small size + per-impl LU kernel timings +
    the sparse (banded) trajectory at paper scale.

    Returns (and writes to ``out_path``) ``{name: us_per_call}``.  The
    ``lu_n1024_*`` entries are the tracked fused-vs-blocked wall-time
    comparison; the ``banded_*`` entries track the blocked band megakernel
    against the legacy scalar kernel and the sequential numpy baseline; the
    ``opt_*`` entries track the EbV-preconditioned optimizer's grouped
    batched solves against the per-leaf unrolled jnp reference it replaced.

    Every shootout is also *recorded into the repro.solvers autotune cache*
    (same keys, same harness), so the committed rows and the registry's
    dispatch decisions cannot silently disagree — scripts/check.sh asserts
    the agreement after this runs."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from repro.core import make_diagonally_dominant
    from repro.core.banded import make_banded_dd
    from repro.kernels import ops as kops
    from repro.solvers import Problem
    from repro.solvers import cache as scache
    from . import table2_dense
    from .common import emit, numpy_banded_baseline, time_call, time_shootout

    rows_us: dict[str, float] = {}
    tune = scache.get_cache()  # seeded below so BENCH rows and dispatch agree
    for name, secs in table2_dense.run(sizes=[256]).items():
        rows_us[name] = secs * 1e6
    for n in SMOKE_LU_SIZES:
        a = make_diagonally_dominant(jax.random.PRNGKey(n), n)
        # round-robin sampling: close races (fused vs its op-identical xla
        # mirror) must not be decided by measurement order / host drift
        prob = Problem(op="factor", structure="dense", n=n)
        fns = {impl: functools.partial(lambda impl, a: kops.lu(a, impl=impl), impl)
               for impl in _capable(prob, SMOKE_LU_IMPLS)}
        times = time_shootout(fns, a, iters=15 if n <= 256 else 5)
        tune.record(prob, {impl: t * 1e6 for impl, t in times.items()})
        for impl, t in times.items():
            rows_us[f"lu_n{n}_{impl}"] = t * 1e6
            emit(f"lu_n{n}_{impl}", t)

    nb, bw = SMOKE_BANDED_N, SMOKE_BANDED_BW
    arow = make_banded_dd(jax.random.PRNGKey(0), nb, bw)
    prob = Problem(op="factor", structure="banded", n=nb, bw=bw)
    fns = {impl: functools.partial(lambda impl, a: kops.banded_lu(a, bw=bw, impl=impl), impl)
           for impl in _capable(prob, SMOKE_BANDED_IMPLS)}
    banded_lu_times = time_shootout(fns, arow, iters=5)
    tune.record(prob, {impl: t * 1e6 for impl, t in banded_lu_times.items()})
    for impl, t in banded_lu_times.items():
        rows_us[f"banded_lu_n{nb}_{impl}"] = t * 1e6
        emit(f"banded_lu_n{nb}_{impl}", t)
    arow_np = np.asarray(arow, np.float64)
    t = time_call(lambda: numpy_banded_baseline(arow_np, bw), warmup=0, iters=1)
    rows_us[f"banded_lu_n{nb}_numpy"] = t * 1e6
    emit(f"banded_lu_n{nb}_numpy", t)
    # factor ONCE with enrich=True: the diagonal-block inverses are a
    # factor-time cost, so the solve shootout times every impl against the
    # same solve-ready Factorization artifact (pallas/xla_scalar read only
    # its packed factors; pallas_inverted consumes the enrichments)
    lub = kops.banded_lu(arow, bw=bw, enrich=True)
    b = jax.random.normal(jax.random.PRNGKey(1), (nb,))
    prob = Problem(op="solve", structure="banded", n=nb, bw=bw, rhs=1)
    fns = {impl: functools.partial(lambda impl, l, r: kops.banded_solve(l, r, bw=bw, impl=impl), impl)
           for impl in _capable(prob, ("pallas", "xla_scalar", "pallas_inverted"))}
    banded_solve_times = time_shootout(fns, lub, b, iters=5)
    tune.record(prob, {impl: t * 1e6 for impl, t in banded_solve_times.items()})
    for impl, t in banded_solve_times.items():
        rows_us[f"banded_solve_n{nb}_{impl}"] = t * 1e6
        emit(f"banded_solve_n{nb}_{impl}", t)
    tune.save()  # dispatch decisions now provably follow the committed rows

    # --- multi-device SPIKE split substitution at the same paper shape:
    # under JAX_PLATFORMS=cpu, the row main() timed under 8 forced host
    # devices in a CPU child before this process imported JAX; otherwise
    # in-process on a mesh of this process's devices.  scripts/check.sh
    # gates the d8 row <= SPIKE_MAX_RATIO x the best single-device
    # substitution above.
    if spike_cpu_s is not None:
        rows_us[f"banded_solve_n{nb}_spike_d8"] = spike_cpu_s * 1e6
        emit(f"banded_solve_n{nb}_spike_d8", spike_cpu_s)
    elif not _cpu_only():
        row = _spike_mesh_row(nb, bw)
        if row is None:
            print(f"banded_solve_n{nb}_spike_SKIPPED,0,one_device"
                  "(set JAX_PLATFORMS=cpu for the 8-host-device row)", file=sys.stderr)
        else:
            d, t = row
            rows_us[f"banded_solve_n{nb}_spike_d{d}"] = t * 1e6
            emit(f"banded_solve_n{nb}_spike_d{d}", t)

    # --- stacked-RHS dense substitution at transfer scale: one n=4096
    # artifact (factored+enriched once, untimed — the factor-once/solve-many
    # traffic shape) serving 64 coalesced RHS columns through the
    # inverted-diagonal trsm with equalized RHS tiling.  Tracks the wide
    # dispatches the solve service emits after RHS coalescing.
    nt, rt = 4096, 64
    at = make_diagonally_dominant(jax.random.PRNGKey(nt), nt)
    art = kops.lu(at, enrich=True)
    bt = jax.random.normal(jax.random.PRNGKey(2), (nt, rt))
    t = time_call(lambda: kops.lu_solve(art, bt), iters=5)
    rows_us[f"trsm_n{nt}_stacked_r{rt}"] = t * 1e6
    emit(f"trsm_n{nt}_stacked_r{rt}", t)

    # --- optimizer trajectory: the EbV-preconditioned step on a model of
    # (128, 128) parameter factors.  `opt_step_d128_registry` is the full
    # update (grouped batched solves through repro.solvers);
    # `opt_precond_*` isolates the preconditioner solves — registry batched
    # dispatch vs the per-leaf unrolled jnp reference the optimizer ran
    # before the registry rewire.
    from repro.core.blocked import blocked_lu
    from repro.core.solve import lu_solve as core_lu_solve
    from repro.train import optimizer as opt_lib

    d, nleaves = 128, 4
    params = {f"w{i}": 0.02 * jax.random.normal(jax.random.PRNGKey(10 + i), (d, d))
              for i in range(nleaves)}
    grads = {f"w{i}": jax.random.normal(jax.random.PRNGKey(20 + i), (d, d))
             for i in range(nleaves)}
    opt = opt_lib.ebv_preconditioned(opt_lib.constant_lr(1e-3))
    state = opt.init(params)
    step = jax.jit(lambda g, s, p: opt.update(g, s, p)[0])
    t = time_call(step, grads, state, params, iters=5)
    rows_us["opt_step_d128_registry"] = t * 1e6
    emit("opt_step_d128_registry", t)

    a3 = jnp.stack([make_diagonally_dominant(jax.random.PRNGKey(30 + i), d)
                    for i in range(nleaves)])
    r3 = jax.random.normal(jax.random.PRNGKey(40), (nleaves, d, d))
    fns = {
        "batched_registry": jax.jit(lambda a, r: kops.linear_solve(a, r)),
        "unrolled_jnp": jax.jit(lambda a, r: jnp.stack(
            [core_lu_solve(blocked_lu(a[i], block=d), r[i]) for i in range(nleaves)]
        )),
    }
    for impl, t in time_shootout(fns, a3, r3, iters=5).items():
        rows_us[f"opt_precond_b{nleaves}_n{d}_{impl}"] = t * 1e6
        emit(f"opt_precond_b{nleaves}_n{d}_{impl}", t)

    # --- serving trajectory: decode host-sync fix (before/after), ragged
    # continuous-batching throughput, the paged KV cache (capacity ratio +
    # shared-prefix warm/cold, gated in scripts/check.sh), and the solve
    # service's factorization cache (serve_solve_cache_cached must beat
    # _refactor >= 2x; gated in scripts/check.sh).
    from . import serve_bench

    for name, t in serve_bench.run().items():
        # *_capacity rows are dimensionless ratios, not seconds
        rows_us[name] = t if name.endswith("_capacity") else t * 1e6

    # --- accuracy tiers: the approximate backends' wall time AND measured
    # relative residual.  The ``*_residual`` companion rows are what
    # scripts/check.sh gates against the bounds the backends declare
    # (``BF16_IR_RESIDUAL_FLOOR`` / ``RAND_LU_RESIDUAL_BOUND``) — an
    # approximate tier that drifts past its advertised accuracy fails CI,
    # not just a unit test at toy sizes.
    from repro.solvers.backends import RAND_LU_RESIDUAL_BOUND

    n = 1024
    a = make_diagonally_dominant(jax.random.PRNGKey(n), n)
    b = jax.random.normal(jax.random.PRNGKey(1), (n,))
    ir_tol = 1e-5
    bf16_fn = functools.partial(kops.linear_solve, a, b, tolerance=ir_tol, impl="bf16_ir")
    t = time_call(bf16_fn, iters=5)
    x = bf16_fn()
    resid = float(jnp.linalg.norm(a @ x - b) / jnp.linalg.norm(b))
    rows_us["lu_n1024_bf16_ir"] = t * 1e6
    emit("lu_n1024_bf16_ir", t)
    rows_us["lu_n1024_bf16_ir_residual"] = resid
    print(f"lu_n1024_bf16_ir_residual,{resid:.3e},relative_residual", flush=True)

    if full:
        # ~2.7 s of the smoke wall clock for a row whose residual contract
        # the chaos drill (scenario 3) already exercises on every check.sh
        # run — so the timing row rides only with ``--smoke --full``.  The
        # residual gate in scripts/check.sh is present-conditional.
        nr, k = 2048, 256
        g1 = jax.random.normal(jax.random.PRNGKey(2), (nr, k))
        g2 = jax.random.normal(jax.random.PRNGKey(3), (k, nr))
        alr = (g1 @ g2) / k  # numerical rank k — the randomized tier's operand class
        xtrue = jax.random.normal(jax.random.PRNGKey(4), (nr,))
        blr = alr @ xtrue  # range-consistent RHS
        rand_fn = functools.partial(
            kops.linear_solve, alr, blr, rank=k, tolerance=RAND_LU_RESIDUAL_BOUND
        )
        t = time_call(rand_fn, iters=3)
        x = rand_fn()
        resid = float(jnp.linalg.norm(alr @ x - blr) / jnp.linalg.norm(blr))
        rows_us[f"rand_lu_n{nr}_k{k}"] = t * 1e6
        emit(f"rand_lu_n{nr}_k{k}", t)
        rows_us[f"rand_lu_n{nr}_k{k}_residual"] = resid
        print(f"rand_lu_n{nr}_k{k}_residual,{resid:.3e},relative_residual", flush=True)

    if out_path is None:
        out_path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "BENCH_kernels.json")
    with open(out_path, "w") as f:
        # timing rows round to 0.1 µs; residual companion rows are ~1e-6
        # and must survive serialization un-flattened
        json.dump(
            {k: (round(v, 1) if abs(v) >= 1 else v) for k, v in rows_us.items()},
            f, indent=2, sort_keys=True,
        )
        f.write("\n")
    print(f"wrote {out_path}", file=sys.stderr)
    return rows_us


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-size matrices (slow)")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset; writes BENCH_kernels.json")
    ap.add_argument(
        "--only", default=None,
        choices=["table1", "table2", "table3", "lm_step"],
    )
    args = ap.parse_args()

    print("name,us_per_call,derived")
    spike_cpu_s = None
    if args.smoke and _cpu_only():
        # the emulated-mesh SPIKE child runs before this process imports
        # JAX, and only where no accelerator is in play
        spike_cpu_s = _spike_subprocess_row(SMOKE_BANDED_N, SMOKE_BANDED_BW, devices=8)

    from repro.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    if args.smoke:
        smoke(full=args.full, spike_cpu_s=spike_cpu_s)
        return

    from . import table1_sparse, table2_dense, table3_transfer, lm_step

    mods = {
        "table1": table1_sparse,
        "table2": table2_dense,
        "table3": table3_transfer,
        "lm_step": lm_step,
    }
    for name, mod in mods.items():
        if args.only and name != args.only:
            continue
        try:
            mod.run(full=args.full)
        except Exception as e:  # keep the suite going; a failed table is a bug
            print(f"{name}_FAILED,0,{type(e).__name__}:{e}", file=sys.stderr)
            raise


if __name__ == "__main__":
    main()
