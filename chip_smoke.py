#!/usr/bin/env python3
"""Bring-up smoke: drive the system's main paths once on one TPU chip.

    python chip_smoke.py                 # one chip: solve-dense, solve-banded, serve-lm
    python chip_smoke.py --four-chips    # four chips: SPIKE vs replicated banded solve only
    python chip_smoke.py --cpu-rehearsal # tiny sizes on whatever platform JAX finds

Phases, each printing one result line in order:

* ``solve-dense``  — ``SolveService`` on a seeded diagonally dominant f32
  system, n=16384 (1 GiB, built on device): one factorization, then four
  right-hand sides over two flushes (the second a factorization-cache hit).
* ``solve-banded`` — the 2-D 5-point Poisson system of
  ``examples/cfd_poisson.py`` at nx=ny=256 (n=65536, bw=256), built directly
  in band form, through the same service.
* ``serve-lm``     — ``granite_moe_1b_a400m`` at its published widths with
  seeded random weights, through the engine ``launch/serve.py`` builds:
  8 ragged greedy requests, 4 slots, served twice.

Solves are checked on the host in float64 with the HPL scaled residual
‖Ax−b‖∞ / (‖A‖∞‖x‖∞·n·ε) (fail above 16) and the relative residual
‖Ax−b‖₂/‖b‖₂ (fail above 1e-4).  The scaled residual alone cannot see a
solve whose dots ran in bf16 passes on these well-conditioned operands;
the relative one can, and each solve phase proves it by a control: the
same solve from operands rounded to bf16 must fail that gate.  Every
factor/solve dispatch must be served by a Pallas kernel with zero
escalations.  Wall times printed here include compilation: they are
bring-up times, not benchmark metrics.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

The script runs in one process and exits non-zero, printing no result, when
JAX finds no TPU (unless ``--cpu-rehearsal``) or when the repository's
``src/repro`` package is not beside it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
HPL_BOUND = 16.0
# f32 accuracy: an f32 solve of the dense operand reads ~6e-7 at n=512,
# growing ~sqrt(n) to ~1.7e-6 at n=4096 (XLA:CPU, float32), so ~3.4e-6 at
# n=16384; a solve from bf16-rounded operands reads ~2e-3 at any n
REL_BOUND = 1e-4


class SmokeFailure(RuntimeError):
    pass


def _fail(msg: str):
    raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# dispatch accounting: which backend served each factor/solve, and no
# escalation anywhere
# ---------------------------------------------------------------------------
class DispatchWatch:
    def __init__(self, solvers):
        self.served: list[tuple[str, str, str]] = []
        self.escalations: list[tuple[str, str, str]] = []
        solvers.add_dispatch_hook(self._on_dispatch)
        solvers.add_escalation_hook(self._on_escalation)

    def _on_dispatch(self, problem, backend):
        self.served.append((problem.op, problem.structure, backend.name))

    def _on_escalation(self, problem, failed, nxt, reason):
        self.escalations.append((problem.op, failed, f"{nxt}: {reason}"))

    def take(self) -> list[tuple[str, str, str]]:
        out, self.served = self.served, []
        return out

    def check(self, phase: str, served, allowed) -> str:
        if self.escalations:
            _fail(f"{phase}: escalation(s) {self.escalations}")
        if not served:
            _fail(f"{phase}: no registry dispatch observed")
        bad = [s for s in served if not allowed(s[2])]
        if bad:
            _fail(f"{phase}: served by non-kernel backend(s) {bad}")
        names = []
        for op, structure, name in served:
            tag = f"{op}:{structure}={name}"
            if tag not in names:
                names.append(tag)
        return ",".join(names)


def _is_kernel(name: str) -> bool:
    return name.startswith("pallas")


# ---------------------------------------------------------------------------
# host-side float64 residuals
# ---------------------------------------------------------------------------
def _residuals(np, r, a_inf: float, x, b) -> tuple[float, float]:
    """Worst column's HPL scaled residual ‖r‖∞ / (‖A‖∞‖x‖∞·n·ε_f32) and
    plain relative residual ‖r‖₂/‖b‖₂, for ``r = Ax − b``."""
    n = r.shape[0]
    eps = float(np.finfo(np.float32).eps)
    scaled = np.abs(r).max(axis=0) / (a_inf * np.abs(x).max(axis=0) * n * eps)
    rel = np.linalg.norm(r, axis=0) / np.linalg.norm(b, axis=0)
    return float(scaled.max()), float(rel.max())


def dense_residual(np, a, b, x):
    a64 = np.asarray(a, np.float64)
    b64 = np.asarray(b, np.float64)
    x64 = np.asarray(x, np.float64)
    return _residuals(np, a64 @ x64 - b64, float(np.abs(a64).sum(axis=1).max()), x64, b64)


def band_residual(np, arow, b, x, bw: int):
    a64 = np.asarray(arow, np.float64)
    b64 = np.asarray(b, np.float64)
    x64 = np.asarray(x, np.float64)
    n = a64.shape[0]
    xp = np.concatenate([np.zeros((bw,) + x64.shape[1:]), x64, np.zeros((bw,) + x64.shape[1:])])
    ax = np.zeros_like(x64)
    for t in range(2 * bw + 1):  # arow[i, t] = A[i, i - bw + t]
        ax += a64[:, t : t + 1] * xp[t : t + n]
    return _residuals(np, ax - b64, float(np.abs(a64).sum(axis=1).max()), x64, b64)


def poisson_band(jnp, nx: int, ny: int):
    """Row-aligned band of the 5-point Poisson operator of
    ``examples/cfd_poisson.py`` (diagonal 4.05, Dirichlet), bandwidth nx —
    built directly in band form, never as the dense (n, n) matrix."""
    n, bw = nx * ny, nx
    p = jnp.arange(n)
    i, j = p % nx, p // nx
    arow = jnp.zeros((n, 2 * bw + 1), jnp.float32)
    arow = arow.at[:, bw].set(4.05)
    arow = arow.at[:, bw - 1].set(jnp.where(i > 0, -1.0, 0.0))
    arow = arow.at[:, bw + 1].set(jnp.where(i < nx - 1, -1.0, 0.0))
    arow = arow.at[:, 0].set(jnp.where(j > 0, -1.0, 0.0))
    arow = arow.at[:, 2 * bw].set(jnp.where(j < ny - 1, -1.0, 0.0))
    return arow, bw


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def _bf16(jnp, x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _service_phase(ctx, name, a, bw, rhs_keys, residual, control_solve):
    """Factor once and solve 4 right-hand sides over two flushes; then the
    bf16-operand control, which the relative-residual gate must reject."""
    jax, jnp, np = ctx["jax"], ctx["jnp"], ctx["np"]
    from repro.serve.solve_service import SolveService

    n = a.shape[0]
    bs = [jax.random.normal(k, (n,), jnp.float32) for k in rhs_keys]
    svc = SolveService()
    watch = ctx["watch"]
    watch.take()
    times, xs = [], []
    for flush in (bs[:2], bs[2:]):
        t0 = time.perf_counter()
        tickets = [svc.submit(a, b, bw=bw) for b in flush]
        out = svc.flush()
        res = [out[t] for t in tickets]
        for r in res:
            if not hasattr(r, "shape"):
                _fail(f"{name}: request failed: {r!r}")
        jax.block_until_ready(res)
        times.append(time.perf_counter() - t0)
        xs.extend(res)
    st = svc.stats
    if st.factor_dispatches != 1 or st.cache_hits < 3:
        _fail(f"{name}: expected one factorization and cache hits, got {st}")
    served = watch.check(name, watch.take(), _is_kernel)
    b = jnp.stack(bs, axis=1)
    scaled, rel = residual(np, a, b, jnp.stack(xs, axis=1))
    x_ctl = control_solve(_bf16(jnp, a), _bf16(jnp, b))
    watch.check(f"{name}/bf16-control", watch.take(), _is_kernel)
    _, rel_ctl = residual(np, a, b, x_ctl)
    ok = scaled <= HPL_BOUND and rel <= REL_BOUND
    print(
        f"{name}: n={n} bw={bw} rhs={len(bs)} served=[{served}] "
        f"factor_dispatches={st.factor_dispatches} cache_hits={st.cache_hits} "
        f"hpl_scaled_residual={scaled!r} relative_residual={rel!r} "
        f"bf16_operand_control_relative_residual={rel_ctl!r} "
        f"bringup_wall_s(flush1_factor+solve,incl_compile)={times[0]!r} "
        f"bringup_wall_s(flush2_solve)={times[1]!r} {'PASS' if ok else 'FAIL'}",
        flush=True,
    )
    if scaled > HPL_BOUND:
        _fail(f"{name}: HPL scaled residual {scaled} > {HPL_BOUND}")
    if rel > REL_BOUND:
        _fail(f"{name}: relative residual {rel} > {REL_BOUND}")
    if rel_ctl <= REL_BOUND:
        _fail(f"{name}: the bf16-operand control passed the relative-residual "
              f"gate ({rel_ctl} <= {REL_BOUND}); the gate cannot see bf16 error")


def phase_solve_dense(ctx, n: int):
    jax = ctx["jax"]
    from repro.core import make_diagonally_dominant

    key = jax.random.PRNGKey(ctx["seed"])
    ka, *kb = jax.random.split(key, 5)
    a = jax.jit(make_diagonally_dominant, static_argnums=1)(ka, n)

    def control(a16, b16):
        from repro.kernels import ops

        return ops.lu_solve(ops.lu(a16), b16)

    _service_phase(ctx, "solve-dense", a, 0, kb, dense_residual, control)


def phase_solve_banded(ctx, nx: int):
    jax, jnp = ctx["jax"], ctx["jnp"]
    arow, bw = poisson_band(jnp, nx, nx)
    kb = jax.random.split(jax.random.PRNGKey(ctx["seed"] + 1), 4)

    def residual(np, a, b, x):
        return band_residual(np, a, b, x, bw)

    def control(a16, b16):
        from repro.kernels import ops

        return ops.banded_solve(ops.banded_lu(a16, bw=bw), b16, bw=bw)

    _service_phase(ctx, "solve-banded", arow, bw, kb, residual, control)


def phase_serve_lm(ctx, reduced: bool):
    jax, np = ctx["jax"], ctx["np"]
    from repro.configs.base import get_config
    from repro.launch.serve import build_engine
    from repro.models import lm
    from repro.serve.engine import GenRequest

    cfg = get_config("granite_moe_1b_a400m")
    if reduced:
        cfg = cfg.reduced()
    t0 = time.perf_counter()
    params = jax.jit(lambda k: lm.init_params(k, cfg))(jax.random.PRNGKey(ctx["seed"] + 2))
    jax.block_until_ready(params)
    t_init = time.perf_counter() - t0
    rng = np.random.default_rng(ctx["seed"])
    lo, hi = (64, 256) if not reduced else (8, 32)
    new_tokens = 32 if not reduced else 8
    reqs = [
        GenRequest(
            tokens=rng.integers(0, cfg.vocab_size, (int(rng.integers(lo, hi + 1)),)).astype(np.int32),
            max_new_tokens=new_tokens, temperature=0.0, seed=i,
        )
        for i in range(8)
    ]
    eng = build_engine(params, cfg, prompt_len=hi, new_tokens=new_tokens, slots=4, bucket=64)
    runs, walls = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        outs = eng.serve(reqs)
        walls.append(time.perf_counter() - t0)
        runs.append([np.asarray(o) for o in outs])
    for k, (r, o) in enumerate(zip(reqs, runs[0])):
        if o.shape != (len(r.tokens) + new_tokens,):
            _fail(f"serve-lm: request {k} returned {o.shape}, expected {len(r.tokens) + new_tokens} tokens")
        if not np.array_equal(o[: len(r.tokens)], r.tokens):
            _fail(f"serve-lm: request {k} lost its prompt")
        if o.min() < 0 or o.max() >= cfg.vocab_size:
            _fail(f"serve-lm: request {k} produced out-of-vocabulary tokens")
    same = all(np.array_equal(a, b) for a, b in zip(*runs))
    new = sum(len(o) - len(r.tokens) for r, o in zip(reqs, runs[0]))
    print(
        f"serve-lm: arch={cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"experts={cfg.num_experts} top{cfg.experts_per_token} dtype={cfg.dtype} "
        f"requests={len(reqs)} answered={len(runs[0])} new_tokens={new} "
        f"prompt_lens={[len(r.tokens) for r in reqs]} deterministic={same} "
        f"bringup_wall_s(init)={t_init!r} bringup_wall_s(serve1,incl_compile)={walls[0]!r} "
        f"bringup_wall_s(serve2)={walls[1]!r} {'PASS' if same else 'FAIL'}",
        flush=True,
    )
    if not same:
        _fail("serve-lm: two serves of the same greedy requests differ")


def phase_four_chips(ctx, nx: int):
    """SPIKE split factor+solve over a 4-device mesh vs the replicated
    backend on the same Poisson operand, both residual-checked."""
    jax, jnp, np = ctx["jax"], ctx["jnp"], ctx["np"]
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh

    if len(jax.devices()) < 4:
        _fail(f"--four-chips needs 4 devices, JAX sees {len(jax.devices())}")
    mesh = make_mesh((4,), ("model",))
    arow, bw = poisson_band(jnp, nx, nx)
    b = jax.random.normal(jax.random.PRNGKey(ctx["seed"] + 3), (arow.shape[0], 2), jnp.float32)
    watch = ctx["watch"]
    for impl in ("spike", "replicated"):
        watch.take()
        t0 = time.perf_counter()
        factors = ops.banded_lu(arow, bw=bw, mesh=mesh, impl=impl)
        jax.block_until_ready(factors)
        t_f = time.perf_counter() - t0
        t0 = time.perf_counter()
        x = ops.banded_solve(factors, b, bw=bw, mesh=mesh if impl == "spike" else None)
        x = jax.block_until_ready(x)
        t_s = time.perf_counter() - t0
        served = watch.check(f"four-chips/{impl}", watch.take(),
                             lambda name: name in ("spike", "replicated") or _is_kernel(name))
        scaled, rel = band_residual(np, arow, b, x, bw)
        lu_leaf = getattr(factors, "local_lu", getattr(factors, "packed", None))
        ok = scaled <= HPL_BOUND and rel <= REL_BOUND
        print(
            f"four-chips/{impl}: n={arow.shape[0]} bw={bw} devices=4 served=[{served}] "
            f"factors_on={lu_leaf.sharding} solution_on={x.sharding} "
            f"hpl_scaled_residual={scaled!r} relative_residual={rel!r} "
            f"bringup_wall_s(factor,incl_compile)={t_f!r} bringup_wall_s(solve,incl_compile)={t_s!r} "
            f"{'PASS' if ok else 'FAIL'}",
            flush=True,
        )
        if not ok:
            _fail(f"four-chips/{impl}: HPL scaled residual {scaled} (bound {HPL_BOUND}) "
                  f"or relative residual {rel} (bound {REL_BOUND}) out of bounds")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the SPIKE-vs-replicated banded phase on 4 devices")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes; run on whatever platform JAX finds (not a chip run)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}; run from a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    if args.cpu_rehearsal and args.four_chips and "device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=4 " + os.environ.get("XLA_FLAGS", "")
        )

    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); nothing was run",
              file=sys.stderr)
        return 1

    from repro import solvers
    from repro.utils.compile_cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    tiny = args.cpu_rehearsal
    print(f"chip_smoke: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(jax.devices())} jax={jax.__version__} compile_cache={cache_dir}",
          flush=True)
    ctx = {"jax": jax, "jnp": jnp, "np": np, "seed": args.seed,
           "watch": DispatchWatch(solvers)}
    try:
        if args.four_chips:
            phase_four_chips(ctx, nx=32 if tiny else 256)
        else:
            phase_solve_dense(ctx, n=384 if tiny else 16384)
            phase_solve_banded(ctx, nx=16 if tiny else 256)
            phase_serve_lm(ctx, reduced=tiny)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    devices = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
