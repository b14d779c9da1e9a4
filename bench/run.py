#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic by the names in ``BENCHMARK.json``,
makes its operands on the device from ``--seed``, warms every shape the
traffic sends, measures whole units of work until ``--seconds`` have passed,
checks the answers against host float64 residuals, and prints one JSON line
last on standard output.  With ``--trace 0`` the line carries the cell's
end-to-end metrics; with ``--trace 1`` the window is traced and the line
carries its per-layer metrics, the device's busy time and a breakdown.
Each number compared is printed beside its limit as the last lines of
standard error.

Exits non-zero, printing no result, when JAX finds no TPU, fewer chips than
the cell asks for, or no ``src/repro`` beside ``bench/``.  The persistent
compile cache is ``artifacts/bench/jax_cache`` and traces go under
``artifacts/bench/trace``, both inside the checkout; solver dispatch reads
the empty autotune cache ``bench/solvers_cache.json``, so it is the
registry's static choice.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
ARTIFACTS = os.path.join(ROOT, "artifacts", "bench")
SOLVERS_CACHE = os.path.join(HERE, "solvers_cache.json")
TRACED_SPANS = ("generate", "factor", "solve", "submit", "flush")


def prepare_environment():
    """Import paths, the pinned autotune cache and the compile cache; before
    anything touches JAX's backends."""
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ["REPRO_SOLVERS_CACHE"] = SOLVERS_CACHE
    import jax

    jax.config.update("jax_compilation_cache_dir", os.path.join(ARTIFACTS, "jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _trace_dir(cell: str) -> str:
    path = os.path.join(ARTIFACTS, "trace", cell)
    shutil.rmtree(path, ignore_errors=True)
    return path


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def _finite(x):
    return x if x is not None and x == x and abs(x) != float("inf") else None


def _served(calls) -> str:
    counts: dict[str, int] = {}
    for c in calls:
        tag = f"{c['op']}:{c['structure']}={c['backend']}"
        counts[tag] = counts.get(tag, 0) + 1
    return " ".join(f"{tag}x{n}" for tag, n in sorted(counts.items())) or "none"


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *, root: str = ROOT,
             solver=None, require_chip: bool = True) -> dict:
    """Run ``cell`` once; returns the result line's fields plus ``notes``
    (earlier stdout lines) and ``check_lines`` (the readings beside their
    limits).  ``solver`` replaces the program (the control)."""
    import jax

    from bench import check, core, system, trace_reduce
    from repro import solvers

    bm = core.load_benchmark(root)
    w = core.workload(bm, cell)
    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise core.NoChip(f"JAX found no TPU (platform {devices[0].platform!r}); nothing was run")
    if require_chip and len(devices) < w["chips"]:
        raise core.NoChip(f"cell {cell} needs {w['chips']} chips, JAX sees {len(devices)}")
    used = devices[: w["chips"]]
    config = core.data(root, "configs", w["config"])
    traffic = core.data(root, "traffic", w["traffic"])
    gen = core.module(root, "generators", config["generator"])
    loop = core.module(root, "loops", traffic["loop"])
    if solver is None:
        solver = system.Program()
    run = core.Run(root, cell, config, traffic, gen, solver, seed, seconds, trace)
    run.device_kind = used[0].device_kind

    compiles = core.Compiles()
    hook = solvers.add_dispatch_hook(run.on_dispatch)
    try:
        state = loop.setup(run)
        if trace:
            log_dir = _trace_dir(cell)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        with compiles.installed(), jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            loop.window(run, state)
        if trace:
            jax.profiler.stop_trace()
            run.reduced = trace_reduce.reduce(trace_reduce.latest_trace(log_dir), TRACED_SPANS,
                                              host_ops_allowed=not require_chip)
            shutil.rmtree(log_dir, ignore_errors=True)
    finally:
        solvers.remove_dispatch_hook(hook)
    run.setup_s = run.window[0] - T_START
    memory_peak = _memory_peak(used)
    loop.release(run, state)
    readings = loop.check_answers(run, state)
    del state
    ok, check_lines = check.judge(readings, config["limits"])
    correct = ok and run.failed == 0 and run.attempted > 0

    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in core.metrics_of(bm, cell, section):
        value = core.module(root, "metrics", m["name"]).value(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": used[0].platform, "kind": used[0].device_kind, "count": len(used),
              "memory_peak_bytes": memory_peak}
    notes = [
        f"bench: cell={cell} seed={seed} solver={solver.name} platform={used[0].platform} "
        f"kind={used[0].device_kind!r} count={len(used)} jax={jax.__version__}",
        f"served (window): {_served(run.calls)}",
        "compiles_in_window: " + " ".join(
            f"{e.rsplit('/', 1)[-1]}={n}" for e, n in compiles.count.items()),
        f"window: attempted={run.attempted} answered={len(run.answers)} failed={run.failed} "
        f"window_s={run.window_s!r} setup_s={run.setup_s!r} memory_peak_bytes={memory_peak}",
    ] + run.notes
    result = {"correct": correct, "attempted": run.attempted, "failed": run.failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.reduced["busy_s"]
        device["window_s"] = run.reduced["window_s"]
        notes.append("device_busy_in_spans_s: " + json.dumps(run.reduced["busy_in_s"]))
        result["breakdown"] = {"device_ops": run.reduced["device_ops"],
                               "idle_gaps": run.reduced["idle_gaps"]}
    result["check"] = {name: {"value": _finite(readings.get(name)), "limit": limit}
                       for name, limit in config["limits"].items()}
    return {"result": result, "notes": notes, "check_lines": check_lines, "readings": readings}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"bench: no src/repro beside {HERE}; run from a checkout of the repository",
              file=sys.stderr)
        return 2
    prepare_environment()
    from bench import core

    try:
        out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except core.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for line in out["notes"]:
        print(line, flush=True)
    for line in out["check_lines"]:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
