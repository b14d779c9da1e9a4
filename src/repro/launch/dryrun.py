import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede any jax import/initialization (device count locks on first
#   backend init).  512 host devices back both production meshes.

"""Multi-pod dry-run driver (deliverable e).

For every (architecture × shape-cell) and both production meshes, lower +
compile the right step function against ShapeDtypeStruct inputs with full
sharding annotations, then record:

  * ``memory_analysis()``  — per-device bytes (proves it fits a 16 GB v5e);
  * ``cost_analysis()``    — per-device HLO FLOPs / bytes (roofline terms);
  * collective operand bytes parsed from the compiled HLO;
  * the op histogram and compile wall time.

Artifacts: ``artifacts/dryrun/<mesh>/<arch>__<cell>.json`` (cached; --force
re-runs).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --mesh single --arch llama3_8b --cell train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --mesh both --all
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs.base import ARCH_IDS, SHAPE_CELLS, get_config, cell_applicable
from repro.dist import sharding as shlib
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.utils import flags
from repro.utils.hlo import collective_bytes, cost_analysis_dict, op_histogram

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")


def _reduced_depth(cfg, layers: int):
    kw = {"num_layers": layers}
    if cfg.family == "encdec":
        kw["encoder_layers"] = layers
    return cfg.replace(**kw)


def _compile_cost(cfg, cell, mesh, rules):
    """Lower+compile with all scans unrolled; exact static cost/collectives."""
    rules = shlib.rules_for(cfg, mesh, rules)
    with shlib.use_mesh_rules(mesh, rules), flags.analysis_unroll():
        fn, args, axes = S.make_cell_fn(cfg, cell)
        in_sh = S.shardings_for_args(args, axes, mesh, rules)
        compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
    cost = cost_analysis_dict(compiled)
    hlo = compiled.as_text()
    coll = collective_bytes(hlo, num_devices=mesh.devices.size, weighted=True)
    return {
        "flops": cost.get("flops", 0.0),
        "bytes": cost.get("bytes accessed", 0.0),
        "wire": coll["total_wire"],
        "operand": coll["total"],
    }


def extrapolated_cost(cfg, cell, mesh, rules) -> dict:
    """Exact per-device totals for the real depth via two-point linear
    extrapolation over unrolled reduced-depth compiles (scan bodies are
    depth-identical, so cost is affine in L — verified by the two points)."""
    l_real = cfg.num_layers
    l2, l4 = (2, 4) if l_real >= 4 else (1, 2)
    c2 = _compile_cost(_reduced_depth(cfg, l2), cell, mesh, rules)
    c4 = _compile_cost(_reduced_depth(cfg, l4), cell, mesh, rules)
    out = {}
    for k in ("flops", "bytes", "wire", "operand"):
        per_layer = (c4[k] - c2[k]) / (l4 - l2)
        out[k] = c2[k] + per_layer * (l_real - l2)
        out[f"{k}_per_layer"] = per_layer
    out["points"] = {f"L{l2}": c2, f"L{l4}": c4}
    return out


def run_cell(arch: str, cell_name: str, *, multi_pod: bool, out_dir: str, force: bool = False,
             rules: dict | None = None, tag: str = "", overrides: dict | None = None) -> dict:
    mesh_name = "multi" if multi_pod else "single"
    os.makedirs(os.path.join(out_dir, mesh_name), exist_ok=True)
    path = os.path.join(out_dir, mesh_name, f"{arch}__{cell_name}{tag}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    cell = SHAPE_CELLS[cell_name]
    ok, reason = cell_applicable(cfg, cell)
    record = {
        "arch": arch, "cell": cell_name, "mesh": mesh_name,
        "kind": cell.kind, "seq_len": cell.seq_len, "global_batch": cell.global_batch,
    }
    if not ok:
        record["status"] = "skipped"
        record["reason"] = reason
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    arch_rules = shlib.rules_for(cfg, mesh, rules)
    t0 = time.time()
    try:
        with shlib.use_mesh_rules(mesh, arch_rules):
            fn, args, axes = S.make_cell_fn(cfg, cell)
            in_sh = S.shardings_for_args(args, axes, mesh, arch_rules)
            donate = (0, 1) if cell.kind == "train" else ((1,) if cell.kind == "decode" else ())
            jitted = jax.jit(fn, in_shardings=in_sh, donate_argnums=donate)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = cost_analysis_dict(compiled)
        hlo = compiled.as_text()
        fallbacks = [list(x) for x in (shlib._CTX.log or [])]
        t1 = time.time()
        extrap = extrapolated_cost(cfg, cell, mesh, rules)
        record.update(
            status="ok",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            analysis_s=round(time.time() - t1, 1),
            devices=int(mesh.devices.size),
            memory={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_bytes_est": mem.argument_size_in_bytes
                + mem.output_size_in_bytes
                + mem.temp_size_in_bytes
                - mem.alias_size_in_bytes,
            },
            cost={
                # exact per-device totals (scan-unrolled two-point extrapolation)
                "flops_per_device": extrap["flops"],
                "bytes_per_device": extrap["bytes"],
                "wire_bytes_per_device": extrap["wire"],
                "collective_operand_bytes": extrap["operand"],
                "extrapolation": extrap["points"],
                # raw static analysis of the rolled-loop production compile
                # (while bodies counted once — kept for cross-reference)
                "flops_static_raw": cost.get("flops", 0.0),
                "bytes_static_raw": cost.get("bytes accessed", 0.0),
            },
            collectives=collective_bytes(hlo, num_devices=int(mesh.devices.size), weighted=True),
            ops=op_histogram(hlo),
            sharding_fallbacks=fallbacks,
        )
    except Exception as e:  # a failure here is a bug in the system — record it
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      trace=traceback.format_exc()[-2000:])
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--arch", choices=ARCH_IDS + ["all"], default="all")
    ap.add_argument("--cell", choices=list(SHAPE_CELLS) + ["all"], default="all")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(ARTIFACT_DIR))
    ap.add_argument("--rules", choices=["default", "zero3"], default="default",
                    help="sharding-rule preset (§Perf comparisons)")
    ap.add_argument("--schedule", choices=["rect", "tri", "ebv"], default=None,
                    help="attention schedule override (§Perf)")
    ap.add_argument("--tag", default="", help="artifact suffix for §Perf variants")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    cells = list(SHAPE_CELLS) if args.cell == "all" else [args.cell]
    meshes = [False, True] if args.mesh == "both" else [args.mesh == "multi"]
    rules = shlib.RULE_PRESETS[args.rules]
    overrides = {"attention_schedule": args.schedule} if args.schedule else None

    failures = 0
    for multi in meshes:
        for arch in archs:
            for cell in cells:
                rec = run_cell(arch, cell, multi_pod=multi, out_dir=args.out, force=args.force,
                               rules=rules, tag=args.tag, overrides=overrides)
                name = f"[{rec['mesh']:6s}] {arch:22s} {cell:12s}"
                if rec["status"] == "ok":
                    gb = rec["memory"]["peak_bytes_est"] / 2**30
                    fl = rec["cost"]["flops_per_device"]
                    cb = rec["cost"]["wire_bytes_per_device"] / 2**20
                    print(f"{name} OK   peak={gb:7.2f} GiB/dev  flops/dev={fl:.3e}  wire={cb:9.1f} MiB  "
                          f"compile={rec.get('compile_s', 0):.0f}s", flush=True)
                elif rec["status"] == "skipped":
                    print(f"{name} SKIP ({rec['reason'][:60]})", flush=True)
                else:
                    failures += 1
                    print(f"{name} FAIL {rec['error'][:120]}", flush=True)
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


if __name__ == "__main__":
    main()
