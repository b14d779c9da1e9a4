"""Roofline analysis (deliverable g): reads the dry-run artifacts and emits
the per-(arch × shape) three-term roofline table.

    compute    = HLO_FLOPs/device  / peak_FLOP/s          (197 TF bf16, v5e)
    memory     = HLO_bytes/device  / HBM_bw               (819 GB/s)
    collective = wire_bytes/device / link_bw              (50 GB/s/link, 1 link
                                                           conservatively)

HLO totals are the scan-unrolled two-point extrapolations recorded by
dryrun.py (exact static counts).  MODEL_FLOPS is the analytic useful work:
6·N·D (train), 2·N·D (prefill), 2·N·B (decode), with N → N_active for MoE.

    PYTHONPATH=src python -m repro.launch.roofline [--mesh single] [--md out.md]
"""
import argparse
import json
import os

from repro.configs.base import ARCH_IDS, SHAPE_CELLS, get_config
from repro.launch.mesh import peaks

# The dry-run compiles for a described v5e pod, so its estimate uses the
# peaks of that target device kind (not of whatever host runs the script).
TARGET_DEVICE_KIND = "TPU v5 lite"

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")


def param_counts(cfg):
    """(total, active) parameter counts — analytic, no tracing."""
    import jax
    from repro.models import lm

    struct = jax.eval_shape(lambda k: lm.init_params(k, cfg), jax.random.PRNGKey(0))
    total = expert_ffn = 0

    def walk(tree, path=""):
        nonlocal total, expert_ffn
        if isinstance(tree, dict):
            for k, v in tree.items():
                walk(v, path + "/" + k)
        else:
            total += tree.size
            # expert FFN weights scale by k/E; the router counts fully
            if "/moe/" in path and not path.endswith("/router"):
                expert_ffn += tree.size

    walk(struct)
    if cfg.num_experts:
        active = total - expert_ffn + expert_ffn * cfg.experts_per_token / cfg.num_experts
    else:
        active = total
    return total, active


def model_flops(cfg, cell, total, active):
    d_tokens = cell.global_batch * cell.seq_len
    if cell.kind == "train":
        return 6.0 * total * d_tokens if not cfg.num_experts else 6.0 * active * d_tokens
    if cell.kind == "prefill":
        return 2.0 * active * d_tokens
    return 2.0 * active * cell.global_batch  # decode: one token per sequence


def suggest(dominant, rec):
    if dominant == "collective":
        return "cut per-layer SP/FSDP gathers (resharding rules; DP-heavier layout) and overlap with compute"
    if dominant == "memory":
        return "raise arithmetic intensity: larger fused blocks, fewer remat round-trips, bf16 end-to-end"
    return "cut wasted FLOPs: triangular attention schedule, less remat recompute"


def analyze(mesh_name: str, out_dir: str):
    rows = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        total, active = param_counts(cfg)
        for cell_name, cell in SHAPE_CELLS.items():
            path = os.path.join(out_dir, mesh_name, f"{arch}__{cell_name}.json")
            if not os.path.exists(path):
                continue
            rec = json.load(open(path))
            if rec["status"] != "ok":
                rows.append({"arch": arch, "cell": cell_name, "status": rec["status"],
                             "reason": rec.get("reason", rec.get("error", ""))[:90]})
                continue
            c = rec["cost"]
            devices = rec["devices"]
            pk = peaks(TARGET_DEVICE_KIND)
            t_comp = c["flops_per_device"] / pk["flops_bf16"]
            t_mem = c["bytes_per_device"] / pk["hbm_bytes_s"]
            t_coll = c["wire_bytes_per_device"] / pk["ici_link_bytes_s"]
            terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
            dominant = max(terms, key=terms.get)
            mf = model_flops(cfg, cell, total, active)
            hlo_total = c["flops_per_device"] * devices
            useful = mf / hlo_total if hlo_total else 0.0
            # roofline fraction: useful work at peak vs the bound set by the
            # dominant term
            step_time = max(terms.values())
            frac = (mf / devices / pk["flops_bf16"]) / step_time if step_time else 0.0
            rows.append({
                "arch": arch, "cell": cell_name, "status": "ok",
                "compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll,
                "dominant": dominant, "model_flops": mf,
                "useful_ratio": useful, "roofline_frac": frac,
                "peak_gib": rec["memory"]["peak_bytes_est"] / 2**30,
                "suggestion": suggest(dominant, rec),
            })
    return rows


def to_markdown(rows, mesh_name):
    out = [f"### Roofline — {mesh_name} pod mesh (per-device terms, seconds/step)\n"]
    out.append("| arch | cell | compute s | memory s | collective s | dominant | MODEL_FLOPS | useful (model/HLO) | roofline frac | peak GiB/dev | next lever |")
    out.append("|---|---|---|---|---|---|---|---|---|---|---|")
    for r in rows:
        if r["status"] != "ok":
            out.append(f"| {r['arch']} | {r['cell']} | — | — | — | {r['status']} | — | — | — | — | {r.get('reason','')} |")
            continue
        out.append(
            f"| {r['arch']} | {r['cell']} | {r['compute_s']:.3e} | {r['memory_s']:.3e} | "
            f"{r['collective_s']:.3e} | **{r['dominant']}** | {r['model_flops']:.2e} | "
            f"{r['useful_ratio']:.2f} | {r['roofline_frac']:.3f} | {r['peak_gib']:.1f} | {r['suggestion']} |"
        )
    return "\n".join(out) + "\n"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--out", default=os.path.abspath(ARTIFACT_DIR))
    ap.add_argument("--json", default=None)
    ap.add_argument("--md", default=None)
    args = ap.parse_args()
    rows = analyze(args.mesh, args.out)
    md = to_markdown(rows, args.mesh)
    print(md)
    if args.json:
        json.dump(rows, open(args.json, "w"), indent=1)
    if args.md:
        open(args.md, "w").write(md)


if __name__ == "__main__":
    main()
