#!/usr/bin/env python3
"""The readings that a cell's limits are set from, in one process on the chip.

    python3 bench/control.py --workload <cell>[,<cell>...] [--seeds 12] [--control-seeds 3]
        [--precisions highest,high,bfloat16] [--seconds 5] [--first-seed 1000]

Runs each cell as a benchmark run does, with a short window: first the
program on ``--seeds`` seeds (the lower readings), then the configuration's
plain reference put in the program's place at each precision of
``--precisions`` on ``--control-seeds`` seeds (``highest`` shows the
reference sound; a lower precision is the control, the upper reading).
Prints one JSON line per run and a summary line last.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

from run import prepare_environment


def _readings(cell: str, args) -> bool:
    from bench import core, system
    from bench.run import run_cell

    w = core.workload(core.load_benchmark(), cell)
    config = core.data(core.ROOT, "configs", w["config"])
    ref = core.module(core.ROOT, "reference", config["reference"])
    plan = [("program", None, args.first_seed + i) for i in range(args.seeds)]
    for p in filter(None, args.precisions.split(",")):
        plan += [(f"reference@{p}", p, args.first_seed + 100 + i) for i in range(args.control_seeds)]

    readings: dict[str, list[dict]] = {}
    for label, precision, seed in plan:
        solver = None if precision is None else system.Reference(ref, precision)
        try:
            out = run_cell(cell, seed, args.seconds, False, solver=solver)
        except core.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return False
        res = out["result"]
        line = {"workload": cell, "solver": label, "seed": seed, "correct": res["correct"],
                "attempted": res["attempted"], "failed": res["failed"],
                "readings": out["readings"], "notes": out["notes"][1:4]}
        print(json.dumps(line), flush=True)
        readings.setdefault(label, []).append(out["readings"])
    summary = {
        label: {name: {"min": min(r[name] for r in rs), "max": max(r[name] for r in rs)}
                for name in rs[0]}
        for label, rs in readings.items()
    }
    print(json.dumps({"workload": cell, "limits": config["limits"], "summary": summary}), flush=True)
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--precisions", default="highest,high,bfloat16")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--first-seed", type=int, default=1000)
    args = ap.parse_args(argv)
    prepare_environment()
    for cell in args.workload.split(","):
        if not _readings(cell, args):
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
