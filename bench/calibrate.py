"""Readings that the check's limits are set from: the served path's
widest logit gap, and the control's, over many seeds in one process.

    python bench/calibrate.py --workload gpt2s-chat --seconds 10 \\
        --seeds 1,2,3 --control fp8

For each seed, runs the cell as ``bench/run.py`` does (weights, warm-up,
the mix's warm phase, a ``--seconds`` window at the cell's own load),
then reads, over the same seeded sample of finished requests, the served
tokens' widest gap below the reference's best logit, and the widest gap
of the tokens that the reference computed in the control's precision puts
first, and puts that gap through the same rule as the served one: the
control has to read ``control_correct: false``.  Prints one JSON line per
seed.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="fp8")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import bench.run as br

    br._prepare_env()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = br.run_cell(ROOT, args.workload, seed, args.seconds, False,
                          control=args.control)
        ctl = out.get("control") or {}
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": out["correct"], "check": out["check"],
            "control_max_logit_gap": ctl.get("max_logit_gap"),
            "control_correct": ctl.get("correct"),
            "memory_peak_bytes": out["device"]["memory_peak_bytes"],
            "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
