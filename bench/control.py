"""Read the correctness comparison's two ends on the chip.

For each seed, runs the cell once as ``bench/run.py`` does (window of
``--seconds`` at the cell's own load) and, on the same sample of served
requests, decides ``correct`` twice at the cell's committed limit: for the
program's tokens, and for the control's, the reference computed one
precision step below the configuration's bf16 (float8 e4m3 weights and
matmul inputs), whose first token at each position stands in for the
served one.  One JSON line per seed; exits 1 if the control comes out
correct on any seed.

  python bench/control.py --workload granite-3-2b.chat-mixed \
      --seconds 20 --seeds 101 102 103
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench.harness import main as harness
    from bench.harness.spec import load_cell
    cell = load_cell(args.workload)
    failed = False
    for seed in args.seeds:
        res = harness.run(cell, seed=seed, seconds=args.seconds, trace=False,
                          control=True)
        ctl = res.get("control", {"correct": None})
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "served": res["check"].get("max_logit_gap"),
                          "control": ctl, "metrics": res["metrics"]}),
              flush=True)
        failed |= ctl["correct"] is not False
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
