"""Run one benchmark cell once.

  python bench/run.py --workload granite-3-2b.chat-mixed --seed 1 \
      --seconds 51 --trace 0

Builds the cell's served path (``launch/serve.py`` ``build_cluster`` on
``ThreadedCluster``) from the configuration and traffic files that
``BENCHMARK.json`` names, warms every shape the window uses, serves the
cell's open-loop traffic for ``--seconds``, then compares a sample of what
it served with the plain reference.  The last line of stdout is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
end-to-end metrics with ``--trace 0``, the per-layer ones with
``--trace 1``), ``device`` and, last, ``check``: each number compared
beside its limit.  JAX's first device must be a TPU; otherwise the run
exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    from bench.harness import main as harness
    t_start = harness.process_start()
    args = parse(argv)
    from bench.harness.spec import load_cell
    cell = load_cell(args.workload)
    try:
        result = harness.run(cell, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), t_start=t_start)
    except harness.NoDevice as e:
        print(f"run: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
