"""Find a cell's knee: serve its traffic at several fixed Poisson rates,
one window each, in one process, and print one JSON line per rate.

The knee is the highest rate at which at least 90% of the interactive
requests meet their TTFT limit and no backlog grows through the window
(the requests waiting for a slot at the window's end are no more than at
its middle).  The cell then runs at about four fifths of it, written as a
number into its traffic file.

  python bench/sweep.py --workload granite-3-2b.chat-mixed --seconds 51 \
      --seed 7 --rates 0.2 0.4 0.6 0.8
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def waiting(records, t: float) -> int:
    return sum(1 for r in records
               if r.due <= t and (r.admitted is None or r.admitted > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    from bench.harness import main as harness
    from bench.harness import stats, traffic
    from bench.harness.serving import Served
    from bench.harness.spec import load_cell
    from repro.launch.compile_cache import enable_compile_cache

    cell = load_cell(args.workload)
    harness.device_check(cell.chips, True)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    key = harness.seed_key(args.seed)
    for rate in args.rates:
        served = Served(cell.config, key, seed=args.seed)
        served.warm_up()
        arrivals = traffic.generate(
            dict(cell.traffic, rate_per_s=rate), seconds=args.seconds,
            seed=args.seed,
            max_seq_len=cell.config["engine"]["max_seq_len"],
            vocab_size=served.model_cfg.vocab_size)
        b = served.run_window(arrivals, seconds=args.seconds,
                              drain_s=cell.traffic["drain_s"])
        records = list(served.records.values())
        e2e = stats.end_to_end(records, start=b["start"], end=b["end"],
                               drain_end=b["drain_end"])
        inter = [r for r in records if r.slo_class == "interactive"]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(records),
            "interactive_ttft_met": sum(
                stats.met_limits(r, b["end"], b["drain_end"]) for r in inter)
            / max(len(inter), 1),
            "waiting_mid": waiting(records, (b["start"] + b["end"]) / 2),
            "waiting_end": waiting(records, b["end"]),
            **e2e}), flush=True)
        served.release()
        del served
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
