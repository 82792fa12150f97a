"""Engine prefix-sharing scenario: hit rate and pool blocks saved.

N requests sharing a 75%-length common prompt prefix are served with
``EngineConfig.prefix_sharing`` on vs off (paged backends), emitted as
``prefix_sharing`` rows carrying the prefix hit rate, the pool blocks
saved during the prompt phase (1 shared chain + N private tails vs N full
chains), COW copies, and a token-parity bit (the streams must be
identical in both modes).  Everything here is a count, so it holds on any
backend; host overhead per round is measured on the chip, from the
program's own spans (``bench/``: ``agent_host_ms_per_round``).

Emits ``BENCH_engine.json``:

  PYTHONPATH=src python benchmarks/engine_bench.py [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np


def _build(arch, num_layers, d_model):
    from repro.configs import ARCHITECTURES
    from repro.models import build_model
    cfg = ARCHITECTURES[arch].reduced(num_layers=num_layers, d_model=d_model)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return model, params


def _mk_engine(model, params, *, backend, batch, max_seq, prefix_sharing):
    from repro.serving import ContinuousBatchingEngine, EngineConfig
    cfg = EngineConfig(max_slots=batch, max_seq_len=max_seq, block_size=8,
                       prefill_chunk_tokens=16, attention_backend=backend,
                       prefix_sharing=prefix_sharing)
    return ContinuousBatchingEngine(model, params, cfg, model_name="bench")


def bench_prefix_sharing(model, params, *, backend, batch=8, prompt_len=32,
                         shared_frac=0.75, max_new=8):
    """N requests sharing a ``shared_frac`` common prompt prefix, served
    with prefix sharing on vs off: hit rate, prompt-phase pool blocks
    saved, COW copies, and a token-parity check."""
    from repro.core.request import Request
    rng = np.random.default_rng(11)
    shared_len = int(prompt_len * shared_frac)
    common = rng.integers(0, 100, size=shared_len).tolist()
    prompts = [common + rng.integers(0, 100,
                                     size=prompt_len - shared_len).tolist()
               for _ in range(batch)]

    def serve(sharing):
        eng = _mk_engine(model, params, backend=backend, batch=batch,
                         max_seq=prompt_len + max_new + 8,
                         prefix_sharing=sharing)
        reqs = [Request(prompt_tokens=p, model="bench", slo=1e9,
                        max_new_tokens=max_new) for p in prompts]
        # leader first: followers match the blocks its chunks publish
        assert eng.admit(reqs[0])
        while eng.prefilling_slots():
            eng.step()
        for r in reqs[1:]:
            assert eng.admit(r)
        while eng.prefilling_slots():
            eng.step()
        prompt_blocks = eng.block_mgr.used_blocks
        for _ in range(10 * max_new):
            eng.step()
            if all(r.finished() for r in reqs):
                break
        assert all(r.finished() for r in reqs)
        assert eng.block_mgr.used_blocks == 0
        return [r.output_tokens for r in reqs], prompt_blocks, eng.stats

    tokens_on, blocks_on, stats = serve(True)
    tokens_off, blocks_off, _ = serve(False)
    denom = max(stats.prompt_tokens_admitted, 1)
    return {
        "backend": backend, "batch": batch, "prompt_len": prompt_len,
        "shared_prefix_len": shared_len,
        "prefix_hits": stats.prefix_hits,
        "prefix_hit_rate": round(stats.prefix_shared_tokens / denom, 4),
        "prefix_shared_blocks": stats.prefix_shared_blocks,
        "prompt_pool_blocks_sharing": blocks_on,
        "prompt_pool_blocks_baseline": blocks_off,
        "blocks_saved": blocks_off - blocks_on,
        "cow_copies": stats.cow_copies,
        "tokens_match": tokens_on == tokens_off,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small CI run (paged-pallas only)")
    ap.add_argument("--out", default="BENCH_engine.json")
    args = ap.parse_args()

    if args.smoke:
        num_layers, d_model = 1, 64
        sharing_backends = ["paged-pallas"]
    else:
        num_layers, d_model = 2, 128
        sharing_backends = ["paged-xla", "paged-pallas"]

    model, params = _build("granite-3-2b", num_layers, d_model)

    t_start = time.time()
    # shared-prompt scenario (paged backends; 8 x 75%-shared prefixes)
    sharing_rows = []
    for backend in sharing_backends:
        row = bench_prefix_sharing(model, params, backend=backend)
        sharing_rows.append(row)
        print(f"{backend:>12} prefix-sharing: hit-rate "
              f"{row['prefix_hit_rate']:.0%}, blocks "
              f"{row['prompt_pool_blocks_baseline']} -> "
              f"{row['prompt_pool_blocks_sharing']} "
              f"(saved {row['blocks_saved']}), tokens_match="
              f"{row['tokens_match']}")

    result = {
        "meta": {
            "backend": jax.default_backend(),
            "model": {"arch": "granite-3-2b-reduced",
                      "num_layers": num_layers, "d_model": d_model},
            "wall_seconds": 0.0,
        },
        "prefix_sharing": sharing_rows,
    }
    result["meta"]["wall_seconds"] = round(time.time() - t_start, 1)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"wrote {args.out} ({result['meta']['wall_seconds']}s)")


if __name__ == "__main__":
    main()
