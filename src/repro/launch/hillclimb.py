import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimb driver: tagged dry-run variants for the three chosen
(arch × shape) pairs, each with an explicit hypothesis (see EXPERIMENTS.md
§Perf for the full hypothesis → change → before/after → verdict log).

  PYTHONPATH=src python -m repro.launch.hillclimb --target granite-decode
"""
import argparse
import dataclasses
import json

from repro.launch import dryrun
from repro.launch.compile_cache import enable_compile_cache


def _report(rec):
    from benchmarks.roofline import analyze
    a = analyze(rec, correct=False)  # raw terms: consistent A/B within a pair
    return (f"tag={rec['tag'] or 'baseline':14s} "
            f"compute={a['compute_s']*1e3:9.2f}ms memory={a['memory_s']*1e3:9.2f}ms "
            f"coll={a['collective_s']*1e3:9.2f}ms dominant={a['dominant']:10s} "
            f"peak={a['peak_gib_per_device']:7.2f}GiB")


# ---------------------------------------------------------------------------
# variants per target
# ---------------------------------------------------------------------------

def granite_decode():
    """H3: decode is memory-bound (KV cache streaming).  Changes:
    pet   — bf16 matmul operands w/ f32 accumulation (no f32 cache copies);
            [applied in attention._sdpa — the live code IS the variant]
    """
    yield dict(tag="pet")  # current code (post-_sdpa change)
    # iteration 2: int8 KV cache (per-row scales) — halves resident cache
    # bytes; Pallas decode kernel dequantizes in VMEM on TPU.
    yield dict(tag="kvquant8",
               config_transform=lambda c: dataclasses.replace(c, kv_quant=True))


def deepseek_train():
    """H1: memory-bound at 362 GiB/dev; peak = full (L,L) f32 scores + remat
    residuals.  Changes:
    mb8       — 8 microbatches: activation batch 16→2 per ubatch;
    chunk512  — q-chunked attention: scores (L,L)→(512,L);
    mb8+chunk — both;
    +seqshard — also shard residual seq dim over 'model'.
    """
    yield dict(tag="mb8", microbatches=8)
    yield dict(tag="chunk512",
               config_transform=lambda c: dataclasses.replace(c, train_attn_chunk=512))
    yield dict(tag="mb8_chunk512", microbatches=8,
               config_transform=lambda c: dataclasses.replace(c, train_attn_chunk=512))
    yield dict(tag="mb8_chunk512_seqshard", microbatches=8,
               config_transform=lambda c: dataclasses.replace(
                   c, train_attn_chunk=512, shard_activations_seq=True))
    # iteration 2 (after measuring the above): donation aliasing + FSDP
    yield dict(tag="seqshard_donate",
               config_transform=lambda c: dataclasses.replace(
                   c, train_attn_chunk=512, shard_activations_seq=True))
    yield dict(tag="seqshard_donate_fsdp",
               rules_overrides={"embed": "data"},
               config_transform=lambda c: dataclasses.replace(
                   c, train_attn_chunk=512, shard_activations_seq=True))
    # iteration 3: fix f32 update promotion (donation now aliases) and try
    # 2-D weight sharding on the WIDE dim only (ff/heads over data×model)
    # instead of the embed-dim FSDP that exploded in iteration 2.
    yield dict(tag="seqshard_dtype",
               config_transform=lambda c: dataclasses.replace(
                   c, train_attn_chunk=512, shard_activations_seq=True))
    yield dict(tag="seqshard_dtype_wide2d",
               rules_overrides={"ff": ("data", "model"),
                                "heads_x_dim": ("data", "model"),
                                "kv_heads_x_dim": ("data", "model"),
                                "vocab": ("data", "model")},
               config_transform=lambda c: dataclasses.replace(
                   c, train_attn_chunk=512, shard_activations_seq=True))


def qwen3_train():
    """H2: collective-bound at 3.87 s (all-gather 132 GiB/dev from the MoE
    scatter).  Changes:
    g16        — dispatch_groups=16 (data-axis-aligned shard-local scatter);
    g16+mb4    — plus microbatching (also shrinks dispatch working set).
    """
    def set_groups(c, g, **kw):
        return dataclasses.replace(c, moe=dataclasses.replace(c.moe, dispatch_groups=g), **kw)
    yield dict(tag="g16", config_transform=lambda c: set_groups(c, 16))
    yield dict(tag="g16_mb4", microbatches=4,
               config_transform=lambda c: set_groups(c, 16))
    # iteration 2: + donation aliasing + seq-sharded activations
    yield dict(tag="g16_mb4_seqshard_donate", microbatches=4,
               config_transform=lambda c: set_groups(c, 16, shard_activations_seq=True))


TARGETS = {
    "granite-decode": ("granite-3-2b", "decode_32k", granite_decode),
    "deepseek-train": ("deepseek-67b", "train_4k", deepseek_train),
    "qwen3-train": ("qwen3-moe-30b-a3b", "train_4k", qwen3_train),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--target", choices=sorted(TARGETS) + ["all"], default="all")
    args = ap.parse_args()
    enable_compile_cache()
    targets = sorted(TARGETS) if args.target == "all" else [args.target]
    for t in targets:
        arch, shape, gen = TARGETS[t]
        print(f"=== {t}: {arch} × {shape} ===")
        base_path = os.path.join(dryrun.OUT_DIR, f"{arch}__{shape}__pod16x16.json")
        if os.path.exists(base_path):
            with open(base_path) as f:
                print("  " + _report(json.load(f)) + "   <- paper-faithful baseline")
        for variant in gen():
            tag = variant.pop("tag")
            done = os.path.join(dryrun.OUT_DIR,
                                f"{arch}__{shape}__pod16x16__{tag}.json")
            if os.path.exists(done):
                with open(done) as f:
                    print("  " + _report(json.load(f)) + "   (cached)", flush=True)
                continue
            overrides = variant.pop("rules_overrides", None)
            if overrides:
                from repro.distributed.sharding import ShardingRules
                variant["rules"] = ShardingRules.default(overrides)
            rec = dryrun.run_one(arch, shape, tag=tag, **variant)
            print("  " + _report(rec), flush=True)


if __name__ == "__main__":
    main()
