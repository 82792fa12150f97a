"""Paged chunk-attention kernel model: gather path vs fused.

Computes, per (prefix_len, block_size) point, the MODELED per-chunk HBM
bytes of the XLA gather path (densify the pre-chunk page pool through the
block table + two-segment masked softmax — exactly what
``attend_prefill_chunk_paged`` falls back to) and of the fused Pallas
paged prefill-chunk kernel (``kernels/paged_prefill_attention.py``): the
gather path moves the whole padded pool slice three times (pool read ->
densified write -> attention read), the fused kernel streams only the
live pages once, in place.  Kernel times come from the chip's profiler
trace (``bench/``), never from here.

Also sweeps the paged decode kernel's multi-page kv tiles
(``pages_per_tile``) across block sizes.

Emits ``BENCH_kernels.json``:

  PYTHONPATH=src python benchmarks/kernel_bench.py [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np


def _time_call(fn, *args, iters: int) -> float:
    """Median wall seconds per call (after one warm/compile call)."""
    jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples))


def modeled_chunk_hbm_bytes(*, prefix: int, table_tokens: int, bs: int,
                            chunk: int, num_q_heads: int, kv_heads: int,
                            head_dim: int, itemsize: int,
                            pages_per_tile: int, fused: bool) -> int:
    """Per-chunk-attention HBM byte model (KV + q/out/in-chunk terms).

    gather: the pool slice named by the (sentinel-padded, ``table_tokens``
    wide) block table is read, written back densified, and read again by
    the attention — 3 passes over k+v regardless of how much of the table
    is live.  fused: the kernel streams each live page once per KV head
    (the GQA group's queries ride in one tile) and tiles wholly past
    ``prefix`` keep a clamped, unchanged block index so the pipeline
    elides their DMAs — charged at tile granularity
    (``pages_per_tile * bs`` rows), minimum one tile (the clamped dead
    fetch of the first grid step).
    """
    row = kv_heads * head_dim * itemsize
    q_out = 2 * num_q_heads * chunk * head_dim * itemsize
    chunk_kv = 2 * chunk * row
    if fused:
        tile_rows = pages_per_tile * bs
        live_rows = min(max(math.ceil(prefix / tile_rows), 1) * tile_rows,
                        table_tokens)
        kv = 2 * live_rows * row
    else:
        kv = 3 * 2 * table_tokens * row
    return kv + chunk_kv + q_out


def bench_prefill_chunk(prefixes, block_sizes, *, chunk, num_q_heads,
                        kv_heads, head_dim):
    from repro.kernels.paged_decode_attention import auto_pages_per_tile

    rows = []
    for bs in block_sizes:
        for prefix in prefixes:
            nb = math.ceil((prefix + chunk) / bs)   # table covers the prompt
            P = auto_pages_per_tile(bs, nb)
            model = dict(prefix=prefix, table_tokens=nb * bs, bs=bs,
                         chunk=chunk, num_q_heads=num_q_heads,
                         kv_heads=kv_heads, head_dim=head_dim, itemsize=4,
                         pages_per_tile=P)
            g_bytes = modeled_chunk_hbm_bytes(fused=False, **model)
            f_bytes = modeled_chunk_hbm_bytes(fused=True, **model)
            rows.append({
                "prefix": prefix, "block_size": bs, "chunk": chunk,
                "pages_per_tile": P,
                "gather_hbm_bytes": g_bytes,
                "fused_hbm_bytes": f_bytes,
                "hbm_bytes_saved": g_bytes - f_bytes,
                "hbm_ratio": round(g_bytes / f_bytes, 3),
            })
    return rows


def cumulative_prefill(prompt_lens, block_sizes, *, chunk, num_q_heads,
                       kv_heads, head_dim):
    """Whole-prompt totals: per-chunk bytes summed over every chunk of the
    prefill (the gather path re-densifies the FULL table each chunk, which
    is what made chunked prefill quadratic in HBM traffic)."""
    rows = []
    for bs in block_sizes:
        for L in prompt_lens:
            table = math.ceil(L / bs) * bs
            n_chunks = math.ceil(L / chunk)
            g = f = 0
            from repro.kernels.paged_decode_attention import \
                auto_pages_per_tile
            P = auto_pages_per_tile(bs, table // bs)
            for i in range(n_chunks):
                model = dict(prefix=i * chunk, table_tokens=table, bs=bs,
                             chunk=chunk, num_q_heads=num_q_heads,
                             kv_heads=kv_heads, head_dim=head_dim,
                             itemsize=4, pages_per_tile=P)
                g += modeled_chunk_hbm_bytes(fused=False, **model)
                f += modeled_chunk_hbm_bytes(fused=True, **model)
            rows.append({"prompt_len": L, "block_size": bs, "chunk": chunk,
                         "gather_hbm_bytes": g, "fused_hbm_bytes": f,
                         "hbm_ratio": round(g / f, 3)})
    return rows


def bench_decode_tiles(block_sizes, *, kv_tokens, num_q_heads, kv_heads,
                       head_dim, iters):
    """Paged decode wall time: single-page grid steps vs auto multi-page
    tiles (identical HBM traffic — the win is MXU tile occupancy, so TPU
    wall time is the metric; interpret-mode numbers only sanity-check that
    fewer grid steps run)."""
    from repro.kernels import ops
    from repro.kernels.paged_decode_attention import auto_pages_per_tile

    rows = []
    rng = np.random.default_rng(1)
    for bs in block_sizes:
        nb = kv_tokens // bs
        N = nb + 8
        q = rng.standard_normal((1, num_q_heads, head_dim)).astype(np.float32)
        kp = rng.standard_normal((N, kv_heads, bs, head_dim)).astype(np.float32)
        vp = rng.standard_normal((N, kv_heads, bs, head_dim)).astype(np.float32)
        bt = rng.permutation(N)[:nb].reshape(1, nb).astype(np.int32)
        lengths = np.array([kv_tokens - 3], np.int32)
        auto_p = auto_pages_per_tile(bs, nb)
        entry = {"block_size": bs, "kv_tokens": kv_tokens,
                 "auto_pages_per_tile": auto_p}
        for label, P in (("single_page_us", 1), ("multi_page_us", auto_p)):
            us = _time_call(
                lambda *a, P=P: ops.paged_decode_attention(
                    *a, pages_per_tile=P),
                jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(bt), jnp.asarray(lengths), iters=iters) * 1e6
            entry[label] = round(us, 1)
        rows.append(entry)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small CI sweep (still covers >= 2k prefixes)")
    ap.add_argument("--out", default="BENCH_kernels.json")
    ap.add_argument("--iters", type=int, default=None)
    args = ap.parse_args()

    if args.smoke:
        prefixes = [256, 2048, 4096]
        block_sizes = [16]
        prompt_lens = [2048, 4096]
        shape = dict(chunk=64, num_q_heads=4, kv_heads=2, head_dim=32)
        iters = args.iters or 3
    else:
        prefixes = [256, 512, 1024, 2048, 4096, 8192]
        block_sizes = [8, 16, 32]
        prompt_lens = [2048, 8192]
        shape = dict(chunk=128, num_q_heads=8, kv_heads=2, head_dim=64)
        iters = args.iters or 5

    on_tpu = jax.default_backend() == "tpu"
    t0 = time.time()
    result = {
        "meta": {
            "backend": jax.default_backend(),
            "pallas_interpret": not on_tpu,
            "shape": shape,
            "note": ("gather/fused modeled HBM bytes hold on any "
                     "backend; decode tile wall times run the Pallas "
                     "kernel in interpret mode off-TPU (Python per grid "
                     "step — not a perf number)"),
        },
        "prefill_chunk": bench_prefill_chunk(prefixes, block_sizes, **shape),
        "prefill_total": cumulative_prefill(prompt_lens, block_sizes, **shape),
        "decode_tiles": bench_decode_tiles(
            block_sizes, kv_tokens=2048 if args.smoke else 4096,
            iters=iters, num_q_heads=shape["num_q_heads"],
            kv_heads=shape["kv_heads"], head_dim=shape["head_dim"]),
    }
    result["meta"]["wall_seconds"] = round(time.time() - t0, 1)

    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"wrote {args.out} ({result['meta']['wall_seconds']}s)")
    for r in result["prefill_chunk"]:
        print(f"prefill bs={r['block_size']:>3} prefix={r['prefix']:>5}: "
              f"gather {r['gather_hbm_bytes']:>12,} B vs fused "
              f"{r['fused_hbm_bytes']:>12,} B  ({r['hbm_ratio']}x)")


if __name__ == "__main__":
    main()
