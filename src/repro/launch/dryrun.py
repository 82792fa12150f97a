import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh).

For each combination this builds the right step function —
``train_step`` (train_4k), ``prefill`` (prefill_32k), ``serve_step``
(decode_32k / long_500k: ONE token against a full-length cache) — from
ShapeDtypeStruct inputs (no allocation), lowers it under the production
mesh with explicit NamedShardings, compiles, and records:

  * ``memory_analysis()``  (per-device argument/output/temp bytes),
  * ``cost_analysis()``    (per-device HLO FLOPs / bytes accessed),
  * collective-traffic stats parsed from the optimized HLO,

into ``experiments/dryrun/<arch>__<shape>__<mesh>.json`` for the roofline
pass (benchmarks/roofline.py).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import (INPUT_SHAPES, ARCHITECTURES, get_arch, get_shape,
                           shape_applicable)
from repro.configs.base import InputShape, ModelConfig
from repro.distributed.sharding import (ShardingRules, batch_axes_tree,
                                        build_shardings)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hlo_analysis import collective_stats
from repro.launch.mesh import make_production_mesh
from repro.models.model_factory import batch_struct, build_model
from repro.training.optimizer import AdamW, AdamWState
from repro.training.train_step import make_train_step

DTYPE = jnp.bfloat16
OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def adapt_config_for_shape(cfg: ModelConfig, shape: InputShape) -> ModelConfig:
    """Hardware adaptation hooks (DESIGN.md §4): zamba2's shared attention
    runs sliding-window in long-context mode so the 500k cache stays
    bounded."""
    if shape.name == "long_500k" and cfg.arch_type == "hybrid" \
            and cfg.sliding_window is None:
        return dataclasses.replace(cfg, sliding_window=4096)
    return cfg


def input_specs(arch: str, shape_name: str, dtype=DTYPE) -> Dict[str, Any]:
    """Public: ShapeDtypeStruct stand-ins for every model input."""
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    cfg = adapt_config_for_shape(cfg, shape)
    return batch_struct(cfg, shape.global_batch, shape.seq_len, shape.kind, dtype)


# ---------------------------------------------------------------------------

def build_lowerable(cfg: ModelConfig, shape: InputShape, mesh,
                    rules: Optional[ShardingRules] = None,
                    microbatches: int = 1, remat: bool = True):
    """Returns (jitted_fn, arg_structs, rules) ready to .lower()."""
    rules = rules or ShardingRules.default()
    cfg = adapt_config_for_shape(cfg, shape)
    model = build_model(cfg)
    params_struct = jax.eval_shape(lambda: model.init(jax.random.key(0), DTYPE))
    param_sh = build_shardings(mesh, params_struct, model.param_axes(), rules)
    data = batch_struct(cfg, shape.global_batch, shape.seq_len, shape.kind, DTYPE)
    data_sh = build_shardings(mesh, data, batch_axes_tree(data), rules)

    if shape.kind == "train":
        opt = AdamW(learning_rate=1e-4)
        opt_struct = jax.eval_shape(opt.init, params_struct)
        opt_sh = AdamWState(
            step=build_shardings(mesh, opt_struct.step, (), rules),
            mu=param_sh, nu=param_sh)
        step_fn = make_train_step(model, opt, microbatches=microbatches,
                                  remat=remat)
        # out_shardings must match the donated inputs or XLA can't alias
        # the params/opt buffers (§Perf H1 'donate': −params−opt of peak).
        metrics_struct = jax.eval_shape(step_fn, params_struct, opt_struct, data)[2]
        from repro.distributed.sharding import replicated
        fn = jax.jit(step_fn, in_shardings=(param_sh, opt_sh, data_sh),
                     out_shardings=(param_sh, opt_sh,
                                    replicated(mesh, metrics_struct)),
                     donate_argnums=(0, 1))
        return fn, (params_struct, opt_struct, data), rules

    cache_struct = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len, DTYPE))
    cache_sh = build_shardings(mesh, cache_struct, model.cache_axes(), rules)

    if shape.kind == "prefill":
        def prefill_fn(params, batch, cache):
            return model.prefill(params, batch, cache)
        fn = jax.jit(prefill_fn, in_shardings=(param_sh, data_sh, cache_sh),
                     donate_argnums=(2,))
        return fn, (params_struct, data, cache_struct), rules

    assert shape.kind == "decode"
    def serve_step(params, cache, tokens, lengths):
        return model.decode_step(params, cache, tokens, lengths)
    tok_sh = build_shardings(mesh, data["tokens"], ("batch",), rules)
    len_sh = build_shardings(mesh, data["lengths"], ("batch",), rules)
    fn = jax.jit(serve_step, in_shardings=(param_sh, cache_sh, tok_sh, len_sh),
                 donate_argnums=(1,))
    return fn, (params_struct, cache_struct, data["tokens"], data["lengths"]), rules


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            rules: Optional[ShardingRules] = None, microbatches: int = 1,
            remat: bool = True, save: bool = True,
            tag: str = "", config_transform=None) -> Dict[str, Any]:
    cfg = get_arch(arch)
    if config_transform is not None:
        cfg = config_transform(cfg)
    shape = get_shape(shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag,
        "applicable": shape_applicable(cfg, shape),
    }
    if not rec["applicable"]:
        rec["skip_reason"] = ("long_500k needs sub-quadratic decode; "
                              f"{arch} is full-attention (DESIGN.md §4)")
        if save:
            _save(rec)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.monotonic()
    with mesh:  # eval_shape may trace with_sharding_constraint
        fn, args, rules = build_lowerable(cfg, shape, mesh, rules,
                                          microbatches=microbatches, remat=remat)
        lowered = fn.lower(*args)
        t_lower = time.monotonic() - t0
        t0 = time.monotonic()
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_stats(hlo)
    n_chips = 512 if multi_pod else 256

    rec.update({
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes_per_device": int(ma.argument_size_in_bytes),
            "output_bytes_per_device": int(ma.output_size_in_bytes),
            "temp_bytes_per_device": int(ma.temp_size_in_bytes),
            "alias_bytes_per_device": int(ma.alias_size_in_bytes),
            "peak_bytes_per_device": int(ma.argument_size_in_bytes
                                         + ma.output_size_in_bytes
                                         + ma.temp_size_in_bytes
                                         - ma.alias_size_in_bytes),
        },
        "cost": {
            "flops_per_device": float(ca.get("flops", 0.0)),
            "bytes_accessed_per_device": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0)),
        },
        "collectives": coll.to_dict(),
        "dropped_shardings": sorted(set(rules.dropped)),
        "model_params": cfg.param_count(),
        "model_active_params": cfg.active_param_count(),
        "tokens_per_step": shape.global_batch * (shape.seq_len if shape.kind == "train" else 1),
        "microbatches": microbatches,
    })
    if save:
        _save(rec)
    return rec


def _save(rec: Dict[str, Any]) -> None:
    os.makedirs(OUT_DIR, exist_ok=True)
    tag = f"__{rec['tag']}" if rec.get("tag") else ""
    path = os.path.join(
        OUT_DIR, f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{tag}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    combos = []
    archs = list(ARCHITECTURES) if (args.all or args.arch is None) else [args.arch]
    shapes = [s.name for s in INPUT_SHAPES] if (args.all or args.shape is None) \
        else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    failures = 0
    for a, s, mp in combos:
        mesh_name = "pod2x16x16" if mp else "pod16x16"
        path = os.path.join(OUT_DIR, f"{a}__{s}__{mesh_name}.json")
        if args.skip_existing and os.path.exists(path):
            print(f"[skip] {a} {s} {mesh_name} (exists)")
            continue
        try:
            rec = run_one(a, s, multi_pod=mp)
            if not rec["applicable"]:
                print(f"[n/a ] {a:24s} {s:12s} {mesh_name}: {rec['skip_reason']}")
                continue
            mem = rec["memory"]["peak_bytes_per_device"] / 2**30
            fl = rec["cost"]["flops_per_device"]
            cb = rec["collectives"]["total_bytes"]
            print(f"[ ok ] {a:24s} {s:12s} {mesh_name}: "
                  f"peak {mem:.2f} GiB/dev, {fl:.3g} flops/dev, "
                  f"{cb/2**20:.1f} MiB collectives, "
                  f"compile {rec['compile_s']:.0f}s")
        except Exception as e:  # noqa: BLE001 — report and continue the sweep
            failures += 1
            print(f"[FAIL] {a} {s} {mesh_name}: {type(e).__name__}: {e}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} dry-run combinations failed")
    print("dry-run sweep complete")


if __name__ == "__main__":
    main()
