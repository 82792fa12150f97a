"""How many layers of a configuration fit one TPU v5e chip.

Compiles the weights' initialisation and the served programs (decode
step, 128-token prefill chunk and decode burst) for a described v5e,
with no chip attached, at the configuration's published widths and
engine settings, for each candidate depth, and prints what the chip's
compiler reports: argument bytes (weights and KV pool; for the
initialisation, its output) and temporary bytes, or its refusal.
Nothing runs.

  JAX_PLATFORMS=cpu PYTHONPATH=src python bench/fit.py \
      bench/configs/deepseek-67b.json 4 5 6
"""
from __future__ import annotations

import os
import sys
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."),
                os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                             "src")]

# bytes_limit that memory_stats() reports on one v5e chip
V5E_BYTES_LIMIT = 16909336064


def compile_steps(config: dict, layers: int, one_chip) -> dict:
    import dataclasses
    import jax
    import jax.numpy as jnp
    from bench.harness.serving import make_weights
    from bench.harness.spec import model_config
    from repro.models import build_model
    from repro.serving.engine import ContinuousBatchingEngine, EngineConfig

    e = config["engine"]
    cfg = dataclasses.replace(model_config(config), num_layers=layers,
                              use_pallas_attention=True)
    model = build_model(cfg)
    ecfg = EngineConfig(max_slots=e["slots"], max_seq_len=e["max_seq_len"],
                        block_size=e["block_size"], dtype=jnp.bfloat16,
                        decode_burst=e["decode_burst"],
                        attention_backend=e["backend"],
                        prefill_chunk_tokens=e["prefill_chunk_tokens"])
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: spec(a.shape, a.dtype), tree)
    params = place(jax.eval_shape(lambda k: model.init(k, jnp.bfloat16),
                                  jax.random.key(0)))
    pool = place(jax.eval_shape(lambda: model.init_paged_cache(
        ecfg.resolved_kv_blocks(), ecfg.block_size, jnp.bfloat16)))
    B, nb, C = ecfg.max_slots, ecfg.max_blocks_per_seq(), \
        ecfg.prefill_chunk_tokens
    i32 = lambda *shape: spec(shape, jnp.int32)  # noqa: E731
    # the engine's own step bodies, on a stand-in that carries only what
    # they read (no pool is allocated here)
    eng = types.SimpleNamespace(cfg=ecfg, paged=True, model=model)
    E = ContinuousBatchingEngine
    programs = {
        "decode": (lambda *a: E._decode_paged_impl(eng, *a),
                   (params, pool, i32(B), i32(B), i32(B, nb))),
        "prefill_chunk": (lambda *a: E._prefill_chunk_paged_impl(eng, *a),
                          (params, pool, i32(B, C), i32(B), i32(B),
                           i32(B, nb))),
        "burst": (lambda *a: E._decode_burst_impl(eng, *a),
                  (params, pool, i32(B), i32(B), i32(B),
                   spec((B,), jnp.bool_), i32(), i32(B, nb))),
    }
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one_chip)
    out = {}
    compiled = make_weights.lower(model, key).compile()
    mem = compiled.memory_analysis()
    out["init"] = (mem.output_size_in_bytes, mem.temp_size_in_bytes, False)
    for name, (fn, args) in programs.items():
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        mem = compiled.memory_analysis()
        out[name] = (mem.argument_size_in_bytes, mem.temp_size_in_bytes,
                     "tpu_custom_call" in compiled.as_text())
    return out


def main(argv) -> int:
    import json
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    # a step traced on this CPU host would carry interpreted kernels
    ops.default_interpret = lambda: False
    config = json.loads(open(argv[0]).read())
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for layers in map(int, argv[1:]):
        try:
            res = compile_steps(config, layers, one_chip)
        except jax.errors.JaxRuntimeError as e:  # the compiler's refusal
            print(json.dumps({"layers": layers, "fits": False,
                              "refused": str(e).splitlines()[0]}),
                  flush=True)
            continue
        worst = max(a + t for a, t, _ in res.values())
        print(json.dumps({"layers": layers, "programs": res,
                          "largest_args_plus_temps": worst,
                          "bytes_limit": V5E_BYTES_LIMIT,
                          "fits": worst < V5E_BYTES_LIMIT}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
