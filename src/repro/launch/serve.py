"""Serving driver: QLM-managed cluster over real JAX engines.

Runs the full QLM stack — request groups, virtual queues, RWT estimator,
global scheduler, LSO agents — against a Poisson workload, and prints SLO
attainment / throughput.  By default the model is built at its published
width with bf16 weights and KV pool (one accelerator's worth);
``--reduced`` selects the 2-layer float32 smoke config for CPU runs.

  PYTHONPATH=src python -m repro.launch.serve --reduced \
      --arch granite-3-2b --requests 40 --rate 2.0

Instance i runs on ``jax.devices()[i % len(jax.devices())]`` with its own
copy of every model's params (its swap registry) and its own KV pool.

Cluster-mode flags (docs/cluster.md):

  --threaded          thread-per-engine serve loop (ThreadedCluster):
                      engines run real concurrent wall-clock rounds
                      instead of the single-thread round-robin poll
  --hetero            heterogeneous capacity tiers — instance i gets the
                      fast/mid/slow EngineConfig tier (slots x2/x1/x0.5,
                      decode_burst 4/2/1), each tier calibrated on its
                      own throwaway engine so the scheduler sees REAL
                      per-tier drain/swap costs; params are placed
                      through distributed/sharding.py rules
  --routing P         solver | slice — group-level MILP placement vs
                      slice-level load balancing (core/routing.py)
  --compare-drivers   run threaded AND round-robin on the same seed,
                      report both (tokens/s head-to-head)
  --compare-routing   run slice AND solver routing on the same seed,
                      report both (attainment head-to-head)
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core.global_scheduler import InstanceInfo
from repro.core.lso import QLMAgent
from repro.core.qlm import QLMConfig, QLMController
from repro.core.request import make_request
from repro.core.virtual_queue import VirtualQueue
from repro.distributed.sharding import ShardingRules, build_shardings
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving import ContinuousBatchingEngine, EngineConfig, ThreadedCluster
from repro.sim.profiles import calibrate_from_engine


def serving_dtype(reduced: bool):
    """Weights and KV pool: bf16 at published width, float32 reduced."""
    return jnp.float32 if reduced else jnp.bfloat16


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init_params(model, key, dtype):
    return model.init(key, dtype)


def build_registry(arch_names, key, *, reduced: bool = False):
    """name -> (Model, params) for each requested arch, at published width
    (or the reduced smoke config), params on JAX's default device."""
    registry = {}
    for name in arch_names:
        cfg = get_arch(name)
        model = build_model(cfg.reduced() if reduced else cfg)
        registry[name] = (model, _init_params(model, key,
                                              serving_dtype(reduced)))
    return registry


def shard_registry(registry):
    """Place every model's params through the TP sharding rules.

    On this CPU driver the mesh is one device, so every leaf lands
    replicated — but the placement goes through the same
    ``build_shardings`` path a multi-device mesh would use, so the
    DEFAULT_RULES TP split (ff / heads over the "model" axis) applies
    unchanged when real devices are present.
    """
    from jax.sharding import Mesh
    devs = np.asarray(jax.devices()[:1])
    mesh = Mesh(devs, ("model",))
    rules = ShardingRules.default()
    out = {}
    for name, (model, params) in registry.items():
        sh = build_shardings(mesh, params, model.param_axes(), rules)
        out[name] = (model, jax.device_put(params, sh))
    return out


# fast / mid / slow capacity tiers for --hetero (instance i -> tier i%3):
# more slots = bigger batches = higher throughput; wider decode_burst =
# fewer host round-trips per token.  The tiers are calibrated separately,
# so the RWT estimator sees genuinely different drain/swap costs.
HETERO_TIERS = ({"slots_scale": 2.0, "decode_burst": 4},
                {"slots_scale": 1.0, "decode_burst": 2},
                {"slots_scale": 0.5, "decode_burst": 1})


def hetero_engine_cfg(base: EngineConfig, idx: int) -> EngineConfig:
    tier = HETERO_TIERS[idx % len(HETERO_TIERS)]
    return dataclasses.replace(
        base,
        max_slots=max(2, int(round(base.max_slots * tier["slots_scale"]))),
        decode_burst=tier["decode_burst"])


def calibrate_registry(registry, ecfg: EngineConfig) -> dict:
    """name -> HardwareProfile, each calibrated on ITS OWN model.

    One throwaway engine per model: the scheduler's swap/drain estimates
    are per (model, device) — reusing the arch-1 profile for every model
    (the old behavior) gave the solver wrong costs for every other arch.
    """
    hw_by_model = {}
    for name, (model, params) in registry.items():
        eng = ContinuousBatchingEngine(model, params, ecfg, model_name=name)
        hw_by_model[name] = calibrate_from_engine(
            eng, token_capacity=ecfg.resolved_kv_blocks() * ecfg.block_size)
        eng.release_cache()
    return hw_by_model


def build_cluster(args, registry, arch_names):
    """Engines + agents + controller honoring --hetero and --routing.

    Homogeneous: one calibration shared by every instance.  Hetero: one
    calibration per TIER (distinct EngineConfig), so each InstanceInfo
    carries its own per-model profiles and the scheduler's placement is
    heterogeneity-aware.  Every calibration runs (and frees its throwaway
    pool) before the first serving pool is allocated.  Instance i serves
    from ``jax.devices()[i % n]`` with the registry placed there.
    """
    debug_inv = bool(getattr(args, "debug_invariants", False))
    base = EngineConfig(max_slots=args.slots, max_seq_len=args.max_seq_len,
                        dtype=serving_dtype(args.reduced),
                        decode_burst=args.decode_burst,
                        attention_backend=args.backend,
                        prefix_sharing=args.prefix_sharing,
                        debug_invariants=debug_inv)
    ecfgs = [hetero_engine_cfg(base, i) if args.hetero else base
             for i in range(args.instances)]
    hw_cache = {}
    for ecfg in ecfgs:
        key = (ecfg.max_slots, ecfg.decode_burst)
        if key not in hw_cache:
            hw_cache[key] = calibrate_registry(registry, ecfg)
    devices = jax.devices()
    placed = {}
    engines, agents, infos = [], [], []
    for i, ecfg in enumerate(ecfgs):
        key = (ecfg.max_slots, ecfg.decode_burst)
        dev = devices[i % len(devices)]
        if dev not in placed:
            # device_put aliases params already on dev instead of copying
            placed[dev] = {name: (model, jax.device_put(params, dev))
                           for name, (model, params) in registry.items()}
        m0, p0 = placed[dev][arch_names[0]]
        eng = ContinuousBatchingEngine(m0, p0, ecfg, model_name=arch_names[0])
        vq = VirtualQueue(i)
        agents.append(QLMAgent(eng, vq, placed[dev]))
        engines.append(eng)
        infos.append(InstanceInfo(i, dict(hw_cache[key]), eng.model_name, vq))
    controller = QLMController(infos, QLMConfig(
        avg_batch_size=args.slots,
        routing=getattr(args, "routing", "solver"),
        debug_invariants=debug_inv))
    controller.attach_engines(engines)
    return engines, agents, infos, controller


def build_workload(args, arch_names, t_start: float):
    rng = np.random.default_rng(args.seed)
    classes = ["interactive", "batch1", "batch2"]
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
    reqs = []
    lo, hi = args.prompt_len
    for i in range(args.requests):
        prompt = rng.integers(0, 100, size=int(rng.integers(lo, hi))).tolist()
        r = make_request(prompt, rng.choice(arch_names), rng.choice(classes),
                         arrival_time=t_start + arrivals[i],
                         max_new_tokens=args.max_new_tokens)
        reqs.append(r)
    return reqs


def summarize(reqs, controller, engines, t_start: float, now: float) -> dict:
    """Printed-stats accounting, mirroring QLMController.slo_attainment:
    requests that never got a first token (rejected / shed / expired, or
    still queued past their deadline at ``now``) are SLO misses, not
    silently excluded."""
    import numpy as np
    # failed-quarantined requests are unconditional misses even when a
    # pre-crash first token landed in time (QLMController.slo_attainment
    # scores them the same way)
    failed = [r for r in reqs if r.failed]
    served = [r for r in reqs if r.ttft() is not None and not r.failed]
    dropped = [r for r in reqs if r.ttft() is None and not r.failed
               and (r.dropped() or now > r.deadline)]
    # rejections the caller's request list doesn't already cover (the
    # async path records rejections on requests that ARE in reqs)
    known = {id(r) for r in reqs}
    extra_rej = [r for r in controller.rejected if id(r) not in known]
    scored = len(served) + len(dropped) + len(extra_rej) + len(failed)
    met = sum(1 for r in served if r.slo_met())
    done_times = [r.completion_time for r in reqs if r.completion_time]
    span = max(max(done_times, default=now) - t_start, 1e-9)
    tokens = sum(e.stats.tokens_generated for e in engines)
    return {
        "requests": len(reqs),
        "served": len(served),
        "rejected": len(extra_rej) + sum(1 for r in reqs if r.rejected),
        "dropped_unserved": len(dropped),
        "failed": len(failed),
        # getattr: summarize also accepts stub controllers without the
        # supervision layer (qlint regression tests, older drivers)
        "redeliveries": getattr(controller, "redeliveries", 0),
        "hangs": getattr(controller, "hangs", 0),
        "drains": getattr(controller, "drains", 0),
        "replacements": getattr(controller, "replacements", 0),
        "migrations": getattr(controller, "migrations", 0),
        "dead_instances": sum(1 for i in range(len(controller.instances))
                              if not controller.is_alive(i))
        if hasattr(controller, "is_alive") else 0,
        # vacuous attainment is 1.0 (QLMController.slo_attainment): a
        # zero-request or all-unscored run met every SLO it was given,
        # and 0.0 would trip "attainment below threshold" alerting
        "slo_attainment": met / scored if scored else 1.0,
        # None, not float("nan"): NaN serializes as bare `NaN`, which is
        # not valid JSON and breaks downstream parsers of --json output
        "mean_ttft_s": float(np.mean([r.ttft() for r in served]))
        if served else None,
        "throughput_rps": len(served) / span,
        "evictions": sum(e.stats.evictions for e in engines),
        "swaps": sum(e.stats.model_swaps for e in engines),
        "tokens": tokens,
        "tokens_per_s": tokens / span,
        "prefix_hits": sum(e.stats.prefix_hits for e in engines),
        "prefix_shared_tokens": sum(e.stats.prefix_shared_tokens
                                    for e in engines),
    }


def _terminal(r) -> bool:
    return r.finished() or r.dropped()


def run_round_robin(args, registry, arch_names) -> dict:
    """Single-thread polling loop: one virtual "round" interleaves every
    engine in turn (the baseline --threaded is compared against)."""
    engines, agents, infos, controller = build_cluster(args, registry,
                                                       arch_names)
    t_start = time.monotonic()
    reqs = build_workload(args, arch_names, t_start)
    pending = list(reqs)
    deadline = t_start + args.max_wall
    while not all(_terminal(r) for r in reqs):
        now = time.monotonic()
        if now > deadline:
            break
        while pending and pending[0].arrival_time <= now:
            controller.submit(pending.pop(0), now)
        for inst, eng, agent in zip(infos, engines, agents):
            inst.current_model = eng.model_name
            agent.run_iteration()
        controller.tick(time.monotonic())
        if not any(e.num_active() for e in engines) and pending:
            time.sleep(min(0.01, max(0.0,
                                     pending[0].arrival_time - now)))
    stats = summarize(reqs, controller, engines, t_start, time.monotonic())
    stats["driver"] = "round-robin"
    stats["routing"] = controller.cfg.routing
    return stats


def run_threaded(args, registry, arch_names) -> dict:
    """Thread-per-engine loop: the main thread plays open-loop client
    (submitting on the wall-clock arrival schedule) while every engine
    decodes concurrently and the controller ticks on its own thread."""
    engines, agents, infos, controller = build_cluster(args, registry,
                                                       arch_names)
    t_start = time.monotonic()
    reqs = build_workload(args, arch_names, t_start)
    return drive_threaded(engines, agents, controller, reqs,
                          max_wall=args.max_wall)


def drive_threaded(engines, agents, controller, reqs, *,
                   max_wall: float) -> dict:
    """Serve ``reqs`` open-loop on a ``ThreadedCluster``: submit each at
    its wall-clock ``arrival_time``, wait (at most ``max_wall`` seconds)
    until all are terminal, stop the threads — re-raising any error a
    thread hit — and summarize from the first arrival."""
    cluster = ThreadedCluster(controller, agents, engines)
    t_start = min((r.arrival_time for r in reqs), default=time.monotonic())
    cluster.start()
    try:
        for r in reqs:
            time.sleep(max(0.0, r.arrival_time - time.monotonic()))
            controller.submit(r, time.monotonic())
        cluster.wait(lambda: all(_terminal(r) for r in reqs),
                     timeout=max_wall)
    finally:
        cluster.stop()
    stats = summarize(reqs, controller, engines, t_start, time.monotonic())
    stats["driver"] = "threaded"
    stats["routing"] = controller.cfg.routing
    stats["engine_rounds"] = list(cluster.rounds)
    stats["controller_ticks"] = cluster.ticks
    return stats


def run_once(args, registry, arch_names) -> dict:
    run = run_threaded if args.threaded else run_round_robin
    return run(args, registry, arch_names)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="2-layer float32 smoke config of each arch (CPU "
                         "runs); default is the published width in bf16")
    ap.add_argument("--max-seq-len", type=int, default=None,
                    help="per-sequence token limit (default 2048, 128 "
                         "with --reduced)")
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(4, 24),
                    metavar=("MIN", "MAX"),
                    help="prompt lengths drawn uniformly from [MIN, MAX)")
    ap.add_argument("--arch2", default=None, help="second model for multi-model serving")
    ap.add_argument("--instances", type=int, default=1)
    ap.add_argument("--requests", type=int, default=30)
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--decode-burst", type=int, default=1,
                    help="fused decode iterations per engine dispatch "
                         "(QLMAgent.run_iteration drives steps(); 1 = the "
                         "single-step loop)")
    ap.add_argument("--backend", default=None,
                    choices=[None, "xla", "pallas", "paged-xla",
                             "paged-pallas"],
                    help="serving attention backend (None follows the "
                         "model config; prefix sharing needs a paged-* "
                         "backend's physical page pool)")
    ap.add_argument("--prefix-sharing", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="refcounted shared-prefix KV pages on the paged "
                         "backends (--no-prefix-sharing for the A/B "
                         "baseline; inert on dense backends)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--threaded", action="store_true",
                    help="thread-per-engine serve loop (ThreadedCluster)")
    ap.add_argument("--hetero", action="store_true",
                    help="heterogeneous capacity tiers (fast/mid/slow), "
                         "each calibrated separately; params placed via "
                         "distributed/sharding.py")
    ap.add_argument("--routing", default="solver",
                    choices=["solver", "slice"],
                    help="group placement policy (core/routing.py)")
    ap.add_argument("--debug-invariants", action="store_true",
                    help="run the engine/queue invariant checkers every "
                         "round/tick")
    ap.add_argument("--max-wall", type=float, default=180.0,
                    help="wall-clock bound per run")
    ap.add_argument("--compare-drivers", action="store_true",
                    help="run threaded AND round-robin same-seed")
    ap.add_argument("--compare-routing", action="store_true",
                    help="run slice AND solver routing same-seed")
    ap.add_argument("--json", default=None, help="write final stats JSON")
    args = ap.parse_args(argv)
    if args.max_seq_len is None:
        args.max_seq_len = 128 if args.reduced else 2048
    return args


def main(argv=None) -> dict:
    args = parse_args(argv)
    enable_compile_cache()
    key = jax.random.key(args.seed)

    arch_names = [args.arch] + ([args.arch2] if args.arch2 else [])
    registry = build_registry(arch_names, key, reduced=args.reduced)
    if args.hetero:
        registry = shard_registry(registry)

    out = {}
    if args.compare_drivers:
        for threaded in (True, False):
            a = argparse.Namespace(**vars(args))
            a.threaded = threaded
            out["threaded" if threaded else "round-robin"] = \
                run_once(a, registry, arch_names)
    elif args.compare_routing:
        for routing in ("slice", "solver"):
            a = argparse.Namespace(**vars(args))
            a.routing = routing
            out[routing] = run_once(a, registry, arch_names)
    else:
        out["run"] = run_once(args, registry, arch_names)

    for name, st in out.items():
        if len(out) > 1:
            print(f"--- {name} ---")
        for k, v in st.items():
            print(f"{k:18s} {v:.3f}" if isinstance(v, float)
                  else f"{k:18s} {v}")
    if args.compare_drivers:
        t, rr = out["threaded"]["tokens_per_s"], \
            out["round-robin"]["tokens_per_s"]
        print(f"tokens/s           threaded {t:.1f} vs round-robin {rr:.1f} "
              f"({t / max(rr, 1e-9):.2f}x)")
    if args.compare_routing:
        print(f"attainment         slice "
              f"{out['slice']['slo_attainment']:.3f} vs solver "
              f"{out['solver']['slo_attainment']:.3f}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
    return out["run"] if "run" in out else out


if __name__ == "__main__":
    main()
