"""One run of one cell: set-up, the measured window, the metrics, the
comparison that decides ``correct``, and the result line."""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench.harness import check as check_lib
from bench.harness import stats as stats_lib
from bench.harness import traffic as traffic_lib
from bench.harness import work as work_lib
from bench.harness.spec import BENCH_DIR, Cell, arch_module, metric_path

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class NoDevice(RuntimeError):
    pass


def process_start() -> float:
    """This process's start on the monotonic clock (Linux)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        return time.monotonic() - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return time.monotonic()


def seed_key(seed: int):
    """A JAX key from any whole-number seed (all its bits count)."""
    import jax
    hi, lo = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(hi)), int(lo))


class Compiles:
    """Counts backend compiles and traces as JAX reports them."""

    def __init__(self):
        self.compiles = self.traces = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1
        elif event == TRACE_EVENT:
            self.traces += 1


def device_check(chips: int, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoDevice(f"JAX's first device is {devices[0].platform!r}, "
                       "not a TPU")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devices)}")
    return devices


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""
    cell: Cell
    records: List[stats_lib.Record]
    bounds: Dict[str, float]
    engine_stats: List[Dict[str, Any]]   # per engine: deltas over the window
    max_slots: int
    tick_seconds: List[float]
    round_work: List[Any]
    dims: Dict[str, Any]                 # the reference module's dims
    arch: Any                            # the architecture module
    peak: Dict[str, float]
    memory: Dict[str, Any]
    trace: Optional[Any] = None          # harness.trace.Trace
    reduced: Optional[Dict[str, Any]] = None


def read_metric(name: str, ctx: Context):
    path = metric_path(name)
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def _stats_dict(engine) -> Dict[str, Any]:
    return dataclasses.asdict(engine.stats)


def _checksums(params) -> Dict[str, List[int]]:
    """Bitwise checksums of every served weight, keyed as the reference's
    ``checksums``: per leaf, the sum of its bf16 bit patterns (per layer
    for the stacked decoder layers)."""
    import jax
    import jax.numpy as jnp

    def bits(a, axes):
        return jnp.sum(jax.lax.bitcast_convert_type(a, jnp.uint16)
                       .astype(jnp.uint32), axis=axes)
    out: Dict[str, List[int]] = {}
    for path, a in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.startswith("blocks/"):
            out[name] = [int(x) for x in jax.jit(
                bits, static_argnums=1)(a, tuple(range(1, a.ndim)))]
        else:
            out[name] = [int(jax.jit(bits, static_argnums=1)(a, None))]
    return out


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, t_start: Optional[float] = None,
        limits: Optional[Dict[str, Any]] = None,
        fault=None, control: bool = False) -> Dict[str, Any]:
    """Run one cell once and return the result line's object.

    ``fault`` (tests) breaks the served path after warm-up; ``control``
    (``bench/control.py``) also puts the fp8 control's tokens through the
    same comparison, under the result's ``control`` key."""
    import jax
    t_start = process_start() if t_start is None else t_start
    devices = device_check(cell.chips, require_tpu)
    dev = devices[0]
    peak = work_lib.peaks(dev.device_kind) if require_tpu else None

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program goes to the persistent cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)

    from bench.harness.serving import Served, served_program_check
    config = cell.config
    key = seed_key(seed)
    served = Served(config, key, seed=seed)
    served.warm_up()
    if fault is not None:
        fault(served)
    arrivals = traffic_lib.generate(
        cell.traffic, seconds=seconds, seed=seed,
        max_seq_len=config["engine"]["max_seq_len"],
        vocab_size=served.model_cfg.vocab_size)
    before = [_stats_dict(e) for e in served.engines]
    c0, tr0 = compiles.compiles, compiles.traces
    trace_dir = str(BENCH_DIR.parent / "bench_out" / "trace"
                    / f"{cell.name}.{seed}")
    setup_s = time.monotonic() - t_start
    bounds = served.run_window(arrivals, seconds=seconds,
                               drain_s=cell.traffic["drain_s"],
                               trace_dir=trace_dir if trace else None)
    log(f"window: {len(arrivals)} requests due in {seconds} s; compiles in "
        f"the window {compiles.compiles - c0}, traces "
        f"{compiles.traces - tr0}; engine rounds {served.rounds}")
    after = [_stats_dict(e) for e in served.engines]
    deltas = [{k: a[k] - b[k] for k in a if isinstance(a[k], (int, float))}
              for a, b in zip(after, before)]
    memory = dev.memory_stats() or {}
    memory_peak = int(memory.get("peak_bytes_in_use", 0))
    temps = served_program_check(served.engines[0]) if require_tpu else {}
    log(f"memory: peak_bytes_in_use {memory.get('peak_bytes_in_use')} "
        f"peak_bytes_reserved {memory.get('peak_bytes_reserved')} "
        f"bytes_limit {memory.get('bytes_limit')}; compiler temporaries "
        f"{temps}")
    log(f"engine: {json.dumps(deltas[0], sort_keys=True)}")

    records = sorted(served.records.values(), key=lambda r: r.idx)
    result: Dict[str, Any] = dict(stats_lib.counts(records))
    # the TTFT tail counts these with the wait they had at the drain's end
    unserved = sum(r.failed or r.first_token(bounds["drain_end"]) is None
                   for r in records)
    log(f"unserved at the drain's end: {unserved} of {len(records)}")
    window = dict(start=bounds["start"], end=bounds["end"],
                  drain_end=bounds["drain_end"])
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if not trace:
        e2e = stats_lib.end_to_end(records, **window)
        e2e["setup_s"] = setup_s
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": float(e2e[k]), "unit": units[k]}
                   for k in units if k in e2e}
    else:
        from bench.harness import trace as trace_lib
        tr = trace_lib.load(trace_lib.find(trace_dir))
        reduced = trace_lib.reduce(tr)
        ctx = Context(cell=cell, records=records, bounds=bounds,
                      engine_stats=deltas,
                      max_slots=served.engines[0].cfg.max_slots,
                      tick_seconds=served.tick_seconds,
                      round_work=served.round_work,
                      dims=check_lib.reference_module(config).dims(config),
                      arch=arch_module(config),
                      peak=peak, memory=memory, trace=tr, reduced=reduced)
        metrics = {}
        for m in cell.per_layer:
            v = read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    result["metrics"] = metrics
    result["device"] = device

    # the comparison, once the program's state is freed
    finished = [r for r in served.requests
                if r.finished() and not served.records[r.req_id].failed]
    served_records = served.records
    served_sums = _checksums(served.registry[served.name][1])
    served.release()
    del served
    gc.collect()
    t_check = time.monotonic()
    ref = check_lib.reference_module(config)
    ref_sums = ref.checksums(config, key)
    differing = sorted(k for k in set(ref_sums) | set(served_sums)
                       if ref_sums.get(k) != served_sums.get(k))
    lim = limits if limits is not None else check_lib.limits(cell.name)
    chosen = check_lib.sample(
        finished, seed, cell.traffic["check_tokens"],
        {k: rec.slot for k, rec in served_records.items()})
    # the reference's weights are the served ones, bit for bit
    checks = {"weights_differing": {"value": len(differing), "limit": 0}}
    if chosen:
        gap = check_lib.widest_gap(ref, config, key, chosen, control)
        checks["max_logit_gap"] = {"value": gap["served"],
                                   "limit": lim["max_logit_gap"]["limit"]}
    else:
        checks["no_finished_request"] = {"value": 1, "limit": 0}
    log(f"check: {len(chosen)} finished requests compared, from "
        f"{len({served_records[r.req_id].slot for r in chosen})} slots, "
        f"{sum(len(r.output_tokens) for r in chosen)} served tokens; "
        f"weight leaves that differ from the reference's: {differing}; "
        f"{time.monotonic() - t_check:.1f} s")
    if chosen:
        log(f"check: served tokens not the reference's first: "
            f"{gap['served.mismatches']}; widest gap at the "
            f"{gap['served.worst']}")
    correct = check_lib.decide(checks)
    if control and chosen:
        ctl = dict(checks, max_logit_gap=dict(checks["max_logit_gap"],
                                              value=gap["control"]))
        result["control"] = {"correct": check_lib.decide(ctl),
                             "max_logit_gap": ctl["max_logit_gap"],
                             "mismatches": gap["control.mismatches"]}
    result = {"correct": correct, **result, "check": checks}
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result
