"""Chip smoke: granite-3-2b at published width, served on TPU through
``launch/serve.py``.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # four one-chip replicas, needs 4 chips

One process runs the phases in order; the first failure exits non-zero.

  (a) device   JAX's first device must be a TPU (no CPU fallback); prints
               its kind, the device count and the jax/jaxlib/libtpu versions.
  (b) kernels  the paged decode and prefill-chunk Pallas kernels at the
               engine's serving shapes in bf16, against the jnp references
               in ``kernels/ref.py``; their programs must hold Mosaic
               kernels (``tpu_custom_call``), not interpreted ones.
  (c) serve    ``serve.parse_args`` / ``build_registry`` / ``build_cluster``
               / ``drive_threaded``: one instance, paged-pallas, decode
               bursts of 4, prefix sharing, 16 slots, max_seq_len 2048, the
               default pool (2048 blocks of 16 tokens); 16 open-loop
               requests with 200-1000-token prompts and 32 new tokens each,
               then 4 follow-up turns that extend earlier conversations and
               must attach their shared prompt pages.  Every request must be
               served with exactly 32 in-vocab tokens, decode bursts must
               have run, and the served decode, prefill-chunk and burst
               programs must hold Mosaic kernels.

``--four-chips`` runs only the replica path: four instances behind the
controller, instance i on ``jax.devices()[i]`` with its own params, pool
and swap registry.  Pass 1 serves an open-loop burst through the
controller, and every step input of instance i must sit on device i;
pass 2 serves the same prompts one at a time, request j on
instance j % 4 and then all on one instance on one chip, and every
request's tokens must be identical between the two.

The weights are random (from ``SEED``): the times and bytes printed are
those of a smoke run, not a measurement.  The last line of stdout is one
JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "granite-3-2b"
BLOCK_SIZE = 16
MAX_SEQ_LEN = 2048
SLOTS = 16
CHUNK = 128
MAX_NEW_TOKENS = 32
FOLLOW_UPS = 4
SEED = 0
MAX_WALL_S = 600.0  # wall-clock bound on each serve pass
# bf16 kernel outputs vs the float32 reference: |out - ref| <= ATOL + RTOL
# * |ref|, a few bf16 ulps (2^-8 relative) at the outputs' unit scale
ATOL = RTOL = 2e-2
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache loads
    included) as they are reported."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.seconds += duration


def phase_device(jax):
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[a] device: FAIL, JAX's first device is {dev.platform!r}, "
              "not a TPU", file=sys.stderr)
        return None
    import importlib.metadata
    import jaxlib
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"[a] device: {dev.device_kind} x{len(devices)} "
          f"(jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
          f"libtpu {libtpu})", flush=True)
    return devices


def _assert_mosaic(compiled_text: str, what: str) -> None:
    check("tpu_custom_call" in compiled_text,
          f"{what}: compiled program holds no tpu_custom_call (the Pallas "
          "kernel was interpreted)")


def _compare(out, want, what: str) -> float:
    """Fail unless ``out`` is finite and within the bf16 tolerance of
    ``want``; returns the largest absolute error."""
    import numpy as np
    out = np.asarray(out, np.float32)
    want = np.asarray(want, np.float32)
    check(np.isfinite(out).all(), f"{what}: non-finite outputs")
    err = np.abs(out - want)
    excess = float(np.max(err - (ATOL + RTOL * np.abs(want))))
    check(excess <= 0, f"{what}: off the reference by {excess:.3g} beyond "
                       "the bf16 tolerance")
    return float(err.max())


def phase_kernels(jax, cfg) -> None:
    """Paged decode / prefill-chunk kernels at the engine's serving shapes
    (16 slots, granite heads, 2048-block pool of one layer) vs the refs."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    check(not ops.default_interpret(),
          "kernels would run in interpret mode on a TPU")
    B, H, KVH, D = SLOTS, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    nb = MAX_SEQ_LEN // BLOCK_SIZE
    N = B * nb
    rng = np.random.default_rng(SEED)
    keys = jax.random.split(jax.random.key(SEED), 6)
    normal = lambda k, shape: jax.random.normal(k, shape, jnp.bfloat16)  # noqa: E731
    k_pages = normal(keys[0], (N, KVH, BLOCK_SIZE, D))
    v_pages = normal(keys[1], (N, KVH, BLOCK_SIZE, D))
    pages = rng.permutation(N).reshape(B, nb)

    def table(tokens):
        # live logical blocks name distinct pages; the rest hold the
        # sentinel N, as the engine's block table does
        live = -(-tokens // BLOCK_SIZE)
        return jnp.asarray(np.where(np.arange(nb)[None] < live[:, None],
                                    pages, N).astype(np.int32))

    lengths = rng.integers(1, MAX_SEQ_LEN + 1, B).astype(np.int32)
    lengths[:2] = (1, MAX_SEQ_LEN)
    q = normal(keys[2], (B, H, D))
    args = (q, k_pages, v_pages, table(lengths), jnp.asarray(lengths))
    out = ops.paged_decode_attention(*args)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_decode_attention_ref(*args)
    err = _compare(out, want, "paged decode kernel")
    _assert_mosaic(ops.paged_decode_attention.lower(*args).compile()
                   .as_text(), "paged_decode_attention")
    print(f"[b] paged decode kernel: ok, max |err| {err:.3g} (B={B} H={H} "
          f"KVH={KVH} D={D}, pool {N}x{BLOCK_SIZE}, atol=rtol={ATOL})",
          flush=True)

    starts = rng.integers(0, MAX_SEQ_LEN - CHUNK + 1, B).astype(np.int32)
    valid = rng.integers(1, CHUNK + 1, B).astype(np.int32)
    starts[0], valid[1] = 0, CHUNK
    q = normal(keys[3], (B, H, CHUNK, D))
    chunk_k = normal(keys[4], (B, KVH, CHUNK, D))
    chunk_v = normal(keys[5], (B, KVH, CHUNK, D))
    args = (q, k_pages, v_pages, chunk_k, chunk_v, table(starts + valid),
            jnp.asarray(starts), jnp.asarray(valid))
    out = ops.paged_prefill_attention(*args)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_prefill_attention_ref(*args)
    # rows past valid[b] are garbage by contract: compare real rows only
    real = np.arange(CHUNK)[None, None, :, None] < valid[:, None, None, None]
    err = _compare(np.where(real, np.asarray(out, np.float32), 0),
                   np.where(real, np.asarray(want, np.float32), 0),
                   "paged prefill-chunk kernel")
    _assert_mosaic(ops.paged_prefill_attention.lower(*args).compile()
                   .as_text(), "paged_prefill_attention")
    print(f"[b] paged prefill-chunk kernel: ok, max |err| {err:.3g} "
          f"(chunk {CHUNK})", flush=True)


def serve_args(*, instances: int, requests: int, rate: float):
    from repro.launch import serve
    return serve.parse_args([
        "--arch", ARCH, "--instances", str(instances),
        "--backend", "paged-pallas", "--decode-burst", "4",
        "--prefix-sharing", "--slots", str(SLOTS),
        "--max-seq-len", str(MAX_SEQ_LEN),
        "--requests", str(requests), "--rate", str(rate),
        "--prompt-len", "200", "1001",
        "--max-new-tokens", str(MAX_NEW_TOKENS),
        "--threaded", "--seed", str(SEED), "--max-wall", str(MAX_WALL_S)])


def check_served(reqs, stats, vocab: int, what: str) -> None:
    check(stats["served"] == len(reqs) and stats["rejected"] == 0
          and stats["dropped_unserved"] == 0 and stats["failed"] == 0,
          f"{what}: not every request was served: {stats}")
    for r in reqs:
        check(r.finished() and len(r.output_tokens) == MAX_NEW_TOKENS,
              f"{what}: request {r.req_id} got {len(r.output_tokens)} of "
              f"{MAX_NEW_TOKENS} tokens")
        check(all(0 <= t < vocab for t in r.output_tokens),
              f"{what}: request {r.req_id} emitted a token outside the "
              f"vocab: {r.output_tokens}")


def check_step_programs(jax, eng) -> dict:
    """The engine's own jitted decode, prefill-chunk and burst programs,
    compiled for the shapes they served, hold Mosaic kernels.  Returns
    each program's temporary bytes as the chip's compiler reports them."""
    import jax.numpy as jnp
    spec = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        t)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    B, nb = eng.cfg.max_slots, eng.cfg.max_blocks_per_seq()
    params, cache = spec(eng.params), spec(eng.cache)
    steps = {
        "decode step": eng._decode_fn.lower(
            params, cache, i32(B), i32(B), i32(B, nb)),
        "prefill-chunk step": eng._chunk_fn.lower(
            params, cache, i32(B, CHUNK), i32(B), i32(B), i32(B, nb)),
        "decode burst": eng._burst_fn.lower(
            params, cache, i32(B), i32(B), i32(B),
            jax.ShapeDtypeStruct((B,), jnp.bool_), i32(), i32(B, nb)),
    }
    temps = {}
    for name, lowered in steps.items():
        compiled = lowered.compile()
        _assert_mosaic(compiled.as_text(), name)
        temps[name] = compiled.memory_analysis().temp_size_in_bytes
    return temps


def follow_ups(reqs, n: int):
    """Next turns of the first ``n`` conversations: each prompt is an
    earlier prompt, its answer and a few new tokens, so prefix sharing
    finds the earlier prompt's pages still indexed in the pool."""
    from repro.core.request import make_request
    now = time.monotonic()
    return [make_request(list(r.prompt_tokens) + list(r.output_tokens)
                         + list(range(16)), ARCH, r.slo_class,
                         arrival_time=now, max_new_tokens=MAX_NEW_TOKENS)
            for r in reqs[:n]]


def phase_serve(jax, cfg, clock: CompileClock) -> None:
    from repro.launch import serve
    args = serve_args(instances=1, requests=16, rate=8.0)
    t0, c0 = time.monotonic(), clock.seconds
    registry = serve.build_registry([ARCH], jax.random.key(SEED),
                                    reduced=args.reduced)
    jax.block_until_ready(registry)
    t1, c1 = time.monotonic(), clock.seconds
    engines, agents, _, controller = serve.build_cluster(args, registry,
                                                         [ARCH])
    t2, c2 = time.monotonic(), clock.seconds
    reqs = serve.build_workload(args, [ARCH], time.monotonic())
    stats = serve.drive_threaded(engines, agents, controller, reqs,
                                 max_wall=MAX_WALL_S)
    t3 = time.monotonic()
    check_served(reqs, stats, cfg.vocab_size, "serve")
    es = engines[0].stats
    check(es.prefill_chunks > 0 and es.decode_iterations > 0
          and es.decode_bursts > 0,
          f"serve: prefill_chunks={es.prefill_chunks} "
          f"decode_iterations={es.decode_iterations} "
          f"decode_bursts={es.decode_bursts}")
    memory = jax.devices()[0].memory_stats() or {}
    print(f"[c] wall seconds (compile seconds within): weights "
          f"{t1 - t0:.2f} ({c1 - c0:.2f}), calibration {t2 - t1:.2f} "
          f"({c2 - c1:.2f}), serve {t3 - t2:.2f} ({clock.seconds - c2:.2f})",
          flush=True)
    print(f"[c] peak_bytes_in_use {memory.get('peak_bytes_in_use')}; "
          f"memory_stats {json.dumps(memory, sort_keys=True)}", flush=True)
    prompts = [r.prompt_len for r in reqs]
    print(f"[c] smoke run, not a measurement: prompts {min(prompts)}-"
          f"{max(prompts)} tokens, prefill_chunks {es.prefill_chunks}, "
          f"decode_iterations {es.decode_iterations} in "
          f"{es.decode_bursts} bursts; summary {json.dumps(stats)}",
          flush=True)

    turns = follow_ups(reqs, FOLLOW_UPS)
    hits0 = es.prefix_hits
    stats = serve.drive_threaded(engines, agents, controller, turns,
                                 max_wall=MAX_WALL_S)
    check_served(turns, stats, cfg.vocab_size, "follow-up turns")
    check(es.prefix_hits > hits0,
          f"follow-up turns: no prefix hit ({es.prefix_lookups} lookups)")
    print(f"[c] {len(turns)} follow-up turns served; prefix_hits "
          f"{es.prefix_hits - hits0}, prefix_shared_tokens "
          f"{es.prefix_shared_tokens}, cow_copies {es.cow_copies}",
          flush=True)

    temps = check_step_programs(jax, engines[0])
    print(f"[c] served decode, prefill-chunk and burst programs hold Mosaic "
          f"kernels; temporary bytes {temps}", flush=True)


def _one_at_a_time(engines, prompts):
    """Serve fresh copies of ``prompts`` [(tokens, slo_class)] one at a
    time, request j on ``engines[j % len(engines)]``, each admitted only
    after the previous one finished, so no batch neighbour can touch its
    numerics.  Returns the requests."""
    from repro.core.request import make_request
    reqs = []
    for j, (tokens, slo_class) in enumerate(prompts):
        eng = engines[j % len(engines)]
        r = make_request(list(tokens), ARCH, slo_class,
                         arrival_time=time.monotonic(),
                         max_new_tokens=MAX_NEW_TOKENS)
        check(eng.admit(r), f"instance {j % len(engines)} refused a request")
        while not r.finished():
            eng.steps()
        reqs.append(r)
    return reqs


def record_step_inputs(jax, eng) -> set:
    """Wrap the engine's jitted steps so that every dispatch adds the
    devices of its array inputs to the returned set."""
    seen = set()
    for name in ("_decode_fn", "_chunk_fn", "_burst_fn", "_cow_fn"):
        def recorded(*args, _fn=getattr(eng, name)):
            seen.update(d for a in jax.tree.leaves(args)
                        if isinstance(a, jax.Array) for d in a.devices())
            return _fn(*args)
        setattr(eng, name, recorded)
    return seen


def phase_four_chips(jax, cfg) -> None:
    from repro.launch import serve
    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, JAX found "
                             f"{len(devices)}")
    args = serve_args(instances=4, requests=16, rate=1000.0)
    registry = serve.build_registry([ARCH], jax.random.key(SEED),
                                    reduced=args.reduced)
    engines, agents, _, controller = serve.build_cluster(args, registry,
                                                         [ARCH])
    for i, (eng, agent) in enumerate(zip(engines, agents)):
        held = {d for tree in (eng.cache, eng.params,
                               agent.registry[ARCH][1])
                for a in jax.tree.leaves(tree) for d in a.devices()}
        check(held == {devices[i]}, f"instance {i}: arrays on {held}, "
                                    f"expected {devices[i]}")
    print("[e] four instances: params, pool and swap registry each on "
          "their own device", flush=True)

    inputs = [record_step_inputs(jax, eng) for eng in engines]
    reqs = serve.build_workload(args, [ARCH], time.monotonic())
    stats = serve.drive_threaded(engines, agents, controller, reqs,
                                 max_wall=MAX_WALL_S)
    check_served(reqs, stats, cfg.vocab_size, "four-chip burst")
    check(all(n > 0 for n in stats["engine_rounds"]),
          f"four-chip burst: engine rounds {stats['engine_rounds']}")
    for i, seen in enumerate(inputs):
        check(seen == {devices[i]}, f"instance {i}: step inputs on {seen}, "
                                    f"expected {devices[i]}")
    tokens = [e.stats.tokens_generated for e in engines]
    print(f"[e] pass 1, open-loop burst: {stats['served']}/{len(reqs)} "
          f"served; engine rounds {stats['engine_rounds']}, tokens per "
          f"engine {tokens}; every step input on its engine's device",
          flush=True)

    prompts = [(r.prompt_tokens, r.slo_class) for r in reqs]
    four = _one_at_a_time(engines, prompts)
    # the one-chip instance reuses device 0: hand the replicas' pools back
    for eng in engines:
        eng.release_cache()
    one_args = serve_args(instances=1, requests=16, rate=1000.0)
    one_engines = serve.build_cluster(one_args, registry, [ARCH])[0]
    single = _one_at_a_time(one_engines, prompts)
    for j, (r4, r1) in enumerate(zip(four, single)):
        check(len(r4.output_tokens) == MAX_NEW_TOKENS
              and r4.output_tokens == r1.output_tokens,
              f"request {j} on instance {j % 4}: four-chip tokens "
              f"{r4.output_tokens} != one-chip tokens {r1.output_tokens}")
    print(f"[e] pass 2, one at a time: request j on instance j % 4, "
          f"{len(four)} requests, tokens identical to one instance on one "
          "chip", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica path and its comparison")
    args = ap.parse_args(argv)

    import jax
    devices = phase_device(jax)
    if devices is None:
        return 1
    from repro.configs import get_arch
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    cfg = get_arch(ARCH)
    try:
        if args.four_chips:
            phase_four_chips(jax, cfg)
        else:
            phase_kernels(jax, cfg)
            phase_serve(jax, cfg, clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
