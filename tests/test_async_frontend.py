"""Async serving front end (serving.frontend) over real JAX engines:
backpressure watermarks, deadline expiry, mid-decode cancellation (KV
release), multi-turn session prefix reuse, clean shutdown, and the
overload comparison against the synchronous driver.

Stdlib asyncio only (no pytest-asyncio): each test drives its own
``asyncio.run``.
"""
import argparse
import asyncio
import time

import jax
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.configs import ARCHITECTURES
from repro.core.global_scheduler import InstanceInfo
from repro.core.lso import QLMAgent
from repro.core.qlm import QLMConfig, QLMController
from repro.core.request import make_request
from repro.core.rwt_estimator import HardwareProfile
from repro.core.virtual_queue import VirtualQueue
from repro.data.workload import Session
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving import (AsyncServer, ContinuousBatchingEngine,
                           EngineConfig, FrontendConfig, run_session)

ARCH = "granite-3-2b"

_CACHE_OPTIONS = ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(scope="module", autouse=True)
def compile_cache():
    """Persistent XLA compilation cache for this module: every runner here
    rebuilds engines (per-instance jit caches), so without it the overload
    comparison measures compilation stalls, not scheduling.  Every small
    program is cached; the previous settings come back afterwards."""
    saved = {name: getattr(jax.config, name) for name in _CACHE_OPTIONS}
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    yield
    for name, value in saved.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def tiny():
    cfg = ARCHITECTURES[ARCH].reduced(num_layers=1, d_model=64)
    model = build_model(cfg)
    return model, model.init(jax.random.key(0))


def _hw():
    return HardwareProfile(prefill_time=0.05, decode_per_token=0.02,
                           inefficiency=1.2, token_capacity=512,
                           swap_time=0.2, model_max_tokens=64)


def _stack(model, params, *, slots=4, max_seq_len=128, backend="paged-xla",
           block_size=8, kv_blocks=None, fcfg=None):
    ecfg = EngineConfig(max_slots=slots, max_seq_len=max_seq_len,
                        block_size=block_size, kv_blocks=kv_blocks,
                        attention_backend=backend, prefix_sharing=True)
    eng = ContinuousBatchingEngine(model, params, ecfg, model_name=ARCH)
    vq = VirtualQueue(0)
    agent = QLMAgent(eng, vq, {ARCH: (model, params)})
    info = InstanceInfo(0, {ARCH: _hw()}, eng.model_name, vq)
    controller = QLMController([info], QLMConfig(avg_batch_size=slots,
                                                 reschedule_cooldown=0.5))
    server = AsyncServer(controller, [agent], fcfg or FrontendConfig())
    return eng, controller, server


def _req(n_prompt=10, n_new=8, slo_class="interactive", seed=0):
    rng = np.random.default_rng(seed)
    return make_request(rng.integers(0, 100, size=n_prompt).tolist(), ARCH,
                        slo_class, arrival_time=time.monotonic(),
                        max_new_tokens=n_new)


# ---------------------------------------------------------------------------
# ingress: watermarks + hard cap (no event loop needed)
# ---------------------------------------------------------------------------

class _StubEngine:
    model_name = ARCH

    def cancel_request(self, req):
        return False

    def num_active(self):
        return 0


class _StubAgent:
    engine = _StubEngine()

    def run_iteration(self):
        pass


def test_backpressure_watermarks_and_hard_cap():
    inst = InstanceInfo(0, {ARCH: _hw()}, ARCH, VirtualQueue(0))
    controller = QLMController(
        [inst], QLMConfig(avg_batch_size=4, reschedule_on_arrival=False))
    cfg = FrontendConfig(queue_depth=8, high_watermark=4, low_watermark=2)
    srv = AsyncServer(controller, [_StubAgent()], cfg)

    async def go():
        batch = [await srv.submit(_req(slo_class="batch1", seed=i))
                 for i in range(4)]
        assert all(s.status == "queued" for s in batch)
        assert not srv._backpressure
        # depth hit the high watermark: batch arrivals shed at the door
        s = await srv.submit(_req(slo_class="batch2", seed=9))
        assert s.status == "rejected" and srv._backpressure
        assert srv.stats.rejected_backpressure == 1
        assert s.request.completion_time is not None   # accounted, finished
        # interactive keeps flowing until the hard cap
        inter = [await srv.submit(_req(seed=20 + i)) for i in range(4)]
        assert all(s.status == "queued" for s in inter)
        assert srv.queue_depth() == 8
        over = await srv.submit(_req(seed=40))
        assert over.status == "rejected"
        assert srv.stats.rejected_full == 1            # even interactive
        # service drains the queue below the low watermark -> released
        now = time.monotonic()
        for s in batch + inter[:2]:
            s.request.first_token_time = now
        ok = await srv.submit(_req(slo_class="batch1", seed=50))
        assert ok.status == "queued" and not srv._backpressure
        assert srv.stats.backpressure_engagements == 1

    asyncio.run(go())
    # rejected requests count as attainment misses
    assert controller.slo_attainment() < 1.0
    assert len(controller.rejected) == 2


def test_rejected_stream_terminates_immediately():
    inst = InstanceInfo(0, {ARCH: _hw()}, ARCH, VirtualQueue(0))
    controller = QLMController(
        [inst], QLMConfig(reschedule_on_arrival=False))
    srv = AsyncServer(controller, [_StubAgent()],
                      FrontendConfig(queue_depth=1))

    async def go():
        await srv.submit(_req(seed=0))
        s = await srv.submit(_req(seed=1))
        assert s.status == "rejected"
        assert await s.drain() == []                   # terminates, no hang
        # unservable model: 400-style recorded rejection, never an
        # exception out of the serve path
        bad = await srv.submit(make_request([1, 2], "no-such-model",
                                            "batch1",
                                            arrival_time=time.monotonic()))
        assert bad.status == "rejected"
        assert srv.stats.rejected_unservable == 1
        assert await bad.drain() == []

    asyncio.run(go())


# ---------------------------------------------------------------------------
# cancellation frees KV mid-decode
# ---------------------------------------------------------------------------

def test_cancellation_mid_decode_frees_kv_blocks(tiny):
    model, params = tiny
    eng, controller, server = _stack(model, params, slots=2)
    free0 = eng.block_mgr.free_blocks
    assert free0 == eng.block_mgr.num_blocks

    async def go():
        async with server:
            victim = _req(n_prompt=12, n_new=64, seed=1)
            keeper = _req(n_prompt=12, n_new=6, seed=2)
            vs = await server.submit(victim)
            ks = await server.submit(keeper)
            got = []
            async for tok in vs:
                got.append(tok)
                if len(got) == 3:
                    vs.cancel()                        # mid-decode
                    break
            await ks.drain()
            await server.drain()
            assert vs.status == "cancelled"
            return got

    got = asyncio.run(go())
    assert len(got) == 3
    assert eng.stats.cancellations == 1
    # the pool is back to its initial free count: nothing leaked
    assert eng.block_mgr.free_blocks == free0
    assert eng.block_mgr.used_blocks == 0 and eng.num_active() == 0
    # cancellation after first token is NOT an attainment miss
    assert controller.slo_attainment(time.monotonic()) == 1.0


# ---------------------------------------------------------------------------
# deadline expiry: never dispatched
# ---------------------------------------------------------------------------

def test_deadline_expired_request_never_dispatches(tiny):
    model, params = tiny
    # shedding off: otherwise the front end evicts the hog and SERVES the
    # doomed request — this test isolates queue-expiry itself
    eng, controller, server = _stack(model, params, slots=1,
                                     fcfg=FrontendConfig(shed_policy="off"))

    async def go():
        async with server:
            hog = _req(n_prompt=10, n_new=48, slo_class="batch1", seed=3)
            hs = await server.submit(hog)
            doomed = _req(n_prompt=10, n_new=8, seed=4)
            ds = await server.submit(doomed)
            assert ds.status == "queued"
            # force the deadline into the past while doomed is still queued
            # (no await between submit returning and this line, so the
            # server loop cannot have dispatched it): how long the hog
            # holds the slot is machine-dependent, a wall-clock slo races.
            # Backdate the arrival rather than zeroing the slo — the slo
            # feeds the group's min-slo invariant at classification time
            # and must stay immutable after admission (qlint invariants)
            doomed.arrival_time -= 1e9
            await ds.drain()
            assert ds.status == "expired"
            await hs.drain()
            await server.drain()

    asyncio.run(go())
    assert server.stats.expired == 1
    # it never reached the engine: no first token, no slot, no KV
    doomed = [r for r in controller.all_requests() if r.expired][0]
    assert doomed.ttft() is None and doomed.finished()
    assert eng.block_mgr.used_blocks == 0
    # the expired request is an attainment miss; the hog met its SLO
    assert controller.slo_attainment(time.monotonic()) == pytest.approx(0.5)


def test_dead_on_arrival_is_rejected_at_the_door():
    inst = InstanceInfo(0, {ARCH: _hw()}, ARCH, VirtualQueue(0))
    controller = QLMController(
        [inst], QLMConfig(reschedule_on_arrival=False))
    srv = AsyncServer(controller, [_StubAgent()], FrontendConfig())

    async def go():
        r = _req(seed=5)
        r.arrival_time = time.monotonic() - 100.0      # deadline long gone
        s = await srv.submit(r)
        assert s.status == "rejected" and r.expired
        assert srv.stats.rejected_deadline == 1

    asyncio.run(go())


# ---------------------------------------------------------------------------
# multi-turn sessions ride the prefix cache
# ---------------------------------------------------------------------------

def test_session_follow_up_turns_hit_prefix_cache(tiny):
    model, params = tiny
    eng, controller, server = _stack(model, params, slots=2)
    rng = np.random.default_rng(11)
    sess = Session(session_id=0, model=ARCH, slo_class="interactive",
                   turn_prompts=[rng.integers(0, 100, size=16).tolist()
                                 for _ in range(3)],
                   max_new_tokens=8, arrival_time=time.monotonic())

    async def go():
        async with server:
            await run_session(server, sess)
            await server.drain()

    asyncio.run(go())
    assert len(sess.requests) == 3
    assert all(r.finished() and r.session_id == 0 for r in sess.requests)
    assert [r.turn for r in sess.requests] == [0, 1, 2]
    # turn N+1 carries turn N's prompt+output as its prompt prefix; the
    # freed-block cache keeps the finished turn's chain matchable
    assert eng.stats.prefix_hits >= 2
    assert eng.stats.prefix_shared_tokens >= 2 * 16
    # each turn's prompt strictly grows by the previous turn's tokens
    p0, p1, p2 = [list(r.prompt_tokens) for r in sess.requests]
    assert p1[:len(p0)] == p0 + list(sess.requests[0].output_tokens)[:0] \
        or p1[:len(p0) + 8] == p0 + list(sess.requests[0].output_tokens)
    assert p2[:len(p1) + 8] == p1 + list(sess.requests[1].output_tokens)
    assert eng.block_mgr.used_blocks == 0


# ---------------------------------------------------------------------------
# clean shutdown + streaming
# ---------------------------------------------------------------------------

def test_drain_stop_clean_shutdown_streams_all_tokens(tiny):
    model, params = tiny
    eng, controller, server = _stack(model, params, slots=4)

    async def go():
        async with server:
            streams = [await server.submit(_req(n_prompt=8, n_new=6, seed=i))
                       for i in range(3)]
            toks = [await s.drain() for s in streams]
            await server.drain()
            return toks

    toks = asyncio.run(go())
    assert all(len(t) == 6 for t in toks)
    assert server.stats.tokens_streamed == 18
    assert not server._live and server._task is None
    assert server.stats.accepted == 3 and server.stats.rejected == 0
    assert eng.block_mgr.used_blocks == 0


def test_stop_cancels_outstanding(tiny):
    model, params = tiny
    eng, controller, server = _stack(model, params, slots=2)

    async def go():
        await server.start()
        s = await server.submit(_req(n_prompt=10, n_new=64, seed=7))
        # wait for it to start decoding, then hard-stop
        while s.request.first_token_time is None:
            await asyncio.sleep(0.005)
        await server.stop(cancel_outstanding=True)
        return s

    s = asyncio.run(go())
    assert s.status == "cancelled"
    assert eng.block_mgr.used_blocks == 0
    assert eng.block_mgr.free_blocks == eng.block_mgr.num_blocks


# ---------------------------------------------------------------------------
# the acceptance bar: 2x overload, async > sync on interactive attainment
# ---------------------------------------------------------------------------

def _overload_args(requests):
    # reschedule_cooldown longer than the run throttles the controller's
    # on-arrival re-solve for BOTH runners, so the comparison isolates
    # what the async front end adds: shedding on its own clock
    # (shed_cooldown) plus deadline expiry of unservable requests
    return argparse.Namespace(
        seed=0, rate=400.0, requests=requests, max_new_tokens=2,
        batch_new_tokens=100, slots=2, decode_burst=8, backend="paged-xla",
        prefix_sharing=True, instances=1, queue_depth=512,
        shed_policy="defer", shed_cooldown=0.15, admit_drain="off",
        sessions=0, session_turns=0, think_time=0.0, slo_scale=0.08,
        reschedule_cooldown=1e9, max_wall=90.0)


def test_async_beats_sync_interactive_attainment_under_overload(tiny):
    from repro.launch.async_serve import run_async, run_sync
    from repro.launch.serve import calibrate_registry

    model, params = tiny
    registry = {ARCH: (model, params)}
    args = _overload_args(400)
    ecfg = EngineConfig(max_slots=args.slots, max_seq_len=128,
                        attention_backend=args.backend,
                        prefix_sharing=args.prefix_sharing)
    hw = calibrate_registry(registry, ecfg)

    # warmup pass: populate the persistent XLA cache for every shape each
    # runner compiles (the async shed/evict/resume paths hit shapes the
    # sync loop never does); the measured runs then compare scheduling
    warm = _overload_args(40)
    run_sync(warm, registry, hw, [ARCH])
    asyncio.run(run_async(warm, registry, hw, [ARCH]))

    sync_stats = run_sync(args, registry, hw, [ARCH])
    async_stats = asyncio.run(run_async(args, registry, hw, [ARCH]))

    assert async_stats["clean_shutdown"] == 1
    assert async_stats["kv_blocks_leaked"] == 0
    assert async_stats["tokens_streamed"] > 0
    # same seed, same workload: the shedding/deadline-aware front end must
    # strictly beat the synchronous driver on interactive attainment
    assert async_stats["attainment_interactive"] \
        > sync_stats["attainment_interactive"], (async_stats, sync_stats)


# ---------------------------------------------------------------------------
# serve-loop crash propagation: a dead loop must fail clients, not hang them
# ---------------------------------------------------------------------------

def test_serve_loop_crash_fails_waiters_instead_of_hanging(tiny):
    model, params = tiny
    eng, controller, server = _stack(model, params, slots=2)

    class _Boom(RuntimeError):
        pass

    async def go():
        await server.start()
        stream = await server.submit(_req(n_prompt=6, n_new=64, seed=11))

        def explode():
            raise _Boom("engine round blew up")

        # crash the next engine round; before the _run crash handler
        # existed this left stream.drain() and server.drain() awaiting
        # tokens forever (observed: an InvariantViolation inside the loop
        # hung the whole suite)
        server.agents[0].run_iteration = explode
        with pytest.raises(_Boom):
            await asyncio.wait_for(stream.drain(), timeout=10)
        with pytest.raises(_Boom):
            await asyncio.wait_for(server.drain(), timeout=10)
        # new submissions fail fast instead of queueing onto a dead loop
        with pytest.raises(_Boom):
            await server.submit(_req(seed=12))
        # the task's own exception was consumed above; swallow it so
        # asyncio.run doesn't log "exception was never retrieved"
        with pytest.raises(_Boom):
            await server._task

    asyncio.run(go())
