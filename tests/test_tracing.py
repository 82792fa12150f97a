"""The served path's spans and admission stamp, on the CPU.

A tiny paged engine serves a few requests through ``ThreadedCluster``
under ``jax.profiler``; the trace is read back as the benchmark reads it
(``bench/harness``).  Every span the path reaches must be there, on the
thread that did the work and nested as ``repro/spans.py`` says; the span
names must be the ones ``PERF.md`` documents; and each request's
admission stamp must be set once and survive eviction, resume and
restart.
"""
import gc
import pathlib
import re
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro import spans
from repro.configs import ARCHITECTURES
from repro.core.global_scheduler import InstanceInfo
from repro.core.lso import QLMAgent
from repro.core.qlm import QLMConfig, QLMController
from repro.core.request import make_request
from repro.core.rwt_estimator import HardwareProfile
from repro.core.virtual_queue import VirtualQueue
from repro.models import build_model
from repro.serving import (ContinuousBatchingEngine, EngineConfig,
                           ThreadedCluster)

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))   # the benchmark's trace reader

# the single-model path never swaps a model or evicts for a new head
NOT_REACHED = {"qlm.lso.swap", "qlm.lso.evict"}


@pytest.fixture(scope="module")
def tiny():
    cfg = ARCHITECTURES["granite-3-2b"].reduced(num_layers=1, d_model=64)
    model = build_model(cfg)
    return model, model.init(jax.random.key(0))


def _engine(tiny, **kw):
    model, params = tiny
    cfg = EngineConfig(max_slots=4, max_seq_len=64, block_size=8,
                       prefill_chunk_tokens=16, attention_backend="paged-xla",
                       decode_burst=4, **kw)
    return ContinuousBatchingEngine(model, params, cfg, model_name="m")


def _hw():
    return HardwareProfile(prefill_time=0.05, decode_per_token=0.02,
                           inefficiency=1.2, token_capacity=512,
                           swap_time=0.2, model_max_tokens=64)


@pytest.fixture(scope="module")
def served(tiny, tmp_path_factory):
    """Serve three requests on one agent thread with the profiler on;
    the main thread also holds the controller lock once while the agent
    runs, re-solves once and collects garbage once."""
    from bench.harness import spans as bench_spans
    eng = _engine(tiny, debug_invariants=True)
    vq = VirtualQueue(0)
    agent = QLMAgent(eng, vq, {"m": tiny})
    ctl = QLMController([InstanceInfo(0, {"m": _hw()}, "m", vq)],
                        QLMConfig(avg_batch_size=4, reschedule_cooldown=0.0))
    ctl.attach_engines([eng])
    cluster = ThreadedCluster(ctl, [agent], [eng])
    hooked = []
    cluster.round_hook = hooked.append
    rng = np.random.default_rng(0)
    reqs = [make_request(rng.integers(0, 100, n).tolist(), "m",
                         "interactive", arrival_time=time.monotonic(),
                         max_new_tokens=12) for n in (20, 9, 30)]
    trace_dir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        cluster.start()
        try:
            for r in reqs:
                ctl.submit(r, time.monotonic())
            ok = cluster.wait(lambda: all(r.finished() for r in reqs),
                              timeout=120.0)
            with ctl.lock:                 # the agent waits here
                time.sleep(0.2)
            ctl.reschedule(time.monotonic())
            gc.collect()
            time.sleep(0.05)               # a few idle rounds
        finally:
            cluster.stop()
    finally:
        jax.profiler.stop_trace()
    assert ok and hooked
    assert cluster._gc_spans not in gc.callbacks
    path = next(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    return bench_spans.load(str(path)), reqs, str(path)


def _inside(inner, outer):
    return inner.thread == outer.thread and outer.start <= inner.start \
        and inner.end <= outer.end


def _outer(span, pool, name):
    return [o for o in pool if o.name == name and _inside(span, o)]


def test_every_reached_span_is_recorded(served):
    ht, _, _ = served
    seen = {s.name for s in ht.spans}
    assert set(spans.NAMES) - NOT_REACHED <= seen, \
        set(spans.NAMES) - NOT_REACHED - seen
    assert seen <= set(spans.NAMES) | {"agent.run_iteration",
                                       "controller.tick", "client.submit"}


def test_round_spans_nest_on_the_agent_thread(served):
    ht, _, _ = served
    waits = [s for s in ht.spans if s.name == "qlm.engine.device_wait"]
    assert waits
    agent_threads = {s.thread for s in ht.spans
                     if s.name == "qlm.agent.loop"}
    assert len(agent_threads) == 1
    nested = 0
    for w in waits:
        assert w.thread in agent_threads
        for b in _outer(w, ht.spans, "qlm.engine.burst"):
            for it in _outer(b, ht.spans, "qlm.agent.iteration"):
                nested += bool(_outer(it, ht.spans, "qlm.agent.loop"))
    assert nested >= 1
    for name in ("qlm.engine.prep", "qlm.engine.dispatch",
                 "qlm.engine.post"):
        for s in ht.spans:
            if s.name == name:
                assert any(_outer(s, ht.spans, r) for r in (
                    "qlm.engine.prefill", "qlm.engine.decode",
                    "qlm.engine.burst")), s
    for s in ht.spans:
        if s.name == "qlm.lso.pull":
            assert _outer(s, ht.spans, "qlm.engine.admit"), s


def test_solve_nests_in_reschedule_and_lock_waits_in_their_taker(served):
    ht, _, _ = served
    solves = [s for s in ht.spans if s.name == "qlm.scheduler.solve"]
    assert solves
    for s in solves:
        assert _outer(s, ht.spans, "qlm.controller.reschedule"), s
    agent = {s.thread for s in ht.spans if s.name == "qlm.agent.loop"}
    waits = [s for s in ht.spans
             if s.name == "qlm.lock_wait" and s.thread in agent]
    assert any(w.end - w.start > 0.02 for w in waits)
    for w in waits:
        assert _outer(w, ht.spans, "qlm.agent.loop"), w


def test_a_collection_is_a_gc_span(served):
    ht, _, _ = served
    main = [s for s in ht.spans if s.name == "python.gc"]
    assert main and all(s.end > s.start for s in main)


def test_the_benchmark_reads_the_rounds(served):
    from bench.harness import spans as bench_spans
    ht, _, path = served
    host = bench_spans.agent_host_ms_per_round(ht)
    lock = bench_spans.lock_wait_ms_per_round(ht)
    assert host is not None and host > 0
    assert lock is not None and 0 < lock < host
    report = bench_spans.report(path)
    assert report["rounds"] == len(bench_spans.loops(ht))


def test_admission_is_stamped_before_the_first_token(served):
    _, reqs, _ = served
    for r in reqs:
        assert r.admitted_time is not None
        assert r.arrival_time <= r.admitted_time <= r.first_token_time


def test_admission_stamp_survives_evict_resume_and_restart(tiny):
    t = [100.0]
    eng = _engine(tiny)
    eng.clock = lambda: t[0]
    r = make_request(list(range(1, 21)), "m", "batch1", arrival_time=99.0,
                     max_new_tokens=8)
    assert eng.admit(r)
    assert r.admitted_time == 100.0
    t[0] = 101.0
    while r.first_token_time is None:
        eng.steps()
    eng.steps()
    t[0] = 102.0
    slot = eng.slots.index(r)
    eng.evict_slot(slot)
    assert r.admitted_time == 100.0
    t[0] = 103.0
    assert eng.admit(r)                    # resume from the snapshot
    assert r.admitted_time == 100.0
    eng.evict_slot(eng.slots.index(r))
    eng._discard_snapshot(r)
    r.restart()
    assert r.admitted_time == 100.0
    assert eng.admit(r)
    assert r.admitted_time == 100.0 <= r.first_token_time


def test_lock_wait_spans_only_a_blocking_acquire():
    lock = spans.TimedRLock()
    with lock:
        with lock:                          # re-entrant: no wait
            pass
    held = threading.Event()
    release = threading.Event()

    def hold():
        with lock:
            held.set()
            release.wait(5.0)

    t = threading.Thread(target=hold)
    t.start()
    assert held.wait(5.0)
    assert not lock.acquire(blocking=False)
    threading.Timer(0.05, release.set).start()
    assert lock.acquire(timeout=5.0)
    lock.release()
    t.join(5.0)
    assert not t.is_alive()


def test_perf_md_lists_the_span_names():
    text = (ROOT / "PERF.md").read_text()
    table = text[text.index("### Spans, counters and stamps"):]
    table = table[:table.index("\n\n", table.index("| ---"))]
    listed = re.findall(r"^\| `((?:qlm|python)\.[a-z_.]+)`", table, re.M)
    assert sorted(listed) == sorted(spans.NAMES)


def test_no_span_inside_a_per_token_loop():
    """Round-level span sites only: no ``span(`` call inside a ``for``
    loop of the engine's round functions."""
    import ast
    import inspect
    from repro.serving import engine as engine_mod
    tree = ast.parse(inspect.getsource(engine_mod))
    for node in ast.walk(tree):
        if isinstance(node, (ast.For, ast.While)):
            for inner in ast.walk(node):
                if isinstance(inner, ast.Call) \
                        and getattr(inner.func, "id", None) == "span":
                    raise AssertionError(
                        f"span opened inside a loop at line {inner.lineno}")
