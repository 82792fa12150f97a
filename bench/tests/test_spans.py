"""The program's own spans in a trace, on synthesised spans whose gap
labels and per-round readings are known by construction; and the
admission-stamp reader against requests built here."""
import types

import pytest

from bench.harness import spans as S
from bench.harness import trace as T
from bench.harness.main import read_metric
from bench.harness.stats import Record

E = T.Event
AGENT, CTL, CLIENT = 0, 1, 2


def _program():
    """Agent thread: two whole rounds [0.05, 0.45) and [0.45, 0.85) and
    one cut by the trace's stop; the controller thread ticks at
    [0.48, 0.52) with a gc pass in it.  Device: busy [0.2, 0.4) and
    [0.6, 0.8) (window 1.0 s)."""
    sp = []
    for lo in (0.05, 0.45):
        sp += [S.Span("qlm.agent.loop", lo, lo + 0.4, AGENT),
               S.Span("agent.run_iteration", lo, lo + 0.32, AGENT),
               S.Span("qlm.agent.iteration", lo + 0.001, lo + 0.32, AGENT),
               S.Span("qlm.engine.burst", lo + 0.01, lo + 0.31, AGENT),
               S.Span("qlm.engine.prep", lo + 0.01, lo + 0.04, AGENT),
               S.Span("qlm.lock_wait", lo + 0.02, lo + 0.03, AGENT),
               S.Span("qlm.engine.dispatch", lo + 0.04, lo + 0.1, AGENT),
               S.Span("qlm.engine.device_wait", lo + 0.1, lo + 0.3, AGENT),
               S.Span("qlm.engine.post", lo + 0.3, lo + 0.31, AGENT),
               S.Span("qlm.agent.heartbeat", lo + 0.32, lo + 0.34, AGENT),
               S.Span("qlm.lock_wait", lo + 0.32, lo + 0.335, AGENT),
               S.Span("qlm.agent.hook", lo + 0.34, lo + 0.36, AGENT),
               S.Span("qlm.agent.idle", lo + 0.36, lo + 0.4, AGENT)]
    sp += [S.Span("qlm.agent.loop", 0.85, 1.2, AGENT),
           S.Span("controller.tick", 0.48, 0.52, CTL),
           S.Span("qlm.controller.tick", 0.48, 0.52, CTL),
           S.Span("python.gc", 0.49, 0.51, CTL)]
    device = [E("while.1", 0.2, 0.4), E("while.1", 0.6, 0.8)]
    return S.HostTrace(1.0, sp), device


def test_innermost_span_per_thread_labels_a_gap():
    ht, device = _program()
    labels = {round(s, 6): k for k, s, _ in S.gaps(device, ht.spans, 1.0)}
    # [0.0, 0.2): mid 0.1 inside the first round's dispatch
    assert labels[0.0] == "qlm.engine.dispatch"
    # [0.4, 0.6): mid 0.5, the second round's dispatch on the agent's
    # thread and the gc pass inside the controller's tick
    assert labels[0.4] == "python.gc+qlm.engine.dispatch"
    # [0.8, 1.0): mid 0.9, inside the third round and nothing else
    assert labels[0.8] == "qlm.agent.loop"


def test_innermost_prefers_the_latest_and_then_the_shortest_span():
    sp = [S.Span("a", 0.0, 1.0, 0), S.Span("b", 0.2, 0.8, 0),
          S.Span("c", 0.2, 0.5, 0), S.Span("d", 0.0, 1.0, 1)]
    assert S.innermost(sp, 0.3) == "c+d"
    assert S.innermost(sp, 0.6) == "b+d"
    assert S.innermost(sp, 1.0) == "none"


def test_without_program_spans_labels_are_the_harness_own():
    ops = [E("while.3", 0.1, 0.5), E("fusion.7", 0.40, 0.45),
           E("copy.90", 0.7, 0.8)]
    host = [E("agent.run_iteration", 0.0, 0.55),
            E("controller.tick", 0.52, 0.62),
            E("client.submit", 0.85, 0.95)]
    sp = [S.Span(h.name, h.start, h.end, i) for i, h in enumerate(host)]
    old = T.idle_gaps(ops, host, 1.0)
    new = [(k, d) for k, _, d in S.gaps(ops, sp, 1.0)]
    assert new == old


def test_agent_host_ms_per_round_leaves_out_device_wait_and_idle():
    ht, _ = _program()
    assert [r.start for r in S.loops(ht)] == [0.05, 0.45]   # third is cut
    # each round: 0.4 s - device_wait 0.2 - idle 0.04
    assert S.agent_host_ms_per_round(ht) == pytest.approx(160.0)


def test_lock_wait_ms_per_round_counts_the_agent_thread_only():
    ht, _ = _program()
    extra = S.Span("qlm.lock_wait", 0.2, 0.3, CLIENT)   # another thread
    ht = S.HostTrace(ht.window_s, ht.spans + [extra])
    # each round: 0.01 in prep and 0.015 in the heartbeat
    assert S.lock_wait_ms_per_round(ht) == pytest.approx(25.0)


def test_no_round_reads_nothing():
    ht = S.HostTrace(1.0, [S.Span("controller.tick", 0.1, 0.2, 0)])
    assert S.agent_host_ms_per_round(ht) is None
    assert S.lock_wait_ms_per_round(ht) is None


def test_readers_without_a_trace_read_nothing():
    ctx = types.SimpleNamespace(trace=None)
    assert read_metric("agent_host_ms_per_round", ctx) is None
    assert read_metric("lock_wait_ms_per_round", ctx) is None


def _record(idx, cls, due, prompt_len):
    return Record(idx=idx, slo_class=cls, ttft_limit_s=2.0,
                  tpot_limit_s=None, due=due, prompt_len=prompt_len,
                  max_new_tokens=8)


def test_admit_wait_reads_the_program_stamp():
    from repro.core.request import make_request
    base = 1e6 + 0.123   # a due time no other request of the process has
    recs = [_record(i, "interactive" if i < 3 else "batch1", base + i, 5)
            for i in range(4)]
    reqs = [make_request([1] * 5, "m", r.slo_class, arrival_time=r.due)
            for r in recs]
    reqs[0].admitted_time = base + 0.5        # waited 0.5 s
    reqs[1].admitted_time = base + 1 + 0.2    # 0.2 s
    reqs[3].admitted_time = base + 3 + 9.0    # batch: not counted
    # reqs[2] never admitted: counts to the drain's end (base + 2 + 1.5)
    ctx = types.SimpleNamespace(records=recs,
                                bounds={"drain_end": base + 3.5})
    assert read_metric("admit_wait_p95_s.interactive", ctx) \
        == pytest.approx(1.5)
    assert read_metric("admit_wait_p95_s.interactive.ttft", ctx) \
        == pytest.approx(1.5)
    # a record that matches no request of the program reads nothing
    recs.append(_record(4, "interactive", base + 7, 5))
    assert read_metric("admit_wait_p95_s.interactive", ctx) is None
    assert len(reqs) == 4   # the requests stay alive while read
