"""Trace reduction on a synthesised trace whose busy time, self times and
idle gaps are known by construction."""
import pytest

from bench.harness import trace as T

E = T.Event


def _trace():
    # device: a loop [0.1, 0.5) holding two kernel calls and a fusion,
    # then a lone op [0.7, 0.8); window 1.0 s
    ops = [E("while.3", 0.1, 0.5),
           E("paged_decode_attention.10", 0.15, 0.25),
           E("paged_decode_attention.10", 0.30, 0.40),
           E("fusion.7", 0.40, 0.45),
           E("copy.90", 0.7, 0.8)]
    host = [E("agent.run_iteration", 0.0, 0.55),
            E("controller.tick", 0.52, 0.62),
            E("client.submit", 0.85, 0.95)]
    return T.Trace(window_s=1.0, device_ops={"/device:TPU:0": ops},
                   host_spans=host)


def test_busy_is_the_union_of_nested_ops():
    tr = _trace()
    assert T.busy_seconds(tr.device_ops["/device:TPU:0"], 1.0) \
        == pytest.approx(0.4 + 0.1)


def test_union_clips_to_the_window():
    ev = [E("a", -0.2, 0.1), E("b", 0.05, 0.2), E("c", 0.9, 1.4)]
    assert T.union(ev, 0.0, 1.0) == [(0.0, 0.2), (0.9, 1.0)]


def test_self_time_subtracts_nested_ops():
    st = T.self_times(_trace().device_ops["/device:TPU:0"])
    assert st["while.3"] == pytest.approx(0.4 - 0.2 - 0.05)
    assert st["paged_decode_attention.10"] == pytest.approx(0.2)
    assert st["fusion.7"] == pytest.approx(0.05)
    assert st["copy.90"] == pytest.approx(0.1)
    assert sum(st.values()) == pytest.approx(0.5)


def test_idle_gaps_are_labelled_by_the_open_host_span():
    tr = _trace()
    gaps = T.idle_gaps(tr.device_ops["/device:TPU:0"], tr.host_spans, 1.0)
    assert [g for g, _ in gaps] == ["controller.tick", "client.submit",
                                    "agent.run_iteration"]
    assert [s for _, s in gaps] == pytest.approx([0.2, 0.2, 0.1])


def test_kernel_seconds_by_instruction_name():
    ops = _trace().device_ops["/device:TPU:0"]
    assert T.kernel_seconds(ops, "paged_decode_attention") \
        == pytest.approx(0.2)
    assert T.kernel_seconds(ops, "paged_prefill_attention") == 0


def test_reduce_reports_busy_window_and_breakdown():
    r = T.reduce(_trace(), top=2)
    assert r["busy_s"] == pytest.approx(0.5)
    assert r["window_s"] == 1.0
    assert [k for k, _ in r["breakdown"]["device_ops"]] == [
        "paged_decode_attention.10", "while.3"]
    assert len(r["breakdown"]["idle_gaps"]) == 2


def test_op_name_from_an_xla_ops_event():
    assert T.op_name("%paged_decode_attention.10 = bf16[16,8,4,64]{3,2,1,0}"
                     " custom-call(...)") == "paged_decode_attention.10"
    assert T.op_name("fusion.3") == "fusion.3"


def test_reduce_refuses_a_trace_without_a_device():
    with pytest.raises(ValueError):
        T.reduce(T.Trace(window_s=1.0, device_ops={}, host_spans=[]))
