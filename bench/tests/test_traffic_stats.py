"""The traffic generator and the end-to-end metric arithmetic."""
import json

import pytest

from bench.harness import stats, traffic
from bench.harness.spec import traffic_path
from bench.harness.stats import Record

MIXES = {"chat-mixed": 2048, "long-prompt": 4096}


def _mix(name):
    return json.loads(traffic_path(name).read_text())


def _gen(name, seed, seconds=51.0):
    return traffic.generate(_mix(name), seconds=seconds, seed=seed,
                            max_seq_len=MIXES[name], vocab_size=1000)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_same_seed_same_schedule(name):
    a, b = _gen(name, 2**31 + 5), _gen(name, 2**31 + 5)
    assert a == b
    assert a != _gen(name, 2**31 + 6)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_every_seed_offers_the_same_work(name):
    a, b = _gen(name, 3), _gen(name, 4)
    key = lambda x: [(r.due_s, len(r.prompt), r.max_new_tokens,  # noqa: E731
                      r.slo_class) for r in x]
    assert key(a) == key(b)
    assert [r.prompt for r in a] != [r.prompt for r in b]


@pytest.mark.parametrize("name", sorted(MIXES))
def test_lengths_respect_max_seq_len(name):
    for r in _gen(name, 9, seconds=200.0):
        assert 1 <= len(r.prompt) and r.max_new_tokens >= 1
        assert len(r.prompt) + r.max_new_tokens <= MIXES[name]
        assert all(0 <= t < 1000 for t in r.prompt)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_arrivals_span_the_window_at_the_rate(name):
    mix, seconds = _mix(name), 51.0
    arr = _gen(name, 1, seconds)
    assert len(arr) == traffic.request_count(mix, seconds)
    due = [r.due_s for r in arr]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < seconds
    shares = {c["name"]: c["share"] for c in mix["classes"]}
    for cls, share in shares.items():
        n = sum(r.slo_class == cls for r in arr)
        assert abs(n - share * len(arr)) <= 1


def _rec(idx, due, stamps, cls="interactive", failed=False, limit=2.0):
    return Record(idx=idx, slo_class=cls, ttft_limit_s=limit,
                  tpot_limit_s=None, due=due, prompt_len=4,
                  max_new_tokens=10, stamps=stamps, failed=failed)


def test_ttft_is_taken_from_the_due_time():
    r = _rec(0, due=10.0, stamps=[(10.7, 1), (11.0, 3)])
    r.submitted = 10.5  # a late submit does not shorten the TTFT
    assert stats.ttft(r, drain_end=20.0) == pytest.approx(0.7)


def test_unserved_failed_and_rejected_requests_miss():
    end, drain = 10.0, 12.0
    ok = _rec(0, 1.0, [(1.5, 1)])
    unserved = _rec(1, 9.0, [])
    failed = _rec(2, 1.0, [(1.2, 1)], failed=True)
    late = _rec(3, 1.0, [(3.5, 1)])
    recs = [ok, unserved, failed, late]
    assert [stats.met_limits(r, end, drain) for r in recs] == [
        True, False, False, False]
    # an unserved request counts with the wait it had when the drain closed
    assert stats.ttft(unserved, drain) == pytest.approx(3.0)
    assert stats.ttft(failed, drain) == pytest.approx(11.0)
    out = stats.end_to_end(recs, start=0.0, end=end, drain_end=drain)
    assert out["interactive_slo_attainment"] == pytest.approx(0.25)
    assert stats.counts(recs) == {"attempted": 4, "failed": 1}


def test_only_tokens_inside_the_window_count():
    recs = [_rec(0, 0.5, [(1.0, 1), (5.0, 4), (10.5, 9)]),
            _rec(1, 9.0, [(9.9, 2), (11.0, 5)])]
    assert stats.tokens_in_window(recs, 0.0, 10.0) == 4 + 2
    out = stats.end_to_end(recs, start=0.0, end=10.0, drain_end=12.0)
    assert out["output_tokens_per_s"] == pytest.approx(0.6)


def test_tpot_uses_tokens_received_in_the_window():
    r = _rec(0, 0.0, [(1.0, 1), (2.0, 3), (11.0, 9)])
    assert stats.tpot(r, window_end=10.0) == pytest.approx(0.5)
    assert stats.tpot(_rec(1, 0.0, [(1.0, 1)]), 10.0) is None


def test_p95_is_taken_over_the_whole_window():
    # 40 requests, nearest rank 38: with 3 slow ones the 95th percentile
    # lands on a slow one, with 2 on a fast one
    fast = [_rec(i, 0.0, [(0.1, 1)]) for i in range(37)]
    slow = [_rec(100 + i, 0.0, [(5.0, 1)]) for i in range(3)]
    out = stats.end_to_end(fast + slow, start=0.0, end=10.0, drain_end=12.0)
    assert out["ttft_p95_s"] == pytest.approx(5.0)
    out = stats.end_to_end(fast + [_rec(7, 0.0, [(0.1, 1)])] + slow[:2],
                           start=0.0, end=10.0, drain_end=12.0)
    assert out["ttft_p95_s"] == pytest.approx(0.1)


def test_percentile_nearest_rank():
    assert stats.percentile(list(range(1, 101)), 95) == 95
    assert stats.percentile([3.0], 99) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 95)
