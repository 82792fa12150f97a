"""End-to-end metric arithmetic over the client's records of one window.

Every statistic covers the whole window: all requests due in it, every
token the client received in it.  Times are on the client's clock (the
host's monotonic clock), taken where the client sees each token.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple


@dataclasses.dataclass
class Record:
    """What the client knows of one request due in the window."""
    idx: int
    slo_class: str
    ttft_limit_s: float
    tpot_limit_s: Optional[float]
    due: float                      # absolute, host monotonic seconds
    prompt_len: int
    max_new_tokens: int
    submitted: Optional[float] = None
    admitted: Optional[float] = None  # first round it sat in a slot
    slot: Optional[Tuple[int, int]] = None  # (engine, slot) it first sat in
    # (time, tokens received so far), one entry per round that added any
    stamps: List[Tuple[float, int]] = dataclasses.field(default_factory=list)
    failed: bool = False            # failed, rejected or shed by the system

    def first_token(self, until: float) -> Optional[float]:
        for t, _ in self.stamps:
            if t <= until:
                return t
        return None

    def received(self, until: float) -> List[Tuple[float, int]]:
        return [(t, n) for t, n in self.stamps if t <= until]


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the sample at or below it."""
    if not values:
        raise ValueError("percentile of an empty sample")
    s = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(s)))
    return s[k - 1]


def ttft(rec: Record, drain_end: float) -> float:
    """First token minus due time.  A request with no first token by the
    end of the drain counts with the wait it had when the drain closed:
    the least its TTFT can be."""
    t = None if rec.failed else rec.first_token(drain_end)
    return (drain_end if t is None else t) - rec.due


def tpot(rec: Record, window_end: float) -> Optional[float]:
    """(last token in the window - first token) / (tokens - 1), for a
    request that received at least 2 tokens in the window."""
    got = rec.received(window_end)
    if rec.failed or not got or got[-1][1] < 2:
        return None
    return (got[-1][0] - got[0][0]) / (got[-1][1] - 1)


def met_limits(rec: Record, window_end: float, drain_end: float) -> bool:
    if rec.failed or rec.first_token(drain_end) is None:
        return False
    if ttft(rec, drain_end) > rec.ttft_limit_s:
        return False
    if rec.tpot_limit_s is not None:
        t = tpot(rec, window_end)
        if t is not None and t > rec.tpot_limit_s:
            return False
    return True


def tokens_in_window(records: Sequence[Record], start: float,
                     end: float) -> int:
    total = 0
    for r in records:
        before = 0
        for t, n in r.stamps:
            if t > end:
                break
            if t >= start:
                total += n - before
            before = n
    return total


def end_to_end(records: Sequence[Record], *, start: float, end: float,
               drain_end: float) -> Dict[str, float]:
    """The end-to-end metrics of one window [start, end] whose requests
    had until ``drain_end`` for a first token."""
    if not records:
        raise ValueError("no request was due in the window")
    tpots = [t for t in (tpot(r, end) for r in records) if t is not None]
    inter = [r for r in records if r.slo_class == "interactive"]
    out = {
        "output_tokens_per_s": tokens_in_window(records, start, end)
        / (end - start),
        "ttft_p95_s": percentile([ttft(r, drain_end) for r in records], 95),
        "tpot_p95_ms": 1e3 * percentile(tpots, 95) if tpots else None,
        "interactive_slo_attainment": (
            sum(met_limits(r, end, drain_end) for r in inter) / len(inter)
            if inter else None),
    }
    return {k: v for k, v in out.items() if v is not None}


def counts(records: Sequence[Record]) -> Dict[str, Any]:
    return {"attempted": len(records),
            "failed": sum(1 for r in records if r.failed)}
