"""The served path's own host spans in a profiler trace, and what they say.

The program opens ``jax.profiler.TraceAnnotation`` spans named ``qlm.*``
and ``python.gc`` (listed in ``src/repro/spans.py``) on the thread that
does the work.  The profiler records them on that thread's host line, on
the same clock as the device's "XLA Ops".  This module reads them, beside
the benchmark's own three wrappers (``trace.HOST_SPANS``), each with the
line it lies on, and reduces them:

  * idle gaps of the device, each named by the innermost span open at
    its middle on every thread, joined by ``+`` (with no program span in
    the trace the names are ``trace.idle_gaps``'s own);
  * host time per agent round: a ``qlm.agent.loop`` pass minus the time
    it spent waiting for the device (``qlm.engine.device_wait``) or
    backing off idle (``qlm.agent.idle``);
  * lock wait per agent round: the time ``qlm.lock_wait`` spans cover on
    the agent's thread within the pass.

A thread is keyed by its line's place in the trace, never by the line's
name: on the CPU every thread's line is named ``python``.  A trace of a
program that opens no such span reduces to nothing, and the readers
return ``None``.

  python -m bench.harness.spans <trace.xplane.pb>

prints the reduction of one trace as JSON: the labelled gaps with their
place in the window, the share of idle time between the first and last
agent round that a program span names, and the per-round readings.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from bench.harness import trace as trace_lib
from bench.harness.spec import BENCH_DIR

PROGRAM = ("qlm.", "python.gc")
LOOP = "qlm.agent.loop"
NOT_HOST = ("qlm.engine.device_wait", "qlm.agent.idle")
LOCK_WAIT = "qlm.lock_wait"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float   # seconds after the profile started
    end: float
    thread: int    # the host line it lies on


@dataclasses.dataclass
class HostTrace:
    window_s: float
    spans: List[Span]


def is_program(name: str) -> bool:
    return name.startswith(PROGRAM)


@functools.lru_cache(maxsize=2)
def load(path: str) -> HostTrace:
    """Every program span and benchmark wrapper of the trace at ``path``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    window = None
    spans: List[Span] = []
    thread = 0
    for plane in data.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            window = (float(stats["profile_stop_time"])
                      - float(stats["profile_start_time"])) * 1e-9
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans.extend(Span(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9,
                              thread) for e in line.events
                         if is_program(e.name)
                         or e.name in trace_lib.HOST_SPANS)
            thread += 1
    if window is None:
        raise ValueError(f"{path}: no profile start and stop times")
    return HostTrace(window, spans)


def for_run(ctx) -> Optional[HostTrace]:
    """The host spans of the run ``ctx`` describes: the newest trace the
    harness wrote for the cell, if its window is the one ``ctx.trace``
    was read from.  ``None`` if there is none, or if the program opened
    no span of its own."""
    if ctx.trace is None:
        return None
    root = str(BENCH_DIR.parent / "bench_out" / "trace")
    paths = glob.glob(os.path.join(glob.escape(root),
                                   glob.escape(ctx.cell.name) + ".*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        return None
    ht = load(max(paths, key=os.path.getmtime))
    if ht.window_s != ctx.trace.window_s \
            or not any(is_program(s.name) for s in ht.spans):
        return None
    return ht


def innermost(spans: Sequence[Span], t: float) -> str:
    """The innermost span open at ``t`` on each thread (the latest
    started, the shortest of those), joined by ``+``; "none" if no span
    is open."""
    best: Dict[int, Span] = {}
    for s in spans:
        if s.start <= t < s.end:
            b = best.get(s.thread)
            if b is None or (s.start, b.end) > (b.start, s.end):
                best[s.thread] = s
    return "+".join(sorted({s.name for s in best.values()})) or "none"


def gaps(device: Sequence[trace_lib.Event], spans: Sequence[Span],
         window_s: float) -> List[Tuple[str, float, float]]:
    """Every hole in the device's busy union inside the window, longest
    first, as (label, start, seconds): named by ``innermost`` at its
    middle."""
    busy = trace_lib.union(device, 0.0, window_s)
    edges = [0.0] + [x for iv in busy for x in iv] + [window_s]
    out = [(innermost(spans, (s + t) / 2), s, t - s)
           for s, t in zip(edges[0::2], edges[1::2]) if t > s]
    return sorted(out, key=lambda g: -g[2])


def loops(ht: HostTrace) -> List[Span]:
    """The agent rounds lying wholly inside the traced window."""
    return [s for s in ht.spans
            if s.name == LOOP and s.start >= 0.0 and s.end <= ht.window_s]


def _covered(spans: Sequence[Span], names: Tuple[str, ...],
             outer: Span) -> float:
    """Seconds that the spans named ``names`` on ``outer``'s thread cover
    inside ``outer``."""
    inner = [trace_lib.Event(s.name, s.start, s.end) for s in spans
             if s.thread == outer.thread and s.name in names
             and outer.start <= s.start and s.end <= outer.end]
    return sum(t - s for s, t in trace_lib.union(inner))


def agent_host_ms_per_round(ht: HostTrace) -> Optional[float]:
    rounds = loops(ht)
    if not rounds:
        return None
    return 1e3 * sum((r.end - r.start) - _covered(ht.spans, NOT_HOST, r)
                     for r in rounds) / len(rounds)


def lock_wait_ms_per_round(ht: HostTrace) -> Optional[float]:
    rounds = loops(ht)
    if not rounds:
        return None
    return 1e3 * sum(_covered(ht.spans, (LOCK_WAIT,), r)
                     for r in rounds) / len(rounds)


def report(path: str, top: int = 20) -> Dict:
    """The reduction of one trace (see the module's docstring)."""
    device = trace_lib.device_events(trace_lib.load(path)) or []
    ht = load(path)
    labelled = gaps(device, ht.spans, ht.window_s)
    rounds = loops(ht)
    inside = []
    if rounds:
        lo = min(r.start for r in rounds)
        hi = max(r.end for r in rounds)
        inside = [g for g in labelled if lo <= g[1] and g[1] + g[2] <= hi]
    idle = sum(g[2] for g in inside)
    named = sum(g[2] for g in inside
                if any(is_program(p) for p in g[0].split("+")))
    counts: Dict[str, int] = {}
    for s in ht.spans:
        counts[s.name] = counts.get(s.name, 0) + 1
    return {
        "window_s": ht.window_s,
        # a round in flight when the session started is not recorded: the
        # device then runs before the first round span opens
        "first_device_op_s": min((e.start for e in device), default=None),
        "last_device_op_end_s": max((e.end for e in device), default=None),
        "first_round_s": min((s.start for s in ht.spans if s.name == LOOP),
                             default=None),
        "last_round_end_s": max((s.end for s in ht.spans if s.name == LOOP),
                                default=None),
        "rounds": len(rounds),
        "agent_host_ms_per_round": agent_host_ms_per_round(ht),
        "lock_wait_ms_per_round": lock_wait_ms_per_round(ht),
        "idle_between_rounds_s": idle,
        "idle_between_rounds_named_share":
            named / idle if idle > 0 else None,
        # label, seconds after the trace's start, seconds before its stop,
        # length
        "gaps": [[k, s, ht.window_s - s - d, d] for k, s, d in
                 labelled[:top]],
        "span_counts": counts,
    }


if __name__ == "__main__":
    print(json.dumps(report(sys.argv[1]), indent=1))
