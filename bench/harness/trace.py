"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

Device operations are the events of each TPU plane's "XLA Ops" line.
Operations nest there (a loop holds the operations of its body), so:

  * busy time is the union of the operations' intervals, and idle time
    is the traced window minus it;
  * time per operation is self time: an event's duration minus what the
    events nested inside it cover;
  * idle gaps are the holes in the union, each labelled with the host
    spans (``jax.profiler.TraceAnnotation``) open at its middle.

The traced window is the profile's own start and stop.  Events are placed
relative to the profile start on one clock for host and device.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
HOST_SPANS = ("agent.run_iteration", "controller.tick", "client.submit")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float   # seconds after the profile started
    end: float


@dataclasses.dataclass
class Trace:
    window_s: float
    device_ops: Dict[str, List[Event]]     # device plane -> op events
    host_spans: List[Event]


def op_name(event_name: str) -> str:
    """The HLO instruction of an "XLA Ops" event ("%fusion.3 = bf16[...]
    fusion(...)" -> "fusion.3")."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def load(path: str) -> Trace:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    start = stop = None
    device_ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start = float(stats["profile_start_time"])
            stop = float(stats["profile_stop_time"])
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        Event(op_name(e.name), e.start_ns * 1e-9,
                              e.end_ns * 1e-9) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                            for e in line.events if e.name in HOST_SPANS)
    if start is None:
        raise ValueError(f"{path}: no profile start and stop times")
    return Trace((stop - start) * 1e-9, device_ops, host)


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(events: Iterable[Event], lo: float = float("-inf"),
          hi: float = float("inf")) -> List[Tuple[float, float]]:
    """The union of the events' intervals, clipped to [lo, hi]."""
    out: List[Tuple[float, float]] = []
    for e in sorted(events, key=lambda e: e.start):
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t))
        else:
            out.append((s, t))
    return out


def busy_seconds(events: Sequence[Event], window_s: float) -> float:
    return sum(t - s for s, t in union(events, 0.0, window_s))


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Seconds of self time per operation name (nested events subtract
    from the event that holds them)."""
    out: Dict[str, float] = collections.defaultdict(float)
    stack: List[List] = []   # [event, seconds covered by its children]
    for e in sorted(events, key=lambda e: (e.start, -(e.end - e.start))):
        while stack and stack[-1][0].end <= e.start:
            done, covered = stack.pop()
            out[done.name] += (done.end - done.start) - covered
        if stack:
            stack[-1][1] += e.end - e.start
        stack.append([e, 0.0])
    while stack:
        done, covered = stack.pop()
        out[done.name] += (done.end - done.start) - covered
    return dict(out)


def idle_gaps(events: Sequence[Event], host: Sequence[Event],
              window_s: float) -> List[Tuple[str, float]]:
    """Every hole in the device's busy union inside the window, longest
    first, named by the host spans open at its middle ("none" if no span
    of the benchmark's was open)."""
    busy = union(events, 0.0, window_s)
    edges = [0.0] + [x for iv in busy for x in iv] + [window_s]
    gaps = []
    for s, t in zip(edges[0::2], edges[1::2]):
        if t <= s:
            continue
        mid = (s + t) / 2
        open_spans = sorted({h.name for h in host if h.start <= mid < h.end})
        gaps.append(("+".join(open_spans) or "none", t - s))
    return sorted(gaps, key=lambda g: -g[1])


def kernel_seconds(events: Sequence[Event], kernel: str) -> float:
    """Device seconds of the calls of one kernel: events whose HLO
    instruction is named after it (``<kernel>`` or ``<kernel>.<n>``)."""
    return sum(e.end - e.start for e in events
               if e.name == kernel or e.name.startswith(kernel + "."))


def reduce(trace: Trace, top: int = 10) -> Dict:
    """Busy and window seconds averaged over the traced chips, and the
    breakdown: the operations with most self time and the longest idle
    gaps (of the first chip)."""
    planes = sorted(trace.device_ops)
    if not planes:
        raise ValueError("the trace holds no TPU plane")
    busy = [busy_seconds(trace.device_ops[p], trace.window_s) for p in planes]
    first = trace.device_ops[planes[0]]
    selfs = self_times(first)
    ops = sorted(selfs.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": trace.window_s,
        "breakdown": {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle_gaps(
                first, trace.host_spans, trace.window_s)[:top]],
        },
    }


def device_events(trace: Trace) -> Optional[List[Event]]:
    planes = sorted(trace.device_ops)
    return trace.device_ops[planes[0]] if planes else None
