"""Host spans of the served path, on the profiler's clock.

Every span is a ``jax.profiler.TraceAnnotation``: the profiler records it
on the host thread that opened it, on the same clock as the device's
"XLA Ops", so an idle gap on the device can be named by what the host
was doing in it.  An annotation records only while a profiler session
runs; otherwise a span costs one Python object.  Span sites sit at round
level, never inside a per-token loop.

This module imports nothing from ``repro``, so every layer may use it.
"""
from __future__ import annotations

import gc
import threading
from typing import Dict, Optional

from jax.profiler import TraceAnnotation

NAMES = (
    # agent thread: one serve-loop pass and its parts
    "qlm.agent.loop",
    "qlm.agent.iteration",
    "qlm.agent.heartbeat",
    "qlm.agent.hook",
    "qlm.agent.idle",
    # agent thread: local scheduling operations
    "qlm.lso.sync",
    "qlm.lso.swap",
    "qlm.lso.evict",
    "qlm.lso.pull",
    # agent thread: the engine's round
    "qlm.engine.admit",
    "qlm.engine.prefill",
    "qlm.engine.decode",
    "qlm.engine.burst",
    "qlm.engine.prep",
    "qlm.engine.dispatch",
    "qlm.engine.device_wait",
    "qlm.engine.post",
    "qlm.engine.invariants",
    # any thread: a blocking wait for the controller lock
    "qlm.lock_wait",
    # client and controller threads
    "qlm.controller.submit",
    "qlm.controller.place",
    "qlm.controller.tick",
    "qlm.controller.sweep",
    "qlm.controller.reschedule",
    "qlm.controller.invariants",
    "qlm.scheduler.predict",
    "qlm.scheduler.solve",
    # any thread: one pass of Python's garbage collector
    "python.gc",
)


def span(name: str, **ints: int) -> TraceAnnotation:
    """A span named ``name`` (one of ``NAMES``), with integer metadata."""
    return TraceAnnotation(name, **ints)


class TimedRLock:
    """A re-entrant lock whose blocking acquires are ``qlm.lock_wait``
    spans.  An acquire that succeeds at once (free, or already held by
    this thread) opens no span, so the spans cover exactly the time a
    thread waited for another to release the lock."""

    def __init__(self):
        self._lock = threading.RLock()

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        if self._lock.acquire(blocking=False):
            return True
        if not blocking:
            return False
        with span("qlm.lock_wait"):
            return self._lock.acquire(timeout=timeout)

    def release(self) -> None:
        self._lock.release()

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self._lock.release()


class GCSpans:
    """Opens a ``python.gc`` span at each garbage-collector ``start`` and
    closes it at ``stop``, through ``gc.callbacks``.  Collections never
    overlap, and both callbacks of one run on the thread that triggered
    it, so one open span at a time is all the hook holds."""

    def __init__(self):
        self._open: Optional[TraceAnnotation] = None

    def __call__(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._open = span("python.gc")
            self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None

    def install(self) -> None:
        if self not in gc.callbacks:
            gc.callbacks.append(self)

    def remove(self) -> None:
        if self in gc.callbacks:
            gc.callbacks.remove(self)
