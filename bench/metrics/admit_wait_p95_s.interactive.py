"""Queue layer: 95th percentile over the interactive requests due in the
window of (the program's admission stamp - due time), in seconds.

The stamp is ``Request.admitted_time``: the engine sets it, on its
lifecycle clock (the host's monotonic clock, the client's), the first
time it puts the request in a slot.  A request not admitted by the end
of the drain counts with the wait it had then, as
``queue_wait_p95_s.interactive`` counts it; that metric stamps admission
when the agent's round has ended, this one where it happens.

The client's records do not carry the stamp: the reader finds the
program's requests among the live objects, each by the due time the
client gave it as its arrival time and its prompt length.  A program
without the stamp, or a record that matches no request, reads nothing.
"""
import dataclasses
import gc

from bench.harness.stats import percentile


def stamps(records):
    """(due, prompt length) -> ``admitted_time`` for every record, or
    ``None``."""
    from repro.core.request import Request
    if "admitted_time" not in {f.name for f in dataclasses.fields(Request)}:
        return None
    keys = {(r.due, r.prompt_len) for r in records}
    out = {}
    for o in gc.get_objects():
        if isinstance(o, Request) and (o.arrival_time, o.prompt_len) in keys:
            out[(o.arrival_time, o.prompt_len)] = o.admitted_time
    return out if len(out) == len(keys) else None


def read(ctx):
    found = stamps(ctx.records)
    if found is None:
        return None
    end = ctx.bounds["drain_end"]
    waits = []
    for r in ctx.records:
        if r.slo_class == "interactive":
            t = found[(r.due, r.prompt_len)]
            waits.append((end if t is None else min(t, end)) - r.due)
    return percentile(waits, 95) if waits else None
