"""Queue layer: 95th percentile over the interactive requests due in the
window of (first round in which the request sits in an engine slot - due
time), in seconds, read after every agent round.  A request not admitted
by the end of the drain counts with the wait it had then."""
from bench.harness.stats import percentile


def read(ctx):
    end = ctx.bounds["drain_end"]
    waits = [(r.admitted if r.admitted is not None else end) - r.due
             for r in ctx.records if r.slo_class == "interactive"]
    return percentile(waits, 95) if waits else None
