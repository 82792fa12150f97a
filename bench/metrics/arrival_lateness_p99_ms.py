"""Client: how late the open-loop generator submitted, as the 99th
percentile over the window of (submit time - due time), in ms, on the
benchmark's own clock.  A starved generator reads high here, so that it
is not read as a fast server."""
from bench.harness.stats import percentile


def read(ctx):
    late = [r.submitted - r.due for r in ctx.records if r.submitted is not None]
    return 1e3 * percentile(late, 99) if late else None
