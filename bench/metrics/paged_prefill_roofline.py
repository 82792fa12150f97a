"""Kernels: the paged prefill-chunk attention kernel's share of its
roofline, in %: the least time the chip could take for the prefill work
of the rounds inside the trace (per round and layer, the larger of FLOPs
/ peak and bytes / HBM bandwidth, from the live prompt spans, as the
configuration's architecture module counts each group of alike layers)
over the kernel's device time in the trace."""
from bench.harness import trace as trace_lib
from bench.harness import work

KERNEL = "paged_prefill_attention"


def read(ctx):
    events = trace_lib.device_events(ctx.trace) if ctx.trace else None
    if not events:
        return None
    seconds = trace_lib.kernel_seconds(events, KERNEL)
    least = 0.0
    for w in ctx.round_work:
        if w.prefill_spans:
            for layers, f, b in ctx.arch.prefill_attention(
                    ctx.dims, w.prefill_spans):
                least += layers * work.least_time(f, b, ctx.peak)
    if seconds <= 0 or least <= 0:
        return None
    return 100.0 * least / seconds
