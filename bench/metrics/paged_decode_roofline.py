"""Kernels: the paged decode attention kernel's share of its roofline, in
%: the least time the chip could take for the decode work of the rounds
inside the trace (per round and layer, the larger of FLOPs / peak and
bytes / HBM bandwidth, from each decoded token's live context) over the
kernel's device time in the trace."""
from bench.harness import trace as trace_lib
from bench.harness import work

KERNEL = "paged_decode_attention"


def read(ctx):
    events = trace_lib.device_events(ctx.trace) if ctx.trace else None
    if not events:
        return None
    seconds = trace_lib.kernel_seconds(events, KERNEL)
    d = ctx.dims
    least = 0.0
    for w in ctx.round_work:
        if w.decode_contexts:
            f, b = work.decode_attention(d["heads"], d["kv_heads"],
                                         d["head_dim"], w.decode_contexts)
            least += d["layers"] * work.least_time(f, b, ctx.peak)
    if seconds <= 0 or least <= 0:
        return None
    return 100.0 * least / seconds
