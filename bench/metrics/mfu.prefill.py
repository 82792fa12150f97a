"""Model step, prefill side: the FLOPs of the prompt tokens prefilled in
the traced part of the window (matmuls, attention over their live
context, and the output head for each prompt's first token) over traced
seconds x the chip's peak bf16 FLOP/s, in %.  The share of ``mfu`` that
bounds what the prefill-chunk kernel can gain.  Counted by the
configuration's architecture module."""


def read(ctx):
    if "trace_start" not in ctx.bounds:
        return None
    total = 0.0
    for w in ctx.round_work:
        if not w.prefill_spans:
            continue
        firsts = w.produced - len(w.decode_contexts)
        total += ctx.arch.model_flops(ctx.dims, w.prefill_spans, [],
                                      firsts)
    if total == 0:
        return None
    span = ctx.bounds["trace_stop"] - ctx.bounds["trace_start"]
    return 100.0 * total / (span * ctx.peak["bf16_flops_per_s"])
