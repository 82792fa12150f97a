"""Model step: FLOPs of the work served in the traced part of the window
(prompt tokens prefilled and tokens decoded, each 2 x N matmul FLOPs per
layer plus attention over its live context, and the output head for each
token produced) over traced seconds x the chip's peak bf16 FLOP/s, in %.
Counted by the configuration's architecture module from the live
lengths of the rounds that ran inside the trace."""


def flops(ctx, rounds):
    return sum(ctx.arch.model_flops(ctx.dims, w.prefill_spans,
                                    w.decode_contexts, w.produced)
               for w in rounds)


def read(ctx):
    if not ctx.round_work or "trace_start" not in ctx.bounds:
        return None
    span = ctx.bounds["trace_stop"] - ctx.bounds["trace_start"]
    return 100.0 * flops(ctx, ctx.round_work) / (
        span * ctx.peak["bf16_flops_per_s"])
