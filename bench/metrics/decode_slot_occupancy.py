"""Engine: decoded tokens over decode iterations times slots, during the
window (``EngineStats.tokens_generated`` / ``decode_iterations`` x
``max_slots``): how full the decode batch ran."""


def read(ctx):
    iters = sum(s["decode_iterations"] for s in ctx.engine_stats)
    if iters == 0:
        return None
    tokens = sum(s["tokens_generated"] for s in ctx.engine_stats)
    return tokens / (iters * ctx.max_slots)
