"""Agent / LSOs: evictions the agents made during the window
(``EngineStats.evictions``), per request due in the window."""


def read(ctx):
    if not ctx.records:
        return None
    return sum(s["evictions"] for s in ctx.engine_stats) / len(ctx.records)
