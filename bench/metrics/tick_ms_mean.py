"""Scheduler: mean time of one ``controller.tick`` (violation check,
reschedule, watchdog, migration sweep), in ms, timed by the benchmark
around the call on the controller's own thread."""


def read(ctx):
    t = ctx.tick_seconds
    return 1e3 * sum(t) / len(t) if t else None
