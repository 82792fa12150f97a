"""Device: 1 - (union of the device's operation intervals / traced
window), from the profiler trace."""


def read(ctx):
    r = ctx.reduced
    if not r or r["window_s"] <= 0:
        return None
    return 1.0 - r["busy_s"] / r["window_s"]
