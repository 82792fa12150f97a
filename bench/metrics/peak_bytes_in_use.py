"""Device: ``memory_stats()["peak_bytes_in_use"]`` after the window, in
GiB.  The run prints ``peak_bytes_reserved`` and the served programs'
compiler temporaries beside it on an earlier line, unsummed."""


def read(ctx):
    peak = ctx.memory.get("peak_bytes_in_use")
    return peak / 2**30 if peak else None
