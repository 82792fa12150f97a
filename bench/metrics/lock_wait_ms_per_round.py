"""Scheduler: time an agent round waits for the controller lock, in ms,
from the program's own spans in the profiler trace: the mean over the
``qlm.agent.loop`` passes lying wholly inside the trace of the time that
``qlm.lock_wait`` spans cover on the agent's thread.  The controller
holds that lock through submits (with their violation check and solve)
and ticks, so this is what the scheduler costs the chip's round."""
from bench.harness import spans


def read(ctx):
    ht = spans.for_run(ctx)
    return None if ht is None else spans.lock_wait_ms_per_round(ht)
