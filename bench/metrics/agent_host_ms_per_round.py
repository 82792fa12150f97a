"""Agent and LSOs: host time of one agent round, in ms, from the program's
own spans in the profiler trace: the mean over the ``qlm.agent.loop``
passes lying wholly inside the trace of (the pass's length - the time its
``qlm.engine.device_wait`` and ``qlm.agent.idle`` spans cover).  What is
left is the host's work between two device rounds: scheduling, LSOs,
input preparation, token bookkeeping, heartbeat, the round hook, waits
for the controller lock and garbage collection."""
from bench.harness import spans


def read(ctx):
    ht = spans.for_run(ctx)
    return None if ht is None else spans.agent_host_ms_per_round(ht)
