"""``admit_wait_p95_s.interactive``, read alike for the cells that report
``ttft_p95_s`` where others report ``interactive_slo_attainment``: a
per-layer metric moves one end-to-end metric, so the two cells name it
apart."""
from bench.harness.main import read_metric


def read(ctx):
    return read_metric("admit_wait_p95_s.interactive", ctx)
