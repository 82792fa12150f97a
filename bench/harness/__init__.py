"""The benchmark's harness: cells, traffic, the served window, metrics,
trace reduction, work counts and the correctness comparison."""
