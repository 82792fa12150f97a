"""Plain references of the benchmark's configurations, independent of the
program: each rebuilds the weights from the run's seed and computes the
forward pass in straightforward ``jax.numpy``."""
