"""Architecture modules of the benchmark's configurations, one per family,
found by the name a configuration file gives under ``reference`` (beside
``bench/reference/<name>.py``).

Each gives ``model_config(config)``, the program's ``ModelConfig`` for the
configuration file, and the work its served rounds compute from live
lengths: ``model_flops(dims, prefill_spans, decode_contexts, produced)``,
and ``decode_attention(dims, contexts)`` and
``prefill_attention(dims, spans)``, each a list of ``(layers, flops,
bytes)``, one entry per group of alike layers (one call of the kernel in
each of ``layers`` layers).  ``dims`` is the reference module's
``dims(config)``."""
