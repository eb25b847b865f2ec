"""Model step: ``prefill_busy_frac`` in the cells of a
``mimo_reference`` configuration, under an entry of their own (the
reader depends on no model dimension)."""
from bench.metrics.prefill_busy_frac import read  # noqa: F401
