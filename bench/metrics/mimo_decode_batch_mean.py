"""Scheduler: ``decode_batch_mean`` in the cells of a ``mimo_reference``
configuration, under an entry of their own (the reader depends on no
model dimension)."""
from bench.metrics.decode_batch_mean import read  # noqa: F401
