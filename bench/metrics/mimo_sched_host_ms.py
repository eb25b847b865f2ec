"""Scheduler: ``sched_host_ms`` in the cells of a ``mimo_reference``
configuration, under an entry of their own (the reader depends on no
model dimension)."""
from bench.metrics.sched_host_ms import read  # noqa: F401
