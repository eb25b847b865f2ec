"""Scheduler: ``host_syncs_per_step`` in the cells of a
``mimo_reference`` configuration, under an entry of their own (the
reader depends on no model dimension)."""
from bench.metrics.host_syncs_per_step import read  # noqa: F401
