"""Page store: the most pages in use at once over the run, as a share of
the pool (``engine.peak_used_pages / engine.pool.n_pages``)."""


def read(run):
    return run.peak_used_pages / run.n_pages if run.n_pages else None
