"""Peak device memory in use over the run, in GiB, as the device reports
it (``memory_stats()["peak_bytes_in_use"]``, fullest chip) after the
window."""


def read(run):
    return run.memory_peak_bytes / 2 ** 30 if run.memory_peak_bytes else None
