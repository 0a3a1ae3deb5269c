"""Peak device memory in GB (1e9 bytes) over the run, on the fullest chip
(``memory_stats()["peak_bytes_in_use"]``)."""


def read(run):
    return run.peak_bytes / 1e9 if run.peak_bytes else None
