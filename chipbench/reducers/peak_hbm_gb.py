"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, in GB."""


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
