"""memory_stats()['peak_bytes_in_use'] after the window, fullest chip.
Memory bounds the pool and the lanes."""


def read(run):
    return run.memory_peak_bytes / 2**30 if run.memory_peak_bytes else None
