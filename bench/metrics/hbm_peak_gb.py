"""Peak device memory in use, in GB: the fullest chip's
``memory_stats()["peak_bytes_in_use"]`` after the window."""


def read(run):
    p = run.memory_peak_bytes
    return p / 1e9 if p is not None else None
