"""Peak device memory of the fullest chip after the window, in GB (1e9
bytes): ``memory_stats()["peak_bytes_in_use"]``."""


def read(ctx):
    return ctx["peak_bytes"] / 1e9
